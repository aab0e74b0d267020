"""Blocking device→host reads of the serve loop per router tick: the
program's ``host_syncs`` counter (core/tracecount.py) over the window,
over the window's ticks."""


def read(run):
    c = getattr(run, "serve_counts", None)
    if not c or not run.ticks:
        return None
    return c["host_syncs"] / run.ticks
