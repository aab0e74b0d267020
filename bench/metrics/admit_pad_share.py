"""Share of the padded admit's positions that hold no prompt token:
1 − ``admit_rows`` / ``admit_positions`` (the program's serve counters,
core/tracecount.py) over the window."""


def read(run):
    c = getattr(run, "serve_counts", None)
    if not c or not c["admit_positions"]:
        return None
    return (1.0 - c["admit_rows"] / c["admit_positions"]) * 100.0
