"""Host time from the scheduler's decode call (the start of its
``serve.decode`` span) to the start of that step's program on the
device, on the clock the decode-step pairs give
(bench/harness/serve_trace.py); the mean over the window's steps."""


def read(run):
    t = getattr(run, "serve_trace", None)
    if t is None or t.dispatch_s is None:
        return None
    return t.dispatch_s * 1e3
