"""Host time per decode tick from the end of ``serve.decode.wait`` (the
step's tokens on the host) to the end of ``serve.commit`` (the tokens in
the router's journal); the mean over the window's ticks."""


def read(run):
    t = getattr(run, "serve_trace", None)
    if t is None or t.commit_lag_s is None:
        return None
    return t.commit_lag_s * 1e3
