"""Device time of the decode programs' other leaf ops per program: the
XLA glue around the named kernels (K/V reads and writes, copies, the
embedding, sampling; bench/harness/serve_trace.py)."""


def read(run):
    t = getattr(run, "serve_trace", None)
    if t is None or not t.decode_programs or not t.decode_kernels:
        return None
    return t.decode_other_s / t.decode_programs * 1e3
