"""Device time of the named Pallas kernels per decode program: the leaf
ops of the window's decode programs that run a kernel named by its
``pallas_call`` (bench/harness/serve_trace.py), over the programs."""


def read(run):
    t = getattr(run, "serve_trace", None)
    if t is None or not t.decode_programs or not t.decode_kernels:
        return None
    return sum(t.decode_kernels.values()) / t.decode_programs * 1e3
