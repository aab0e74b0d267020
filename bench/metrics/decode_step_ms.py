"""Device time of one decode step: the decode programs run in the
window, from the profiler trace, per program."""


def read(run):
    t = run.trace
    if t is None or not t.decode_programs:
        return None
    return t.decode_device_s / t.decode_programs * 1e3
