"""Seconds from the start of the process to the opening of the window:
imports, engine build, seeded weights, compilation and warm-up."""


def read(run):
    return run.setup_s
