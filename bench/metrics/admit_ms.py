"""Mean host time of one admit (the padded prefill-insert), from the call
until its first tokens are ready, over the admits of the window."""


def read(run):
    return sum(run.admit_s) / len(run.admit_s) * 1e3 if run.admit_s else None
