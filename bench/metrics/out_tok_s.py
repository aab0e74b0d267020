"""Output tokens per second: every token committed to the router's
journal during the window, over the whole window."""


def read(run):
    return run.tokens / run.window_s if run.window_s > 0 else None
