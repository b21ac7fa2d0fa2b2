"""Output tokens emitted in the window over the window's seconds."""


def read(run):
    n = sum(len(r.times) for r in run.requests)
    return n / run.window_s if n else None
