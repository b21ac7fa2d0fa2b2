"""Peak device memory in use on the fullest chip, after the window."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
