"""Process start to window start: loading, weights, warm-up, compiles."""


def read(run):
    return run.setup_s
