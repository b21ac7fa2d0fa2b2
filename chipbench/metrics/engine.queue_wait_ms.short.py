"""Mean time a request waited in the engine's queue, from ``submit`` to
the start of its admission: the engine's ``queue_wait`` spans over the
requests it admitted in the window."""


def read(run):
    s = run.spans.get("queue_wait")
    n = run.counters.get("admitted")
    return None if s is None or not n else 1e3 * s / n
