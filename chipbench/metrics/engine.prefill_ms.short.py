"""Mean host time of one admission's batch-1 prefill: the engine's
``admit.prefill`` spans (prefill, prompt-page writer and the read of the
first token, which syncs) over its ``prefills`` counter."""


def read(run):
    s = run.spans.get("admit.prefill")
    n = run.counters.get("prefills")
    return None if s is None or not n else 1e3 * s / n
