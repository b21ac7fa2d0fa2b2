"""Host time per decode tick outside the waits on the device: the
engine's ``tick`` spans less its ``decode.wait`` (the host blocked on
the tick's tokens) and ``admit.prefill`` spans, over its decode
dispatches."""


def read(run):
    tick, wait = run.spans.get("tick"), run.spans.get("decode.wait")
    n = run.counters.get("decode_steps")
    if tick is None or wait is None or not n:
        return None
    host = tick - wait - run.spans.get("admit.prefill", 0.0)
    return 1e3 * host / n
