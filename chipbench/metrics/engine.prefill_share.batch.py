"""Share of the window spent in serial batch-1 prefills, every decode
slot waiting: the engine's ``admit.prefill`` spans over the window.  A
window with no admission reads 0; a program without the ``prefills``
counter reads nothing."""


def read(run):
    if not run.spans or "prefills" not in run.counters:
        return None
    return 100.0 * run.spans.get("admit.prefill", 0.0) / run.window_s
