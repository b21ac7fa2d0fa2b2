"""Share of the window the scheduler spent in ``tick_begin`` (admission
and the serial prefills it runs), from the engine's own phase spans."""


def read(run):
    s = run.spans.get("tick_begin")
    return 100.0 * s / run.window_s if s else None
