"""The readers of the engine's own spans give numbers on a tiny traced
run on the CPU.

The run is the harness's traced run with its profiler left off (the
CPU has no device trace to reduce), so the engine is built with its
tracer and every metric read from spans and counters is reported.
"""

import time

import jax
import pytest

from chipbench import harness
from chipbench.tests.test_harness import SEED, TINY_LIMIT
from chipbench.tests.tiny import PEAKS, tiny_cell

SPAN_METRICS = {
    "m4b.seda.short-open": ["engine.queue_wait_ms.short",
                            "engine.prefill_ms.short"],
    "m4b.seda.long-batch": ["engine.prefill_share.batch",
                            "engine.host_ms_per_tick.batch"],
}


class NoProfiler:
    """The harness's ``Tracer`` without the profiler: never starts."""

    times = None

    def __init__(self, seconds, logdir):
        pass

    def poll(self, now):
        pass

    def stop(self, now):
        pass


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_readers_read(monkeypatch, name):
    monkeypatch.setattr(harness, "Tracer", NoProfiler)
    cell = tiny_cell(name, trace=True)
    cell.cell["check"]["logit_gap"] = TINY_LIMIT
    out = harness.run_cell(cell, SEED, 2.0, True, jax.devices(), PEAKS,
                           time.perf_counter(), log=lambda m: None)
    assert out["correct"], out["checks"]
    for metric in SPAN_METRICS[name]:
        value = out["metrics"][metric]["value"]
        assert isinstance(value, float) and value >= 0.0, metric
    if name == "m4b.seda.long-batch":
        assert out["metrics"]["engine.host_ms_per_tick.batch"]["value"] > 0
    else:
        assert out["metrics"]["engine.prefill_ms.short"]["value"] > 0
