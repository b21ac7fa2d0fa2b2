"""The trace reduction: exact on a synthetic trace, and on the small
trace recorded on a v5e (``data/tiny.xplane.pb.xz``: one second of the
tiny cell, made by ``record_trace.py``) it agrees with a brute-force
count of the same events and reads the kernels and the idle share the
recording showed."""

import os

import numpy as np
import pytest
from jax.profiler import ProfileData

from chipbench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "tiny.xplane.pb.xz")

SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "fused_crypt_mac" } }
  event_metadata { key: 3 value { id: 3 name: "jit_decode_fn(123)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_decode_fn(456)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 8000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "engine.tick_begin" } }
}
"""


def test_synthetic_trace():
    red = trace.reduce(ProfileData.from_text_proto(SYNTHETIC))
    # Window 1000..9000 ns; busy 1000..4000 and 7000..8000 ns.
    assert red["window_s"] == pytest.approx(8e-6)
    assert red["busy_s"] == pytest.approx(4e-6)
    assert red["idle_share"] == pytest.approx(0.5)
    # The second op starts inside the first: it is charged as nested,
    # so the first keeps only the microsecond it alone covers.
    assert red["op_s"] == pytest.approx({"fusion": 2e-6,
                                         "fused_crypt_mac": 2e-6})
    # Programs clipped to the window: 1000..4500 and 7000..9000 ns.
    assert red["module_s"] == pytest.approx({"jit_decode_fn": 5.5e-6})
    gaps = dict((n, s) for n, s in red["idle_gaps"])
    assert red["idle_gaps"][0] == ["engine.tick_begin", pytest.approx(3e-6)]
    assert gaps["no host span"] == pytest.approx(1e-6)


def test_trace_without_window_is_an_error():
    text = SYNTHETIC.replace('"chipbench.window"', '"other"')
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.reduce(ProfileData.from_text_proto(text))


def test_self_time_of_nested_ops():
    evs = [("%while.3 = (...) while()", 0, 100), ("%fusion.1 = f()", 10, 30),
           ("%closed_call.2 = u32[] custom-call(), "
            'custom_call_target="tpu_custom_call"', 40, 90)]
    assert trace.self_times(evs, 0, 100) == pytest.approx(
        {"while": 30e-9, "fusion": 20e-9,
         "closed_call:tpu_custom_call": 50e-9})


def test_union_merges_and_clips():
    assert trace.union([(0, 5), (3, 8), (10, 12)], 1, 11) == [[1, 8],
                                                              [10, 11]]


def test_recorded_trace_matches_brute_force():
    pd = trace.load(RECORDED)
    red = trace.reduce(pd)
    ops = trace.device_ops(pd)
    assert list(ops) == ["/device:TPU:0"] and red["devices"] == 1
    (lo, hi), = [(s, e) for n, s, e in trace.host_spans(pd)
                 if n == trace.WINDOW]
    # Brute force: mark every 10 ns bin an op overlaps.
    step = 10.0
    bins = np.zeros(int((hi - lo) // step) + 1, bool)
    for _, s, e in ops["/device:TPU:0"]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            bins[int((s - lo) // step):int(np.ceil((e - lo) / step))] = True
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert red["busy_s"] == pytest.approx(bins.sum() * step / 1e9, rel=0.02)
    assert 0.0 < red["idle_share"] < 1.0
    assert sum(red["op_s"].values()) >= red["busy_s"] * (1 - 1e-9)
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_recorded_trace_numbers():
    """The reduction of the recorded trace as first read (on the chip
    run that recorded it): pinned, so that a change of the reduction
    shows."""
    from chipbench import kernels
    red = trace.reduce(trace.load(RECORDED))
    assert red["window_s"] == pytest.approx(0.955610608)
    assert red["busy_s"] == pytest.approx(0.010154962)
    assert red["idle_share"] == pytest.approx(0.9893733264208385)
    assert kernels.crypt_mac_seconds(red) == pytest.approx(0.003681926)
    assert red["device_ops"][0][0] == "closed_call:tpu_custom_call"
    assert 0.0 < red["module_s"]["jit_decode_fn"] <= red["busy_s"]
