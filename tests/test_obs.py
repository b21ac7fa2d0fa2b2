"""Observability subsystem: metrics registry, tracer, audit chain.

Covers the telemetry contracts:
  * registry — declared counters behind the old ``engine.stats`` dict
    API (snapshot/reset, auto-declare on unknown assignment, kind
    conflicts rejected, Prometheus text well-formed);
  * histograms — ``percentile()`` matches numpy's default linear
    interpolation;
  * tracer — exports valid Chrome trace-event JSON with tick-phase
    spans correctly nested inside their tick span; each span is also a
    profiler annotation in the ``.xplane.pb``; the engine's spans count
    what its counters count; an untraced engine opens none;
  * audit log — the SHA-256 chain verifies end-to-end and any
    single-field tamper, truncation, or reorder breaks it;
  * engines — tracing + metrics + audit enabled is observation-only
    (token-identical for every scheme); the cluster rolls shard
    counters up with per-shard labels.
"""

import collections
import glob
import json
import re

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.secure_exec import SCHEMES
from repro.models import lm as lm_mod
from repro.models.layers import init_params
from repro.obs.audit import AuditLog
from repro.obs.metrics import (ENGINE_COUNTERS, Histogram, MetricsRegistry,
                               StatsView)
from repro.obs.trace import NO_SPAN, SpanTracer
from repro.serve.cluster import ClusterEngine
from repro.serve.engine import SecureServingEngine
from repro.tenancy import KeyHierarchy, TenantRegistry


@pytest.fixture(scope="module")
def smoke():
    arch = get_arch("minitron-4b")
    cfg = arch.make_smoke_config()
    params = init_params(lm_mod.lm_specs(cfg), jax.random.PRNGKey(0))
    return arch, cfg, params


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [list(map(int, rng.integers(1, 256, n))) for n in (5, 7, 9)]


def _engine(smoke, **kw):
    arch, cfg, params = smoke
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_tokens", 4)
    kw.setdefault("pages_per_slot", 4)
    return SecureServingEngine(arch, cfg, params, **kw)


def _count_annotations(mp, opened: list) -> None:
    """Record the name of every profiler annotation opened through
    ``jax.profiler`` while ``mp`` (a MonkeyPatch) is active."""
    for cls in ("TraceAnnotation", "StepTraceAnnotation"):
        base = getattr(jax.profiler, cls)

        class Counted(base):
            def __init__(self, name, **kw):
                opened.append(name)
                super().__init__(name, **kw)
        mp.setattr(jax.profiler, cls, Counted)


@pytest.fixture(scope="module")
def traced_run(smoke, prompts):
    """A traced engine that served three requests through two slots
    (so one waited in the queue), and the profiler annotations its
    spans opened."""
    opened: list = []
    with pytest.MonkeyPatch.context() as mp:
        _count_annotations(mp, opened)
        eng = _engine(smoke, scheme="seda", trace=True)
        for p in prompts:
            eng.submit(prompt=p, max_new_tokens=4)
        eng.run()
    return eng, opened


class TestRegistry:
    def test_counters_snapshot_and_reset(self):
        reg = MetricsRegistry()
        reg.counter("a", "first").inc()
        reg.counter("a").inc(4)
        reg.gauge("g").set(7)
        reg.histogram("h").observe(2.5)
        snap = reg.snapshot(labels={"shard": "0"})
        assert snap["counters"] == {"a": 5}
        assert snap["gauges"] == {"g": 7}
        assert snap["histograms"]["h"]["count"] == 1
        assert snap["labels"] == {"shard": "0"}
        reg.reset()
        snap = reg.snapshot()
        assert snap["counters"] == {"a": 0}
        assert snap["histograms"]["h"]["count"] == 0

    def test_stats_view_dict_api(self):
        reg = MetricsRegistry()
        for name, help_ in ENGINE_COUNTERS.items():
            reg.counter(name, help_)
        stats = StatsView(reg)
        stats["admitted"] += 1
        stats["admitted"] += 2
        assert stats["admitted"] == 3
        assert dict(stats)["admitted"] == 3
        assert "admitted" in stats
        assert set(stats.keys()) == set(ENGINE_COUNTERS)
        assert len(stats) == len(ENGINE_COUNTERS)
        with pytest.raises(KeyError):
            stats.__getitem__("never_declared")

    def test_autodeclare_unknown_key(self):
        reg = MetricsRegistry()
        stats = StatsView(reg)
        stats["brand_new"] = 3
        assert reg.counters["brand_new"].value == 3
        assert stats["brand_new"] == 3

    def test_kind_conflict_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")
        with pytest.raises(ValueError):
            reg.histogram("x")

    def test_lazy_gauge_and_labels(self):
        reg = MetricsRegistry()
        backing = {"t0": 4, "t1": 2}
        reg.gauge("resident", fn=lambda: dict(backing), label="tenant")
        backing["t0"] = 9
        assert reg.snapshot()["gauges"]["resident"] == {"t0": 9, "t1": 2}

    def test_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("ticks", "engine ticks").inc(3)
        reg.gauge("free", "free pages").set(11)
        reg.gauge("resident", fn=lambda: {"t0": 4}, label="tenant")
        reg.histogram("lat").observe(1.0)
        text = reg.prometheus(labels={"shard": "1"})
        assert "# TYPE repro_ticks counter" in text
        assert 'repro_ticks{shard="1"} 3' in text
        assert 'repro_free{shard="1"} 11' in text
        assert 'repro_resident{shard="1",tenant="t0"} 4' in text
        assert 'repro_lat_count{shard="1"} 1' in text

    def test_prometheus_escaping_parses_back(self):
        # Hostile label values and help text: backslash, quote, newline.
        reg = MetricsRegistry()
        evil = 'a\\b"c\nd'
        reg.gauge("resident", 'help \\ with\nnewline',
                  fn=lambda: {evil: 7}, label="tenant")
        reg.counter("ticks", "plain").inc(2)
        text = reg.prometheus(labels={"shard": evil})

        # Exposition-format invariant: every sample is one line, every
        # quoted label value uses only \\ \" \n escapes.
        samples = {}
        helps = {}
        for line in text.splitlines():
            if line.startswith("# HELP "):
                name, help_ = line[7:].split(" ", 1)
                helps[name] = help_
                continue
            if line.startswith("#") or not line:
                continue
            name_part, value = line.rsplit(" ", 1)
            labels = {}
            if "{" in name_part:
                name, rest = name_part.split("{", 1)
                body = rest.rsplit("}", 1)[0]
                for m in re.finditer(r'(\w+)="((?:[^"\\]|\\.)*)"', body):
                    raw = m.group(2)
                    assert "\n" not in raw
                    labels[m.group(1)] = (raw.replace("\\n", "\n")
                                          .replace('\\"', '"')
                                          .replace("\\\\", "\\"))
            else:
                name = name_part
            samples[(name, tuple(sorted(labels.items())))] = float(value)

        key = ("repro_resident",
               (("shard", evil), ("tenant", evil)))
        assert samples[key] == 7
        assert "\n" not in helps["repro_resident"]
        assert helps["repro_resident"].replace("\\n", "\n").replace(
            "\\\\", "\\") == 'help \\ with\nnewline'
        # Labeled-gauge expansions carry HELP/TYPE headers too.
        assert "# TYPE repro_resident gauge" in text


class TestHistogram:
    def test_percentiles_match_numpy(self):
        rng = np.random.default_rng(7)
        xs = rng.normal(size=257).tolist()
        h = Histogram("lat")
        for x in xs:
            h.observe(x)
        for q in (0, 5, 25, 50, 75, 90, 95, 99, 100):
            want = float(np.percentile(xs, q, method="linear"))
            assert h.percentile(q) == pytest.approx(want, rel=1e-12, abs=0)
        assert h.count == len(xs)
        assert h.min == min(xs) and h.max == max(xs)

    def test_sample_window_rolls_but_totals_persist(self):
        h = Histogram("lat", max_samples=4)
        for v in range(10):
            h.observe(v)
        assert h.count == 10 and h.sum == sum(range(10))
        assert h.samples == [6.0, 7.0, 8.0, 9.0]


class TestTrace:
    def test_chrome_trace_json(self, tmp_path):
        tr = SpanTracer(pid=3, tid=1)
        with tr.span("outer", tick=0):
            with tr.span("inner"):
                pass
        path = tmp_path / "trace.json"
        doc = tr.export(str(path))
        loaded = json.loads(path.read_text())
        assert loaded == doc
        events = loaded["traceEvents"]
        assert [e["name"] for e in events] == ["outer", "inner"]
        for e in events:
            assert e["ph"] == "X"
            assert e["pid"] == 3 and e["tid"] == 1
            assert e["dur"] >= 0
        outer, inner = events
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6

    def test_ring_buffer_bounded(self):
        tr = SpanTracer(capacity=8)
        for i in range(20):
            tr.add(f"s{i}", 0, 1000)
        events = tr.events()
        assert len(events) == 8
        assert events[0]["name"] == "s12"

    def test_phase_spans_nested(self, traced_run):
        eng, _ = traced_run
        events = eng.tracer.events()
        ticks = [e for e in events if e["name"] == "tick"]
        phases = [e for e in events if e["name"].startswith(
            ("tick_begin", "decode_dispatch", "decode_collect", "tick_end"))]
        assert ticks and phases
        names = {e["name"] for e in phases}
        assert names == {"tick_begin", "decode_dispatch",
                         "decode_collect", "tick_end"}
        for ph in phases:
            assert any(t["ts"] - 1e-6 <= ph["ts"] and
                       ph["ts"] + ph["dur"] <= t["ts"] + t["dur"] + 1e-6
                       for t in ticks), ph["name"]


    def test_span_lands_in_profiler_trace(self, tmp_path):
        from jax.profiler import ProfileData
        tr = SpanTracer()
        jax.profiler.start_trace(str(tmp_path))
        with tr.span("tick", step_num=1, tick=1):
            with tr.span("decode.wait"):
                jax.numpy.ones(4).block_until_ready()
        jax.profiler.stop_trace()
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        host = {ev.name for p in ProfileData.from_file(path).planes
                if p.name.startswith("/host:")
                for line in p.lines for ev in line.events}
        assert {"engine.tick", "engine.decode.wait"} <= host
        assert [e["name"] for e in tr.events()] == ["decode.wait", "tick"]

    def test_engine_spans_match_counters(self, traced_run):
        eng, opened = traced_run
        events = eng.tracer.events()
        names = collections.Counter(e["name"] for e in events)
        stats = eng.stats
        assert names["admit.prefill"] == stats["prefills"] == 3
        assert names["decode.wait"] == stats["decode_steps"] > 0
        assert names["queue_wait"] == stats["admitted"] == 3
        assert names["tick"] == eng.tick
        waits = sorted(e["dur"] for e in events if e["name"] == "queue_wait")
        assert waits[-1] > 0           # the third request waited a slot
        assert {e["args"]["rid"] for e in events
                if e["name"] == "admit.prefill"} == {0, 1, 2}
        # Every span but the backdated queue wait is a profiler
        # annotation, named engine.<span>.
        assert collections.Counter(opened) == collections.Counter(
            "engine." + n for n in names.elements() if n != "queue_wait")

    def test_untraced_engine_opens_no_span(self, smoke, prompts,
                                           monkeypatch):
        opened, added = [], []
        _count_annotations(monkeypatch, opened)
        monkeypatch.setattr(SpanTracer, "add",
                            lambda self, *a, **kw: added.append(a))
        eng = _engine(smoke, scheme="seda")
        assert eng._span("tick") is NO_SPAN
        eng.submit(prompt=prompts[0], max_new_tokens=3)
        eng.run()
        assert eng.tick > 1 and eng.tracer is None
        assert opened == [] and added == []

    def test_decode_program_scopes(self, smoke):
        """The decode program keeps its name and carries a named scope
        for each of its layers in the compiled HLO's op_name."""
        eng = _engine(smoke, scheme="seda")
        text = eng._decode_fn_for(4).lower(
            *eng._decode_analysis_args(4)).compile().as_text()
        assert re.match(r"HloModule jit_decode_fn\b", text)
        ops = set(re.findall(r'op_name="([^"]*)"', text))
        for scope in ("pageio.read/crossing", "pageio.read/dense",
                      "model.attention", "model.ffn", "model.head",
                      "sample", "pageio.write"):
            assert any(scope in op for op in ops), scope


class TestAudit:
    def _log(self, n=5):
        log = AuditLog()
        for i in range(n):
            log.append("rotation", tenant=f"t{i % 2}", new_epoch=i)
        return log

    def test_chain_verifies_and_round_trips(self, tmp_path):
        log = self._log()
        assert len(log) == 5
        assert log.verify_chain()
        assert log.records()[0]["prev"] == "0" * 64
        path = tmp_path / "audit.jsonl"
        log.dump(str(path))
        loaded = AuditLog.load(str(path))
        assert loaded.verify_chain()
        assert loaded.head == log.head
        assert len(loaded.events("rotation")) == 5

    def test_tamper_detected(self):
        log = self._log()
        # Single-field edit: flip one byte of a recorded field.
        log._records[2]["tenant"] = "t9"
        assert not log.verify_chain()

        log = self._log()
        del log._records[1]                     # truncation / drop
        assert not log.verify_chain()

        log = self._log()
        log._records[1], log._records[2] = \
            log._records[2], log._records[1]    # reorder
        assert not log.verify_chain()

        log = self._log()
        log._records[4]["hash"] = "f" * 64      # forged head
        assert not log.verify_chain()

    def test_reserved_fields_rejected(self):
        log = AuditLog()
        with pytest.raises(ValueError):
            log.append("rotation", seq=3)
        with pytest.raises(ValueError):
            log.append("rotation", hash="x")


class TestEngineObs:
    @pytest.mark.parametrize("scheme", sorted(SCHEMES))
    def test_token_parity_all_schemes(self, smoke, prompts, scheme):
        bare = _engine(smoke, scheme=scheme)
        rids = [bare.submit(p, max_new_tokens=4) for p in prompts[:2]]
        want = [bare.run()[r].generated for r in rids]

        eng = _engine(smoke, scheme=scheme, trace=True, audit=True)
        rids = [eng.submit(p, max_new_tokens=4) for p in prompts[:2]]
        done = eng.run()
        assert [done[r].generated for r in rids] == want
        assert len(eng.tracer) > 0
        assert eng.audit.verify_chain()

    def test_engine_snapshot_and_rotation_audit(self, smoke, prompts):
        reg = TenantRegistry(KeyHierarchy(2), max_tenants=2)
        reg.register("a")
        sess = reg.open_session("a")
        eng = _engine(smoke, scheme="seda", registry=reg, rotate_every=2,
                      trace=True, audit=True)
        eng.submit(prompts[0], max_new_tokens=6, session=sess)
        eng.run()
        snap = eng.snapshot()
        assert snap["counters"]["admitted"] == 1
        assert snap["counters"]["decode_steps"] > 0
        assert snap["counters"]["rotations"] > 0
        assert snap["gauges"]["pool_free_pages"] == \
            snap["gauges"]["pool_pages_total"]       # drained engine
        assert snap["histograms"]["tick_seconds"]["count"] > 0
        assert snap["histograms"]["ttft_ticks"]["count"] == 1
        rotations = eng.audit.events("rotation")
        assert rotations and rotations[0]["tenant"] == "a"
        assert eng.audit.verify_chain()
        assert snap["counters"]["audit_events"] == len(eng.audit)
        assert "# TYPE repro_admitted counter" in eng.prometheus()

    def test_cluster_rollup_labels(self, smoke, prompts):
        cluster = ClusterEngine(*smoke, shards=2, max_slots=2,
                                page_tokens=4, pages_per_slot=4,
                                scheme="seda", trace=True, audit=True)
        rids = [cluster.submit(p, max_new_tokens=4) for p in prompts]
        done = cluster.run()
        assert len(done) == len(rids)
        snap = cluster.snapshot()
        shards = snap["shards"]
        assert [s["labels"]["shard"] for s in shards] == ["0", "1"]
        assert snap["rollup"]["admitted"] == \
            sum(s["counters"]["admitted"] for s in shards) == 3
        text = cluster.prometheus()
        assert 'repro_admitted{shard="0"}' in text
        assert 'repro_admitted{shard="1"}' in text
        assert "repro_migrations" in text
        # One shared audit chain across shards.
        assert cluster.audit is cluster.engines[0].audit
        assert cluster.audit.verify_chain()
        # Cluster trace merges every shard's track plus its own.
        pids = {e["pid"] for e in
                cluster.export_trace()["traceEvents"]}
        assert pids == {0, 1, 2}

    def test_trace_valid_under_cluster_rotation(self, smoke, prompts,
                                                tmp_path):
        """Key rotation mid-run must not corrupt the merged trace:
        still valid JSON, and per pid the spans of any single phase
        never overlap (a rotation pausing a shard cannot interleave
        two `decode_dispatch` spans on one track)."""
        reg = TenantRegistry(KeyHierarchy(2), max_tenants=2)
        reg.register("a")
        reg.register("b")
        sessions = [reg.open_session(t) for t in ("a", "b", "a")]
        cluster = ClusterEngine(*smoke, shards=2, max_slots=2,
                                page_tokens=4, pages_per_slot=4,
                                scheme="seda", registry=reg,
                                rotate_every=2, trace=True, audit=True)
        for p, s in zip(prompts, sessions):
            cluster.submit(p, max_new_tokens=6, session=s)
        cluster.run()
        assert cluster.snapshot()["rollup"]["rotations"] > 0

        path = tmp_path / "trace.json"
        doc = cluster.export_trace(str(path))
        loaded = json.loads(path.read_text())   # valid JSON on disk
        assert loaded == doc
        by_pid_phase: dict = {}
        for e in loaded["traceEvents"]:
            assert set(e) >= {"name", "ph", "pid", "tid", "ts", "dur"}
            by_pid_phase.setdefault((e["pid"], e["name"]), []).append(e)
        assert len(by_pid_phase) > 1
        for (pid, name), spans in by_pid_phase.items():
            if name == "queue_wait":
                continue    # requests wait side by side, so waits overlap
            spans.sort(key=lambda e: e["ts"])
            for prev, nxt in zip(spans, spans[1:]):
                assert prev["ts"] + prev["dur"] <= nxt["ts"] + 1e-6, \
                    (pid, name)
