"""One run of one cell: set-up, the measured window, the check.

``run.py`` is the command; this module is what it drives, split out so
that the benchmark's own tests can drive a whole run on the CPU at a
tiny size.  Everything that belongs to one cell, configuration or
metric is read from files found by name:

* ``BENCHMARK.json`` (the manifest): the cell's chips and the metrics
  it reports;
* ``chipbench/workloads/<cell>.json``: scheme, loop, slots, pages,
  length distributions, rate, and the limit of its check;
* ``chipbench/configs/<config>.json``: the model's sizes;
* ``chipbench/metrics/<metric>.py``: one reader per metric;
* ``chipbench/peaks.json``: the chip's peaks by ``device_kind``.

The system under test is the program's ``SecureServingEngine``, driven
through ``submit`` and ``step`` exactly as a server drives it.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import traceback

import numpy as np

from chipbench import model, record, reference, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")
TRACE_S = 10.0          # longest traced stretch of a --trace 1 run
# The engine's root key is deployment state, not an input: one fixed key
# for every seed.  The program compiles its keys into the page programs
# as constants, so a key per seed would recompile every one of them in
# every run (PERF.md, Open questions).
KEY_SEED = 0x5EDA


class Unavailable(RuntimeError):
    """The run cannot measure here (no chip, too few chips, no peaks)."""


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict            # the manifest's workload entry
    cell: dict             # chipbench/workloads/<name>.json
    config: dict           # chipbench/configs/<config>.json
    metrics: list          # [(manifest metric entry)] this run reports


def load_cell(name: str, trace: bool, root: str = ROOT) -> Cell:
    """The cell's files and the metrics a run of it reports."""
    manifest = _json(root, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = _json(root, "chipbench", "workloads", name + ".json")
    config = _json(root, "chipbench", "configs", entry["config"] + ".json")
    kind = "per_layer" if trace else "end_to_end"
    mets = [m for m in manifest[kind]
            if name in m.get("workloads", [name])]
    return Cell(name, entry, cell, config, mets)


def reader(name: str, root: str = ROOT):
    """The ``read(run)`` function of one metric, from its own file."""
    path = os.path.join(root, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(device_kind: str, root: str = ROOT) -> dict:
    table = _json(root, "chipbench", "peaks.json")["devices"]
    if device_kind not in table:
        raise Unavailable(f"device kind {device_kind!r} has no entry in "
                          f"chipbench/peaks.json")
    return table[device_kind]


def check_devices(chips: int):
    """The devices a cell runs on; fails without enough TPU chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Unavailable(f"no TPU: JAX sees {devices[0].platform}")
    if len(devices) < chips:
        raise Unavailable(f"{len(devices)} chips, the cell needs {chips}")
    return devices


# -- the program ------------------------------------------------------------

def program_config(c: dict):
    """(arch, LMConfig) of the program for a configuration file."""
    from repro.configs import get_arch
    arch = get_arch(c["arch"])
    cfg = dataclasses.replace(
        arch.make_config(), n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"], dtype=c["dtype"],
        gated_ffn=c["hidden_act"] == "silu-gated",
        tie_embeddings=c["tie_word_embeddings"])
    return arch, cfg


class Compiles:
    """Counts programs compiled and programs loaded from the persistent
    cache, through JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiled = self.loaded = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def snapshot(self):
        """(programs compiled, programs loaded from the cache): a load
        also ends in a backend-compile event."""
        return (self.compiled - self.loaded, self.loaded)

    def events(self) -> int:
        return self.compiled + self.loaded


# Ticks in a row that must load and compile nothing before warm-up ends:
# longer than the engine's period of deferred checks (16 ticks), so that
# whatever runs once a period has run before the window opens.
QUIET_TICKS = 24


def warm(eng, cell: dict, compiles: "Compiles") -> None:
    """Run every program the window can use once, through the engine's
    own ``submit`` and ``step``: each prompt length alone (its prefill,
    page writer and the decode at its context), then short requests
    until ``QUIET_TICKS`` ticks in a row compile and load nothing."""
    lengths = traffic.shapes(cell)["prompt"]

    def drain():
        while _busy(eng):
            eng.step()

    for p in lengths:
        eng.submit(prompt=[1] * p, max_new_tokens=2)
        drain()
    quiet = 0
    while quiet < QUIET_TICKS:
        if not _busy(eng):
            eng.submit(prompt=[1] * lengths[0], max_new_tokens=8)
        before = compiles.events()
        eng.step()
        quiet = quiet + 1 if compiles.events() == before else 0
    drain()


def build(c: dict, cell: dict, seed: int, traced: bool, device):
    """The engine with this seed's weights (made on the device)."""
    import jax
    from repro.core.secure_memory import SecureKeys
    from repro.models.lm import lm_specs
    from repro.serve.engine import SecureServingEngine
    arch, cfg = program_config(c)
    params = model.adapt(model.make_weights(c, seed, device), lm_specs(cfg))
    eng = SecureServingEngine(
        arch, cfg, params, scheme=cell["scheme"], max_slots=cell["slots"],
        page_tokens=cell["page_tokens"],
        pages_per_slot=cell["pages_per_slot"],
        keys=SecureKeys.derive(KEY_SEED), trace=traced)
    # The pool as every later tick holds it (committed to the device, as
    # a program's output is), so that set-up compiles what ticks run.
    eng.pool = jax.device_put(eng.pool, device)
    return eng


def annotate(eng) -> None:
    """Host annotations in the profiler's trace around each tick phase,
    so that idle gaps can be named by what the host was doing."""
    import jax

    def wrap(name, fn):
        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return wrapped

    for name in ("_tick_begin", "_decode_dispatch", "_decode_collect",
                 "_tick_end", "step"):
        if hasattr(eng, name):        # a phase the engine no longer has
            setattr(eng, name, wrap("engine." + name.lstrip("_"),
                                    getattr(eng, name)))


def _waiting(eng) -> int:
    return sum(r.state == "waiting" for r in eng.requests.values())


def _busy(eng) -> bool:
    return any(r.state in ("waiting", "running")
               for r in eng.requests.values())


# -- the window ---------------------------------------------------------------

class Window:
    """Drives the engine for ``seconds`` and records every token."""

    def __init__(self, eng, cell: dict, reqs: list, run: record.Run):
        self.eng, self.cell, self.run = eng, cell, run
        self.reqs = reqs
        self.records = [record.ReqRecord(len(r.prompt), r.due) for r in reqs]
        self.recs = {}                 # engine rid -> ReqRecord
        self.seen = collections.Counter()
        self.error = None

    def submit(self, r: traffic.Req, now: float | None) -> None:
        rid = self.eng.submit(prompt=r.prompt, max_new_tokens=r.max_new)
        self.recs[rid] = self.records[r.idx]
        self.recs[rid].submit = now

    def window_requests(self, seconds: float) -> list:
        """Closed loop: the requests served in the window.  Open loop:
        every request due in it, sent or not."""
        if self.cell["loop"] == "closed":
            return [r for r in self.records if r.times]
        return [r for r in self.records if r.due < seconds]

    def note(self, finished: list, t: float | None) -> dict:
        """Token times of one step; returns its page counts."""
        eng, pt = self.eng, self.cell["page_tokens"]
        live = [s.req for s in eng.slots if s is not None] + list(finished)
        written = 0
        for req in live:
            new = len(req.generated) - self.seen[req.rid]
            if new <= 0:
                continue
            if self.seen[req.rid] == 0:
                written += math.ceil(len(req.prompt) / pt)
            written += new - (self.seen[req.rid] == 0)
            self.seen[req.rid] = len(req.generated)
            if t is not None:
                self.recs[req.rid].times.extend([t] * new)
        return {"pages_written": written}

    def step(self, t0: float) -> None:
        stats = self.eng.stats
        pages0, dec0 = stats["decode_page_reads"], stats["decode_steps"]
        finished = self.eng.step()
        t = time.perf_counter() - t0
        s = self.note(finished, t)
        s.update(t=t, decodes=stats["decode_steps"] - dec0,
                 pages_read=stats["decode_page_reads"] - pages0)
        self.run.steps.append(s)

    def fill(self) -> None:
        """Closed loop: queue every request, then one step admits the
        first ``slots`` of them (part of set-up)."""
        for r in self.reqs:
            self.submit(r, None)
        self.note(self.eng.step(), None)

    def measure(self, seconds: float, tracer=None) -> float:
        """The window; returns its length as run."""
        import jax
        closed = self.cell["loop"] == "closed"
        pending = collections.deque(() if closed else self.reqs)
        t0 = time.perf_counter()
        now = 0.0
        try:
            while now < seconds:
                if tracer is not None:
                    tracer.poll(now)
                while pending and pending[0].due <= now:
                    self.submit(pending.popleft(), time.perf_counter() - t0)
                if _busy(self.eng):
                    self.step(t0)
                else:
                    nxt = pending[0].due if pending else seconds
                    time.sleep(max(0.0, min(nxt, seconds) - now))
                now = time.perf_counter() - t0
        except Exception as e:  # noqa: BLE001 - any fault fails the run
            traceback.print_exc()
            self.error = e
        if tracer is not None:
            tracer.stop(now)
        if self.error is None:
            jax.block_until_ready(self.eng.pool)
        return now


class Tracer:
    """Profiles the last ``TRACE_S`` seconds of the window; the trace is
    written out after the window has closed."""

    def __init__(self, seconds: float, logdir: str):
        self.lo = max(0.0, seconds - TRACE_S)
        self.logdir = logdir
        self.on = False
        self.span = None
        self.times = None

    def poll(self, now: float) -> None:
        import jax
        if not self.on and self.times is None and now >= self.lo:
            shutil.rmtree(self.logdir, ignore_errors=True)
            jax.profiler.start_trace(self.logdir)
            self.span = jax.profiler.TraceAnnotation("chipbench.window")
            self.span.__enter__()
            self.on, self.start = True, now

    def stop(self, now: float) -> None:
        import jax
        if self.on:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False
            self.times = (self.start, now)


# -- the check ----------------------------------------------------------------

def sample(window: Window, seed: int, k: int) -> list:
    """(prompt, served tokens) of ``k`` requests the window served: the
    one with the most served tokens, then others drawn from the seed,
    finished ones first."""
    reqs = [r for r in map(window.eng.requests.get, window.recs)
            if r.generated]
    if not reqs:
        return []
    longest = max(reqs, key=lambda r: (len(r.generated), -r.rid))
    rest = [r for r in reqs if r is not longest]
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(rest)) if rest else []
    ranked = sorted((rest[i] for i in order),
                    key=lambda r: r.state != "finished")
    chosen = [longest] + ranked[:k - 1]
    return [(list(r.prompt), list(r.generated)) for r in chosen]


def reference_gaps(c: dict, seed: int, picked: list, device,
                   control: bool = False) -> tuple:
    """Widest gap (and tokens compared) of the served tokens against the
    float32 reference, with weights made again from the seed."""
    w = model.make_weights(c, seed, device)
    gaps = [reference.served_gaps(c, w, p, s, control) for p, s in picked]
    del w
    n = sum(len(g) for g in gaps)
    return (float(max(g.max() for g in gaps)) if gaps else float("inf"), n)


# -- one run ------------------------------------------------------------------

def run_cell(cellspec: Cell, seed: int, seconds: float, traced: bool,
             devices, peaks: dict, t_start: float, log=print,
             control: bool = False) -> dict:
    """One run: returns the result object (the last line of output).

    ``control=True`` puts the float8 control in the program's place for
    the check: ``logit_gap`` and ``correct`` are then the control's, read
    on the tokens the control puts first at the positions the window
    served, and ``program_logit_gap`` is the program's own reading.  The
    benchmark's own runs never do this."""
    import jax
    from repro.launch.serve import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = Compiles()
    c, cell = cellspec.config, cellspec.cell
    device = devices[0]
    marks = [("start", t_start), ("imports", time.perf_counter())]
    eng = build(c, cell, seed, traced, device)
    jax.block_until_ready(eng.params)
    marks.append(("weights and engine", time.perf_counter()))
    warm(eng, cell, compiles)
    marks.append(("warm-up", time.perf_counter()))
    reqs = traffic.make_requests(cell, seed, seconds, c["vocab_size"])
    run = record.Run(config=c, cell=cell, peaks=peaks, seconds=seconds)
    win = Window(eng, cell, reqs, run)
    if cell["loop"] == "closed":
        win.fill()
    marks.append(("traffic and fill", time.perf_counter()))
    log("[chipbench] set-up phases: " + ", ".join(
        f"{n} {b - a:.1f} s" for (_, a), (n, b) in zip(marks, marks[1:])))
    tracer_eng = eng.tracer
    if traced:
        annotate(eng)
        tracer_eng.clear()
    tracer = Tracer(seconds, TRACE_DIR) if traced else None
    c_setup = compiles.snapshot()
    stats0 = dict(eng.stats)
    run.setup_s = time.perf_counter() - t_start
    log(f"[chipbench] set-up {run.setup_s:.1f} s, "
        f"{'cold' if c_setup[0] else 'warm'}: {c_setup[0]} programs "
        f"compiled, {c_setup[1]} loaded from the compile cache")

    run.window_s = win.measure(seconds, tracer)
    c_win = tuple(a - b for a, b in zip(compiles.snapshot(), c_setup))
    run.counters = {k: eng.stats[k] - stats0.get(k, 0) for k in eng.stats}
    if traced:
        run.spans = collections.Counter()
        for ev in tracer_eng.events():
            run.spans[ev["name"]] += ev["dur"] / 1e6
    run.requests = win.window_requests(seconds)
    run.peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in devices[:cellspec.entry["chips"]])
    attempted = len(run.requests)
    failed = 0
    if win.error is not None:
        log(f"[chipbench] the window failed: {win.error!r}")
        failed = max(1, sum(1 for r in eng.requests.values()
                            if r.state in ("running", "waiting", "failed")))
    try:
        mac_ok = eng.deferred_check()
    except Exception:  # noqa: BLE001 - a check that cannot run has failed
        traceback.print_exc()
        mac_ok = False
    dec = run.counters.get("decode_steps", 0)
    log(f"[chipbench] queue at the window's end: {_waiting(eng)} "
        f"waiting, {sum(s is not None for s in eng.slots)} in slots")
    log(f"[chipbench] window {run.window_s:.2f} s: {c_win[0]} programs "
        f"compiled and {c_win[1]} loaded inside it; {dec} decode ticks, "
        f"fused read on {run.counters.get('fused_read_ticks', 0)} of them "
        f"(reference read on {run.counters.get('reference_read_ticks', 0)})"
        f"; requests attempted {attempted}, failed {failed}")

    picked = sample(win, seed, cell["check"]["requests"])
    integrity = run.counters.get("integrity_failures", 0)
    fused = run.counters.get("fused_read_ticks", 0) / max(dec, 1)
    log(f"[chipbench] fused read share of decode ticks {fused:.3f}")
    del win, eng, reqs, tracer_eng
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    log(f"[chipbench] program state freed: {live} bytes of arrays live")
    gap, n_tok = reference_gaps(c, seed, picked, device)
    log(f"[chipbench] reference: {len(picked)} requests, {n_tok} served "
        f"tokens compared")
    program_gap = gap
    if control:
        gap = reference_gaps(c, seed, picked, device, True)[0]
        log(f"[chipbench] the float8 control in the program's place: widest "
            f"gap {gap!r} (the program's {program_gap!r})")

    if traced and tracer.times is not None:
        from chipbench import trace as tr
        red = tr.reduce(tr.load(tr.find_xplane(TRACE_DIR)))
        run.trace, run.traced = red, tracer.times
    metrics = {}
    for m in cellspec.metrics:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {
        "logit_gap": {"value": gap, "limit": cell["check"]["logit_gap"]},
        "failed": {"value": failed, "limit": 0},
        "window_compiles": {"value": c_win[0] + c_win[1], "limit": 0},
        "integrity_failures": {"value": integrity, "limit": 0},
        "pool_mac_failures": {"value": int(not mac_ok), "limit": 0},
    }
    correct = all(v["limit"] is not None and v["value"] <= v["limit"]
                  for v in checks.values())
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices), "memory_peak_bytes": run.peak_bytes}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": dev}
    if run.trace is not None:
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    if control:
        out["program_logit_gap"] = program_gap
    out["checks"] = checks
    for name, v in checks.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    return out
