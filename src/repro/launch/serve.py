"""Serving driver: load a SeDA-secured checkpoint and decode batches.

    PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b \
        --smoke --ckpt-dir /tmp/ck --prompt-len 16 --gen-len 16 --batch 4

Weights restore ONLY if their layer MACs verify (tampered checkpoints
are refused); the deferred model-MAC check runs after the generation
loop (paper Table I semantics).

``--engine paged`` serves through the continuous-batching secure
engine instead: the KV cache lives as a paged, MAC-protected pool
(page size = the scheme's optBlk granularity multiple), decode steps
verify only touched pages and re-MAC only dirty ones::

    PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b \
        --smoke --engine paged --scheme seda --batch 8 --gen-len 16

``--tenants N`` registers N tenants in a key-management registry and
serves the batch round-robin across their sessions: every tenant's KV
pages live under its own (tenant, epoch) keys from the hierarchical
KDF, with weighted-fair admission and tenant-scoped eviction.
``--rotate-every K`` additionally rotates one tenant's keys every K
scheduler ticks (round-robin), exercising live lazy rotation::

    PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b \
        --smoke --engine paged --scheme seda --batch 8 --gen-len 16 \
        --tenants 4 --rotate-every 8

``--shards N`` serves through the cluster engine instead: one shard
engine (and one paged pool, shard-bound RePA/CTR identity included)
per device, least-loaded routing with tenant affinity, and secure page
migration under imbalance.  On a host with N accelerators each shard
owns one of them; on CPU the N devices are conjured via
``--xla_force_host_platform_device_count`` (set by :func:`main` before
jax initializes its backends)::

    PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b \
        --smoke --engine paged --scheme seda --batch 8 --gen-len 16 \
        --shards 2

``--prompt-len`` takes a comma list for the paged engine, cycled over
the requests (``--prompt-len 128,512`` mixes short and long prompts).

:func:`main` turns on JAX's persistent compilation cache: the directory
``JAX_COMPILATION_CACHE_DIR`` names when it is set, otherwise
``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.secure_ckpt import latest_step, load_checkpoint
from repro.configs import get_arch
from repro.core.secure_memory import SecureKeys
from repro.models import lm as lm_mod
from repro.models.layers import init_params, shape_structs
from repro.serve.serve_step import (greedy_sample, make_decode_step,
                                    make_prefill_step)

log = logging.getLogger("repro.serve")

# src/repro/launch/serve.py -> the checkout root.
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    is used as it is.  Otherwise the cache lives at a fixed path in the
    checkout, so a second run of the same programs finds its compiles.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _force_host_devices(n: int) -> None:
    """Ask the CPU backend for ``n`` devices (a no-op for accelerators
    and once the CPU backend has initialized)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


class _JsonFormatter(logging.Formatter):
    """One JSON object per record: ts/level/event/msg + extra fields."""

    def format(self, record: logging.LogRecord) -> str:
        doc = {"ts": round(record.created, 3),
               "level": record.levelname.lower(),
               "event": getattr(record, "event", "message"),
               "msg": record.getMessage()}
        doc.update(getattr(record, "fields", None) or {})
        return json.dumps(doc, sort_keys=True)


def _setup_logging(args) -> None:
    """Route CLI output through the ``repro.serve`` logger.

    Default: plain messages on stdout, character-identical to the old
    ``print`` output.  ``--json-logs`` swaps in one structured JSON
    record per line; ``--quiet`` drops everything below WARNING.
    """
    log.handlers.clear()
    handler = logging.StreamHandler(sys.stdout)
    handler.setFormatter(_JsonFormatter() if args.json_logs
                         else logging.Formatter("%(message)s"))
    log.addHandler(handler)
    log.setLevel(logging.WARNING if args.quiet else logging.INFO)
    log.propagate = False


def _log(event: str, msg: str, **fields) -> None:
    log.info(msg, extra={"event": event, "fields": fields})


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", default="16",
                    help="prompt tokens per request; a comma list is "
                         "cycled over the requests (--engine paged only)")
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--engine", choices=("simple", "paged"), default="simple")
    ap.add_argument("--scheme", default="seda",
                    help="protection scheme for --engine paged")
    ap.add_argument("--page-tokens", type=int, default=8)
    ap.add_argument("--pages-per-slot", type=int, default=0,
                    help="0 = sized from prompt+gen length")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="0 = batch * pages_per_slot")
    ap.add_argument("--tenants", type=int, default=0,
                    help="serve through N per-tenant key domains "
                         "(--engine paged only; 0 = single-tenant)")
    ap.add_argument("--rotate-every", type=int, default=0,
                    help="rotate one tenant's keys every K ticks "
                         "(round-robin; needs --tenants)")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve through an N-shard cluster engine, one "
                         "paged pool per device (--engine paged only; "
                         "0 = single shard engine)")
    ap.add_argument("--fault-tolerance", action="store_true",
                    help="contain integrity faults instead of aborting: "
                         "quarantine failing pages, recover sessions by "
                         "secure recompute, fail over compromised shards "
                         "(--engine paged only)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress informational output")
    ap.add_argument("--json-logs", action="store_true",
                    help="one structured JSON record per log line")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable tick-phase tracing; write Chrome "
                         "trace-event JSON here (--engine paged only)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write a metrics snapshot (JSON) after the run")
    ap.add_argument("--metrics-prom", default=None, metavar="PATH",
                    help="write Prometheus text exposition after the run")
    ap.add_argument("--audit-proof-out", default=None, metavar="PATH",
                    help="capture one Merkle membership proof per live "
                         "session after the first tick, verify each "
                         "host-independently, and write the bundle plus "
                         "the final (cluster) root here as JSON "
                         "(--engine paged only)")
    ap.add_argument("--audit-out", default=None, metavar="PATH",
                    help="enable the hash-chained audit log; dump it "
                         "here as JSON lines (--engine paged only)")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="per-tenant wall-clock ttft SLO target in ms; "
                         "breaches are counted + audited "
                         "(--engine paged only)")
    ap.add_argument("--slo-p99-ticks", type=float, default=0.0,
                    help="rolling p99 tick-latency SLO target in ms "
                         "(--engine paged only)")
    ap.add_argument("--http-port", type=int, default=0,
                    help="serve /healthz (SLO health JSON) and /metrics "
                         "(Prometheus text) on 127.0.0.1:PORT during "
                         "the run (--engine paged only)")
    ap.add_argument("--profile-json", default=None, metavar="PATH",
                    help="write the protection-vs-model device-cost "
                         "profile (obs/profiler.py) here after the run "
                         "(--engine paged only; compiles one decode "
                         "variant per bucket)")
    args = ap.parse_args(argv)
    args.prompt_lens = [int(x) for x in str(args.prompt_len).split(",")]
    if args.shards > 1:
        _force_host_devices(args.shards)
    _setup_logging(args)
    enable_compile_cache()
    if len(args.prompt_lens) > 1 and args.engine != "paged":
        raise SystemExit("a --prompt-len list needs --engine paged (the "
                         "simple loop decodes one dense batch)")
    if args.tenants and args.engine != "paged":
        raise SystemExit("--tenants needs --engine paged")
    if args.shards and args.engine != "paged":
        raise SystemExit("--shards needs --engine paged")
    if args.rotate_every and not args.tenants:
        raise SystemExit("--rotate-every needs --tenants (there are no "
                         "tenant keys to rotate otherwise)")
    if args.engine != "paged" and (args.trace_out or args.metrics_json
                                   or args.metrics_prom or args.audit_out
                                   or args.audit_proof_out
                                   or args.slo_ttft_ms or args.slo_p99_ticks
                                   or args.http_port or args.profile_json
                                   or args.fault_tolerance):
        raise SystemExit("--trace-out/--metrics-json/--metrics-prom/"
                         "--audit-out/--audit-proof-out/--slo-*/"
                         "--http-port/--profile-json/"
                         "--fault-tolerance need --engine paged (the "
                         "simple loop has no observability surface)")

    arch = get_arch(args.arch)
    if arch.kind == "encdec":
        raise SystemExit("use the decoder demo in examples/ for enc-dec")
    cfg = arch.make_smoke_config() if args.smoke else arch.make_config()
    specs = lm_mod.lm_specs(cfg)
    keys = SecureKeys.derive(args.seed)

    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        step = latest_step(args.ckpt_dir)
        path = os.path.join(args.ckpt_dir, f"step_{step:08d}")
        params, _ = load_checkpoint(path, shape_structs(specs), keys)
        _log("checkpoint", f"[serve] loaded + verified checkpoint {path}",
             path=path)
    else:
        params = init_params(specs, jax.random.PRNGKey(args.seed))
        _log("checkpoint", "[serve] no checkpoint: serving fresh init")

    if args.engine == "paged":
        return _serve_paged(arch, cfg, params, args)

    prompt_len = args.prompt_lens[0]
    max_len = prompt_len + args.gen_len
    prefill = jax.jit(make_prefill_step(arch, cfg, max_len))
    decode = jax.jit(make_decode_step(arch, cfg))

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(
        1, cfg.vocab, (args.batch, prompt_len), dtype=np.int64)
        .astype(np.int32))
    logits, caches = prefill(params, {"tokens": prompts})
    tok = greedy_sample(logits)
    out = [tok]
    t0 = time.perf_counter()
    for _ in range(args.gen_len - 1):
        logits, caches = decode(params, tok, caches)
        tok = greedy_sample(logits)
        out.append(tok)
    dt = time.perf_counter() - t0
    toks = jnp.concatenate(out, axis=1)
    rate = args.batch * args.gen_len / max(dt, 1e-9)
    _log("summary", f"[serve] {args.gen_len} tokens x {args.batch} requests "
         f"({rate:.1f} tok/s)",
         gen_len=args.gen_len, batch=args.batch, tok_per_s=rate)
    return {"tokens": np.asarray(toks), "tok_per_s": rate}


def _serve_paged(arch, cfg, params, args) -> dict:
    """Continuous-batching path: paged, MAC-protected KV pool."""
    from repro.serve.engine import SecureServingEngine

    pages_per_slot = args.pages_per_slot or -(
        -(max(args.prompt_lens) + args.gen_len) // args.page_tokens)
    n_pages = args.n_pages or args.batch * pages_per_slot
    registry = None
    sessions = []
    if args.tenants:
        from repro.tenancy import KeyHierarchy, TenantRegistry
        registry = TenantRegistry(KeyHierarchy(args.seed),
                                  max_tenants=args.tenants)
        for t in range(args.tenants):
            registry.register(f"tenant-{t}")
            sessions.append(registry.open_session(f"tenant-{t}"))
    obs_kw = dict(trace=bool(args.trace_out), audit=bool(args.audit_out))
    ft = True if args.fault_tolerance else None
    if args.shards:
        from repro.serve.cluster import ClusterEngine
        per_shard = -(-args.batch // args.shards)
        eng = ClusterEngine(
            arch, cfg, params, shards=args.shards, scheme=args.scheme,
            max_slots=per_shard, page_tokens=args.page_tokens,
            pages_per_slot=pages_per_slot,
            n_pages=-(-n_pages // args.shards),
            keys=SecureKeys.derive(args.seed),
            registry=registry, rotate_every=args.rotate_every,
            fault_tolerance=ft, **obs_kw)
        stats_of = lambda: dict(eng.engine_stats, **eng.stats)  # noqa: E731
    else:
        eng = SecureServingEngine(
            arch, cfg, params, scheme=args.scheme, max_slots=args.batch,
            page_tokens=args.page_tokens, pages_per_slot=pages_per_slot,
            n_pages=n_pages, keys=SecureKeys.derive(args.seed),
            registry=registry, rotate_every=args.rotate_every,
            fault_tolerance=ft, **obs_kw)
        stats_of = lambda: eng.stats  # noqa: E731

    # SLO watchdogs: one monitor per shard engine; /healthz reports the
    # worst shard.  Without targets (and without --http-port) nothing
    # attaches, so the hot path stays untouched.
    monitors = []
    if args.slo_ttft_ms or args.slo_p99_ticks or args.http_port:
        from repro.obs.slo import SLOMonitor
        for shard_eng in (eng.engines if args.shards else [eng]):
            monitors.append(SLOMonitor(
                ttft_ms=args.slo_ttft_ms or None,
                p99_tick_ms=args.slo_p99_ticks or None,
                min_stall_s=1.0).attach(shard_eng))
    server = None
    if args.http_port:
        server = _start_http(args.http_port, monitors, eng)
        _log("http", f"[serve] /healthz + /metrics on "
             f"127.0.0.1:{args.http_port}", port=args.http_port)

    rng = np.random.default_rng(args.seed)
    rids = []
    for i in range(args.batch):
        prompt_len = args.prompt_lens[i % len(args.prompt_lens)]
        prompt = list(map(int, rng.integers(1, cfg.vocab, prompt_len)))
        session = sessions[i % len(sessions)] if sessions else None
        rids.append(eng.submit(prompt=prompt, max_new_tokens=args.gen_len,
                               session=session))
    proof_bundle = None
    if args.audit_proof_out:
        # One tick admits the batch; every session is then resident and
        # can prove membership against the live Merkle root — the
        # verification below is exactly what a tenant runs, keyless.
        eng.step()
        proof_bundle = _capture_audit_proofs(eng, sessions,
                                             bool(args.shards))
        _log("audit-proof", f"[serve] {proof_bundle['verified']} session "
             f"proofs captured + verified at tick {proof_bundle['tick']}",
             tick=proof_bundle["tick"], proofs=proof_bundle["verified"])
    t0 = time.perf_counter()
    done, sig = _run_graceful(eng, is_cluster=bool(args.shards))
    dt = time.perf_counter() - t0
    if sig is not None:
        n_done = sum(1 for r in rids
                     if eng.requests[r].state == "finished")
        _log("shutdown", f"[serve] signal {sig}: graceful shutdown after "
             f"tick {eng.tick} ({n_done}/{args.batch} requests finished); "
             f"flushing observability artifacts",
             signal=int(sig), tick=eng.tick, finished=n_done,
             requests=args.batch)
    n_tokens = sum(len(eng.requests[r].generated) for r in rids)
    rate = n_tokens / max(dt, 1e-9)
    stats = stats_of()
    mode = f"paged/{args.scheme}" + (
        f"/{args.tenants} tenants" if args.tenants else "") + (
        f"/{args.shards} shards" if args.shards else "")
    extra = (f", {stats['migrations']} migrations" if args.shards else "")
    mac_ok = eng.deferred_check()
    _log("summary", f"[serve] {mode}: {n_tokens} tokens over "
         f"{args.batch} requests ({rate:.1f} tok/s incl. compile), "
         f"{stats['preemptions']} preemptions, "
         f"{stats['rotations']} key rotations{extra}, "
         f"deferred {'root' if args.shards else 'pool'} MAC "
         f"{'OK' if mac_ok else 'FAIL'}",
         mode=mode, tokens=n_tokens, requests=args.batch, tok_per_s=rate,
         ticks=eng.tick, stats=dict(stats), deferred_mac_ok=bool(mac_ok))
    if done.latency:
        _log("latency", f"[serve] latency (ticks): "
             f"ttft p50={done.latency['p50_ttft_ticks']:.1f} "
             f"p95={done.latency['p95_ttft_ticks']:.1f} "
             f"p99={done.latency['p99_ttft_ticks']:.1f}",
             **done.latency)
    # Final stall poll *now*, before the obs dumps: profiling compiles
    # for seconds, and idle time after the run finished is not a stall.
    for m in monitors:
        m.check_stalled()
    _dump_obs(eng, args)
    if args.audit_proof_out:
        _dump_audit_proofs(eng, args, proof_bundle)
    if monitors:
        from repro.obs.slo import merge_health
        health = merge_health([m.health() for m in monitors])
        _log("slo", f"[serve] SLO health: {health['status']}",
             **{"health": health})
    if server is not None:
        server.shutdown()
    if sig is None and all(eng.requests[r].state == "finished"
                           for r in rids):
        toks = np.asarray([done[r].generated for r in rids], np.int32)
    else:
        # Interrupted (or fault-tolerant with lost sessions): per-
        # request emission lengths are ragged.
        toks = [list(map(int, eng.requests[r].generated)) for r in rids]
    if any(m.hard_breach for m in monitors):
        _log("slo", "[serve] hard SLO breach (integrity alarm or stuck "
             "tick) — exiting non-zero")
        raise SystemExit(3)
    return {"tokens": toks, "tok_per_s": rate, "stats": stats,
            "latency": done.latency, "deferred_mac_ok": bool(mac_ok),
            "engine": eng}


def _run_graceful(eng, *, is_cluster: bool):
    """Drive the engine tick-by-tick so SIGINT/SIGTERM drain cleanly.

    A signal only sets a flag: the in-flight tick always finishes (no
    torn pool state, audit chain stays intact), the loop exits before
    the next one, and the caller flushes artifacts and applies the
    normal SLO exit-code discipline on the partial result.  Returns
    ``(result, signum-or-None)``; prior handlers are restored."""
    import signal

    from repro.serve.engine import RunResult, latency_percentiles

    got: list = []
    prev = {}
    for s in (signal.SIGINT, signal.SIGTERM):
        try:
            prev[s] = signal.signal(s, lambda signum, frame:
                                    got.append(signum))
        except ValueError:  # pragma: no cover - not the main thread
            pass

    def busy() -> bool:
        if is_cluster:
            return eng._busy()
        return bool(eng._n_waiting()
                    or any(s is not None for s in eng.slots))

    try:
        for _ in range(100_000):
            if not busy() or got:
                break
            eng.step()
        else:
            raise RuntimeError("serve loop exceeded max_ticks")
        if got:
            result = RunResult(
                {rid: req for rid, req in eng.requests.items()
                 if req.state == "finished"})
            result.latency = latency_percentiles(eng.requests.values())
            return result, got[0]
        # Drained: run() performs the end-of-run deferred checks (and,
        # under fault tolerance, keeps ticking if containment requeued
        # work) and builds the result exactly as before.
        return eng.run(), None
    finally:
        for s, h in prev.items():
            signal.signal(s, h)


def _start_http(port: int, monitors: list, eng):
    """Stdlib /healthz + /metrics endpoint on localhost, daemon thread.

    /healthz returns the merged monitor health (HTTP 503 once any
    shard is *failing* — integrity alarm or stuck tick — so probes can
    pull the instance); /metrics returns the Prometheus exposition of
    the engine (cluster: all shards, ``shard=`` labels).
    """
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from repro.obs.slo import merge_health

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 - http.server API
            if self.path.split("?")[0] == "/healthz":
                for m in monitors:
                    m.check_stalled()
                doc = merge_health([m.health() for m in monitors])
                code = 503 if doc["status"] == "failing" else 200
                body = json.dumps(doc, indent=2, sort_keys=True).encode()
                ctype = "application/json"
            elif self.path.split("?")[0] == "/metrics":
                body = eng.prometheus().encode()
                code, ctype = 200, "text/plain; version=0.0.4"
            else:
                body, code, ctype = b"not found\n", 404, "text/plain"
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # noqa: D102 - quiet by default
            pass

    server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server


def _capture_audit_proofs(eng, sessions, is_cluster: bool) -> dict:
    """Audit proofs for every live session, tenant-verified in place."""
    from repro.serve import merkle_pool as mkp
    proofs = []
    for session in (sessions or [None]):
        got = eng.audit_proof(session)
        proofs.extend(got if is_cluster else [got])
    for p in proofs:
        mkp.verify_proof(p, expected_root=p.root, tenant=p.tenant)
    return {"tick": eng.tick, "verified": len(proofs),
            "proofs": [p.to_dict() for p in proofs]}


def _dump_audit_proofs(eng, args, bundle) -> None:
    """Write the captured proof bundle + the final attested root(s)."""
    from repro.serve import merkle_pool as mkp
    if args.shards:
        pairs = eng.sharded.merkle_roots()
        final = {"cluster_root": mkp.compress_roots(pairs).hex(),
                 "shard_roots": [[s, r.hex()] for s, r in pairs]}
    else:
        final = {"root": eng.merkle.root_hex()}
    payload = dict(bundle or {"tick": eng.tick, "verified": 0,
                              "proofs": []})
    payload["final"] = final
    with open(args.audit_proof_out, "w") as f:
        json.dump(payload, f, indent=1)
    _log("audit-proof", f"[serve] audit-proof bundle "
         f"({len(payload['proofs'])} proofs) -> {args.audit_proof_out}",
         path=args.audit_proof_out, proofs=len(payload["proofs"]))


def _dump_obs(eng, args) -> None:
    """Write the requested observability artifacts after a paged run."""
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(eng.snapshot(), f, indent=2, sort_keys=True)
        _log("metrics", f"[serve] metrics snapshot -> {args.metrics_json}",
             path=args.metrics_json)
    if args.metrics_prom:
        with open(args.metrics_prom, "w") as f:
            f.write(eng.prometheus())
        _log("metrics", f"[serve] prometheus text -> {args.metrics_prom}",
             path=args.metrics_prom)
    if args.trace_out:
        doc = eng.export_trace(args.trace_out)
        _log("trace", f"[serve] {len(doc['traceEvents'])} trace events -> "
             f"{args.trace_out}",
             path=args.trace_out, events=len(doc["traceEvents"]))
    if args.profile_json:
        doc = eng.profile()
        with open(args.profile_json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        _log("profile", f"[serve] device-cost profile -> "
             f"{args.profile_json}", path=args.profile_json)
    if args.audit_out:
        eng.audit.dump(args.audit_out)
        _log("audit", f"[serve] {len(eng.audit)} audit records "
             f"(chain {'OK' if eng.audit.verify_chain() else 'BROKEN'}) -> "
             f"{args.audit_out}",
             path=args.audit_out, records=len(eng.audit),
             chain_ok=eng.audit.verify_chain())


if __name__ == "__main__":
    main()
