"""Cluster scheduler: sharded secure serving across many devices.

A :class:`ClusterEngine` serves one logical request stream over N
shards, each shard a full
:class:`~repro.serve.engine.SecureServingEngine` (continuous batching,
paged MAC-protected pool, optional per-tenant key domains) pinned to
its own accelerator:

* **routing** — :meth:`submit` places each request on the least-loaded
  shard, with tenant affinity: among near-tied shards, one already
  holding the tenant's pages wins (its key rows are hot and its quota
  accounting is local);
* **one multi-device dispatch per tick** — every shard's jitted decode
  is *dispatched* before any shard is *collected* (the engine tick is
  split into begin/dispatch/collect/end phases), so the per-tick
  device work of all shards overlaps instead of serializing;
* **shard-bound integrity** — every shard's pool carries the shard id
  in its RePA bindings and CTR counters (:mod:`repro.serve.kv_pages`),
  and the per-shard deferred pool MACs roll up into a cluster root MAC
  (:mod:`repro.serve.sharded_pool`) checked off the critical path;
* **secure page migration** — when a shard starves (queued work it
  cannot admit, or imminent page-growth pressure) while another has
  room, the starved shard's youngest running slot MOVES: its pages are
  decrypted + verified under the source shard's binding, hop devices
  as plaintext inside the trusted computation, and are re-encrypted +
  re-MACed under the destination's binding — no eviction, no prefill
  recompute, and the source-shard ciphertext is useless at the
  destination;
* **cluster-wide rotation** — :meth:`rotate` runs through the shared
  registry, whose pre/post hooks fan out to every shard engine: pages
  about to leave the retained key window are eagerly resealed on
  whichever shard holds them;
* **shard failover** — with ``fault_tolerance`` on, a shard whose tick
  or root-MAC contribution raises is folded out of the cluster: its
  sessions drain onto surviving shards by secure recompute (a
  compromised shard's pages are never migrated or trusted), its pool
  MAC leaves the root compression, and ``shard_failovers`` counts the
  event.  Page-level faults stay contained inside the shard engine
  (slot quarantine + recovery) and never escalate to failover.

Works on one host: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
gives N CPU devices; with a single device the shards stay logical
(separate pools, same device) and everything — including cross-shard
replay rejection — behaves identically.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import secure_memory as sm
from repro.obs import audit as audit_mod
from repro.obs import metrics as metrics_mod
from repro.obs import trace as trace_mod
from repro.serve import kv_pages as kvp
from repro.serve.engine import (IntegrityError, RunResult,
                                SecureServingEngine, SubmitAPI,
                                SubmitRequest, latency_percentiles)
from repro.serve.sharded_pool import ShardedKVPool

__all__ = ["ClusterEngine"]


class ClusterEngine(SubmitAPI):
    """N shard engines behind one ``submit()``/``run()`` plane.

    Single-tenant use::

        cluster = ClusterEngine(arch, cfg, params, shards=2,
                                scheme="seda", max_slots=2,
                                page_tokens=8, pages_per_slot=4)
        rids = [cluster.submit(prompt=p, max_new_tokens=8) for p in prompts]
        done = cluster.run()        # RunResult, same shape as Engine's

    Multi-tenant: pass ``registry=`` exactly as for the single engine;
    sessions are cluster-wide (the registry is shared by every shard).
    ``max_slots`` / ``n_pages`` are PER SHARD — a cluster of 4 shards
    with ``max_slots=4`` decodes up to 16 slots per tick.
    """

    def __init__(self, arch, cfg, params, *, shards: int = 2,
                 scheme: str = "seda", max_slots: int = 4,
                 page_tokens: int = 8, pages_per_slot: int = 8,
                 n_pages: Optional[int] = None,
                 keys: Optional[sm.SecureKeys] = None,
                 registry=None, rotate_every: int = 0,
                 defer_interval: int = 16, devices=None,
                 migrate: bool = True, fault_tolerance=None,
                 trace=None, audit=None,
                 **engine_kw):
        if shards < 1:
            raise ValueError("need at least one shard")
        if rotate_every and registry is None:
            raise ValueError("rotate_every needs a tenant registry")
        if devices is None:
            local = jax.local_devices()
            # One physical device: keep the shards logical (no committed
            # placement) — bit-identical to the single-device engine.
            devices = ([None] * shards if len(local) == 1
                       else [local[s % len(local)] for s in range(shards)])
        elif len(devices) != shards:
            raise ValueError(f"{len(devices)} devices for {shards} shards")
        self.registry = registry
        self.rotate_every = rotate_every
        self.defer_interval = defer_interval
        self.migrate = migrate
        # Shard failover mirrors the engine knob: None = strict (an
        # IntegrityError escapes and aborts the cluster); True or a
        # RecoveryPolicy also turns on page-level containment inside
        # every shard engine.
        self.ft = None
        if fault_tolerance:
            from repro.serve.faults import RecoveryPolicy
            self.ft = (RecoveryPolicy() if fault_tolerance is True
                       else fault_tolerance)
        self.failed_shards: set = set()
        # Per-migration audit hand-offs: {"rid", "from_shard",
        # "to_shard", "src_root", "proof"} — the destination-side proof
        # taken the moment the slot landed (see ``_migrate_slot``).
        self.migration_proofs: list = []
        if keys is None:
            keys = sm.SecureKeys.derive(0)
        # One chained audit log for the whole cluster: every shard's
        # records land on a single chain (the shard id is a field), so
        # cross-shard event ordering is itself tamper-evident.
        if isinstance(audit, audit_mod.AuditLog):
            self.audit = audit                # adopt even when empty/falsy
        elif audit:
            self.audit = audit_mod.AuditLog()
        else:
            self.audit = None
        self.engines = []
        for s in range(shards):
            dev = devices[s]
            self.engines.append(SecureServingEngine(
                arch, cfg,
                params if dev is None else jax.device_put(params, dev),
                scheme=scheme, max_slots=max_slots, page_tokens=page_tokens,
                pages_per_slot=pages_per_slot, n_pages=n_pages,
                keys=keys if dev is None else jax.device_put(keys, dev),
                registry=registry, rotate_every=0,
                shard_id=s, n_shards=shards, device=dev,
                preempt_hook=self._take_preempted,
                defer_interval=defer_interval,
                fault_tolerance=self.ft,
                trace=bool(trace), audit=self.audit, **engine_kw))
        self.sharded = ShardedKVPool(self.engines)
        self.devices = devices
        self.tick = 0
        self.requests: dict = {}            # cluster rid -> Request
        self._next_rid = 0
        self._rotate_rr = 0
        self._orphans: deque = deque()      # preempted, awaiting re-route
        self.metrics = metrics_mod.MetricsRegistry()
        for name, help_ in metrics_mod.CLUSTER_COUNTERS.items():
            self.metrics.counter(name, help_)
        self._stats = metrics_mod.StatsView(self.metrics)
        # The cluster's own tracer sits on its own pid track (one past
        # the last shard) so the cluster_tick span does not interleave
        # with shard 0's phase spans.  Each shard engine traces under
        # pid=shard_id (they build their own tracers above).
        self.tracer = None
        self._span_hists: dict = {}
        if trace:
            self.tracer = (trace if isinstance(trace, trace_mod.SpanTracer)
                           else trace_mod.SpanTracer(pid=shards))
            self._span_hists["cluster_tick"] = self.metrics.histogram(
                "cluster_tick_seconds",
                metrics_mod.CLUSTER_HISTOGRAMS["cluster_tick_seconds"])

    # -- observability -------------------------------------------------------

    # ``with self._span(name):`` around one piece of the cluster's work.
    _span = trace_mod.owner_span

    @property
    def stats(self):
        """The cluster-level counters under the old dict API."""
        return self._stats

    def _audit(self, event: str, **fields) -> None:
        """Append one cluster-level security event (no-op when off)."""
        if self.audit is not None:
            self.audit.append(event, shard=-1, tick=self.tick, **fields)

    def snapshot(self) -> dict:
        """Cluster metrics + every shard's snapshot + the rollup.

        ``shards`` carries each engine's own snapshot (labeled
        ``shard=<id>``); ``rollup`` is the summed counter view
        (:attr:`engine_stats` — ``rotations`` takes the max, not the
        sum).
        """
        out = self.metrics.snapshot()
        out["shards"] = [e.snapshot() for e in self.engines]
        out["rollup"] = dict(self.engine_stats)
        return out

    def prometheus(self) -> str:
        """Prometheus text: cluster metrics + per-shard blocks
        (each shard's samples carry its ``shard=`` label)."""
        return "".join([self.metrics.prometheus()]
                       + [e.prometheus() for e in self.engines])

    def profile(self, buckets=None, uniform: bool = False,
                refresh: bool = False) -> dict:
        """Per-shard device-cost profiles + the cluster rollup.

        ``shards`` carries each engine's :meth:`profile` export (every
        entry labeled with its ``shard`` id, matching the ``shard=``
        labels on the per-shard profiler gauges); ``rollup`` sums the
        attributed protection/model bytes+flops across shards and
        recomputes the combined overhead ratios.
        """
        shards = [e.profile(buckets, uniform, refresh)
                  for e in self.engines]
        rollup = {k: {"bytes": 0.0, "flops": 0.0}
                  for k in ("protection", "model", "other", "total")}
        for shard in shards:
            for prof in shard["profiles"]:
                for k in rollup:
                    rollup[k]["bytes"] += prof[k]["bytes"]
                    rollup[k]["flops"] += prof[k]["flops"]
        model = rollup["model"]
        rollup["overhead_bytes_ratio"] = (
            rollup["protection"]["bytes"] / model["bytes"]
            if model["bytes"] else 0.0)
        rollup["overhead_flops_ratio"] = (
            rollup["protection"]["flops"] / model["flops"]
            if model["flops"] else 0.0)
        return {"scheme": self.engines[0].scheme if self.engines else None,
                "shards": shards, "rollup": rollup}

    def export_trace(self, path: Optional[str] = None) -> dict:
        """One Chrome trace merging cluster + every shard's spans
        (per-shard ``pid`` tracks show the dispatch/collect overlap)."""
        if self.tracer is None:
            raise ValueError("cluster was built without trace=...")
        extra = []
        for engine in self.engines:
            if engine.tracer is not None:
                extra += engine.tracer.events()
        return self.tracer.export(path, extra_events=extra)

    # -- submission / routing ------------------------------------------------

    def _submit(self, request: SubmitRequest) -> int:
        """Route one request to a shard; returns a cluster-wide rid."""
        tokens = [int(t) for t in request.prompt]
        tenant_index = (request.session.index
                        if request.session is not None else None)
        shard = self._route(tenant_index,
                            tokens if request.share_prefix else None)
        engine = self.engines[shard]
        local_rid = engine._submit(dataclasses.replace(request,
                                                       prompt=tokens))
        rid = self._next_rid
        self._next_rid += 1
        self.requests[rid] = engine.requests[local_rid]
        return rid

    def _load(self, engine) -> int:
        return (engine._n_waiting()
                + sum(1 for s in engine.slots if s is not None))

    def _has_tenant(self, engine, tenant_index: int) -> bool:
        if engine._tenant_waiting.get(tenant_index):
            return True
        return any(s is not None and s.tenant is not None
                   and s.tenant.index == tenant_index
                   for s in engine.slots)

    def _route(self, tenant_index: Optional[int],
               tokens: Optional[list] = None) -> int:
        """Prefix-holding shards first, then least-loaded.

        Prefix caches are shard-local (cache pages are sealed into one
        shard's pool and shard-bound by the RePA binding), so a request
        whose prompt prefix is cached anywhere goes to the shard
        covering the most tokens — skipping prefill beats starting on
        an idler shard.  Within the candidate set: least-loaded, with
        tenant affinity breaking near-ties."""
        cover = [0] * len(self.engines)
        if tenant_index is not None and tokens is not None and \
                len(tokens) > 1:
            cover = [e.prefix_cache.match_tokens(tenant_index, tokens[:-1])
                     if e.prefix_cache is not None else 0
                     for e in self.engines]
        for s in self.failed_shards:      # folded-out shards take nothing
            cover[s] = -1
        top = max(cover)
        best = None
        for s, engine in enumerate(self.engines):
            if s in self.failed_shards or cover[s] < top:
                continue
            score = float(self._load(engine))
            if tenant_index is not None and \
                    self._has_tenant(engine, tenant_index):
                score -= 0.5
            if best is None or score < best[0]:
                best = (score, s)
        return best[1]

    def _take_preempted(self, req) -> bool:
        """Engine preempt hook: the cluster re-routes evicted work."""
        self._orphans.append(req)
        return True

    def _requeue_orphans(self) -> None:
        while self._orphans:
            req = self._orphans.popleft()
            shard = self._route(
                req.tenant_idx,
                req.prompt + req.generated if req.share_prefix else None)
            engine = self.engines[shard]
            if req.tenant_idx is not None:
                if not engine._tenant_active(req.tenant_idx):
                    engine._activate_vtime(req.tenant_idx)
                engine._tenant_waiting.setdefault(
                    req.tenant_idx, deque()).appendleft(req)
            else:
                engine.waiting.appendleft(req)
            self.stats["rerouted_preemptions"] += 1

    # -- the cluster tick ----------------------------------------------------

    def step(self) -> list:
        """One cluster tick: every shard admits, then every shard's
        decode is dispatched, then every shard is collected — one
        multi-device dispatch wave per tick.  Returns finished
        requests across all shards.

        With ``fault_tolerance`` on, a shard whose tick phase raises
        without page context is failed over (:meth:`_failover`) while
        the other shards' tick proceeds untouched; raises that carry
        page context are contained inside that shard (quarantine +
        recompute) and never escalate to failover."""
        with self._span("cluster_tick", step_num=self.tick + 1):
            self.tick += 1
            if (self.registry is not None and self.rotate_every
                    and self.tick % self.rotate_every == 0
                    and self.registry.n_tenants):
                idx = self._rotate_rr % self.registry.n_tenants
                self._rotate_rr += 1
                self.rotate(self.registry.by_index(idx).tenant_id)
            finished: list = []
            if self.ft is None:
                actives = [e._tick_begin(finished) for e in self.engines]
                pendings = [e._decode_dispatch(a) if a else None
                            for e, a in zip(self.engines, actives)]
                for engine, active, pending in zip(self.engines, actives,
                                                   pendings):
                    if pending is not None:
                        engine._decode_collect(active, pending, finished)
                for engine in self.engines:
                    engine._tick_end()
            else:
                self._step_ft(finished)
            if self.migrate and self._n_live() > 1:
                self._maybe_migrate()
            self._requeue_orphans()
            if self.defer_interval and \
                    self.tick % self.defer_interval == 0:
                self._root_check()
            return finished

    def _step_ft(self, finished: list) -> None:
        """The guarded tick phases: dispatch-all-before-collect-any is
        preserved across the surviving shards; a shard that raises is
        skipped for the rest of the tick and failed over at the end."""
        live = self._live_engines()
        failed_now: dict = {}

        def guard(engine, fn, *a):
            if engine.shard_id in failed_now:
                return None
            try:
                return fn(*a)
            except IntegrityError as err:
                if getattr(err, "ctx", None) is not None:
                    # Engine-raised with fault context: page-level,
                    # contained in place on that shard.
                    engine._contain_error(err)
                else:
                    failed_now[engine.shard_id] = err
                return None

        actives = [guard(e, e._tick_begin, finished) for e in live]
        pendings = [guard(e, e._decode_dispatch, a) if a else None
                    for e, a in zip(live, actives)]
        for engine, active, pending in zip(live, actives, pendings):
            if pending is not None:
                guard(engine, engine._decode_collect, active, pending,
                      finished)
        for engine in live:
            guard(engine, engine._tick_end)
        for shard, err in failed_now.items():
            self._failover(shard, err)

    def run(self, max_ticks: int = 100_000) -> RunResult:
        """Drive cluster ticks until every submitted request finished
        (or, with fault tolerance on, failed for good)."""
        for _ in range(max_ticks):
            if self._busy():
                self.step()
                continue
            if self._end_checks():
                break
        else:
            raise RuntimeError("run() exceeded max_ticks")
        result = RunResult({rid: req for rid, req in self.requests.items()
                            if req.state == "finished"})
        result.latency = latency_percentiles(self.requests.values())
        return result

    def _end_checks(self) -> bool:
        """End-of-run deferred checks across the surviving shards.

        Strict mode raises on any failure; with fault tolerance on, a
        shard-localizable failure is contained (page quarantine or
        shard failover — either may requeue work, in which case the
        run loop keeps ticking).  Returns True once fully drained."""
        for engine in self._live_engines():
            if engine.policy.deferred_model_mac:
                try:
                    engine._deferred_check()
                except IntegrityError as err:
                    if self.ft is None:
                        raise
                    engine._contain_error(err)
            if not engine.verify_every_step and not bool(engine._ok_accum):
                err = IntegrityError(
                    "accumulated page-MAC verification failed "
                    f"(shard {engine.shard_id})")
                if self.ft is None:
                    raise err
                # The accumulator cannot say which tick failed; the
                # whole shard is suspect and folds out.
                engine._ok_accum = jnp.asarray(True)
                self._failover(engine.shard_id, err)
        self._root_check()
        self._requeue_orphans()
        return not self._busy()

    def _busy(self) -> bool:
        if self._orphans:
            return True
        return any(e._n_waiting() or any(s is not None for s in e.slots)
                   for e in self.engines)

    def _live_engines(self) -> list:
        return [e for e in self.engines
                if e.shard_id not in self.failed_shards]

    def _n_live(self) -> int:
        return len(self.engines) - len(self.failed_shards)

    def rotate(self, tenant_id: str) -> int:
        """Cluster-wide live rotation (fans out to every shard)."""
        if self.registry is None:
            raise ValueError("rotate() needs a tenant registry")
        return self.registry.rotate(tenant_id)

    def _root_check(self) -> None:
        self.stats["root_checks"] += 1
        if self.sharded.deferred_root_check():
            return
        msg = f"cluster root MAC check failed (tick {self.tick})"
        self._audit("integrity_error", op="root_check", detail=msg)
        if self.ft is None:
            raise IntegrityError(msg)
        # Localize: a root mismatch means at least one shard's pool MAC
        # diverged from its incrementally-folded mirror (or its own
        # deferred identity).  Those shards fold out; their sessions
        # recompute on survivors.
        bad = self.sharded.failing_shards()
        if not bad:
            raise IntegrityError(msg)   # unlocalizable — do not serve on
        for shard in bad:
            self._failover(shard, IntegrityError(msg))

    def _failover(self, shard: int, err=None) -> None:
        """Fold one failed shard out of the cluster.

        Every session on the shard — running or queued — drains onto
        the survivors by secure recompute (re-routed by
        :meth:`_requeue_orphans`, re-prefilled from prompt + emitted
        tokens at re-admission).  The failed shard's pages are NEVER
        migrated or trusted, its free list is emptied so nothing can
        land there, and its pool MAC leaves the cluster root
        compression.  Raises when no survivor would remain."""
        if shard in self.failed_shards:
            return
        if len(self.failed_shards) + 1 >= len(self.engines):
            raise IntegrityError(
                f"shard {shard} failed with no survivor left"
                + (f": {err}" if err is not None else ""))
        self.failed_shards.add(shard)
        engine = self.engines[shard]
        drained = 0
        for i, slot in enumerate(engine.slots):
            if slot is None:
                continue
            req = slot.req
            engine._preempt(i)          # hook hands the req to _orphans
            req.recovering = True
            drained += 1
        if engine.registry is None:
            while engine.waiting:
                self._orphans.append(engine.waiting.popleft())
                drained += 1
        else:
            for queue in engine._tenant_waiting.values():
                while queue:
                    self._orphans.append(queue.popleft())
                    drained += 1
        engine.free_pages = []
        self.sharded.fold_out(shard)
        self.stats["shard_failovers"] += 1
        self._audit("shard_failover", shard=shard, sessions=drained,
                    detail=str(err) if err is not None else None)

    def deferred_check(self) -> bool:
        """Cluster root MAC + every shard's deferred pool MAC."""
        return self.sharded.deferred_root_check()

    def audit_proof(self, session=None, *, rid: Optional[int] = None) -> list:
        """Cluster-wide audit proofs for one session (or one request).

        One :class:`repro.serve.merkle_pool.AuditProof` per active
        shard holding the session's frames, each carrying the ordered
        active shard-root set and the cluster root they compress to —
        so the tenant verifies leaf -> shard root -> cluster root
        entirely host-independently (``verify_proof``), with no keys
        and no pool access.  Failed-over shards are folded out of the
        root set exactly as they are from the pool-MAC compression.
        """
        import dataclasses as _dc

        from repro.serve import merkle_pool as mkp
        pairs = self.sharded.merkle_roots()
        cluster = {"shard_roots": [(s, r.hex()) for s, r in pairs],
                   "root": mkp.compress_roots(pairs).hex()}
        proofs = []
        for shard in self.sharded._active:
            engine = self.engines[shard]
            try:
                p = engine.audit_proof(session, rid=rid)
            except KeyError:
                continue            # rid not resident on this shard
            if p.pages:
                proofs.append(_dc.replace(p, cluster=cluster))
        return proofs

    @property
    def engine_stats(self) -> dict:
        """Per-shard engine stats, summed — except ``rotations``:
        every engine's post-rotation hook observes every registry
        rotation, so summing would multiply the count by the shard
        fan-out; the max IS the cluster-wide rotation count."""
        agg: dict = {}
        for engine in self.engines:
            for k, v in engine.stats.items():
                agg[k] = agg.get(k, 0) + v
        if "rotations" in agg:
            agg["rotations"] = max(e.stats["rotations"]
                                   for e in self.engines)
        return agg

    # -- secure page migration ----------------------------------------------

    def _growth_pressure(self, engine) -> bool:
        """Queued work the shard cannot admit, or imminent page growth
        its free list cannot cover."""
        free = len(engine.free_pages)
        heads = []
        if engine.registry is None:
            if engine.waiting:
                heads.append(engine.waiting[0])
        else:
            heads += [q[0] for q in engine._tenant_waiting.values() if q]
        if heads and any(engine._admission_pages(r) > free for r in heads):
            return True
        need_soon = sum(
            1 for s in engine.slots if s is not None
            and (s.length + 1) // engine.page_tokens >= len(s.pages))
        return need_soon > free

    def _pick_migration(self, src: int):
        """(victim slot, destination shard) for one starved shard."""
        engine = self.engines[src]
        candidates = [i for i, s in enumerate(engine.slots) if s is not None]
        if not candidates:
            return None
        victim = max(candidates, key=lambda i: engine.slots[i].admit_seq)
        slot = engine.slots[victim]
        n = len(slot.pages)
        best = None
        for d, dst in enumerate(self.engines):
            if d == src or d in self.failed_shards or \
                    None not in dst.slots:
                continue
            # Headroom: the slot must land AND keep growing a while.
            if len(dst.free_pages) < n + 1:
                continue
            if slot.tenant is not None and \
                    dst.tenant_resident_pages(slot.tenant.index) + n > \
                    slot.tenant.page_quota:
                continue
            if best is None or len(dst.free_pages) > best[0]:
                best = (len(dst.free_pages), d)
        if best is None:
            return None
        return victim, best[1]

    def _maybe_migrate(self) -> None:
        for src in range(len(self.engines)):
            if src in self.failed_shards:
                continue
            if not self._growth_pressure(self.engines[src]):
                continue
            pick = self._pick_migration(src)
            if pick is None:
                continue
            if self.ft is None:
                self._migrate_slot(src, *pick)
                continue
            try:
                self._migrate_slot(src, *pick)
            except IntegrityError as err:
                # Migration re-verifies the source pages before the
                # move; a failure is a source-shard page fault and is
                # contained there (the slot stays put, recovery takes
                # over).
                self.engines[src]._contain_error(err)

    def _migrate_slot(self, src: int, slot_idx: int, dst: int) -> None:
        """Move one running slot's pages src -> dst, resealing them
        under the destination shard's binding (no recompute)."""
        import jax.numpy as jnp
        import numpy as np

        es, ed = self.engines[src], self.engines[dst]
        slot = es.slots[slot_idx]
        n = len(slot.pages)
        p = es.pages_per_slot                         # bucketed dispatch size
        src_ids = np.full((p,), es.spec.scratch_page, np.int32)
        src_ids[:n] = slot.pages
        tenant = slot.tenant
        if tenant is None:
            leaf_pages, ok = es._page_reader(p)(es.pool,
                                                jnp.asarray(src_ids))
        else:
            rows = np.zeros((p,), np.int32)
            epochs = np.zeros((p,), np.uint32)
            for j, e in enumerate(slot.page_epochs):
                epochs[j] = e
                if e & kvp.PREFIX_ROLE:
                    # Shared prefix page: read under the tenant's
                    # epoch-independent cache binding.  The copy lands
                    # at the destination as a PRIVATE page (the cache
                    # and its refcounts are shard-local).
                    rows[j] = self.registry.cache_row(tenant.index)
                    continue
                try:
                    rows[j] = self.registry.key_row(tenant.index, e)
                except KeyError as exc:
                    raise es._integrity_fail(
                        f"migration source shard {src} slot {slot_idx} "
                        f"page {j}: {exc.args[0]}",
                        op="migration", tenant=tenant.tenant_id,
                        to_shard=dst) from exc
            owners = np.full((p,), tenant.index, np.uint32)
            leaf_pages, ok = es._page_reader(p)(
                es.pool, jnp.asarray(src_ids), es._bank(),
                jnp.asarray(rows), jnp.asarray(owners), jnp.asarray(epochs))
        if not es.page_io.report_verdict(ok, "migration"):
            raise es._integrity_fail(
                f"secure migration: source shard {src} page verification "
                f"failed (slot {slot_idx}, scheme={es.scheme})",
                op="migration", slot=slot_idx, to_shard=dst,
                pages=[int(p) for p in slot.pages])
        dst_pages = [ed.free_pages.pop() for _ in range(n)]
        dst_ids = np.full((p,), ed.spec.scratch_page, np.int32)
        dst_ids[:n] = dst_pages
        if ed._device is not None and ed._device != es._device:
            leaf_pages = jax.device_put(leaf_pages, ed._device)
        if tenant is None:
            ed.pool = ed._page_writer(p)(ed.pool, jnp.asarray(dst_ids),
                                         leaf_pages, ed._next_epoch())
            page_epochs = []
        else:
            cur = tenant.current_epoch
            row = self.registry.key_row(tenant.index, cur)
            ed.pool = ed._page_writer(p)(
                ed.pool, jnp.asarray(dst_ids), leaf_pages, ed._next_epoch(),
                ed._bank(), jnp.full((p,), row, jnp.int32),
                jnp.full((p,), tenant.index, jnp.uint32),
                jnp.full((p,), np.uint32(cur), jnp.uint32))
            page_epochs = [cur] * n
        # Host state: the slot moves wholesale; its request never
        # leaves the "running" state and nothing is recomputed.
        dst_slot = ed.slots.index(None)
        for j in range(len(ed.onchip)):
            col = es.onchip[j][:, slot_idx]
            if ed._device is not None and ed._device != es._device:
                col = jax.device_put(col, ed._device)
            ed.onchip[j] = ed.onchip[j].at[:, dst_slot].set(col)
        es.slots[slot_idx] = None
        es.page_table.clear(slot_idx)
        # Shared prefix pages stay behind with the source shard's cache
        # (only their pin is dropped); the private tail is freed.
        if slot.shared_n:
            es.prefix_cache.release(slot.shared_entries)
        es._free(slot.pages[slot.shared_n:])
        ed._admit_seq += 1
        slot.pages = dst_pages
        slot.page_epochs = page_epochs
        slot.shared_n = 0
        slot.shared_entries = []
        slot.admit_seq = ed._admit_seq
        ed.slots[dst_slot] = slot
        ed.page_table.install(dst_slot, slot)
        # Thread the audit trail through the move: the migrated session
        # immediately re-proves membership against the destination
        # shard's root, and the hand-off (old root -> new proof) is
        # recorded so a tenant can audit that its transcript survived
        # the migration rather than trusting it did.
        src_root = dst_root = None
        if es.merkle is not None and ed.merkle is not None:
            es._merkle_sync()
            src_root = es.merkle.root_hex()
            proof = ed.audit_proof(rid=slot.req.rid)
            dst_root = proof.root
            self.migration_proofs.append(
                {"rid": slot.req.rid, "from_shard": src, "to_shard": dst,
                 "src_root": src_root, "proof": proof.to_dict()})
        self.stats["migrations"] += 1
        self._audit("migration", from_shard=src, to_shard=dst, pages=n,
                    tenant=tenant.tenant_id if tenant is not None else None,
                    src_root=src_root, dst_root=dst_root)
