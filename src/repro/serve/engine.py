"""Continuous-batching secure serving engine over the paged KV pool.

The engine multiplexes many requests over ``max_slots`` decode lanes
and a shared pool of MAC-protected KV pages (:mod:`repro.serve.kv_pages`):

* **admission** — waiting requests are prefetched into a free slot when
  the pool has pages for their prompt; prefill runs per request (with
  power-of-two length bucketing so prefill compiles once per bucket,
  not once per distinct prompt length) and the resulting cache pages
  are encrypted + MACed into the pool;
* **decode** — one jitted computation per tick batches every running
  slot: gather pages -> decrypt -> verify touched pages -> attend/append
  -> re-encrypt + re-MAC only the dirty page per slot.  All schemes from
  :data:`repro.core.secure_exec.SCHEMES` run through the same step.
  The step runs over a pow2 **page-count-bucketed** window from the
  two-level page table (:class:`repro.serve.kv_pages.TwoLevelPageTable`)
  picked host-side per tick, so protection work scales with the pages
  a tick actually touches (one compile per bucket), not with
  ``pages_per_slot``;
* **growth / eviction** — slots allocate pages on demand as decodes
  lengthen; under a full pool the youngest running request is preempted
  (pages freed, request requeued, KV recomputed on re-admission), so
  long-running decodes never deadlock the pool;
* **deferred verification** — the pool-level MAC (the model-MAC level
  of :mod:`repro.core.multilevel`) is checked off the critical path,
  every ``defer_interval`` ticks, amortizing it across the batch.

**Multi-tenant mode.**  Constructed with a
:class:`repro.tenancy.registry.TenantRegistry`, the engine becomes a
shared-accelerator serving plane with per-tenant cryptographic
domains:

* requests must carry a :class:`~repro.tenancy.registry.SessionHandle`
  into :meth:`submit`; the registry validates it and pins the request
  to its tenant;
* every KV page is encrypted + MACed under its owner's (tenant, epoch)
  keys, with the identity folded into the RePA binding — a page
  written by tenant A fails verification when read under tenant B's
  keys or under a stale epoch;
* admission is **weighted-fair** (stride scheduling over tenant
  virtual time, weighted by ``Tenant.weight``) and **quota-gated**: a
  tenant at its page quota queues its own requests rather than
  evicting anyone else's;
* eviction is **tenant-scoped**: a tenant under memory pressure
  preempts its *own* youngest request before touching others';
* :meth:`rotate` bumps a tenant's key epoch **live**: resident pages
  re-encrypt to the new epoch lazily on their next dirty write, reads
  of previous-epoch pages keep verifying against the retained key, and
  pages about to fall out of the retention window are **eagerly
  resealed** (one jitted decrypt-old → re-encrypt-new crossing, via
  :func:`repro.serve.kv_pages.reseal_pages`) — no slot is preempted
  and no KV is recomputed.

**Sharded mode.**  Constructed with ``shard_id``/``n_shards`` (and
optionally ``device``), the engine becomes one shard of a
:class:`repro.serve.cluster.ClusterEngine`: its pool's RePA bindings
and CTR counters carry the shard id (pages are cryptographically
pinned to this device), its tick is split into dispatch/collect halves
so the cluster can overlap every shard's decode in one multi-device
dispatch, and pool updates are observable (``attach_pool_listener``)
so the cluster can roll per-shard deferred pool MACs into a root MAC.

**Fault containment.**  Constructed with ``fault_tolerance`` (``True``
or a :class:`repro.serve.faults.RecoveryPolicy`), an integrity failure
no longer aborts the process: :meth:`step` catches it, localizes the
failing page(s) by re-reading every resident page through the raw
verify path, permanently quarantines the condemned physical frames
(never reallocated; scrubbed from the free list, the prefix cache and
the deferred pool MAC), and preempts only the affected slot for
**secure-recompute recovery** — re-admission re-prefills the prompt
plus all already-emitted tokens, so the recovered stream is
token-identical to a fault-free run.  A bounded re-read retry
distinguishes transient faults from persistent tamper; a retry budget
with exponential backoff bounds how often one session may recover
before it is declared dead (``sessions_lost``).  Detection stays loud
(audit events, counters, SLO integration) while the blast radius
shrinks to one session.

Host-side scheduling state (free list, queues, lengths, page epochs)
is plain Python; everything that touches tensor data stays inside jit.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mac as mac_mod
from repro.core import multilevel
from repro.core import secure_memory as sm
from repro.core import vn as vn_mod
from repro.core.secure_exec import SCHEMES
from repro.models import lm as lm_mod
from repro.obs import audit as audit_mod
from repro.obs import metrics as metrics_mod
from repro.obs import profiler as profiler_mod
from repro.obs import trace as trace_mod
from repro.serve import kv_pages as kvp
from repro.serve import merkle_pool as mkp
from repro.serve.serve_step import greedy_sample

assert mkp.MAC_BYTES == mac_mod.MAC_BYTES  # jax-free module, own literal

__all__ = ["IntegrityError", "Request", "RunResult", "SecureServingEngine",
           "SubmitAPI", "SubmitRequest", "latency_percentiles"]


class IntegrityError(RuntimeError):
    """A MAC gate (page/block) or the deferred pool MAC failed."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list
    max_new_tokens: int
    generated: list = dataclasses.field(default_factory=list)
    state: str = "waiting"          # waiting | running | finished | failed
    n_evictions: int = 0
    # Fault-containment state: recovering marks a session preempted by
    # an integrity failure (cleared — and counted — on re-admission);
    # hold_until delays re-admission for exponential backoff;
    # integrity_retries counts recoveries against the retry budget.
    recovering: bool = False
    hold_until: int = 0
    integrity_retries: int = 0
    tenant_idx: Optional[int] = None
    submit_tick: int = 0
    first_tick: Optional[int] = None    # tick the first token appeared
    done_tick: Optional[int] = None
    share_prefix: bool = True       # may use / populate the prefix cache
    submit_time: float = 0.0        # perf_counter at submit (ttft_seconds)
    queued_ns: int = 0              # perf_counter_ns when last queued

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens


@dataclasses.dataclass
class SubmitRequest:
    """The admission argument object of the unified ``submit()``.

    One dataclass consumed by both :class:`SecureServingEngine` and
    :class:`repro.serve.cluster.ClusterEngine` (via :class:`SubmitAPI`),
    so the two surfaces cannot drift apart again.  ``share_prefix=False``
    opts a request out of the shared-prefix cache in both directions:
    it neither reads cached pages nor seals its own prefix in.
    """

    prompt: list
    max_new_tokens: int = 16
    session: Optional[object] = None    # tenancy SessionHandle | None
    share_prefix: bool = True


class SubmitAPI:
    """The one keyword-only ``submit()`` shared by engine and cluster.

    Subclasses implement ``_submit(SubmitRequest) -> rid``; this mixin
    owns argument handling, so ``Engine.submit`` and
    ``ClusterEngine.submit`` are the same surface by construction.
    Legacy positional calls (``submit(prompt, max_new_tokens)``) keep
    working through a thin :class:`DeprecationWarning` shim.
    """

    def _submit(self, request: SubmitRequest) -> int:
        raise NotImplementedError

    def submit(self, request=None, /, *legacy, **kw) -> int:
        """Queue one request; returns its rid.

        Preferred forms::

            eng.submit(SubmitRequest(prompt=toks, max_new_tokens=8))
            eng.submit(prompt=toks, max_new_tokens=8, session=sess)

        The legacy positional form ``submit(toks, 8)`` still works but
        warns.
        """
        if isinstance(request, SubmitRequest):
            if legacy or kw:
                raise TypeError("submit(SubmitRequest) takes no other "
                                "arguments")
            return self._submit(request)
        if request is not None:
            warnings.warn(
                "positional submit(prompt, ...) is deprecated; pass a "
                "SubmitRequest or keyword arguments",
                DeprecationWarning, stacklevel=2)
            if "prompt" in kw:
                raise TypeError("submit() got prompt twice")
            kw["prompt"] = request
            if legacy:
                if len(legacy) > 1 or "max_new_tokens" in kw:
                    raise TypeError("submit() takes at most prompt and "
                                    "max_new_tokens positionally")
                kw["max_new_tokens"] = legacy[0]
        elif legacy:
            raise TypeError("submit() got positional arguments but no "
                            "prompt")
        return self._submit(SubmitRequest(**kw))


class RunResult(dict):
    """``{rid: Request}`` plus aggregate ``latency`` percentiles."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.latency: dict = {}


def latency_percentiles(requests) -> dict:
    """p50/p95/p99 latency over finished requests.

    Interpolated (``np.percentile``, linear) rather than nearest-rank:
    cluster benchmarks read tail latency off handfuls of requests,
    where nearest-rank p95/p99 degenerate to the max and hide real
    movement between runs.
    """
    ttft, tpt = [], []
    for r in requests:
        if r.state != "finished" or r.first_tick is None:
            continue
        ttft.append(r.first_tick - r.submit_tick)
        if r.done_tick is not None and len(r.generated) > 1:
            tpt.append((r.done_tick - r.first_tick) / (len(r.generated) - 1))
    if not ttft:
        return {}
    out = {}
    for q in (50, 95, 99):
        out[f"p{q}_ttft_ticks"] = float(
            np.percentile(ttft, q, method="linear"))
    for q in (50, 95, 99):
        if tpt:
            out[f"p{q}_ticks_per_token"] = float(
                np.percentile(tpt, q, method="linear"))
    return out


@dataclasses.dataclass
class _Slot:
    req: Request
    length: int                     # KV tokens resident (host mirror)
    pages: list                     # owned pool page ids, in token order
    admit_seq: int
    tenant: object = None           # tenancy.registry.Tenant | None
    page_epochs: list = dataclasses.field(default_factory=list)
    # Shared-prefix state: the first ``shared_n`` entries of ``pages``
    # are read-only prefix-cache pages (epoch word PREFIX_ROLE), pinned
    # via ``shared_entries``; ``replay`` holds the prompt tokens the
    # skipped prefill still owes the decode loop (teacher-forced — the
    # sampled token of the LAST replay step is the first real output).
    shared_n: int = 0
    shared_entries: list = dataclasses.field(default_factory=list)
    replay: deque = dataclasses.field(default_factory=deque)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _bucket_len(n: int, cap: int) -> int:
    """Round ``n`` up to the next power of two, capped at ``cap``."""
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)


class SecureServingEngine(SubmitAPI):
    """Batched secure decoding with paged, MAC-protected KV residency.

    Typical single-tenant use::

        eng = SecureServingEngine(arch, cfg, params, scheme="seda",
                                  max_slots=4, page_tokens=8,
                                  pages_per_slot=4, n_pages=12)
        rids = [eng.submit(prompt=prompt, max_new_tokens=8)
                for prompt in prompts]
        done = eng.run()            # RunResult: {rid: Request} + .latency

    Multi-tenant use::

        reg = TenantRegistry(KeyHierarchy(0))
        reg.register("alice", weight=2.0, page_quota=8)
        eng = SecureServingEngine(arch, cfg, params, registry=reg, ...)
        sess = reg.open_session("alice")
        eng.submit(prompt=prompt, max_new_tokens=8, session=sess)
        eng.rotate("alice")         # live key rotation
        done = eng.run()

    With ``prefix_cache=True`` (registry required) the engine keeps a
    content-addressed :class:`repro.serve.kv_pages.PrefixCache`: a
    submitted prompt whose leading pages were already sealed by an
    earlier same-tenant request skips their prefill entirely — the
    shared pages are installed read-only in the slot directory, the
    remaining prompt tokens are teacher-forced through the normal
    batched decode (token-identical to a full prefill), and the first
    dirty write to a shared page triggers a copy-on-write reseal into a
    private page.  Cross-tenant sharing happens only through the
    explicit :meth:`share_prefix` reseal.
    """

    def __init__(self, arch, cfg, params, *, scheme: str = "seda",
                 max_slots: int = 4, page_tokens: int = 8,
                 pages_per_slot: int = 8, n_pages: Optional[int] = None,
                 keys: Optional[sm.SecureKeys] = None,
                 use_kernel: Optional[bool] = None,
                 defer_interval: int = 16,
                 eos_id: Optional[int] = None,
                 verify_every_step: bool = True,
                 registry=None, rotate_every: int = 0,
                 prefill_buckets: Optional[bool] = None,
                 shard_id: int = 0, n_shards: int = 1,
                 device=None, preempt_hook=None,
                 prefix_cache: bool = False,
                 prefix_cache_pages: Optional[int] = None,
                 fault_tolerance=None,
                 merkle: bool = True,
                 trace=None, audit=None):
        if arch.kind != "lm":
            raise ValueError("the paged serving engine supports decoder-only "
                             "LMs (enc-dec serving stays on serve_step)")
        if scheme not in SCHEMES:
            raise KeyError(f"unknown scheme {scheme!r}")
        if rotate_every and registry is None:
            raise ValueError("rotate_every needs a tenant registry — there "
                             "is no key hierarchy to rotate without one")
        if prefix_cache and registry is None:
            raise ValueError("prefix_cache needs a tenant registry — cache "
                             "pages are sealed under per-tenant cache keys")
        self.arch, self.cfg = arch, cfg
        self.scheme = scheme
        self.max_slots = max_slots
        self.page_tokens = page_tokens
        self.pages_per_slot = pages_per_slot
        self.max_len = page_tokens * pages_per_slot
        if n_pages is None:
            n_pages = max_slots * pages_per_slot
        self.n_pages = n_pages
        self.keys = keys if keys is not None else sm.SecureKeys.derive(0)
        self.defer_interval = defer_interval
        self.eos_id = eos_id
        self.verify_every_step = verify_every_step
        self.registry = registry
        self.rotate_every = rotate_every
        self.shard_id = shard_id
        self.n_shards = n_shards
        self._device = device
        # Called as preempt_hook(request) on eviction; returning True
        # means the caller (the cluster scheduler) took ownership and
        # the request must NOT be requeued locally — it may be re-routed
        # to a less loaded shard instead.
        self._preempt_hook = preempt_hook
        # fault_tolerance=None keeps the strict discipline (an
        # IntegrityError escapes step()/run() and aborts); True or a
        # RecoveryPolicy turns on quarantine + secure-recompute
        # recovery (see the module docstring).
        self.ft = None
        if fault_tolerance:
            from repro.serve.faults import RecoveryPolicy
            self.ft = (RecoveryPolicy() if fault_tolerance is True
                       else fault_tolerance)
        self.params = (params if device is None
                       else jax.device_put(params, device))

        cache_tree = lm_mod.cache_specs(cfg, max_slots, self.max_len)
        flat, self.treedef = jax.tree_util.tree_flatten(cache_tree)
        paged = kvp.paged_flags(cache_tree)
        lengths = kvp.length_flags(cache_tree)
        self.paged_idx = [i for i, f in enumerate(paged) if f]
        self.len_leaves = [(i, flat[i].shape[0])
                           for i, f in enumerate(lengths) if f]
        self.onchip_idx = [i for i in range(len(flat))
                           if not paged[i] and not lengths[i]]
        self.n_leaves = len(flat)
        if use_kernel is None:
            # The fused Pallas kernels are Mosaic (TPU) kernels: on the
            # chip they carry the narrow-block B-AES + NH schemes; on
            # CPU the jnp reference runs unless a caller asks for the
            # kernels (interpret mode) explicitly.
            platform = (device.platform if device is not None
                        else jax.default_backend())
            use_kernel = platform == "tpu"
        self.spec = kvp.build_page_spec(
            cache_tree, scheme=scheme, page_tokens=page_tokens,
            n_pages=n_pages, max_slots=max_slots, max_len=self.max_len,
            use_kernel=use_kernel, shard=shard_id, n_shards=n_shards)
        self.page_io = kvp.PageIO(self.spec, self.keys)
        self.prefix_cache = None
        if prefix_cache:
            if self.onchip_idx:
                raise ValueError(
                    "prefix_cache is unavailable for archs with recurrent "
                    "on-chip state (Mamba SSM/conv): the skipped prefill's "
                    "state cannot be reconstructed from cached KV pages")
            cap = (prefix_cache_pages if prefix_cache_pages is not None
                   else max(1, n_pages // 4))
            self.prefix_cache = kvp.PrefixCache(page_tokens, cap)
        self.policy = (multilevel.SEDA_DEFAULT
                       if SCHEMES[scheme].verify == "layer"
                       else multilevel.SGX_LIKE if SCHEMES[scheme].emulate_tree
                       else multilevel.MGX_LIKE)
        # Length bucketing is safe when every cache leaf is either paged
        # (read path zeroes positions >= length) or a length mirror;
        # recurrent on-chip state (Mamba SSM/conv) would absorb the pad
        # tokens, so those archs keep exact-length prefill.
        if prefill_buckets is None:
            prefill_buckets = not self.onchip_idx
        self.prefill_buckets = prefill_buckets

        # Device state.
        self._pool_listeners: list = []
        pool = kvp.init_pool(self.spec)
        onchip = [jnp.zeros(flat[i].shape, flat[i].dtype)
                  for i in self.onchip_idx]
        if device is not None:
            pool = jax.device_put(pool, device)
            onchip = [jax.device_put(a, device) for a in onchip]
        self.pool = pool
        self.onchip = onchip
        self._ok_accum = jnp.asarray(True)

        # Host scheduling state.
        self.waiting: deque = deque()           # single-tenant FIFO
        self._tenant_waiting: dict = {}         # tenant idx -> deque
        self._vtime: dict = {}                  # tenant idx -> virtual time
        self._rotate_rr = 0
        self.slots: list = [None] * max_slots
        self.free_pages: list = list(range(n_pages))
        # Physical frames permanently retired after a localized
        # integrity failure: never on the free list, never reallocated.
        self.quarantined: set = set()
        self.requests: dict = {}
        self._next_rid = 0
        self._admit_seq = 0
        self._epoch = 0
        self.tick = 0
        self._prefill_shapes: set = set()
        self._init_obs(trace, audit)

        # Auditable Merkle level over the page MACs: listener-driven,
        # O(1) on the hot path, batched into ``_tick_end``.  ``merkle=
        # False`` keeps only the verifier-side folds (the bench uses it
        # to price the maintenance against the plain CBC-MAC root).
        self.merkle = None
        if merkle:
            self.merkle = mkp.MerklePagePool(
                self.n_pages, shard=shard_id,
                leaf_fn=lambda pool: kvp.merkle_leaf_macs(pool, self.spec),
                owners_fn=self._page_owners,
                quarantined_fn=lambda: self.quarantined)
            self.attach_pool_listener(self.merkle.on_pool_update)
            self.merkle.on_pool_update(None, self.pool)

        # Two-level page table: the slot directory (level 1) feeds pow2
        # page-count-bucketed decode windows (level 2); the decode step
        # compiles once per (bucket, uniform) variant on demand.
        self.page_table = kvp.TwoLevelPageTable(max_slots, pages_per_slot)
        self._decode_fns: dict = {}
        self._prefill_fn = jax.jit(self._build_prefill_fn())
        self._writers: dict = {}
        self._resealers: dict = {}
        self._copiers: dict = {}
        self._page_readers: dict = {}
        self._page_writers: dict = {}
        if registry is not None:
            # Rotations repair every engine sharing the registry, no
            # matter which one (or which operator call) triggered them:
            # the pre hook reseals pages that would leave the retained
            # window (while the dying epoch's keys are still banked),
            # the post hook preempts anything a reseal could not save.
            registry.attach_rotation_hook(self._pre_rotation, pre=True)
            registry.attach_rotation_hook(self._on_rotation)

    # -- pool indirection (sharded-pool observability) ----------------------

    @property
    def pool(self) -> kvp.PagedKVPool:
        return self._pool

    @pool.setter
    def pool(self, new_pool: kvp.PagedKVPool) -> None:
        old = getattr(self, "_pool", None)
        self._pool = new_pool
        for listener in self._pool_listeners:
            listener(old, new_pool)

    def attach_pool_listener(self, listener) -> None:
        """``listener(old_pool, new_pool)`` runs on every pool update —
        the cluster's sharded pool mirrors per-shard deferred MACs into
        its root MAC this way, without syncing the device."""
        self._pool_listeners.append(listener)

    # -- observability (metrics / tracing / audit) ---------------------------

    def _init_obs(self, trace, audit) -> None:
        """Wire the observability layer (:mod:`repro.obs`).

        The metrics registry is always on — its counters ARE the old
        ``stats`` dict, one attribute bump per event — and gauges are
        lazy callbacks sampled only at :meth:`snapshot` time.  The span
        tracer and the wall-clock phase histograms only exist when
        ``trace`` was passed (``True`` or a
        :class:`~repro.obs.trace.SpanTracer`): :meth:`_span` then times
        the engine's layer boundaries, and otherwise returns a shared
        no-op, so a default engine pays no timing call on its hot path.
        ``audit`` (``True`` or a shared
        :class:`~repro.obs.audit.AuditLog`) enables the hash-chained
        security event log.
        """
        self.metrics = metrics_mod.MetricsRegistry()
        for name, help_ in metrics_mod.ENGINE_COUNTERS.items():
            self.metrics.counter(name, help_)
        self._stats = metrics_mod.StatsView(self.metrics)
        g = metrics_mod.ENGINE_GAUGES
        self.metrics.gauge("pool_free_pages", g["pool_free_pages"],
                           fn=lambda: len(self.free_pages))
        self.metrics.gauge("pool_pages_total", g["pool_pages_total"],
                           fn=lambda: self.n_pages)
        self.metrics.gauge("slots_active", g["slots_active"],
                           fn=lambda: sum(1 for s in self.slots
                                          if s is not None))
        self.metrics.gauge("waiting_requests", g["waiting_requests"],
                           fn=self._n_waiting)
        if self.registry is not None:
            self.metrics.gauge(
                "tenant_resident_pages", g["tenant_resident_pages"],
                label="tenant",
                fn=lambda: {
                    self.registry.by_index(i).tenant_id:
                        self.tenant_resident_pages(i)
                    for i in range(self.registry.n_tenants)})
        if self.prefix_cache is not None:
            self.metrics.gauge("prefix_cache_pages",
                               g["prefix_cache_pages"],
                               fn=lambda: self.prefix_cache.pages_used)
            self.metrics.gauge("prefix_cache_refs", g["prefix_cache_refs"],
                               fn=lambda: self.prefix_cache.total_refs)
        # Device-cost profiler gauges sample the profile() cache only —
        # an engine that never called profile() exposes empty dicts and
        # never compiles anything at snapshot time.
        self._cost_profiles: dict = {}

        def _profile_gauge(attr):
            return lambda: {
                f"{b}{'u' if u else ''}": getattr(p, attr)
                for (b, u), p in sorted(self._cost_profiles.items())}

        self.metrics.gauge(
            "protection_overhead_ratio", g["protection_overhead_ratio"],
            label="bucket", fn=_profile_gauge("overhead_bytes_ratio"))
        self.metrics.gauge(
            "protection_overhead_flops_ratio",
            g["protection_overhead_flops_ratio"],
            label="bucket", fn=_profile_gauge("overhead_flops_ratio"))
        self.metrics.gauge(
            "roofline_utilization", g["roofline_utilization"],
            label="bucket",
            fn=lambda: {
                f"{b}{'u' if u else ''}":
                    p.roofline().get("utilization", 0.0)
                for (b, u), p in sorted(self._cost_profiles.items())})
        h = metrics_mod.ENGINE_HISTOGRAMS
        self._ttft_ticks = self.metrics.histogram("ttft_ticks",
                                                  h["ttft_ticks"])
        self._ttft_seconds = self.metrics.histogram("ttft_seconds",
                                                    h["ttft_seconds"])
        self._bucket_hist = self.metrics.histogram("decode_bucket",
                                                   h["decode_bucket"])
        # isinstance first: an EMPTY shared log is falsy (len == 0) but
        # must still be adopted — the cluster hands shards a fresh one.
        if isinstance(audit, audit_mod.AuditLog):
            self.audit = audit
        elif audit:
            self.audit = audit_mod.AuditLog()
        else:
            self.audit = None
        self.tracer = None
        self._span_hists: dict = {}
        if trace:
            self.tracer = (trace if isinstance(trace, trace_mod.SpanTracer)
                           else trace_mod.SpanTracer(pid=self.shard_id))
            h = metrics_mod.ENGINE_HISTOGRAMS
            for span, key in (("tick", "tick_seconds"),
                              ("tick_begin", "phase_tick_begin_seconds"),
                              ("decode_dispatch",
                               "phase_decode_dispatch_seconds"),
                              ("decode_collect",
                               "phase_decode_collect_seconds"),
                              ("tick_end", "phase_tick_end_seconds")):
                self._span_hists[span] = self.metrics.histogram(key, h[key])
        # kv_pages-level integrity verdict hook: every host-synced MAC
        # gate verdict (decode read, reseal, CoW, cache insert/share,
        # migration, deferred checks) lands in the counters no matter
        # which crossing produced it.
        self.page_io.verdict_hooks.append(self._on_verdict)

    def _on_verdict(self, ok: bool, op: str, ctx: dict) -> None:
        self.stats["integrity_verdicts"] += 1
        if not ok:
            self.stats["integrity_failures"] += 1

    def _observe_ttft(self, req: Request) -> None:
        self._ttft_ticks.observe(req.first_tick - req.submit_tick)
        if req.submit_time:
            self._ttft_seconds.observe(time.perf_counter() - req.submit_time)

    # ``with self._span(name):`` around one piece of the engine's work.
    _span = trace_mod.owner_span

    @property
    def stats(self):
        """The counters under the old dict API (see
        :class:`repro.obs.metrics.StatsView`)."""
        return self._stats

    def _audit(self, event: str, **fields) -> None:
        """Append one security event (no-op without an audit log)."""
        if self.audit is not None:
            self.audit.append(event, shard=self.shard_id,
                              scheme=self.scheme, tick=self.tick, **fields)
            self.stats["audit_events"] += 1

    def _integrity_fail(self, msg: str, **ctx) -> IntegrityError:
        """Audit + build (the caller raises) one integrity failure.

        ``ctx`` (op, tenant, slot, page/pages…) rides on the exception
        as ``err.ctx`` so the fault-containment layer can quarantine
        the named pages without re-localizing."""
        self._audit("integrity_error", detail=msg, **ctx)
        err = IntegrityError(msg)
        err.ctx = dict(ctx)
        return err

    def snapshot(self) -> dict:
        """JSON-able metrics snapshot (gauges sampled now)."""
        return self.metrics.snapshot(labels={"shard": str(self.shard_id)}
                                     if self.n_shards > 1 else None)

    def prometheus(self) -> str:
        """Prometheus text exposition of this engine's metrics."""
        return self.metrics.prometheus(
            labels={"shard": str(self.shard_id)}
            if self.n_shards > 1 else None)

    def export_trace(self, path: Optional[str] = None) -> dict:
        """Chrome trace-event JSON of the recorded phase spans."""
        if self.tracer is None:
            raise ValueError("engine was built without trace=...")
        return self.tracer.export(path)

    # -- traced builders ----------------------------------------------------

    def _merge_cache_leaves(self, dense, onchip, lengths):
        leaves = [None] * self.n_leaves
        for j, idx in enumerate(self.paged_idx):
            leaves[idx] = dense[j]
        for idx, steps in self.len_leaves:
            leaves[idx] = jnp.broadcast_to(lengths[None, :],
                                           (steps, self.max_slots))
        for j, idx in enumerate(self.onchip_idx):
            leaves[idx] = onchip[j]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def _decode_fn_for(self, bucket: int, uniform: bool = False):
        """The jitted decode step for one pow2 page-count bucket.

        One compile per (bucket, uniform) pair — bounded by
        2 * (log2(pages_per_slot) + 1) variants over an engine's life.
        """
        key = (bucket, uniform)
        if key not in self._decode_fns:
            self.stats["decode_bucket_compiles"] += 1
            self._decode_fns[key] = jax.jit(
                self._build_decode_fn(bucket, uniform))
        return self._decode_fns[key]

    def _build_decode_fn(self, bucket: int, uniform: bool = False):
        cfg, io = self.cfg, self.page_io
        tenant_mode = self.registry is not None

        def core(params, pool, onchip, page_table, lengths, active, tokens,
                 epoch, read_ctx, write_ctx):
            # Named scopes mark the program's layers in the compiled HLO's
            # op_name metadata (the profiler's per-op view).
            with jax.named_scope("pageio.read"):
                dense, ok = io.read(pool, page_table, lengths, read_ctx,
                                    uniform)
            caches = self._merge_cache_leaves(dense, onchip, lengths)
            logits, new_caches = lm_mod.lm_decode(cfg, params, tokens, caches)
            with jax.named_scope("sample"):
                tok = greedy_sample(logits)                # (S, 1)
            new_leaves = jax.tree_util.tree_leaves(new_caches)
            vn = vn_mod.kv_page_vn(epoch)
            with jax.named_scope("pageio.write"):
                new_pool = io.write_dirty(
                    pool, page_table,
                    [new_leaves[i] for i in self.paged_idx], lengths, active,
                    vn, write_ctx, uniform)
            new_onchip = []
            for j, idx in enumerate(self.onchip_idx):
                leaf = new_leaves[idx]
                keep = active.reshape((1, self.max_slots)
                                      + (1,) * (leaf.ndim - 2))
                new_onchip.append(jnp.where(keep, leaf, onchip[j]))
            return new_pool, new_onchip, tok, ok

        if not tenant_mode:
            def decode_fn(params, pool, onchip, page_table, lengths, active,
                          tokens, epoch):
                return core(params, pool, onchip, page_table, lengths,
                            active, tokens, epoch, None, None)
            return decode_fn

        def decode_fn(params, pool, onchip, page_table, lengths, active,
                      tokens, epoch, bank, key_idx, owners, key_epochs,
                      cur_key_idx, cur_epochs):
            read_ctx = kvp.PageKeyCtx.make(
                bank, key_idx.reshape(-1),
                jnp.repeat(owners, bucket), key_epochs.reshape(-1))
            write_ctx = kvp.PageKeyCtx.make(bank, cur_key_idx, owners,
                                            cur_epochs)
            return core(params, pool, onchip, page_table, lengths, active,
                        tokens, epoch, read_ctx, write_ctx)

        return decode_fn

    def _build_prefill_fn(self):
        cfg, max_len = self.cfg, self.max_len

        def prefill_fn(params, tokens, last_pos):       # tokens: (1, Lp)
            logits, caches = lm_mod.lm_prefill(cfg, params,
                                               {"tokens": tokens}, max_len,
                                               last_pos=last_pos)
            leaves = jax.tree_util.tree_leaves(caches)
            return (greedy_sample(logits),
                    [leaves[i] for i in self.paged_idx],
                    [leaves[i] for i in self.onchip_idx])

        return prefill_fn

    def _writer(self, n_write_pages: int):
        if n_write_pages not in self._writers:
            spec, keys = self.spec, self.keys

            if self.registry is None:
                def write(pool, page_ids, paged_leaves, epoch):
                    vn = vn_mod.kv_page_vn(epoch)
                    return kvp.write_prefill(pool, spec, keys, page_ids,
                                             paged_leaves, n_write_pages, vn)
            else:
                def write(pool, page_ids, paged_leaves, epoch, ctx):
                    vn = vn_mod.kv_page_vn(epoch)
                    return kvp.write_prefill(pool, spec, keys, page_ids,
                                             paged_leaves, n_write_pages, vn,
                                             ctx)

            self._writers[n_write_pages] = jax.jit(write)
        return self._writers[n_write_pages]

    # Migration halves (used by the cluster engine): decrypt+verify N
    # whole pages on THIS shard / re-protect N transferred pages into
    # THIS shard's pool.  Split in two so the plaintext can hop devices
    # between the dispatches.

    def _page_reader(self, n: int):
        if n not in self._page_readers:
            spec, keys = self.spec, self.keys

            if self.registry is None:
                def read(pool, page_ids):
                    return kvp.read_pages_raw(pool, spec, keys, page_ids)
            else:
                def read(pool, page_ids, bank, rows, owners, epochs):
                    ctx = kvp.PageKeyCtx.make(bank, rows, owners, epochs)
                    return kvp.read_pages_raw(pool, spec, keys, page_ids,
                                              ctx)

            self._page_readers[n] = jax.jit(read)
        return self._page_readers[n]

    def _page_writer(self, n: int):
        if n not in self._page_writers:
            spec, keys = self.spec, self.keys

            if self.registry is None:
                def write(pool, page_ids, leaf_pages, epoch):
                    vn = vn_mod.kv_page_vn(epoch)
                    real = page_ids < spec.n_pages
                    return kvp.write_pages(pool, spec, keys, page_ids,
                                           leaf_pages, vn, real)
            else:
                def write(pool, page_ids, leaf_pages, epoch, bank, rows,
                          owners, epochs):
                    ctx = kvp.PageKeyCtx.make(bank, rows, owners, epochs)
                    vn = vn_mod.kv_page_vn(epoch)
                    real = page_ids < spec.n_pages
                    return kvp.write_pages(pool, spec, keys, page_ids,
                                           leaf_pages, vn, real, ctx)

            self._page_writers[n] = jax.jit(write)
        return self._page_writers[n]

    # -- public API ---------------------------------------------------------

    def _submit(self, request: SubmitRequest) -> int:
        prompt = [int(t) for t in request.prompt]
        max_new_tokens = request.max_new_tokens
        session = request.session
        if not prompt or max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and max_new_tokens>=1")
        total = len(prompt) + max_new_tokens
        if total > self.max_len:
            raise ValueError(f"prompt+max_new_tokens={total} exceeds "
                             f"max_len={self.max_len}")
        worst_pages = _ceil_div(total, self.page_tokens)
        if worst_pages > min(self.pages_per_slot, self.n_pages):
            raise ValueError(f"request needs up to {worst_pages} pages; pool "
                             f"has {self.n_pages} (per-slot cap "
                             f"{self.pages_per_slot})")
        tenant = None
        if self.registry is not None:
            if session is None:
                raise PermissionError("multi-tenant engine: submit() needs a "
                                      "registry session handle")
            tenant = self.registry.validate(session)
            if worst_pages > tenant.page_quota:
                raise ValueError(
                    f"request needs up to {worst_pages} pages; tenant "
                    f"{tenant.tenant_id!r} quota is {tenant.page_quota}")
        elif session is not None:
            raise ValueError("session handle given but the engine has no "
                             "tenant registry")
        rid = self._next_rid
        self._next_rid += 1
        now = time.perf_counter_ns()
        req = Request(rid, prompt, max_new_tokens, submit_tick=self.tick,
                      share_prefix=bool(request.share_prefix),
                      submit_time=now / 1e9, queued_ns=now)
        self.requests[rid] = req
        if tenant is not None:
            req.tenant_idx = tenant.index
            if not self._tenant_active(tenant.index):
                self._activate_vtime(tenant.index)
            self._tenant_waiting.setdefault(tenant.index,
                                            deque()).append(req)
        else:
            self.waiting.append(req)
        return rid

    def _tenant_active(self, index: int) -> bool:
        """Tenant has queued or running work (stride-scheduler sense)."""
        if self._tenant_waiting.get(index):
            return True
        return any(s is not None and s.tenant is not None
                   and s.tenant.index == index for s in self.slots)

    def _activate_vtime(self, index: int) -> None:
        """Re-anchor an (in)active tenant's virtual time on activation.

        Standard WFQ no-credit-for-idle rule: a tenant entering the
        backlog starts at max(its own virtual time, the system virtual
        time), approximated by the minimum virtual time of currently
        active tenants (or the maximum ever reached when the system is
        idle).  Without this, a late-arriving tenant would start at 0
        and monopolize admission until it "caught up" with incumbents.
        """
        active = [v for j, v in self._vtime.items()
                  if j != index and self._tenant_active(j)]
        if active:
            floor = min(active)
        else:
            floor = max(self._vtime.values(), default=0.0)
        self._vtime[index] = max(self._vtime.get(index, 0.0), floor)

    def rotate(self, tenant_id: str) -> int:
        """Live key rotation for one tenant (lazy re-encryption).

        Bumps the tenant's epoch in the registry.  Pages written under
        the *previous* epoch keep verifying (its keys stay in the
        bank); each re-encrypts to the new epoch on its next dirty
        write.  Before any key material moves, every attached engine's
        pre-rotation hook (:meth:`_pre_rotation`) eagerly reseals pages
        that would leave the retained window — decrypt under the dying
        epoch, re-encrypt under the current one, in one jitted crossing
        — so no slot is preempted and no KV recomputed.
        """
        if self.registry is None:
            raise ValueError("rotate() needs a tenant registry")
        return self.registry.rotate(tenant_id)

    def _page_owners(self) -> np.ndarray:
        """Per-frame owning tenant index (-1 = free / unowned).

        Fed into the Merkle leaves at sync time so every membership
        proof is tenant-bound; frames of two tenants can never swap
        proofs even with byte-identical MACs.  Same-tenant prefix
        sharing keeps a single owner, and cross-tenant sharing reseals
        into the destination's own frames, so the map is single-valued
        by construction.
        """
        owners = np.full(self.n_pages, -1, np.int64)
        for s in self.slots:
            if s is None or s.tenant is None:
                continue
            for p in s.pages:
                owners[p] = s.tenant.index
        return owners

    def audit_proof(self, session=None, *, rid: Optional[int] = None):
        """O(log n) membership proof for a session's resident frames.

        Returns a :class:`repro.serve.merkle_pool.AuditProof` — leaf
        MACs, sibling paths, shard id and the current shard Merkle root
        — which the tenant verifies host-independently with
        :func:`repro.serve.merkle_pool.verify_proof`.  On a
        multi-tenant engine the proof covers every resident frame of
        the session's tenant (narrow with ``rid=``); on a single-tenant
        engine it covers every resident frame.
        """
        if self.merkle is None:
            raise ValueError("audit_proof() needs the Merkle level "
                             "(engine built with merkle=False)")
        tenant = None
        if rid is not None:
            slot = next((s for s in self.slots
                         if s is not None and s.req.rid == rid), None)
            if slot is None:
                raise KeyError(f"request {rid} has no resident slot")
            tenant = slot.tenant
        elif self.registry is not None:
            if session is None:
                raise PermissionError("multi-tenant engine: audit_proof() "
                                      "needs a session handle")
            tenant = self.registry.validate(session)
        pages: list = []
        for s in self.slots:
            if s is None:
                continue
            if rid is not None and s.req.rid != rid:
                continue
            if tenant is not None and (s.tenant is None
                                       or s.tenant.index != tenant.index):
                continue
            pages.extend(s.pages)
        self._merkle_sync()
        proof = self.merkle.audit_proof(
            pages, tenant=None if tenant is None else tenant.index)
        self.stats["audit_proofs"] += 1
        self._audit("audit_proof",
                    tenant=None if tenant is None else tenant.tenant_id,
                    pages=len(proof.pages), root=proof.root)
        return proof

    def share_prefix(self, tokens, *, from_session, to_session) -> int:
        """Explicitly reseal one tenant's cached prefix for another.

        The ONLY cross-tenant sharing path: a plain cache match never
        crosses tenants (entries are keyed and sealed per tenant, so a
        borrowed page id simply fails its MAC gate).  Here the operator
        presents valid sessions for BOTH tenants; the source tenant's
        cached chain covering ``tokens`` is decrypt-verified under the
        source cache binding and re-sealed page-by-page under the
        destination tenant's cache binding, then indexed on the
        destination's own chain.  Returns the number of pages shared.
        """
        if self.prefix_cache is None:
            raise ValueError("share_prefix() needs prefix_cache=True")
        src = self.registry.validate(from_session)
        dst = self.registry.validate(to_session)
        tokens = [int(t) for t in tokens]
        pc = self.prefix_cache
        src_chain = pc.match(src.index, tokens)
        if not src_chain:
            return 0
        covered = sum(e.n_tokens for e in src_chain)
        matched_dst, missing = pc.missing(dst.index, tokens[:covered])
        if not missing:
            return 0            # already cached for dst (or partial leaf)
        m = len(matched_dst)    # chunk-aligned: dst already holds m chunks
        src_entries = src_chain[m:]
        short = pc.free_capacity()
        if short < len(missing):
            self._free(pc.reclaim(len(missing) - short))
        k = min(len(missing), pc.free_capacity(), len(self.free_pages))
        if k == 0:
            return 0
        missing, src_entries = missing[:k], src_entries[:k]
        dst_pages = [self.free_pages.pop() for _ in range(k)]
        n = max(self.pages_per_slot, k)
        src_ids = np.full((n,), self.spec.scratch_page, np.int32)
        dst_ids = np.full((n,), self.spec.scratch_page, np.int32)
        src_ids[:k] = [e.page_id for e in src_entries]
        dst_ids[:k] = dst_pages
        src_rows = np.full((n,), self.registry.cache_row(src.index), np.int32)
        dst_rows = np.full((n,), self.registry.cache_row(dst.index), np.int32)
        role = np.full((n,), kvp.PREFIX_ROLE, np.uint32)
        new_pool, ok = self._copier(n)(
            self.pool, self._bank(), jnp.asarray(src_ids),
            jnp.asarray(dst_ids), jnp.asarray(src_rows), jnp.asarray(role),
            jnp.full((n,), src.index, jnp.uint32), jnp.asarray(dst_rows),
            jnp.asarray(role), jnp.full((n,), dst.index, jnp.uint32),
            self._next_epoch())
        if not self.page_io.report_verdict(ok, "prefix_share"):
            self._free(dst_pages)
            raise self._integrity_fail(
                f"reseal-on-share {src.tenant_id!r} -> {dst.tenant_id!r} "
                f"failed source verification", op="prefix_share",
                tenant=src.tenant_id, to_tenant=dst.tenant_id,
                pages=[int(e.page_id) for e in src_entries])
        self.pool = new_pool
        parent = matched_dst[-1] if matched_dst else None
        for (key, n_tok), page_id in zip(missing, dst_pages):
            parent = pc.insert(key, parent, page_id, n_tok)
        self.stats["prefix_shared_pages"] += k
        self._audit("prefix_share", tenant=src.tenant_id,
                    to_tenant=dst.tenant_id, pages=k)
        return k

    def _pre_rotation(self, tenant, new_epoch: int) -> None:
        """Eagerly reseal pages about to fall out of the key window.

        Runs while the dying epoch's keys are still in the bank.  All
        such pages across this engine's slots are resealed to the
        tenant's CURRENT epoch (which stays retained after the bump) in
        one batched ``reseal_pages`` dispatch per slot.
        """
        oldest_after = new_epoch - self.registry.retain + 1
        cur = tenant.current_epoch
        for i, slot in enumerate(self.slots):
            if slot is None or slot.tenant is not tenant:
                continue
            # Cache-bound pages (epoch word PREFIX_ROLE) live outside
            # the epoch window: their keys never rotate.
            stale = [j for j, e in enumerate(slot.page_epochs)
                     if not (e & kvp.PREFIX_ROLE) and e < oldest_after]
            if not stale:
                continue
            self._reseal_slot(i, stale, cur)

    def _reseal_slot(self, slot_idx: int, page_pos: list,
                     to_epoch: int) -> None:
        """Reseal the given page positions of one slot to ``to_epoch``."""
        slot = self.slots[slot_idx]
        tenant = slot.tenant
        n = self.pages_per_slot                       # padded/bucketed size
        page_ids = np.full((n,), self.spec.scratch_page, np.int32)
        old_rows = np.zeros((n,), np.int32)
        old_epochs = np.zeros((n,), np.uint32)
        new_row = self.registry.key_row(tenant.index, to_epoch)
        for k, j in enumerate(page_pos):
            page_ids[k] = slot.pages[j]
            old_epochs[k] = slot.page_epochs[j]
            old_rows[k] = self.registry.key_row(tenant.index,
                                                slot.page_epochs[j])
        owners = np.full((n,), tenant.index, np.uint32)
        new_pool, ok = self._resealer(n)(
            self.pool, self._bank(), jnp.asarray(page_ids),
            jnp.asarray(old_rows), jnp.asarray(old_epochs),
            jnp.asarray(owners),
            jnp.full((n,), new_row, jnp.int32),
            jnp.full((n,), np.uint32(to_epoch), jnp.uint32),
            self._next_epoch())
        # Gate BEFORE committing: a failed decrypt means the old bytes
        # were tampered, and storing their reseal would launder them
        # under fresh, valid MACs.
        if not self.page_io.report_verdict(ok, "reseal"):
            raise self._integrity_fail(
                f"reseal of slot {slot_idx} pages {page_pos} failed "
                f"verification (tenant {tenant.tenant_id!r})",
                op="reseal", tenant=tenant.tenant_id, slot=slot_idx,
                pages=[int(slot.pages[j]) for j in page_pos])
        self.pool = new_pool
        for j in page_pos:
            slot.page_epochs[j] = to_epoch
        self.stats["reseals"] += 1
        self._audit("reseal", tenant=tenant.tenant_id, slot=slot_idx,
                    pages=len(page_pos), to_epoch=to_epoch)

    def _resealer(self, n: int):
        if n not in self._resealers:
            spec, keys = self.spec, self.keys

            def reseal(pool, bank, page_ids, old_rows, old_epochs, owners,
                       new_rows, new_epochs, epoch):
                old_ctx = kvp.PageKeyCtx.make(bank, old_rows, owners,
                                              old_epochs)
                new_ctx = kvp.PageKeyCtx.make(bank, new_rows, owners,
                                              new_epochs)
                vn = vn_mod.kv_page_vn(epoch)
                return kvp.reseal_pages(pool, spec, keys, page_ids, vn,
                                        old_ctx, new_ctx)

            self._resealers[n] = jax.jit(reseal)
        return self._resealers[n]

    def _on_rotation(self, tenant, new_epoch: int) -> None:
        """Post-rotation hook: preempt anything a reseal missed.

        After an eager reseal nothing should be left outside the
        window; this is the belt-and-braces fallback (e.g. a slot whose
        page-epoch mirror was tampered between the hooks)."""
        oldest_retained = new_epoch - self.registry.retain + 1
        for i, slot in enumerate(self.slots):
            if (slot is not None and slot.tenant is tenant
                    and any(not (e & kvp.PREFIX_ROLE) and e < oldest_retained
                            for e in slot.page_epochs)):
                self._preempt(i)
        self.stats["rotations"] += 1
        self._audit("rotation", tenant=tenant.tenant_id,
                    new_epoch=new_epoch)

    def step(self) -> list:
        """One scheduler tick: admit, grow/evict, batched decode.

        Returns the requests that finished during this tick.  The tick
        is split into :meth:`_tick_begin` (host scheduling + prefill),
        dispatch/collect decode halves, and :meth:`_tick_end` (deferred
        verification), so a cluster scheduler can interleave the phases
        of many shard engines — dispatching every shard's decode before
        blocking on any of them.
        """
        with self._span("tick", step_num=self.tick + 1):
            finished: list = []
            if self.ft is None:
                active_idx = self._tick_begin(finished)
                if active_idx:
                    pending = self._decode_dispatch(active_idx)
                    self._decode_collect(active_idx, pending, finished)
                self._tick_end()
                return finished
            # Fault-contained tick: an IntegrityError raised by any phase
            # (admission reseal/CoW/cache-insert, stale-epoch page-table
            # checks, the decode MAC gate, the deferred pool check) is
            # localized and quarantined instead of escaping.  Skipping the
            # remainder of a phase for one tick is token-invariant: no
            # slot's bookkeeping advanced for the skipped work.
            try:
                active_idx = self._tick_begin(finished)
            except IntegrityError as err:
                self._contain_error(err)
                active_idx = []
            try:
                if active_idx:
                    pending = self._decode_dispatch(active_idx)
                    self._decode_collect(active_idx, pending, finished)
            except IntegrityError as err:
                self._contain_error(err)
            try:
                self._tick_end()
            except IntegrityError as err:
                self._contain_error(err)
            return finished

    def _tick_begin(self, finished: list) -> list:
        """Advance the tick: rotation policy, admission, growth.

        Returns the slot indices active for this tick's decode."""
        self.tick += 1
        with self._span("tick_begin"):
            if (self.registry is not None and self.rotate_every
                    and self.tick % self.rotate_every == 0
                    and self.registry.n_tenants):
                idx = self._rotate_rr % self.registry.n_tenants
                self._rotate_rr += 1
                self.rotate(self.registry.by_index(idx).tenant_id)
            self._admit(finished)
            self._ensure_growth()
            if self.prefix_cache is not None:
                self._ensure_cow()
            return [i for i, s in enumerate(self.slots) if s is not None]

    def _tick_end(self) -> None:
        with self._span("tick_end"):
            if (self.policy.deferred_model_mac and self.defer_interval
                    and self.tick % self.defer_interval == 0):
                with self._span("tick_end.deferred_check"):
                    self._deferred_check()
            # Merkle maintenance shares the deferred cadence but not the
            # scheme gate: audit proofs exist for every scheme (the
            # page-MAC table is part of the pool under all of them).
            if (self.merkle is not None and self.defer_interval
                    and self.tick % self.defer_interval == 0):
                with self._span("tick_end.merkle"):
                    self._merkle_sync()

    def _merkle_sync(self) -> None:
        roots, leaves = self.merkle.sync()
        self.stats["merkle_root_updates"] += roots
        self.stats["merkle_leaf_updates"] += leaves

    def run(self, max_ticks: int = 100_000) -> RunResult:
        """Drive ticks until every submitted request finished.

        Returns a :class:`RunResult`: ``{rid: Request}`` for finished
        requests, with per-request latency percentiles (ticks-to-first
        -token and ticks-per-token) on ``.latency``.
        """
        for _ in range(max_ticks):
            if self._n_waiting() or any(s is not None for s in self.slots):
                self.step()
                continue
            if self._drained():
                break
        else:
            raise RuntimeError("run() exceeded max_ticks")
        result = RunResult({rid: r for rid, r in self.requests.items()
                            if r.state == "finished"})
        result.latency = self.latency_stats()
        return result

    def _drained(self) -> bool:
        """End-of-run verification; True when nothing was re-queued.

        Without fault tolerance a failed check raises exactly as
        before.  With it, a failure is contained — which may re-queue
        recovering sessions, in which case :meth:`run` keeps ticking.
        """
        if self.policy.deferred_model_mac:
            if self.ft is None:
                self._deferred_check()
            else:
                try:
                    self._deferred_check()
                except IntegrityError as err:
                    self._contain_error(err)
        if not self.verify_every_step and not self.page_io.report_verdict(
                self._ok_accum, "decode_accum"):
            err = self._integrity_fail(
                "accumulated page-MAC verification failed", op="decode_accum")
            if self.ft is None:
                raise err
            self._contain_error(err)
            self._ok_accum = jnp.asarray(True)
        return not (self._n_waiting()
                    or any(s is not None for s in self.slots))

    def latency_stats(self) -> dict:
        """p50/p95/p99 ticks-to-first-token + ticks-per-token (finished)."""
        return latency_percentiles(self.requests.values())

    def deferred_check(self) -> bool:
        """Model-level deferred MAC over the whole pool (paper Table I)."""
        return bool(kvp.deferred_pool_check(self.pool, self.spec))

    def decode_cost_analysis(self, bucket: Optional[int] = None) -> dict:
        """XLA cost analysis of the jitted batched decode step.

        ``bytes accessed`` makes the protection traffic HLO-visible:
        the delta vs. the ``off`` scheme is the metadata + crypto
        traffic a scheme adds to one batched decode.  ``bucket``
        selects the page-count-bucketed variant to analyse (default:
        the all-resident ``pages_per_slot`` window) — the delta across
        buckets is the gather/crypt/MAC work touched-page bucketing
        removes for short live contexts.
        """
        if bucket is None:
            bucket = self.pages_per_slot
        fn = self._decode_fn_for(bucket)
        args = self._decode_analysis_args(bucket)
        cost = fn.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return dict(cost or {})

    def _decode_analysis_args(self, bucket: int) -> list:
        """Shape-representative args for lowering one decode variant
        (shared by :meth:`decode_cost_analysis` and the profiler)."""
        args = [
            self.params, self.pool, self.onchip,
            jnp.zeros((self.max_slots, bucket), jnp.int32),
            jnp.ones((self.max_slots,), jnp.int32),
            jnp.ones((self.max_slots,), bool),
            jnp.zeros((self.max_slots, 1), jnp.int32),
            jnp.uint32(1),
        ]
        if self.registry is not None:
            args += [
                self._bank(),
                jnp.zeros((self.max_slots, bucket), jnp.int32),
                jnp.zeros((self.max_slots,), jnp.uint32),
                jnp.zeros((self.max_slots, bucket), jnp.uint32),
                jnp.zeros((self.max_slots,), jnp.int32),
                jnp.zeros((self.max_slots,), jnp.uint32),
            ]
        return args

    def profile(self, buckets=None, uniform: bool = False,
                refresh: bool = False) -> dict:
        """Attributed device-cost profile (protection vs model HLO
        cost) of the decode variants — see :mod:`repro.obs.profiler`.

        Compiles each requested (bucket, uniform) variant on first use
        and caches the :class:`~repro.obs.profiler.CostProfile`; the
        ``protection_overhead_ratio`` / ``roofline_utilization`` lazy
        gauges sample this cache, so snapshots never trigger a compile.
        """
        if buckets is None:
            buckets = [self.pages_per_slot]
        profiles = []
        for bucket in buckets:
            key = (int(bucket), bool(uniform))
            if refresh or key not in self._cost_profiles:
                self._cost_profiles[key] = profiler_mod.profile_decode(
                    self, bucket, uniform)
            profiles.append(self._cost_profiles[key])
        return {"scheme": self.scheme, "shard": self.shard_id,
                "profiles": [p.to_dict() for p in profiles]}

    @property
    def n_free_pages(self) -> int:
        return len(self.free_pages)

    def tenant_resident_pages(self, index: int) -> int:
        """Pool pages currently owned by one tenant's running slots."""
        return sum(len(s.pages) for s in self.slots
                   if s is not None and s.tenant is not None
                   and s.tenant.index == index)

    # -- scheduler internals ------------------------------------------------

    def _n_waiting(self) -> int:
        return len(self.waiting) + sum(len(q) for q in
                                       self._tenant_waiting.values())

    def _next_epoch(self) -> jnp.ndarray:
        self._epoch += 1
        return jnp.uint32(self._epoch)

    # -- admission ----------------------------------------------------------

    def _prefill(self, seq: list):
        """Run (bucketed) prefill for one request's token sequence."""
        lp = len(seq)
        if self.prefill_buckets:
            padded = seq + [0] * (_bucket_len(lp, self.max_len) - lp)
        else:
            padded = seq
        if len(padded) not in self._prefill_shapes:
            self._prefill_shapes.add(len(padded))
            self.stats["prefill_compiles"] += 1
        self.stats["prefills"] += 1
        return self._prefill_fn(self.params,
                                jnp.asarray([padded], jnp.int32),
                                jnp.int32(lp - 1))

    def _admission_pages(self, req: Request) -> int:
        # +1 so the first decode's write position is always covered.
        return min(len(req.prompt + req.generated) // self.page_tokens + 1,
                   self.pages_per_slot)

    def _held(self, req: Request) -> bool:
        """Recovery backoff: re-admission is delayed past hold_until."""
        return req.hold_until > self.tick

    def _admit(self, finished: list) -> None:
        if self.registry is None:
            while None in self.slots:
                # FCFS over requests not held back by recovery backoff.
                req = next((r for r in self.waiting
                            if not self._held(r)), None)
                if req is None or \
                        len(self.free_pages) < self._admission_pages(req):
                    break
                self.waiting.remove(req)
                self._admit_one(req, None, finished)
            return
        # Weighted-fair (stride) admission across tenant queues: among
        # tenants whose head request fits (free pages AND page quota),
        # admit the one with the least virtual time; charge it the
        # pages it allocated, scaled by 1/weight.  A quota-capped
        # tenant queues its own work — it never evicts other tenants.
        while None in self.slots:
            best = None
            for idx, queue in self._tenant_waiting.items():
                if not queue or self._held(queue[0]):
                    continue
                tenant = self.registry.by_index(idx)
                n_alloc = self._admission_pages(queue[0])
                if n_alloc > len(self.free_pages):
                    continue
                if self.tenant_resident_pages(idx) + n_alloc > \
                        tenant.page_quota:
                    continue
                vt = self._vtime[idx]
                if best is None or vt < best[0]:
                    best = (vt, idx, tenant, n_alloc)
            if best is None:
                break
            _, idx, tenant, n_alloc = best
            req = self._tenant_waiting[idx].popleft()
            self._vtime[idx] += n_alloc / tenant.weight
            self._admit_one(req, tenant, finished)

    def _admit_one(self, req: Request, tenant, finished: list) -> None:
        if self.tracer is not None:
            # Ring buffer only: the wait began at submit (or preemption),
            # before any profiler annotation could have been opened.
            self.tracer.add("queue_wait", req.queued_ns,
                            time.perf_counter_ns(),
                            {"tick": self.tick, "rid": req.rid})
        seq = req.prompt + req.generated
        if (self.prefix_cache is not None and tenant is not None
                and req.share_prefix and len(seq) > 1):
            # Match over seq[:-1] so at least one token is left to feed
            # the decode loop (the hit path generates via decode only).
            hit = self.prefix_cache.match(tenant.index, seq[:-1])
            if hit:
                self._admit_hit(req, tenant, hit, seq, finished)
                return
        n_alloc = self._admission_pages(req)
        slot_idx = self.slots.index(None)
        pages = [self.free_pages.pop() for _ in range(n_alloc)]
        with self._span("admit.prefill", rid=req.rid):
            tok, paged_leaves, onchip_leaves = self._prefill(seq)
            n_write = _ceil_div(len(seq), self.page_tokens)
            page_ids = np.full((self.pages_per_slot,),
                               self.spec.scratch_page, np.int32)
            page_ids[: len(pages)] = pages
            if tenant is None:
                self.pool = self._writer(n_write)(
                    self.pool, jnp.asarray(page_ids), paged_leaves,
                    self._next_epoch())
                page_epochs = []
            else:
                epoch = tenant.current_epoch
                row = self.registry.key_row(tenant.index, epoch)
                ctx = kvp.PageKeyCtx.make(
                    self._bank(),
                    np.full((self.pages_per_slot,), row, np.int32),
                    np.full((self.pages_per_slot,), tenant.index, np.uint32),
                    np.full((self.pages_per_slot,), epoch, np.uint32))
                self.pool = self._writer(n_write)(
                    self.pool, jnp.asarray(page_ids), paged_leaves,
                    self._next_epoch(), ctx)
                page_epochs = [epoch] * len(pages)
            for j, idx in enumerate(self.onchip_idx):
                self.onchip[j] = self.onchip[j].at[:, slot_idx].set(
                    onchip_leaves[j][:, 0])
            first = int(tok[0, 0])          # syncs on the prefill
        self._admit_seq += 1
        self.stats["admitted"] += 1
        slot = _Slot(req, length=len(seq), pages=pages,
                     admit_seq=self._admit_seq, tenant=tenant,
                     page_epochs=page_epochs)
        self.slots[slot_idx] = slot
        self.page_table.install(slot_idx, slot)
        req.state = "running"
        self._note_recovered(req)
        req.generated.append(first)
        if req.first_tick is None:
            req.first_tick = self.tick
            self._observe_ttft(req)
        if (self.prefix_cache is not None and tenant is not None
                and req.share_prefix):
            self._prefix_insert(tenant, seq, slot)
        self._maybe_finish(slot_idx, finished)

    def _admit_hit(self, req: Request, tenant, hit: list, seq: list,
                   finished: list) -> None:
        """Admit a request whose leading pages are already cached.

        No prefill runs.  The matched chain's pages are installed
        read-only at the front of the slot (``shared_n``, epoch word
        :data:`~repro.serve.kv_pages.PREFIX_ROLE`), the slot length is
        set to the covered token count, and the rest of the prompt is
        queued on ``slot.replay``: each tick teacher-forces the next
        prompt token through the normal batched decode (its KV lands in
        private pages), and the sampled token of the LAST replay step
        is the first real output — token-identical to a full prefill
        because causal KV at position p depends only on tokens 0..p.
        """
        covered = sum(e.n_tokens for e in hit)
        n_shared = len(hit)
        slot_idx = self.slots.index(None)
        self.prefix_cache.acquire(hit)
        slot = _Slot(req, length=covered,
                     pages=[e.page_id for e in hit],
                     admit_seq=self._admit_seq + 1, tenant=tenant,
                     page_epochs=[kvp.PREFIX_ROLE] * n_shared,
                     shared_n=n_shared, shared_entries=list(hit),
                     replay=deque(seq[covered:]))
        self._admit_seq += 1
        self.stats["admitted"] += 1
        self.stats["prefix_hit_pages"] += n_shared
        self.stats["prefill_pages_skipped"] += n_shared
        self.slots[slot_idx] = slot
        self.page_table.install(slot_idx, slot)
        req.state = "running"
        self._note_recovered(req)

    def _note_recovered(self, req: Request) -> None:
        """Count a recompute-recovery re-admission (any shard's)."""
        if req.recovering:
            req.recovering = False
            self.stats["sessions_recovered"] += 1
            self._audit("session_recovered", rid=req.rid,
                        retries=req.integrity_retries)

    def _prefix_insert(self, tenant, seq: list, slot: _Slot) -> None:
        """Seed the cache from a freshly-prefilled slot (full miss only).

        Copy-reseals the slot's leading chunk pages into cache-owned
        free pages under the tenant's cache binding (session epoch word
        → ``PREFIX_ROLE``); the slot keeps decoding on its private
        pages.  Gated on ``ok`` BEFORE the pool commits, so tampered
        session pages cannot be laundered into valid cache MACs.
        """
        pc = self.prefix_cache
        matched, missing = pc.missing(tenant.index, seq)
        if matched or not missing:
            return              # partial hits never extend the chain here
        short = pc.free_capacity()
        if short < len(missing):
            self._free(pc.reclaim(len(missing) - short))
        k = min(len(missing), pc.free_capacity(), len(self.free_pages))
        if k == 0:
            return
        missing = missing[:k]
        dst_pages = [self.free_pages.pop() for _ in range(k)]
        n = self.pages_per_slot
        src_ids = np.full((n,), self.spec.scratch_page, np.int32)
        dst_ids = np.full((n,), self.spec.scratch_page, np.int32)
        src_ids[:k] = slot.pages[:k]
        dst_ids[:k] = dst_pages
        epoch = tenant.current_epoch
        src_rows = np.full((n,), self.registry.key_row(tenant.index, epoch),
                           np.int32)
        src_epochs = np.full((n,), epoch, np.uint32)
        dst_rows = np.full((n,), self.registry.cache_row(tenant.index),
                           np.int32)
        dst_epochs = np.full((n,), kvp.PREFIX_ROLE, np.uint32)
        owners = np.full((n,), tenant.index, np.uint32)
        new_pool, ok = self._copier(n)(
            self.pool, self._bank(), jnp.asarray(src_ids),
            jnp.asarray(dst_ids), jnp.asarray(src_rows),
            jnp.asarray(src_epochs), jnp.asarray(owners),
            jnp.asarray(dst_rows), jnp.asarray(dst_epochs),
            jnp.asarray(owners), self._next_epoch())
        if not self.page_io.report_verdict(ok, "prefix_insert"):
            self._free(dst_pages)
            raise self._integrity_fail(
                f"prefix-cache insert for tenant {tenant.tenant_id!r} "
                f"failed source verification",
                op="prefix_insert", tenant=tenant.tenant_id,
                pages=[int(p) for p in slot.pages[:k]])
        self.pool = new_pool
        parent = None
        for (key, n_tok), page_id in zip(missing, dst_pages):
            parent = pc.insert(key, parent, page_id, n_tok)
        self.stats["prefix_inserted_pages"] += k
        self._audit("prefix_insert", tenant=tenant.tenant_id, pages=k)

    def _copier(self, n: int):
        """Jitted page-copy reseal (cache insert / CoW / share), padded
        to ``n`` lanes with scratch pages."""
        if n not in self._copiers:
            io = self.page_io

            def copy(pool, bank, src_ids, dst_ids, src_rows, src_epochs,
                     src_owners, dst_rows, dst_epochs, dst_owners, epoch):
                src_ctx = kvp.PageKeyCtx.make(bank, src_rows, src_owners,
                                              src_epochs)
                dst_ctx = kvp.PageKeyCtx.make(bank, dst_rows, dst_owners,
                                              dst_epochs)
                vn = vn_mod.kv_page_vn(epoch)
                return io.copy(pool, src_ids, dst_ids, vn, src_ctx, dst_ctx)

            self._copiers[n] = jax.jit(copy)
        return self._copiers[n]

    # -- growth / eviction ---------------------------------------------------

    def _ensure_growth(self) -> None:
        order = sorted((i for i, s in enumerate(self.slots) if s is not None),
                       key=lambda i: self.slots[i].admit_seq)
        for i in order:
            slot = self.slots[i]
            if slot is None:                      # evicted by an older slot
                continue
            need = slot.length // self.page_tokens
            while self.slots[i] is not None and len(slot.pages) <= need:
                tenant = slot.tenant
                if tenant is not None and \
                        self.tenant_resident_pages(tenant.index) + 1 > \
                        tenant.page_quota:
                    # Over quota: the tenant preempts ITS OWN youngest.
                    self._preempt(self._pick_victim(tenant))
                    continue
                if self.free_pages:
                    slot.pages.append(self.free_pages.pop())
                    if tenant is not None:
                        slot.page_epochs.append(tenant.current_epoch)
                    continue
                self._preempt(self._pick_victim(tenant))

    def _ensure_cow(self) -> None:
        """Copy-on-write any shared page this tick's decode will dirty.

        Runs after growth, before dispatch: the dirty page is
        ``length // page_tokens``; when it is still inside the shared
        prefix it is privatized first, so decode never writes a
        refcounted cache page.  By construction only the LAST shared
        page can ever be partial, so at most one CoW fires per slot
        over its whole life.
        """
        for i, slot in enumerate(self.slots):
            if slot is None or not slot.shared_n:
                continue
            if slot.length // self.page_tokens < slot.shared_n:
                self._cow_page(i)

    def _cow_page(self, idx: int) -> None:
        """Privatize one slot's deepest shared page before it is dirtied."""
        slot = self.slots[idx]
        tenant = slot.tenant
        pos = slot.shared_n - 1     # only the deepest shared page is partial
        while not self.free_pages:
            freed = self.prefix_cache.reclaim(1)
            if freed:
                self._free(freed)
                break
            self._preempt(self._pick_victim(tenant))
            if self.slots[idx] is None:
                return              # the CoW slot itself was the victim
        dst = self.free_pages.pop()
        n = self.pages_per_slot
        src_ids = np.full((n,), self.spec.scratch_page, np.int32)
        dst_ids = np.full((n,), self.spec.scratch_page, np.int32)
        src_ids[0] = slot.pages[pos]
        dst_ids[0] = dst
        epoch = tenant.current_epoch
        src_rows = np.full((n,), self.registry.cache_row(tenant.index),
                           np.int32)
        src_epochs = np.full((n,), kvp.PREFIX_ROLE, np.uint32)
        dst_rows = np.full((n,), self.registry.key_row(tenant.index, epoch),
                           np.int32)
        dst_epochs = np.full((n,), epoch, np.uint32)
        owners = np.full((n,), tenant.index, np.uint32)
        new_pool, ok = self._copier(n)(
            self.pool, self._bank(), jnp.asarray(src_ids),
            jnp.asarray(dst_ids), jnp.asarray(src_rows),
            jnp.asarray(src_epochs), jnp.asarray(owners),
            jnp.asarray(dst_rows), jnp.asarray(dst_epochs),
            jnp.asarray(owners), self._next_epoch())
        if not self.page_io.report_verdict(ok, "cow"):
            self._free([dst])
            raise self._integrity_fail(
                f"copy-on-write of slot {idx} shared page {pos} failed "
                f"verification (tenant {tenant.tenant_id!r})",
                op="cow", tenant=tenant.tenant_id, slot=idx,
                page=int(slot.pages[pos]))
        self.pool = new_pool
        slot.pages[pos] = dst
        slot.page_epochs[pos] = epoch
        slot.shared_n -= 1
        self.prefix_cache.release([slot.shared_entries.pop()])
        self.stats["prefix_cow_pages"] += 1
        self._audit("cow", tenant=tenant.tenant_id, slot=idx, page=int(dst))

    def _pick_victim(self, tenant=None) -> int:
        """Youngest running slot (LIFO preemption, vLLM-style) — scoped
        to ``tenant``'s own slots in multi-tenant mode, so one tenant's
        memory pressure never evicts another's requests.  May be the
        slot whose growth triggered the eviction."""
        candidates = [i for i, s in enumerate(self.slots) if s is not None
                      and (tenant is None or s.tenant is tenant)]
        return max(candidates, key=lambda i: self.slots[i].admit_seq)

    def _unpin_shared(self, slot: _Slot) -> None:
        """Drop a dying slot's pin on its shared prefix pages.

        Shared pages belong to the cache, not the slot — only the
        private tail returns to the free list."""
        if slot.shared_n:
            self.prefix_cache.release(slot.shared_entries)
            del slot.pages[: slot.shared_n]
            del slot.page_epochs[: slot.shared_n]
            slot.shared_n = 0
            slot.shared_entries = []

    def _preempt(self, idx: int) -> None:
        slot = self.slots[idx]
        self._unpin_shared(slot)
        self._free(slot.pages)
        self.slots[idx] = None
        self.page_table.clear(idx)
        slot.req.state = "waiting"
        slot.req.queued_ns = time.perf_counter_ns()
        slot.req.n_evictions += 1
        self.stats["preemptions"] += 1
        if self._preempt_hook is not None and self._preempt_hook(slot.req):
            return          # the cluster took it (re-routes across shards)
        if slot.tenant is not None:               # preempted go to the front
            self._tenant_waiting[slot.tenant.index].appendleft(slot.req)
        else:
            self.waiting.appendleft(slot.req)

    def _release(self, idx: int) -> None:
        slot = self.slots[idx]
        self._unpin_shared(slot)
        self._free(slot.pages)
        self.slots[idx] = None
        self.page_table.clear(idx)
        slot.req.state = "finished"

    def _maybe_finish(self, idx: int, finished: list) -> None:
        slot = self.slots[idx]
        req = slot.req
        hit_eos = (self.eos_id is not None and req.generated
                   and req.generated[-1] == self.eos_id)
        if req.done or hit_eos:
            req.done_tick = self.tick
            self._release(idx)
            finished.append(req)

    # -- fault containment (quarantine + secure-recompute recovery) ----------

    def _free(self, pages) -> None:
        """Return pages to the free list — minus quarantined frames,
        which are permanently retired."""
        self.free_pages.extend(p for p in pages
                               if int(p) not in self.quarantined)

    def _n_recovering(self) -> int:
        """Sessions currently preempted for secure-recompute recovery
        (queued or backing off) — the SLO monitor's degraded signal."""
        return sum(1 for r in self.requests.values() if r.recovering)

    def _commit_repair(self, new_pool: kvp.PagedKVPool) -> None:
        """Commit a repaired pool, resyncing listeners wholesale.

        The tamper being repaired bypassed the pool setter (untrusted
        memory does not announce writes), so folding the repair's
        *delta* into the cluster mirrors would propagate the attacker's
        divergence.  Listeners are instead told to re-adopt the
        repaired pool MAC (``old_pool=None``)."""
        self._pool = new_pool
        for listener in self._pool_listeners:
            listener(None, new_pool)

    def _quarantine_pages(self, pages) -> None:
        """Permanently retire physical frames after a localized fault.

        The frames leave the free list forever, the prefix cache drops
        any entry holding them, their MAC/VN metadata rows are scrubbed
        and the deferred pool MAC is rebuilt from the scrubbed page
        MACs — the pool's XOR identity holds again without trusting a
        single tampered byte."""
        fresh = sorted({int(p) for p in pages} - self.quarantined)
        if not fresh:
            return
        self.quarantined.update(fresh)
        self.free_pages = [p for p in self.free_pages
                           if p not in self.quarantined]
        if self.prefix_cache is not None:
            self.prefix_cache.evict_pages(fresh)
        pool = self.pool
        ids = jnp.asarray(fresh, jnp.int32)
        page_macs = pool.page_macs.at[ids].set(0)
        block_macs = tuple(bm.at[ids].set(0) for bm in pool.block_macs)
        page_vns = pool.page_vns.at[ids].set(0)
        pool_mac = mac_mod.xor_aggregate(page_macs[: self.spec.n_pages])
        self._commit_repair(pool._replace(
            page_macs=page_macs, block_macs=block_macs,
            page_vns=page_vns, pool_mac=pool_mac))
        self.stats["integrity_quarantined_pages"] += len(fresh)
        self._audit("quarantine", pages=fresh)

    def _rebuild_pool_mac(self) -> None:
        """Recompute the deferred pool MAC from the stored page MACs.

        The containment fallback when localization finds no failing
        page yet a pool-level check failed: the pool MAC itself — not
        any page — was hit, and rebuilding it from page MACs that all
        just re-verified restores the XOR identity.  Free pages' MACs
        are unverifiable here, but they protect no live data and are
        overwritten (and freshly MACed) by their next prefill."""
        pool = self.pool
        self._commit_repair(pool._replace(
            pool_mac=mac_mod.xor_aggregate(
                pool.page_macs[: self.spec.n_pages])))
        self._audit("pool_mac_rebuild")

    def _probe_page(self, slot_idx: int, pos: int) -> bool:
        """Re-read one resident page through the raw verify path.

        Retried ``ft.reread_retries`` extra times so a transient fault
        does not condemn a healthy frame as persistent tamper.  Probe
        verdicts flow through ``report_verdict`` like any other MAC
        gate (op ``probe``)."""
        slot = self.slots[slot_idx]
        pid = int(slot.pages[pos])
        ids = jnp.asarray([pid], jnp.int32)
        attempts = 1 + (self.ft.reread_retries if self.ft is not None else 0)
        for _ in range(attempts):
            if self.registry is None:
                _, ok = self._page_reader(1)(self.pool, ids)
            else:
                tenant = slot.tenant
                epoch = slot.page_epochs[pos]
                if epoch & kvp.PREFIX_ROLE:
                    row = self.registry.cache_row(tenant.index)
                else:
                    try:
                        row = self.registry.key_row(tenant.index, epoch)
                    except KeyError:
                        return False    # unverifiable == condemned
                _, ok = self._page_reader(1)(
                    self.pool, ids, self._bank(),
                    jnp.asarray([row], jnp.int32),
                    jnp.asarray([tenant.index], jnp.uint32),
                    jnp.asarray([np.uint32(epoch)], jnp.uint32))
            if self.page_io.report_verdict(ok, "probe", slot=slot_idx,
                                           page=pid):
                return True
        return False

    def _localize(self, slot_idxs=None) -> list:
        """Per-page probe sweep over the given (default: all occupied)
        slots; returns ``[(slot_idx, pos, page_id), ...]`` for every
        resident page that persistently fails verification."""
        idxs = (slot_idxs if slot_idxs is not None
                else range(self.max_slots))
        bad = []
        for i in idxs:
            slot = self.slots[i]
            if slot is None:
                continue
            for pos in range(len(slot.pages)):
                if not self._probe_page(i, pos):
                    bad.append((i, pos, int(slot.pages[pos])))
        return bad

    def _preempt_recover(self, idx: int) -> None:
        """Preempt one slot for secure-recompute recovery — or declare
        its session dead once the retry budget is spent."""
        slot = self.slots[idx]
        req = slot.req
        req.integrity_retries += 1
        if self.ft is not None and \
                req.integrity_retries > self.ft.max_retries:
            self._unpin_shared(slot)
            self._free(slot.pages)
            self.slots[idx] = None
            self.page_table.clear(idx)
            req.state = "failed"
            req.recovering = False
            self.stats["sessions_lost"] += 1
            self._audit("session_lost", rid=req.rid, slot=idx,
                        retries=req.integrity_retries)
            return
        req.recovering = True
        if self.ft is not None and self.ft.backoff_ticks:
            req.hold_until = self.tick + self.ft.backoff_ticks * (
                1 << (req.integrity_retries - 1))
        self._audit("session_recovery", rid=req.rid, slot=idx,
                    retries=req.integrity_retries)
        self._preempt(idx)

    def _contain_error(self, err: IntegrityError) -> None:
        """Quarantine + recover after a caught integrity failure.

        Pages named by the error's context are condemned directly;
        otherwise a full localization sweep re-verifies every resident
        page.  When nothing persistently fails — a transient fault or
        a hit on the pool MAC itself — the deferred identity is
        rebuilt instead, so the next pool-level check passes without
        laundering any tampered page."""
        ctx = getattr(err, "ctx", None) or {}
        pages = [int(p) for p in ctx.get("pages", [])]
        if "page" in ctx and int(ctx["page"]) not in pages:
            pages.append(int(ctx["page"]))
        if not pages:
            pages = [b[2] for b in self._localize()]
        self._audit("fault_contained", detail=str(err),
                    op=ctx.get("op"), pages=pages)
        if pages:
            self._quarantine_pages(pages)
            for i, slot in enumerate(self.slots):
                if slot is not None and any(
                        int(p) in self.quarantined for p in slot.pages):
                    self._preempt_recover(i)
        else:
            self._rebuild_pool_mac()

    # -- decode --------------------------------------------------------------

    def _bank(self):
        """The registry key bank, replicated onto this shard's device."""
        return self.registry.bank_for(self._device)

    def _uniform_row(self, active_idx: list):
        """The single bank row serving every page this tick, or None.

        The host-side single-key fast-path gate: when every resident
        page AND every dirty write of the tick resolves to one
        (tenant, epoch) bank row, the vmapped per-page crypt is
        overkill — the uniform decode fn runs the flat single-key route
        (fused kernels included) with bit-identical RePA metadata.
        """
        tenant, row = None, None
        for i in active_idx:
            slot = self.slots[i]
            t = slot.tenant
            if t is None:
                return None
            if any(e != t.current_epoch for e in slot.page_epochs):
                return None
            r = self.registry.key_row(t.index, t.current_epoch)
            if row is None:
                tenant, row = t, r
            elif r != row:
                return None
        return (tenant, row)

    def _tenant_decode_args(self, active_idx: list, bucket: int) -> tuple:
        """Per-slot/per-page key selections for one decode tick.

        Per-page arrays are shaped to the tick's page-count ``bucket``
        (the level-2 window), matching the bucketed page table.
        Returns ``(args, uniform)`` — when ``uniform`` the whole batch
        resolves to one bank row (arrays are filled uniformly so the
        single gathered key covers scratch writes of inactive slots
        too) and the caller dispatches the single-key decode fn.
        """
        s, p = self.max_slots, bucket
        uni = self._uniform_row(active_idx)
        if uni is not None:
            tenant, row = uni
            epoch = np.uint32(tenant.current_epoch)
            return ([self._bank(),
                     jnp.full((s, p), row, jnp.int32),
                     jnp.full((s,), tenant.index, jnp.uint32),
                     jnp.full((s, p), epoch, jnp.uint32),
                     jnp.full((s,), row, jnp.int32),
                     jnp.full((s,), epoch, jnp.uint32)], True)
        key_idx = np.zeros((s, p), np.int32)
        owners = np.zeros((s,), np.uint32)
        key_epochs = np.zeros((s, p), np.uint32)
        cur_key_idx = np.zeros((s,), np.int32)
        cur_epochs = np.zeros((s,), np.uint32)
        for i, slot in enumerate(self.slots):
            if slot is None or slot.tenant is None:
                continue
            tenant = slot.tenant
            owners[i] = tenant.index
            cur_epochs[i] = tenant.current_epoch
            cur_key_idx[i] = self.registry.key_row(tenant.index,
                                                   tenant.current_epoch)
            for j, epoch in enumerate(slot.page_epochs[:p]):
                key_epochs[i, j] = epoch
                if epoch & kvp.PREFIX_ROLE:
                    # Shared prefix page: sealed under the tenant's
                    # epoch-independent cache binding, not a session
                    # epoch row.
                    key_idx[i, j] = self.registry.cache_row(tenant.index)
                    continue
                try:
                    key_idx[i, j] = self.registry.key_row(tenant.index,
                                                          epoch)
                except KeyError as e:
                    # A resident page claiming an epoch its tenant has
                    # no retained key for is an integrity violation
                    # (stale-epoch replay / page-table tamper), not a
                    # scheduling error.
                    raise self._integrity_fail(
                        f"slot {i} page {j}: {e.args[0]}",
                        op="stale_epoch", tenant=tenant.tenant_id,
                        slot=i, page=int(slot.pages[j])) from e
        return ([self._bank(), jnp.asarray(key_idx),
                 jnp.asarray(owners), jnp.asarray(key_epochs),
                 jnp.asarray(cur_key_idx), jnp.asarray(cur_epochs)], False)

    def _decode(self, active_idx: list, finished: list) -> None:
        pending = self._decode_dispatch(active_idx)
        self._decode_collect(active_idx, pending, finished)

    def _decode_dispatch(self, active_idx: list):
        """Launch this tick's batched decode; no host sync.

        The page-count bucket is picked HERE, host-side, from the live
        lengths (no device value is consulted), so the dispatch stays
        async and a cluster can dispatch every shard before collecting
        any.  Protection work inside the jitted step scales with the
        bucket's page window, not with ``pages_per_slot``.

        Returns the (still-async) ``(toks, ok)`` device values; the
        pool/onchip state is already swapped to the new (async) arrays.
        """
        with self._span("decode_dispatch"):
            bucket = self.page_table.bucket_for(
                (self.slots[i].length for i in active_idx), self.page_tokens)
            page_table = self.page_table.window(bucket)
            lengths = np.zeros((self.max_slots,), np.int32)
            active = np.zeros((self.max_slots,), bool)
            tokens = np.zeros((self.max_slots, 1), np.int32)
            for i in active_idx:
                slot = self.slots[i]
                lengths[i] = slot.length
                active[i] = True
                # Replay (shared-prefix hit) teacher-forces the prompt
                # suffix the skipped prefill still owes the KV cache.
                tokens[i, 0] = (slot.replay[0] if slot.replay
                                else slot.req.generated[-1])
            args = [self.params, self.pool, self.onchip,
                    jnp.asarray(page_table), jnp.asarray(lengths),
                    jnp.asarray(active), jnp.asarray(tokens),
                    self._next_epoch()]
            uniform = False
            if self.registry is not None:
                tenant_args, uniform = self._tenant_decode_args(active_idx,
                                                                bucket)
                args += tenant_args
            decode_fn = self._decode_fn_for(bucket, uniform)
            if uniform or self.registry is None:
                # Single-key tick: flat crypt/MAC route, fused kernels when
                # the spec qualifies.
                self.stats["uniform_fast_ticks"] += 1
            elif kvp._kernel_read_ok(self.spec) and \
                    self.spec.cfg.verify != "none":
                # Mixed bank rows, but the fused kernel stays on via its
                # per-page round-key gather.  (verify == "none" reads skip
                # MACs entirely and never enter the fused kernel, so they
                # must not count as fused ticks.)
                self.stats["fused_mixed_ticks"] += 1
            if self.spec.cfg.verify != "none":
                # Which implementation verified this tick's page read: the
                # fused kernel, or the jnp reference (wide-block schemes,
                # or kernels off).
                if kvp._kernel_read_ok(self.spec):
                    self.stats["fused_read_ticks"] += 1
                else:
                    self.stats["reference_read_ticks"] += 1
            if kvp._kernel_write_ok(self.spec) and \
                    self.spec.cfg.verify != "none":
                # The tick's dirty-page reseal runs the one-pass fused
                # write kernel (single-key, uniform, or mixed-row alike) —
                # write_pages never touches the vmapped reference.
                self.stats["fused_write_ticks"] += 1
            self.stats["decode_page_reads"] += len(active_idx) * bucket
            self._bucket_hist.observe(bucket)
            self.pool, self.onchip, toks, ok = decode_fn(*args)
            self.stats["decode_steps"] += 1
            return toks, ok

    def _decode_collect(self, active_idx: list, pending,
                        finished: list) -> None:
        """Sync on a dispatched decode and apply host bookkeeping."""
        with self._span("decode_collect"):
            toks, ok = pending
            with self._span("decode.wait"):
                if self.verify_every_step:
                    ok = bool(ok)
                toks = np.asarray(toks)
            if self.verify_every_step:
                if not self.page_io.report_verdict(ok, "decode"):
                    self._decode_failure(active_idx)
            else:
                self._ok_accum = self._ok_accum & ok
            for i in active_idx:
                slot = self.slots[i]
                if slot is None:
                    # Quarantined + preempted by _decode_failure: its
                    # bookkeeping must not advance — recompute recovery
                    # replays from the last good token.
                    continue
                if slot.tenant is not None:
                    # The dirty page was just re-encrypted under the
                    # tenant's CURRENT epoch (lazy rotation lands here).
                    dirty = slot.length // self.page_tokens
                    if dirty < len(slot.page_epochs):
                        slot.page_epochs[dirty] = slot.tenant.current_epoch
                slot.length += 1
                if slot.replay:
                    slot.replay.popleft()
                    if slot.replay:
                        continue    # mid-replay: the sample is discarded
                    # The LAST replay step's sample is the first real
                    # output (exactly what a full prefill would have
                    # returned).
                slot.req.generated.append(int(toks[i, 0]))
                if slot.req.first_tick is None:
                    slot.req.first_tick = self.tick
                    self._observe_ttft(slot.req)
                self._maybe_finish(i, finished)

    def _decode_failure(self, active_idx: list) -> None:
        """The decode-tick MAC gate failed: localize, then contain.

        Localization re-reads every active slot's resident pages and
        condemns the ones that persistently fail.  Without fault
        tolerance the strict discipline raises — now with the failing
        page(s) in the error context.  With it, the condemned frames
        are quarantined and only their slots preempted for recovery;
        every other slot's reads verified, so its token and dirty write
        are bit-identical to a fault-free tick and bookkeeping
        proceeds.  An empty localization is a transient fault: the
        tick's tokens came from reads that now re-verify, so nothing is
        preempted."""
        bad = self._localize(active_idx)
        ctx = {}
        if bad:
            slot = self.slots[bad[0][0]]
            ctx = dict(slot=bad[0][0], pages=[b[2] for b in bad])
            if slot is not None and slot.tenant is not None:
                ctx["tenant"] = slot.tenant.tenant_id
        if self.ft is None:
            raise self._integrity_fail(
                f"page MAC verification failed at tick {self.tick} "
                f"(scheme={self.scheme}, shard={self.shard_id})",
                op="decode", **ctx)
        if not bad:
            self._audit("transient_fault", op="decode")
            return
        self._audit("fault_contained", op="decode",
                    pages=[b[2] for b in bad])
        self._quarantine_pages([b[2] for b in bad])
        for idx in sorted({b[0] for b in bad}):
            if self.slots[idx] is not None:
                self._preempt_recover(idx)

    def _deferred_check(self) -> None:
        self.stats["deferred_checks"] += 1
        if not self.page_io.report_verdict(self.deferred_check(), "deferred"):
            raise self._integrity_fail(
                "deferred pool-level MAC check failed "
                f"(tick {self.tick}, scheme={self.scheme})", op="deferred")
