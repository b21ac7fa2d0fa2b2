"""Paged, MAC-protected KV-cache pool for batched secure serving.

The serving-side boundary in SeDA is the KV/latent cache: during long
decodes it is the tensor that lives in untrusted memory.  This module
co-designs the serving memory layout with the protection machinery:

* the cache is a pool of fixed-size **pages** (``page_tokens`` tokens
  per page, per sequence, spanning all layers);
* each page's per-layer payload is padded to the scheme's optBlk
  granularity (``block_bytes`` from :mod:`repro.core.secure_exec`), so
  a page is always a whole number of protection blocks — the page is
  the unit of ownership AND of MAC bookkeeping;
* each page carries a MAC (XOR aggregate of its optBlk MACs, per
  :mod:`repro.core.mac`) and a VN (:func:`repro.core.vn.kv_page_vn`);
* reads verify only the pages a decode step touches; writes re-MAC
  only dirty pages; a pool-level deferred MAC (the model-MAC level of
  :mod:`repro.core.multilevel`) is maintained incrementally and checked
  off the critical path.

Trust model (matches the paper's Table III assignments): ciphertext
pages (and, for the block-gated SGX/MGX schemes, their per-block MAC
tables) are untrusted; page MACs and VNs model on-chip SRAM metadata
for MGX/SeDA (SGX's off-chip VN table and integrity tree are charged as
emulated traffic, as in :mod:`repro.core.secure_exec`).  Replaying an
old page ciphertext therefore fails verification: the on-chip VN has
moved on and the MAC binding (PA, VN, layer, fmap, blk) no longer
matches.

Everything here is pure and jit-compatible; the serving engine traces
``read_pages`` + model decode + ``write_dirty`` as ONE jitted
computation.  On the B-AES/NH schemes with narrow blocks BOTH boundary
directions run fused Pallas kernels: reads through decrypt+hash
(:func:`repro.kernels.fused_crypt_mac.ops.secure_read_kernel`) and
writes through encrypt+hash-of-fresh-ciphertext
(:func:`repro.kernels.fused_crypt_mac.ops.secure_write_kernel`) — the
dirty-page reseal touches its bytes once, not once to encrypt and once
to MAC.

**Multi-tenant pages.**  Every boundary crossing optionally takes a
:class:`PageKeyCtx`: a stacked key bank (one row per retained
(tenant, epoch) — see :mod:`repro.tenancy.registry`) plus per-page row
indices and (tenant, epoch) identities.  With a ctx, each page is
encrypted/MACed under *its own tenant-epoch keys* (gathered from the
bank inside the traced computation and applied via ``vmap``), and the
tenant identity is folded into the RePA tuple twice over:

* the MAC binding's ``fmap`` word carries ``tenant_idx`` and the key
  epoch alongside the leaf index, and
* the CTR counter gains the tenant-epoch VN salt (word 0) and a
  ``tenant_idx ‖ epoch`` word (word 2),

so a page written under tenant A's keys fails verification when read
under tenant B's — or under a stale epoch — even before the key
mismatch scrambles the plaintext.  ``ctx=None`` keeps the single-key
fast path (including the fused-kernel route) bit-identical to the
single-tenant engine.  When every page of a crossing resolves to ONE
bank row, ``uniform=True`` keeps the per-page (tenant, epoch) words in
the RePA binding but dispatches the flat single-key crypt/MAC route
(including the fused kernels) instead of the vmapped per-page one —
bit-identical metadata, single-key speed.  MIXED-row crossings stay on
the fused kernels too, in BOTH directions: the mixed variants gather
each page's AES schedule, B-AES diversifiers and NH key row from the
bank inside one fused pass
(:func:`repro.kernels.fused_crypt_mac.ops.secure_read_kernel_mixed` /
:func:`repro.kernels.fused_crypt_mac.ops.secure_write_kernel_mixed`),
so a mixed-tenant tick's dirty-page reseal never falls back to the
vmapped per-page reference either.

**Touched-page windows.**  :class:`TwoLevelPageTable` (slot directory
-> pow2 page-count-bucketed windows) lets every boundary crossing run
on just the pages a tick touches: ``read_pages``/``write_dirty``
derive all shapes from the page table actually passed, so a (S, P)
window with P < pages_per_slot gathers/crypts/MACs P pages per slot —
protection work follows the live context, not pool capacity.

**Sharded pools.**  A :class:`PageSpec` additionally carries a
``(shard, n_shards)`` identity.  The shard id is folded into the RePA
binding (``fmap`` bits 28–31) and XORed into CTR counter word 0, so a
page is cryptographically pinned to its device: a byte-identical page
(ciphertext + MAC + VN) captured on shard 0 and replayed into shard
1's pool recomputes a different MAC under shard 1's binding and fails
its gate.  ``shard=0, n_shards=1`` (the default) is bit-identical to
the unsharded layout.  :func:`reseal_pages` (decrypt old keys →
re-encrypt new, one fused crossing) and :func:`migrate_pages` (reseal
across pools/shards) are the primitives live rotation and secure
cross-shard migration build on.

**One IO surface.**  Every boundary crossing is a method of
:class:`PageIO`, a facade bound to one ``(spec, keys)`` pair — the
prefix cache, the engine and the cluster all go through it.  The
module-level ``read_pages``/``write_pages``/... functions are thin
delegating wrappers kept for existing callers; both spellings are
bit-identical.

**Shared-prefix pages.**  :class:`PrefixCache` is the host-side
content-addressed index over pages sealed under a tenant's dedicated
*cache binding*: epoch word :data:`PREFIX_ROLE` (fmap bit 27) selects
the tenant's epoch-independent cache keys instead of a session epoch,
so a prefix sealed once is verify-read by many sessions — VN-stable,
no re-MAC on hit — and survives ``rotate()``.  Divergence is
copy-on-write: the engine reseals the first dirty shared page into a
private page under the session binding (see
:mod:`repro.serve.engine`).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baes, ctr, mac
from repro.core.layout import SEGMENT_BYTES
from repro.core.secure_exec import SCHEMES, SchemeConfig, emulated_tree_probe

__all__ = [
    "LeafPageSpec",
    "PageSpec",
    "PagedKVPool",
    "PageKeyCtx",
    "PageIO",
    "PrefixCache",
    "PrefixCacheEntry",
    "PREFIX_ROLE",
    "TwoLevelPageTable",
    "page_count_bucket",
    "PAGED_FIELDS",
    "paged_flags",
    "length_flags",
    "build_page_spec",
    "init_pool",
    "read_pages",
    "write_pages",
    "write_prefill",
    "write_dirty",
    "read_pages_raw",
    "reseal_pages",
    "migrate_pages",
    "deferred_pool_check",
]

# fmap-word bit budget: leaf idx (0-7) | tenant (8-15) | epoch word
# (16-27) | shard (28-31).  The shard field caps a sharded pool's
# fan-out.  The epoch word spends its top bit (fmap bit 27) as the
# prefix-cache ROLE: a page sealed into the shared-prefix cache
# carries epoch word PREFIX_ROLE instead of a session epoch, selecting
# the tenant's epoch-independent cache keys — session epochs occupy
# the remaining 11 bits (fmap 16-26).  The crypt/MAC plumbing below is
# role-agnostic: the role bit rides inside the epoch word through
# _tenant_words / _block_binding unchanged.
MAX_SHARDS = 16
PREFIX_ROLE = 0x800          # bit 11 of the epoch word -> fmap bit 27

# Cache NamedTuple fields whose leaves have a (steps, B, max_len, ...)
# sequence layout and cross the untrusted boundary.  Everything else
# (lengths, Mamba SSM/conv state) is small per-sequence register state
# that stays on-chip.
PAGED_FIELDS = frozenset({"k", "v", "c_kv", "k_pe"})


class LeafPageSpec(NamedTuple):
    """Static page layout for one paged cache leaf (hashable)."""

    leaf_idx: int        # index in the flat cache-leaf list
    steps: int           # layer-stack dim of the scanned segment
    base_layer: int      # global layer id of stack index 0 (MAC binding)
    rest: tuple          # per-token trailing dims, e.g. (n_kv, head_dim)
    dtype: str
    tok_bytes: int       # bytes per token per layer
    lp_bytes: int        # per-layer page payload, padded to block_bytes
    page_bytes: int      # steps * lp_bytes
    n_blocks: int        # optBlks per page = page_bytes // block_bytes
    pa_base: int         # pool base address in 16B-segment units


class PageSpec(NamedTuple):
    """Static description of the whole paged pool (hashable jit arg)."""

    leaves: tuple        # tuple[LeafPageSpec, ...]
    page_tokens: int
    pages_per_slot: int
    n_pages: int         # real pages; arrays carry one extra scratch row
    max_slots: int
    max_len: int         # page_tokens * pages_per_slot
    scheme: str          # key into core.secure_exec.SCHEMES
    use_kernel: bool     # route crypto through the Pallas kernels
    shard: int = 0       # this pool's shard id (folded into RePA/CTR)
    n_shards: int = 1    # cluster fan-out this pool belongs to

    @property
    def cfg(self) -> SchemeConfig:
        return SCHEMES[self.scheme]

    @property
    def scratch_page(self) -> int:
        """Write sink for inactive slots / unallocated table entries."""
        return self.n_pages

    @property
    def blocks_per_read(self) -> int:
        """optBlks touched by one full gather (tree-traffic emulation)."""
        return (sum(l.n_blocks for l in self.leaves)
                * self.max_slots * self.pages_per_slot)


class PagedKVPool(NamedTuple):
    """The cache as it lives across the boundary (+ its metadata)."""

    cts: tuple           # per paged leaf: (n_pages + 1, page_bytes) u8
    page_macs: jax.Array     # (n_pages + 1, MAC_BYTES) u8
    block_macs: tuple        # block-gated schemes: per leaf
    #                          (n_pages + 1, n_blocks, MAC_BYTES) u8; else ()
    page_vns: jax.Array      # (n_pages + 1,) u32
    pool_mac: jax.Array      # (MAC_BYTES,) u8 — deferred model-level MAC


class PageKeyCtx(NamedTuple):
    """Per-page tenant key selection for one boundary crossing.

    The four ``bank_*`` arrays are the registry's stacked key bank
    (K rows, one per retained (tenant, epoch)); the three per-page
    arrays select a row and carry the identity folded into the RePA
    binding.  All seven are ordinary traced arrays, so the same
    compiled step serves any tenant mix / post-rotation key state.
    """

    bank_key: jax.Array          # (K, 16) u8 cipher keys
    bank_round_keys: jax.Array   # (K, 11, 16) u8 schedules
    bank_hash_key: jax.Array     # (K, n_lanes) u32 NH lanes
    bank_salt: jax.Array         # (K,) u32 CTR-counter salts
    key_idx: jax.Array           # (N,) i32 bank row per page
    owners: jax.Array            # (N,) u32 tenant index per page
    epochs: jax.Array            # (N,) u32 key epoch per page

    @classmethod
    def make(cls, bank, key_idx, owners, epochs) -> "PageKeyCtx":
        """Build from a registry ``KeyBank`` + per-page selections."""
        return cls(bank.key, bank.round_keys, bank.hash_key, bank.salt,
                   jnp.asarray(key_idx, jnp.int32),
                   jnp.asarray(owners, jnp.uint32),
                   jnp.asarray(epochs, jnp.uint32))

    def take(self, n: int) -> "PageKeyCtx":
        """Ctx for the first ``n`` pages (static prefix slice)."""
        return self._replace(key_idx=self.key_idx[:n],
                             owners=self.owners[:n], epochs=self.epochs[:n])


# ---------------------------------------------------------------------------
# Two-level page table: slot directory -> bucketed page windows.
# ---------------------------------------------------------------------------


def page_count_bucket(n: int, cap: int) -> int:
    """Round a live page count up to the next power of two, capped."""
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)


class TwoLevelPageTable:
    """Host-side two-level page table over the paged pool.

    Level 1 — the **slot directory**: one variable-length page-id list
    per decode lane (plus, in tenant mode, the parallel per-page
    key-epoch list).  The directory holds the scheduler's *slot
    entries* (any object with ``pages`` and, optionally,
    ``page_epochs`` list attributes) and reads them live at window
    emission, so growth/eviction/migration bookkeeping — including
    wholesale list reassignment — is reflected without copying.

    Level 2 — the **page window**: a fixed-shape ``(max_slots, bucket)``
    int32 table emitted per boundary crossing, where ``bucket`` is the
    pow2 page-count bucket covering every live slot's touched pages
    (the pages holding positions <= length, i.e. ``length //
    page_tokens + 1`` of them).  The jitted decode step compiles once
    per bucket — at most ``log2(pages_per_slot) + 1`` variants,
    mirroring PR 2's prefill length bucketing — and its
    gather/crypt/MAC/verify work scales with the bucket, not with
    ``pages_per_slot``: a short live context in a large pool no longer
    pays for the pool's resident capacity.

    Invariant: every emitted window is a *prefix* of each slot's page
    list (pages are table-ordered by token position), and the bucket
    always covers each live slot's dirty write page, so decode output
    is token-identical to the all-resident window for every scheme.
    """

    def __init__(self, max_slots: int, pages_per_slot: int):
        self.max_slots = max_slots
        self.pages_per_slot = pages_per_slot
        self._entries: list = [None] * max_slots

    def install(self, idx: int, entry) -> None:
        """Register one lane's directory entry — any object carrying a
        ``pages`` list attribute (and ``page_epochs`` in tenant mode)."""
        self._entries[idx] = entry

    def clear(self, idx: int) -> None:
        self._entries[idx] = None

    def bucket_for(self, live_lengths, page_tokens: int) -> int:
        """Pow2 page-count bucket covering every live slot's touched
        pages *and* its dirty write page (``length // page_tokens + 1``
        pages per slot)."""
        need = 1
        for ln in live_lengths:
            need = max(need, ln // page_tokens + 1)
        return page_count_bucket(need, self.pages_per_slot)

    def window(self, bucket: int) -> np.ndarray:
        """Level-2 page window: (max_slots, bucket) int32, -1 where a
        slot is empty or holds fewer pages than the bucket."""
        tab = np.full((self.max_slots, bucket), -1, np.int32)
        for i, entry in enumerate(self._entries):
            pages = None if entry is None else entry.pages
            if not pages:
                continue
            k = min(len(pages), bucket)
            tab[i, :k] = pages[:k]
        return tab


# ---------------------------------------------------------------------------
# Structure classification + spec construction.
# ---------------------------------------------------------------------------


def _iter_field_flags(node: Any, wanted: frozenset):
    """Yield one bool per flat leaf: is it under a ``wanted`` field?"""
    if hasattr(node, "_fields"):  # cache NamedTuples (KVCache, MLACache, ...)
        for name in node._fields:
            sub = getattr(node, name)
            n_sub = len(jax.tree_util.tree_leaves(sub))
            hit = name in wanted
            for _ in range(n_sub):
                yield hit
    elif isinstance(node, (list, tuple)):
        for child in node:
            yield from _iter_field_flags(child, wanted)
    elif isinstance(node, dict):
        for key in sorted(node):
            yield from _iter_field_flags(node[key], wanted)
    else:
        yield False


def paged_flags(cache_tree: Any) -> list:
    """Per-flat-leaf bools: True for leaves that go through the pool."""
    return list(_iter_field_flags(cache_tree, PAGED_FIELDS))


def length_flags(cache_tree: Any) -> list:
    """Per-flat-leaf bools: True for per-layer ``length`` leaves."""
    return list(_iter_field_flags(cache_tree, frozenset({"length"})))


def build_page_spec(cache_tree: Any, *, scheme: str, page_tokens: int,
                    n_pages: int, max_slots: int, max_len: int,
                    use_kernel: bool = False, shard: int = 0,
                    n_shards: int = 1) -> PageSpec:
    """Lay the paged leaves of a cache pytree out as a protected pool.

    ``cache_tree`` is the ShapeDtypeStruct tree from
    ``lm.cache_specs(cfg, max_slots, max_len)``.  The page-size /
    block-granularity invariant: each leaf's per-layer page payload
    (``page_tokens`` tokens) is padded up to the scheme's optBlk
    granularity, so page size is always a whole multiple of the SeDA
    block size and a page never shares a protection block with its
    neighbour.
    """
    if max_len % page_tokens:
        raise ValueError(f"max_len {max_len} not a multiple of "
                         f"page_tokens {page_tokens}")
    if not 0 < n_shards <= MAX_SHARDS or not 0 <= shard < n_shards:
        raise ValueError(f"shard {shard} / n_shards {n_shards} outside the "
                         f"{MAX_SHARDS}-shard fmap-word budget")
    cfg = SCHEMES[scheme]
    flags = paged_flags(cache_tree)
    leaves = jax.tree_util.tree_leaves(cache_tree)
    if len(flags) != len(leaves):
        raise ValueError("flag walk disagrees with tree_leaves order")
    specs = []
    cursor = 0          # pool byte cursor across leaves
    base_layer = 0
    for idx, (leaf, is_paged) in enumerate(zip(leaves, flags)):
        if not is_paged:
            continue
        steps, bsz, seq = leaf.shape[0], leaf.shape[1], leaf.shape[2]
        if bsz != max_slots or seq != max_len:
            raise ValueError(
                f"paged leaf {idx} has shape {leaf.shape}, expected "
                f"(steps, {max_slots}, {max_len}, ...)")
        rest = tuple(int(d) for d in leaf.shape[3:])
        itemsize = jnp.dtype(leaf.dtype).itemsize
        tok_bytes = itemsize
        for d in rest:
            tok_bytes *= d
        lp_bytes = (-(-page_tokens * tok_bytes // cfg.block_bytes)
                    * cfg.block_bytes)
        page_bytes = steps * lp_bytes
        specs.append(LeafPageSpec(
            leaf_idx=idx, steps=steps, base_layer=base_layer, rest=rest,
            dtype=jnp.dtype(leaf.dtype).name, tok_bytes=tok_bytes,
            lp_bytes=lp_bytes, page_bytes=page_bytes,
            n_blocks=page_bytes // cfg.block_bytes,
            pa_base=cursor // SEGMENT_BYTES))
        cursor += (n_pages + 1) * page_bytes
        base_layer += steps
    if not specs:
        raise ValueError("cache tree has no paged (KV/latent) leaves — "
                         "the paged engine needs at least one attention "
                         "or MLA layer")
    return PageSpec(tuple(specs), page_tokens, max_len // page_tokens,
                    n_pages, max_slots, max_len, scheme, use_kernel,
                    shard, n_shards)


def init_pool(spec: PageSpec) -> PagedKVPool:
    cfg = spec.cfg
    cts = tuple(jnp.zeros((spec.n_pages + 1, l.page_bytes), jnp.uint8)
                for l in spec.leaves)
    block_macs = ()
    if cfg.verify == "block":
        block_macs = tuple(
            jnp.zeros((spec.n_pages + 1, l.n_blocks, mac.MAC_BYTES), jnp.uint8)
            for l in spec.leaves)
    return PagedKVPool(
        cts=cts,
        page_macs=jnp.zeros((spec.n_pages + 1, mac.MAC_BYTES), jnp.uint8),
        block_macs=block_macs,
        page_vns=jnp.zeros((spec.n_pages + 1,), jnp.uint32),
        pool_mac=jnp.zeros((mac.MAC_BYTES,), jnp.uint8),
    )


# ---------------------------------------------------------------------------
# Per-page crypto/MAC primitives (flattened over a batch of pages).
# ---------------------------------------------------------------------------


def _block_pa(spec: PageSpec, leaf: LeafPageSpec,
              page_ids: jax.Array) -> jax.Array:
    """(N,) page ids -> (N, n_blocks) u32 optBlk PAs (16B-segment units)."""
    bb = spec.cfg.block_bytes
    segs_per_page = leaf.page_bytes // SEGMENT_BYTES
    blk = jnp.arange(leaf.n_blocks, dtype=jnp.uint32) * (bb // SEGMENT_BYTES)
    return (jnp.uint32(leaf.pa_base)
            + page_ids.astype(jnp.uint32)[:, None] * jnp.uint32(segs_per_page)
            + blk[None, :])


def _tenant_words(ctx: PageKeyCtx, per_page: int):
    """Per-entry (salt, tenant ‖ epoch) u32 words, repeated ``per_page``."""
    salts = jnp.repeat(ctx.bank_salt[ctx.key_idx], per_page)
    tenant = jnp.repeat((ctx.owners << jnp.uint32(16))
                        | (ctx.epochs & jnp.uint32(0xFFFF)), per_page)
    return salts, tenant


def _shard_ctr_word(spec: PageSpec) -> jnp.ndarray:
    """Shard id XORed into CTR counter word 0 (zero for shard 0)."""
    return jnp.uint32(spec.shard) << jnp.uint32(24)


def _block_counters(spec: PageSpec, leaf: LeafPageSpec, page_ids: jax.Array,
                    vns: jax.Array,
                    ctx: PageKeyCtx | None = None) -> jax.Array:
    """PA||VN counter words per optBlk: (N * n_blocks, 4) u32 (see
    :func:`_counter_words`)."""
    return jnp.stack(_counter_words(spec, leaf, page_ids, vns, ctx), axis=-1)


def _counter_words(spec: PageSpec, leaf: LeafPageSpec, page_ids: jax.Array,
                   vns: jax.Array, ctx: PageKeyCtx | None = None) -> list:
    """PA||VN counter words per optBlk: four (N * n_blocks,) u32 columns.

    With a tenant ctx, word 0 carries the tenant-epoch VN salt and
    word 2 the ``tenant_idx ‖ epoch`` identity, so CTR streams never
    collide across tenants or epochs even at equal (PA, VN).  On a
    sharded pool the shard id is XORed into word 0 — the keystream of a
    page never repeats across shards even under one engine-wide key.
    """
    pa = _block_pa(spec, leaf, page_ids).reshape(-1)
    vn_col = jnp.repeat(vns.astype(jnp.uint32), leaf.n_blocks)
    shard_w = _shard_ctr_word(spec)
    if ctx is None:
        return [jnp.full_like(pa, shard_w), pa, jnp.zeros_like(pa), vn_col]
    salts, tenant = _tenant_words(ctx, leaf.n_blocks)
    return [salts ^ shard_w, pa, tenant, vn_col]


def _block_binding(spec: PageSpec, leaf: LeafPageSpec, page_ids: jax.Array,
                   vns: jax.Array,
                   ctx: PageKeyCtx | None = None) -> mac.Binding:
    """MAC binding tuple for every optBlk of N pages (flattened).

    With a tenant ctx the ``fmap`` word is extended to
    ``leaf_idx | tenant_idx << 8 | key_epoch << 16`` — the RePA tuple
    then binds each block MAC to its owner and key epoch, so relocating
    a page across tenants (or replaying a stale-epoch page) breaks the
    binding independently of the key mismatch.  Bits 28-31 carry the
    pool's shard id, pinning every MAC to its device: a byte-identical
    page replayed onto another shard fails its gate.
    """
    n = page_ids.shape[0]
    bb = spec.cfg.block_bytes
    blocks_per_layer = leaf.lp_bytes // bb
    blk = jnp.arange(leaf.n_blocks, dtype=jnp.uint32)
    layer = jnp.uint32(leaf.base_layer) + blk // jnp.uint32(blocks_per_layer)
    pa = _block_pa(spec, leaf, page_ids).reshape(-1)
    fmap = jnp.uint32(leaf.leaf_idx) | (jnp.uint32(spec.shard)
                                        << jnp.uint32(28))
    if ctx is not None:
        fmap = jnp.repeat(
            fmap | (ctx.owners << jnp.uint32(8))
            | ((ctx.epochs & jnp.uint32(0xFFF)) << jnp.uint32(16)),
            leaf.n_blocks)
    return mac.Binding.make(
        pa,
        jnp.repeat(vns.astype(jnp.uint32), leaf.n_blocks),
        jnp.tile(layer, n),
        fmap,
        jnp.tile(blk, n))


def _uniform_keys(ctx: PageKeyCtx):
    """Single-row key view for the uniform fast path (row of page 0)."""
    row = ctx.key_idx[0]
    return (ctx.bank_key[row], ctx.bank_round_keys[row],
            ctx.bank_hash_key[row])


def _crypt(spec: PageSpec, leaf: LeafPageSpec, buf: jax.Array,
           page_ids: jax.Array, vns: jax.Array, keys,
           ctx: PageKeyCtx | None = None,
           uniform: bool = False) -> jax.Array:
    """XOR-crypt (enc == dec) page payloads.  buf: (N, page_bytes) u8.

    ``ctx=None``: every page under the engine-wide ``keys``.  With a
    ctx, each page's keys are gathered from the bank row it selects and
    the crypt is vmapped over pages (per-page key schedules); with
    ``uniform=True`` every page is known (host-side) to select the same
    bank row, so a single gathered key runs the flat single-key route —
    counters/bindings are unchanged, only the dispatch shape is.
    """
    cfg = spec.cfg
    if cfg.name == "off":
        return buf
    if cfg.baes:
        counters = _block_counters(spec, leaf, page_ids, vns, ctx)
        if ctx is not None and not uniform:
            rks = ctx.bank_round_keys[ctx.key_idx]         # (N, 11, 16)
            kks = ctx.bank_key[ctx.key_idx]                # (N, 16)
            per_page = counters.reshape(-1, leaf.n_blocks, 4)

            def one(buf1, rk1, kk1, ctr1):
                return baes.baes_encrypt(buf1, rk1, ctr1,
                                         block_bytes=cfg.block_bytes, key=kk1)

            return jax.vmap(one)(buf, rks, kks, per_page)
        if ctx is None:
            key, round_keys = keys.key, keys.round_keys
        else:
            key, round_keys, _ = _uniform_keys(ctx)
        out = baes.baes_encrypt(buf.reshape(-1), round_keys, counters,
                                block_bytes=cfg.block_bytes, key=key)
        return out.reshape(buf.shape)
    # T-AES: one AES invocation per 16B segment, PA advancing per segment.
    segs_per_page = leaf.page_bytes // SEGMENT_BYTES
    pa = (jnp.uint32(leaf.pa_base)
          + page_ids.astype(jnp.uint32)[:, None] * jnp.uint32(segs_per_page)
          + jnp.arange(segs_per_page, dtype=jnp.uint32)[None, :]).reshape(-1)
    vn_col = jnp.repeat(vns.astype(jnp.uint32), segs_per_page)
    shard_w = _shard_ctr_word(spec)
    if ctx is None:
        word0 = jnp.full_like(pa, shard_w)
        counters = jnp.stack([word0, pa, jnp.zeros_like(pa), vn_col], axis=-1)
        otp = ctr.ctr_keystream(keys.round_keys, counters)
        return (buf.reshape(-1, SEGMENT_BYTES) ^ otp).reshape(buf.shape)
    salts, tenant = _tenant_words(ctx, segs_per_page)
    counters = jnp.stack([salts ^ shard_w, pa, tenant, vn_col], axis=-1)
    if uniform:
        _, round_keys, _ = _uniform_keys(ctx)
        otp = ctr.ctr_keystream(round_keys, counters)
        return (buf.reshape(-1, SEGMENT_BYTES) ^ otp).reshape(buf.shape)
    per_page = counters.reshape(-1, segs_per_page, 4)
    otp = jax.vmap(ctr.ctr_keystream)(
        ctx.bank_round_keys[ctx.key_idx], per_page)
    return (buf.reshape(-1, segs_per_page, SEGMENT_BYTES) ^ otp).reshape(
        buf.shape)


def _page_block_macs(spec: PageSpec, leaf: LeafPageSpec, ct: jax.Array,
                     page_ids: jax.Array, vns: jax.Array, keys,
                     ctx: PageKeyCtx | None = None,
                     uniform: bool = False) -> jax.Array:
    """optBlk MACs of N ciphertext pages: (N, n_blocks, MAC_BYTES) u8."""
    cfg = spec.cfg
    binding = _block_binding(spec, leaf, page_ids, vns, ctx)
    n = page_ids.shape[0]
    if ctx is not None and not uniform:
        per_page = mac.Binding(
            *(jnp.broadcast_to(f, (n * leaf.n_blocks,))
              .reshape(n, leaf.n_blocks) for f in binding))

        def one(ct1, binding1, hk1, rk1):
            return mac.block_macs(ct1.reshape(-1, cfg.block_bytes), binding1,
                                  hash_key_u32=hk1, round_keys=rk1,
                                  engine=cfg.mac_engine)

        return jax.vmap(one)(ct, per_page, ctx.bank_hash_key[ctx.key_idx],
                             ctx.bank_round_keys[ctx.key_idx])
    if ctx is None:
        hash_key, round_keys = keys.hash_key, keys.round_keys
    else:
        _, round_keys, hash_key = _uniform_keys(ctx)
    blocks = ct.reshape(-1, cfg.block_bytes)
    macs = mac.block_macs(blocks, binding, hash_key_u32=hash_key,
                          round_keys=round_keys, engine=cfg.mac_engine)
    return macs.reshape(n, leaf.n_blocks, mac.MAC_BYTES)


# Pages per fused dispatch: the crossing runs over the window in chunks
# of about this many bytes, so its byte <-> word-plane relayouts stay
# small however many pages a tick touches.
_FUSED_CHUNK_BYTES = 8 << 20


def _fused_crossing(spec: PageSpec, leaf: LeafPageSpec, buf: jax.Array,
                    page_ids: jax.Array, vns: jax.Array, keys,
                    ctx: PageKeyCtx | None, uniform: bool, write: bool):
    """Kernel-fused crypt + optBlk-MAC pass over N pages.

    Read (``write=False``: decrypt + hash the incoming ciphertext) and
    write (``write=True``: encrypt + hash the fresh ciphertext) build
    the SAME binding/counters and key selections — only the kernel
    direction differs, so the two cannot drift apart.  ``ctx=None``
    (engine-wide keys) and uniform ctxs run single-key; a MIXED ctx
    (pages resolving to different bank rows) gathers each page's
    round-key schedule and NH key row from the bank and stays fused —
    the tenant words land in the binding/counters either way.

    Reads take (N, page_bytes) u8 ciphertext and return the plaintext
    as (N, page values) of the leaf's dtype; writes take the values and
    return u8 ciphertext — the kernels convert to and from their word
    planes directly, with no byte-level relayout of the plaintext.  The
    pages go through in chunks of ``_FUSED_CHUNK_BYTES`` (one
    ``lax.map`` step each).  Returns ``(out, page MACs (N, MAC_BYTES)
    u8)`` — each page's optBlk MACs already XOR-aggregated, which is
    all a layer-verified scheme keeps.
    """
    from repro.kernels.fused_crypt_mac import ops as fused_ops
    bb = spec.cfg.block_bytes
    n = page_ids.shape[0]
    per = max(1, min(n, _FUSED_CHUNK_BYTES // leaf.page_bytes))
    chunks = -(-n // per)

    def chunked(x, fill=0):
        x = jnp.pad(x, ((0, chunks * per - n),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=fill)
        return x.reshape((chunks, per) + x.shape[1:])

    per_page = [buf, page_ids, vns.astype(jnp.uint32)]
    if ctx is not None:
        per_page += [ctx.key_idx, ctx.owners, ctx.epochs]
    xs = [chunked(x) for x in per_page]
    xs[1] = chunked(page_ids, spec.scratch_page)
    if ctx is None:
        round_keys, hash_key = keys.round_keys, keys.hash_key
    elif uniform:
        _, round_keys, hash_key = _uniform_keys(ctx)
    else:
        round_keys, hash_key = ctx.bank_round_keys, ctx.bank_hash_key

    def one(chunk):
        data, ids, vn = chunk[:3]
        cctx = (None if ctx is None else
                ctx._replace(key_idx=chunk[3], owners=chunk[4],
                             epochs=chunk[5]))
        rows = (None if ctx is None or uniform
                else jnp.repeat(cctx.key_idx, leaf.n_blocks))
        out, words = fused_ops.secure_crossing(
            data.reshape(-1), _block_binding(spec, leaf, ids, vn, cctx),
            jnp.stack(_counter_words(spec, leaf, ids, vn, cctx)),
            round_keys, hash_key, block_bytes=bb, write=write,
            out_dtype=out_dtype, row_idx=rows)
        agg = jax.lax.reduce(words.reshape(2, per, leaf.n_blocks),
                             jnp.uint32(0), jax.lax.bitwise_xor, (2,))
        return out.reshape(per, -1), agg

    out_dtype = jnp.uint8 if write else jnp.dtype(leaf.dtype)
    out, agg = jax.lax.map(one, xs)
    agg = agg.transpose(1, 0, 2).reshape(2, chunks * per)[:, :n]
    page_macs = jax.lax.bitcast_convert_type(agg.T, jnp.uint8)
    return (out.reshape(chunks * per, -1)[:n],
            page_macs.reshape(n, mac.MAC_BYTES))


def _fused_read(spec: PageSpec, leaf: LeafPageSpec, ct: jax.Array,
                page_ids: jax.Array, vns: jax.Array, keys,
                ctx: PageKeyCtx | None = None, uniform: bool = False):
    """Kernel-fused decrypt + page MACs (see :func:`_fused_crossing`)."""
    return _fused_crossing(spec, leaf, ct, page_ids, vns, keys, ctx,
                           uniform, write=False)


def _kernel_read_ok(spec: PageSpec) -> bool:
    """Narrow-block B-AES + NH with layer-level verification — the
    envelope the fused kernels (and their page-aggregated MACs) cover."""
    cfg = spec.cfg
    return (spec.use_kernel and cfg.baes and cfg.mac_engine == "nh"
            and cfg.verify == "layer"
            and cfg.block_bytes // SEGMENT_BYTES <= 11
            and all(jnp.dtype(l.dtype).itemsize in (1, 2, 4)
                    for l in spec.leaves))


# The fused write kernel has the same capability envelope as the read
# one (narrow-block B-AES + NH): a spec whose reads fuse also writes
# fused, so a kernel-capable tick never touches the vmapped reference
# in either direction.
_kernel_write_ok = _kernel_read_ok


def _fused_write(spec: PageSpec, leaf: LeafPageSpec, buf: jax.Array,
                 page_ids: jax.Array, vns: jax.Array, keys,
                 ctx: PageKeyCtx | None = None, uniform: bool = False):
    """Kernel-fused encrypt + page MACs: the dirty page's plaintext
    is re-encrypted and its fresh ciphertext NH-hashed in ONE Pallas
    visit, instead of an encrypt dispatch followed by a MAC dispatch
    re-reading the ciphertext (see :func:`_fused_crossing`)."""
    return _fused_crossing(spec, leaf, buf, page_ids, vns, keys, ctx,
                           uniform, write=True)


# ---------------------------------------------------------------------------
# Dense <-> page byte layout.
# ---------------------------------------------------------------------------


def _page_values(leaf: LeafPageSpec, buf: jax.Array) -> jax.Array:
    """(..., page_bytes) u8 -> (..., page_bytes // itemsize) leaf-dtype
    values (the same bytes, little-endian)."""
    dtype = jnp.dtype(leaf.dtype)
    if dtype.itemsize == 1:
        return jax.lax.bitcast_convert_type(buf, dtype)
    grouped = buf.reshape(buf.shape[:-1] + (-1, dtype.itemsize))
    return jax.lax.bitcast_convert_type(grouped, dtype)


def _values_to_bytes(leaf: LeafPageSpec, vals: jax.Array) -> jax.Array:
    """Inverse of :func:`_page_values`."""
    as_u8 = jax.lax.bitcast_convert_type(vals, jnp.uint8)
    return as_u8.reshape(vals.shape[:-1] + (leaf.page_bytes,))


def _pages_to_dense(spec: PageSpec, leaf: LeafPageSpec, pt: jax.Array,
                    lengths: jax.Array) -> jax.Array:
    """(S, P, page values) -> (steps, S, P*page_tokens, *rest), invalid
    token positions (>= length) zeroed so masked attention never sees
    decrypt garbage (and schemes stay token-bit-identical).

    P is the page-count window of this crossing — the full
    ``pages_per_slot`` or a smaller pow2 bucket: the dense view covers
    exactly the gathered window (a PREFIX of the context, since pages
    are table-ordered), so attention over it is token-identical to the
    full-length view whenever every valid position fits the window.
    """
    s, p = pt.shape[:2]
    ptok = spec.page_tokens
    win_len = p * ptok
    itemsize = jnp.dtype(leaf.dtype).itemsize
    elems = leaf.tok_bytes // itemsize
    per_layer = pt.reshape(s, p, leaf.steps, leaf.lp_bytes // itemsize)
    vals = per_layer[..., : ptok * elems].reshape(s, p, leaf.steps, ptok,
                                                  elems)
    # (S, P, steps, ptok, elems) -> (steps, S, P*ptok, *rest)
    dense = vals.transpose(2, 0, 1, 3, 4).reshape(
        (leaf.steps, s, win_len) + leaf.rest)
    valid = (jnp.arange(win_len, dtype=jnp.int32)[None, :]
             < lengths[:, None])                       # (S, L)
    valid = valid.reshape((1, s, win_len) + (1,) * len(leaf.rest))
    return jnp.where(valid, dense, jnp.zeros((), dense.dtype))


def _dense_to_pages(spec: PageSpec, leaf: LeafPageSpec,
                    pages: jax.Array) -> jax.Array:
    """(N, steps, ptok, *rest) token data -> (N, page values), each
    layer's payload zero-padded to the block granularity."""
    n = pages.shape[0]
    itemsize = jnp.dtype(leaf.dtype).itemsize
    flat = pages.reshape(n, leaf.steps, -1)
    pad = leaf.lp_bytes // itemsize - flat.shape[-1]
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, 0), (0, pad)))
    return flat.reshape(n, leaf.page_bytes // itemsize)


def _values_to_tokens(spec: PageSpec, leaf: LeafPageSpec,
                      vals: jax.Array) -> jax.Array:
    """(N, page values) -> (N, steps, ptok, *rest) token data
    (inverse of :func:`_dense_to_pages`, padding stripped)."""
    n = vals.shape[0]
    ptok = spec.page_tokens
    elems = leaf.tok_bytes // jnp.dtype(leaf.dtype).itemsize
    per_layer = vals.reshape(n, leaf.steps, -1)[..., : ptok * elems]
    return per_layer.reshape((n, leaf.steps, ptok) + leaf.rest)


# ---------------------------------------------------------------------------
# PageIO: the one IO surface over the pool.  Every boundary crossing —
# batched decode read, bulk/prefill/dirty write, raw page read, reseal
# and migration — is a method here; the module-level free functions
# below are thin delegating wrappers kept so existing callers stay
# bit-identical.
# ---------------------------------------------------------------------------


class PageIO:
    """All pool boundary crossings for one ``(spec, keys)`` binding.

    The facade binds what is static for an engine — the pool layout
    (:class:`PageSpec`) and the engine-wide fallback keys — while the
    pool itself, an immutable NamedTuple rewritten by every write,
    flows through the methods functionally.  Everything is pure and
    jit-compatible: the engine traces ``io.read`` + model decode +
    ``io.write_dirty`` as one computation, and the prefix cache /
    cluster share the same entry point (``io.copy`` / ``io.migrate``).
    """

    def __init__(self, spec: PageSpec, keys):
        self.spec = spec
        self.keys = keys
        # Host-side integrity verdict observers: the crossings below
        # run inside jit (verdicts are async device booleans), so the
        # caller reports each verdict the moment it host-syncs one via
        # :meth:`report_verdict` — the observability layer counts and
        # audit-logs them without touching the traced computation.
        self.verdict_hooks: list = []
        # Fault-injection hooks (repro.serve.faults): each may rewrite
        # the verdict *before* it fans out to the observers, so an
        # injected failure is indistinguishable downstream from a real
        # one.  Empty (zero-cost) outside chaos tests/benchmarks.
        self.fault_hooks: list = []

    def report_verdict(self, ok, op: str, **ctx) -> bool:
        """Fan one host-synced MAC-gate verdict out to the hooks.

        Returns ``bool(ok)`` so gate sites can write
        ``if not io.report_verdict(ok, "decode_read"): raise ...``
        with zero extra device syncs.
        """
        ok = bool(ok)
        for hook in self.fault_hooks:
            ok = bool(hook(ok, op, ctx))
        for hook in self.verdict_hooks:
            hook(ok, op, ctx)
        return ok

    def read(self, pool: PagedKVPool, page_table: jax.Array,
             lengths: jax.Array, ctx: PageKeyCtx | None = None,
             uniform: bool = False):
        """Gather + decrypt + verify the paged leaves for a batched decode.

        Args:
          page_table: (max_slots, P) int32; -1 = unallocated.  P may be
            the full ``pages_per_slot`` or a smaller pow2 page-count
            bucket (see :class:`TwoLevelPageTable`) — every shape below
            follows the table, so gather/crypt/MAC work scales with the
            bucket's page window, not with pool capacity.  The window
            must cover every valid token
            (``P * page_tokens > max(lengths)``).
          lengths: (max_slots,) int32 valid tokens per slot.
          ctx: optional per-page tenant keys (N = max_slots * P
            entries, row-major over the page table).
          uniform: host-side promise that every ctx entry selects one
            bank row — dispatches the flat single-key route with
            unchanged per-page bindings.  Mixed-row ctxs keep the fused
            kernel too, via its per-page round-key gather
            (:func:`_fused_read`).

        Returns ``(dense_leaves, ok)`` — one dense (steps, S,
        P*page_tokens, *rest) array per paged leaf, and the AND of
        every gated MAC check over the *touched* pages (pages holding
        positions < length).
        """
        spec, keys = self.spec, self.keys
        cfg = spec.cfg
        s, p = page_table.shape
        ptab = jnp.where(page_table < 0, spec.scratch_page, page_table)
        flat_ids = ptab.reshape(-1)
        vns = pool.page_vns[flat_ids]
        page_start = (jnp.arange(p, dtype=jnp.int32)
                      * spec.page_tokens)[None, :]
        touched = page_start < lengths[:, None]        # (S, P)

        ok = jnp.asarray(True)
        agg = jnp.zeros((s, p, mac.MAC_BYTES), jnp.uint8)
        dense = []
        # Named scopes split the read in the compiled HLO's op_name
        # metadata: the crossing (decrypt + verify) and the dense view.
        for li, leaf in enumerate(spec.leaves):
            ct = pool.cts[li][flat_ids].reshape(s, p, leaf.page_bytes)
            need_macs = cfg.verify != "none"
            if need_macs and _kernel_read_ok(spec):
                with jax.named_scope("crossing"):
                    pt, page_macs = _fused_read(
                        spec, leaf, ct.reshape(-1, leaf.page_bytes),
                        flat_ids, vns, keys, ctx, uniform)
                    agg = agg ^ page_macs.reshape(s, p, mac.MAC_BYTES)
                with jax.named_scope("dense"):
                    dense.append(_pages_to_dense(
                        spec, leaf, pt.reshape(s, p, -1), lengths))
                continue
            with jax.named_scope("crossing"):
                pt = _crypt(spec, leaf, ct.reshape(-1, leaf.page_bytes),
                            flat_ids, vns, keys, ctx,
                            uniform).reshape(s, p, leaf.page_bytes)
                macs = None
                if need_macs:
                    macs = _page_block_macs(
                        spec, leaf, ct.reshape(-1, leaf.page_bytes),
                        flat_ids, vns, keys, ctx,
                        uniform).reshape(s, p, leaf.n_blocks, mac.MAC_BYTES)
                if cfg.verify == "block":
                    stored = pool.block_macs[li][flat_ids].reshape(
                        macs.shape)
                    ok = ok & jnp.all((macs == stored)
                                      | ~touched[..., None, None])
                elif cfg.verify == "layer":
                    agg = agg ^ mac.xor_aggregate(macs, axis=2)
            with jax.named_scope("dense"):
                dense.append(_pages_to_dense(
                    spec, leaf, _page_values(leaf, pt), lengths))
        if cfg.verify == "layer":
            stored = pool.page_macs[flat_ids].reshape(s, p, mac.MAC_BYTES)
            ok = ok & jnp.all((agg == stored) | ~touched[..., None])
        if cfg.emulate_tree:
            # Tree/VN traffic is charged for the WINDOW actually
            # gathered — the emulated SGX metadata cost shrinks with
            # the bucket too.
            ok = ok & emulated_tree_probe(
                sum(leaf.n_blocks for leaf in spec.leaves) * s * p)
        return dense, ok

    def write(self, pool: PagedKVPool, page_ids: jax.Array,
              leaf_pages: list, vn, real_mask: jax.Array,
              ctx: PageKeyCtx | None = None,
              uniform: bool = False) -> PagedKVPool:
        """Encrypt + MAC N pages and scatter them into the pool.

        Args:
          page_ids: (N,) int32 destinations (scratch row for masked
            slots — duplicates are only ever the scratch page, so
            last-write-wins is harmless).
          leaf_pages: per paged leaf, (N, steps, page_tokens, *rest).
          vn: scalar uint32 version number for this write event.
          real_mask: (N,) bool — writes that land on real (non-scratch)
            pages and therefore participate in the deferred pool MAC.
          ctx: optional per-page tenant keys (N entries).
        """
        spec, keys = self.spec, self.keys
        cfg = spec.cfg
        n = page_ids.shape[0]
        vns = jnp.broadcast_to(jnp.asarray(vn, jnp.uint32), (n,))
        agg = jnp.zeros((n, mac.MAC_BYTES), jnp.uint8)
        new_cts = []
        new_block_macs = list(pool.block_macs)
        for li, leaf in enumerate(spec.leaves):
            vals = _dense_to_pages(spec, leaf, leaf_pages[li])
            if cfg.verify != "none" and _kernel_write_ok(spec):
                # One fused Pallas pass: encrypt + NH of the fresh
                # ciphertext — the write-side twin of the fused read,
                # for uniform AND mixed-row key selections.
                ct, page_macs = _fused_write(spec, leaf, vals, page_ids, vns,
                                             keys, ctx, uniform)
                new_cts.append(pool.cts[li].at[page_ids].set(ct))
                agg = agg ^ page_macs
                continue
            ct = _crypt(spec, leaf, _values_to_bytes(leaf, vals), page_ids,
                        vns, keys, ctx, uniform)
            macs = None
            if cfg.verify != "none":
                macs = _page_block_macs(spec, leaf, ct, page_ids, vns, keys,
                                        ctx, uniform)
            new_cts.append(pool.cts[li].at[page_ids].set(ct))
            if cfg.verify != "none":
                if cfg.verify == "block":
                    new_block_macs[li] = (
                        pool.block_macs[li].at[page_ids].set(macs))
                agg = agg ^ mac.xor_aggregate(macs, axis=1)
        old_macs = pool.page_macs[page_ids]            # read before scatter
        new_page_macs = pool.page_macs.at[page_ids].set(agg)
        new_vns = pool.page_vns.at[page_ids].set(vns)
        # Deferred model-level MAC: incremental XOR update, O(dirty).
        delta = jnp.where(real_mask[:, None], old_macs ^ agg,
                          jnp.zeros((), jnp.uint8))
        pool_mac = pool.pool_mac ^ mac.xor_aggregate(delta)
        return PagedKVPool(tuple(new_cts), new_page_macs,
                           tuple(new_block_macs), new_vns, pool_mac)

    def write_prefill(self, pool: PagedKVPool, page_ids: jax.Array,
                      dense_leaves: list, n_write_pages: int, vn,
                      ctx: PageKeyCtx | None = None,
                      uniform: bool = False) -> PagedKVPool:
        """Protect the first ``n_write_pages`` pages of one
        freshly-prefilled slot.  ``dense_leaves``: per paged leaf,
        (steps, 1, max_len, *rest).
        """
        spec = self.spec
        ptok = spec.page_tokens
        leaf_pages = []
        for leaf, dense_leaf in zip(spec.leaves, dense_leaves):
            toks = dense_leaf[:, 0, : n_write_pages * ptok]
            pages = toks.reshape((leaf.steps, n_write_pages, ptok)
                                 + leaf.rest)
            leaf_pages.append(jnp.moveaxis(pages, 1, 0))  # (N, steps, ...)
        ids = page_ids[:n_write_pages]
        real = ids < spec.n_pages
        if ctx is not None:
            ctx = ctx.take(n_write_pages)
        return self.write(pool, ids, leaf_pages, vn, real, ctx, uniform)

    def write_dirty(self, pool: PagedKVPool, page_table: jax.Array,
                    dense_leaves: list, lengths: jax.Array,
                    active: jax.Array, vn, ctx: PageKeyCtx | None = None,
                    uniform: bool = False) -> PagedKVPool:
        """Re-encrypt + re-MAC the ONE dirty page per active slot.

        ``lengths`` are the pre-increment lengths: the decode step just
        wrote its token at position ``length``, so the dirty page is
        ``length // page_tokens``.  Inactive slots write to the scratch
        row.

        ``ctx`` (one entry per slot) carries each slot's *current*
        tenant epoch — this is where lazy rotation lands: a page's next
        dirty write re-encrypts it under the new epoch keys.
        """
        spec = self.spec
        s = page_table.shape[0]
        ptok = spec.page_tokens
        dirty = lengths // ptok                        # (S,) page slot-index
        pid = jnp.take_along_axis(page_table, dirty[:, None], axis=1)[:, 0]
        real = active & (pid >= 0)
        pid = jnp.where(real, pid, spec.scratch_page)
        tok_idx = (dirty[:, None] * ptok
                   + jnp.arange(ptok, dtype=jnp.int32)[None])
        leaf_pages = []
        for leaf, dense_leaf in zip(spec.leaves, dense_leaves):
            idx = tok_idx.reshape((1, s, ptok) + (1,) * len(leaf.rest))
            page = jnp.take_along_axis(dense_leaf, idx, axis=2)
            leaf_pages.append(jnp.moveaxis(page, 0, 1))  # (S, steps, ...)
        return self.write(pool, pid, leaf_pages, vn, real, ctx, uniform)


    def read_raw(self, pool: PagedKVPool, page_ids: jax.Array,
                 ctx: PageKeyCtx | None = None, uniform: bool = False):
        """Decrypt + verify N whole pages, returning token payloads.

        Unlike :meth:`read` this is page-shaped, not slot-shaped: it
        returns per paged leaf a (N, steps, page_tokens, *rest) array —
        the exact ``leaf_pages`` layout :meth:`write` consumes — plus
        the AND of every gated MAC check over the *real* pages
        (scratch-page entries are ignored, so callers can pad to a
        bucketed size).  This is the read half of resealing and secure
        migration.
        """
        spec, keys = self.spec, self.keys
        cfg = spec.cfg
        n = page_ids.shape[0]
        vns = pool.page_vns[page_ids]
        real = page_ids < spec.n_pages
        ok = jnp.asarray(True)
        agg = jnp.zeros((n, mac.MAC_BYTES), jnp.uint8)
        out = []
        for li, leaf in enumerate(spec.leaves):
            ct = pool.cts[li][page_ids]
            need_macs = cfg.verify != "none"
            if need_macs and _kernel_read_ok(spec):
                pt, page_macs = _fused_read(spec, leaf, ct, page_ids, vns,
                                            keys, ctx, uniform)
                agg = agg ^ page_macs
                out.append(_values_to_tokens(spec, leaf, pt))
                continue
            pt = _crypt(spec, leaf, ct, page_ids, vns, keys, ctx, uniform)
            macs = None
            if need_macs:
                macs = _page_block_macs(spec, leaf, ct, page_ids, vns, keys,
                                        ctx, uniform)
            if cfg.verify == "block":
                stored = pool.block_macs[li][page_ids]
                ok = ok & jnp.all((macs == stored) | ~real[:, None, None])
            elif cfg.verify == "layer":
                agg = agg ^ mac.xor_aggregate(macs, axis=1)
            out.append(_values_to_tokens(spec, leaf, _page_values(leaf, pt)))
        if cfg.verify == "layer":
            stored = pool.page_macs[page_ids]
            ok = ok & jnp.all((agg == stored) | ~real[:, None])
        if cfg.emulate_tree:
            ok = ok & emulated_tree_probe(
                n * sum(leaf.n_blocks for leaf in spec.leaves))
        return out, ok

    def reseal(self, pool: PagedKVPool, page_ids: jax.Array, vn,
               old_ctx: PageKeyCtx | None = None,
               new_ctx: PageKeyCtx | None = None,
               uniform: bool = False):
        """Decrypt N pages under ``old_ctx`` and re-protect under
        ``new_ctx`` in place — the eager-rotation primitive.

        One fused crossing: gather → decrypt+verify (old keys/epoch
        words) → re-encrypt + re-MAC (new keys/epoch words, fresh
        ``vn``) → scatter back to the SAME page ids.  Plaintext is
        bit-preserved, so decode output is unchanged; the pool/page
        metadata moves to the new epoch without preempting any slot.
        Returns ``(new_pool, ok)`` — the caller must gate on ``ok`` (a
        failed decrypt means the old bytes were tampered; writing their
        reseal would launder them).
        """
        leaf_pages, ok = self.read_raw(pool, page_ids, old_ctx, uniform)
        real = page_ids < self.spec.n_pages
        new_pool = self.write(pool, page_ids, leaf_pages, vn, real, new_ctx,
                              uniform)
        return new_pool, ok

    def copy(self, pool: PagedKVPool, src_ids: jax.Array,
             dst_ids: jax.Array, vn,
             src_ctx: PageKeyCtx | None = None,
             dst_ctx: PageKeyCtx | None = None):
        """Reseal N pages to *different* page ids within one pool.

        The rebinding primitive the prefix cache builds on: decrypt +
        verify the source pages under ``src_ctx``, re-encrypt + re-MAC
        the same plaintext into ``dst_ids`` under ``dst_ctx``.  Cache
        insert copies session pages into cache-bound pages
        (session epoch word → :data:`PREFIX_ROLE`), copy-on-write
        copies a shared cache page back into a private session page,
        and reseal-on-share copies one tenant's cache page into
        another's.  Returns ``(new_pool, ok)``; callers must gate on
        ``ok`` before committing the new pool (a tampered source must
        not be laundered into a freshly-MACed copy).
        """
        return self.migrate(pool, self.spec, pool, src_ids, dst_ids, vn,
                            src_ctx, dst_ctx)

    def migrate(self, src_pool: PagedKVPool, src_spec: PageSpec,
                dst_pool: PagedKVPool, src_ids: jax.Array,
                dst_ids: jax.Array, vn,
                src_ctx: PageKeyCtx | None = None,
                dst_ctx: PageKeyCtx | None = None):
        """Secure page migration: reseal N pages from ``src_pool`` into
        this IO's pool (single-dispatch form, for pools on one device).

        Decrypts under the *source* shard binding (shard id in the RePA
        fmap + CTR words), verifies, then re-encrypts + re-MACs under
        the *destination* binding — the page arrives cryptographically
        pinned to its new device and the old ciphertext is useless
        there.  For pools on different devices, run :meth:`read_raw` on
        the source device, transfer the plaintext leaf pages, and
        :meth:`write` on the destination (what the cluster engine
        does).  Returns ``(new_dst_pool, ok)``.
        """
        dst_spec = self.spec
        if src_spec.leaves != dst_spec.leaves:
            raise ValueError("migration needs identically-laid-out pools")
        leaf_pages, ok = PageIO(src_spec, self.keys).read_raw(
            src_pool, src_ids, src_ctx)
        real = dst_ids < dst_spec.n_pages
        new_dst = self.write(dst_pool, dst_ids, leaf_pages, vn, real,
                             dst_ctx)
        return new_dst, ok


# ---------------------------------------------------------------------------
# Free-function wrappers: the pre-PageIO module API, delegating 1:1.
# ---------------------------------------------------------------------------


def read_pages(pool: PagedKVPool, spec: PageSpec, keys, page_table: jax.Array,
               lengths: jax.Array, ctx: PageKeyCtx | None = None,
               uniform: bool = False):
    """Thin wrapper over :meth:`PageIO.read` (kept for existing callers)."""
    return PageIO(spec, keys).read(pool, page_table, lengths, ctx, uniform)


def write_pages(pool: PagedKVPool, spec: PageSpec, keys, page_ids: jax.Array,
                leaf_pages: list, vn, real_mask: jax.Array,
                ctx: PageKeyCtx | None = None,
                uniform: bool = False) -> PagedKVPool:
    """Thin wrapper over :meth:`PageIO.write` (kept for existing callers)."""
    return PageIO(spec, keys).write(pool, page_ids, leaf_pages, vn,
                                    real_mask, ctx, uniform)


def write_prefill(pool: PagedKVPool, spec: PageSpec, keys,
                  page_ids: jax.Array, dense_leaves: list, n_write_pages: int,
                  vn, ctx: PageKeyCtx | None = None,
                  uniform: bool = False) -> PagedKVPool:
    """Thin wrapper over :meth:`PageIO.write_prefill`."""
    return PageIO(spec, keys).write_prefill(pool, page_ids, dense_leaves,
                                            n_write_pages, vn, ctx, uniform)


def write_dirty(pool: PagedKVPool, spec: PageSpec, keys,
                page_table: jax.Array, dense_leaves: list,
                lengths: jax.Array, active: jax.Array, vn,
                ctx: PageKeyCtx | None = None,
                uniform: bool = False) -> PagedKVPool:
    """Thin wrapper over :meth:`PageIO.write_dirty`."""
    return PageIO(spec, keys).write_dirty(pool, page_table, dense_leaves,
                                          lengths, active, vn, ctx, uniform)


def read_pages_raw(pool: PagedKVPool, spec: PageSpec, keys,
                   page_ids: jax.Array, ctx: PageKeyCtx | None = None,
                   uniform: bool = False):
    """Thin wrapper over :meth:`PageIO.read_raw`."""
    return PageIO(spec, keys).read_raw(pool, page_ids, ctx, uniform)


def reseal_pages(pool: PagedKVPool, spec: PageSpec, keys,
                 page_ids: jax.Array, vn,
                 old_ctx: PageKeyCtx | None = None,
                 new_ctx: PageKeyCtx | None = None,
                 uniform: bool = False):
    """Thin wrapper over :meth:`PageIO.reseal`."""
    return PageIO(spec, keys).reseal(pool, page_ids, vn, old_ctx, new_ctx,
                                     uniform)


def migrate_pages(src_pool: PagedKVPool, src_spec: PageSpec,
                  dst_pool: PagedKVPool, dst_spec: PageSpec, keys,
                  src_ids: jax.Array, dst_ids: jax.Array, vn,
                  src_ctx: PageKeyCtx | None = None,
                  dst_ctx: PageKeyCtx | None = None):
    """Thin wrapper over :meth:`PageIO.migrate`."""
    return PageIO(dst_spec, keys).migrate(src_pool, src_spec, dst_pool,
                                          src_ids, dst_ids, vn, src_ctx,
                                          dst_ctx)


def deferred_pool_check(pool: PagedKVPool, spec: PageSpec) -> jax.Array:
    """Model-level deferred MAC (paper Table I): the XOR of every real
    page MAC must equal the incrementally-maintained pool MAC.  Run off
    the critical path (end of request / every N steps)."""
    return jnp.all(mac.xor_aggregate(pool.page_macs[: spec.n_pages])
                   == pool.pool_mac)


def merkle_leaf_macs(pool: PagedKVPool, spec: PageSpec) -> np.ndarray:
    """Host copy of the real-page MAC rows — the Merkle leaf material.

    This is the single point where the auditable Merkle level
    (:mod:`repro.serve.merkle_pool`, which is deliberately jax-free)
    touches pool state: the scratch row is excluded (it is not part of
    any integrity fold), and quarantined frames are excluded later by
    the maintainer itself, which hashes them to a distinguished
    *retired* leaf regardless of the scrubbed MAC bytes this returns.
    The pull is a tiny ``n_pages x MAC_BYTES`` transfer, only ever run
    at the amortized ``_tick_end`` cadence or on an explicit proof
    request — never on the decode dispatch path.
    """
    return np.asarray(pool.page_macs[: spec.n_pages], np.uint8)


# ---------------------------------------------------------------------------
# PrefixCache: content-addressed index over cache-bound shared pages.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PrefixCacheEntry:
    """One cached prefix chunk: a sealed page + its chain position.

    ``key`` is ``(tenant_index, chain_hash)`` where the chain hash
    covers every token from position 0 through this chunk — a page is
    only reachable by walking its full ancestry, so two prefixes
    collide only if their entire token histories do.  ``n_tokens`` may
    be short of a full page for the chain's leaf chunk (a partially
    filled final page); only leaves may be partial.
    """

    key: tuple
    parent: Optional["PrefixCacheEntry"]
    page_id: int
    n_tokens: int
    refs: int = 0
    last_use: int = 0


class PrefixCache:
    """Host-side content-addressed secure prefix cache.

    Entries index pool pages sealed under the owning tenant's dedicated
    *cache binding* — epoch word :data:`PREFIX_ROLE`, selecting the
    tenant's epoch-independent cache keys (see
    :meth:`repro.tenancy.registry.TenantRegistry.cache_row`).  A page
    sealed once is verify-read by every session that matches its chain
    (VN-stable: shared reads never re-MAC), and keys are per tenant, so
    a match can only ever hand a session pages its own tenant sealed —
    cross-tenant sharing must go through the engine's explicit
    reseal-on-share.

    **Keying.**  Token streams are chunked page-sized; chunk ``i``'s
    chain hash is ``H(chain[i-1] ‖ tokens_i)``.  Lookup walks the chain
    from chunk 0 and returns the longest fully-matched entry run (plus,
    after the last full chunk, the longest matching *partial* leaf), so
    a hit is always a page-aligned prefix of the slot's context — the
    windows-are-prefixes invariant of :class:`TwoLevelPageTable` holds
    with zero new window shapes.

    **Lifecycle.**  Slots ``acquire`` the whole matched chain (every
    ancestor's refcount rises, so a parent's refcount always dominates
    its children's) and ``release`` it on finish/preempt/CoW.  Eviction
    (``reclaim``) is LRU over refcount-zero *leaf* entries — the
    dominance invariant means cascading from the leaves can never
    strand a referenced descendant.

    The cache stores page *ids* only; sealing bytes in and out of those
    pages is the engine's job via :class:`PageIO`.
    """

    def __init__(self, page_tokens: int, capacity_pages: int):
        if capacity_pages < 1:
            raise ValueError("prefix cache needs capacity >= 1 page")
        self.page_tokens = page_tokens
        self.capacity_pages = capacity_pages
        self._entries: dict[tuple, PrefixCacheEntry] = {}
        self._children: dict[tuple, set] = {}
        self._clock = 0

    # -- chain hashing ------------------------------------------------------

    @staticmethod
    def _chain_hash(parent_hash: bytes, chunk) -> bytes:
        buf = np.asarray(list(chunk), np.uint32).tobytes()
        return hashlib.sha256(parent_hash + buf).digest()

    def _chain(self, tokens):
        """Page-sized chunks of ``tokens`` with their chain hashes:
        list of ``(hash, n_tokens)``; only the last may be partial."""
        out, h = [], b""
        ptok = self.page_tokens
        for start in range(0, len(tokens), ptok):
            chunk = tokens[start: start + ptok]
            h = self._chain_hash(h, chunk)
            out.append((h, len(chunk)))
        return out

    # -- lookup / refcounts -------------------------------------------------

    def match(self, tenant_index: int, tokens) -> list:
        """Longest cached chain covering a prefix of ``tokens``.

        Pure (no refcount/LRU side effects).  Walks full page-sized
        chunks first; after the first miss, probes partial leaves of
        the next chunk longest-first, so an exact-length partial page
        cached by a shorter prompt still hits.
        """
        matched, h = [], b""
        ptok = self.page_tokens
        consumed = 0
        while consumed < len(tokens):
            chunk = tokens[consumed: consumed + ptok]
            full_h = self._chain_hash(h, chunk)
            entry = self._entries.get((tenant_index, full_h))
            if entry is not None and entry.n_tokens == len(chunk):
                matched.append(entry)
                h = full_h
                consumed += len(chunk)
                continue
            for c in range(len(chunk) - 1, 0, -1):
                part_h = self._chain_hash(h, chunk[:c])
                entry = self._entries.get((tenant_index, part_h))
                if entry is not None and entry.n_tokens == c:
                    matched.append(entry)
                    break
            break
        return matched

    def match_tokens(self, tenant_index: int, tokens) -> int:
        """Tokens a :meth:`match` would cover (cluster routing metric)."""
        return sum(e.n_tokens for e in self.match(tenant_index, tokens))

    def missing(self, tenant_index: int, tokens):
        """Insertion plan after the longest match: ``(matched,
        missing)`` where ``missing`` is ``[(key, n_tokens), ...]`` for
        the chunks a full-chain insert still needs, in chain order."""
        matched = self.match(tenant_index, tokens)
        covered = sum(e.n_tokens for e in matched)
        if matched and matched[-1].n_tokens % self.page_tokens:
            return matched, []          # partial leaf: chain can't extend
        h = matched[-1].key[1] if matched else b""
        missing = [((tenant_index, ch), n)
                   for ch, n in self._chain(tokens[covered:])]
        return matched, missing

    def acquire(self, entries) -> None:
        """Pin a matched chain: every entry's refcount rises by one
        (ancestors included, preserving refcount dominance)."""
        self._clock += 1
        for e in entries:
            e.refs += 1
            e.last_use = self._clock

    def release(self, entries) -> None:
        for e in entries:
            if e.refs <= 0:
                raise RuntimeError(f"prefix-cache refcount underflow on "
                                   f"{e.key[1].hex()[:12]}")
            e.refs -= 1

    # -- insertion / eviction -----------------------------------------------

    def insert(self, key: tuple, parent: Optional[PrefixCacheEntry],
               page_id: int, n_tokens: int) -> PrefixCacheEntry:
        """Index a freshly cache-sealed page under its chain key.

        The caller has already copied the page's bytes into
        ``page_id`` under the cache binding (:meth:`PageIO.copy`); the
        cache only tracks ownership.  New entries start unreferenced —
        the inserting slot keeps decoding on its private pages.
        """
        if key in self._entries:
            raise ValueError("prefix chunk already cached")
        if len(self._entries) >= self.capacity_pages:
            raise ValueError("prefix cache over capacity — reclaim first")
        if parent is not None and parent.n_tokens % self.page_tokens:
            raise ValueError("cannot extend a partial (leaf) chunk")
        entry = PrefixCacheEntry(key=key, parent=parent, page_id=page_id,
                                 n_tokens=n_tokens)
        self._clock += 1
        entry.last_use = self._clock
        self._entries[key] = entry
        if parent is not None:
            self._children.setdefault(parent.key, set()).add(key)
        return entry

    @property
    def pages_used(self) -> int:
        return len(self._entries)

    @property
    def total_refs(self) -> int:
        """Total refcount pins across entries (gauge exposition)."""
        return sum(e.refs for e in self._entries.values())

    def free_capacity(self) -> int:
        return self.capacity_pages - len(self._entries)

    def _evict(self, entry: PrefixCacheEntry) -> None:
        del self._entries[entry.key]
        if entry.parent is not None:
            kids = self._children.get(entry.parent.key)
            if kids is not None:
                kids.discard(entry.key)
                if not kids:
                    del self._children[entry.parent.key]

    def reclaim(self, n_pages: int) -> list:
        """Evict up to ``n_pages`` unreferenced entries, LRU leaf-first
        (refcount dominance makes leaf-first cascade-safe); returns the
        freed page ids for the engine to reuse."""
        freed = []
        while len(freed) < n_pages:
            cands = [e for e in self._entries.values()
                     if e.refs == 0 and not self._children.get(e.key)]
            if not cands:
                break
            victim = min(cands, key=lambda e: e.last_use)
            self._evict(victim)
            freed.append(victim.page_id)
        return freed

    def evict_pages(self, page_ids) -> int:
        """Drop every entry holding one of ``page_ids`` — plus its
        descendants, unreachable without their ancestor — from the
        index regardless of refcounts: the quarantine path.  A page
        whose physical frame was retired must never satisfy a future
        match.  Slots already pinned keep their entry objects
        (:meth:`release` operates on the objects, not the index); the
        chain simply stops being discoverable.  Returns the number of
        entries dropped."""
        bad = {int(p) for p in page_ids}
        dropped, progress = 0, True
        while progress:
            progress = False
            for e in list(self._entries.values()):
                orphaned = (e.parent is not None
                            and e.parent.key not in self._entries)
                if e.page_id in bad or orphaned:
                    self._evict(e)
                    dropped += 1
                    progress = True
        return dropped

    def flush(self, tenant_index: Optional[int] = None) -> list:
        """Evict every unreferenced entry (optionally one tenant's) —
        the revocation path for the epoch-independent cache binding.
        Returns the freed page ids; referenced chains survive."""
        freed, progress = [], True
        while progress:
            progress = False
            for e in list(self._entries.values()):
                if tenant_index is not None and e.key[0] != tenant_index:
                    continue
                if e.refs == 0 and not self._children.get(e.key):
                    self._evict(e)
                    freed.append(e.page_id)
                    progress = True
        return freed
