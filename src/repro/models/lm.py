"""Decoder-only language model: specs / forward / loss / prefill / decode.

Supports every assigned LM arch through the block layout:

  * homogeneous stacks (minitron, granite, smollm, olmoe, mamba2) scan
    one block body over stacked per-layer params;
  * periodic hybrids (jamba: attn every 8th mixer, MoE every 2nd ffn)
    scan a period of block bodies over stacked per-period params;
  * prefix-irregular stacks (deepseek-v3: 3 dense layers then 58 MoE)
    split into homogeneous segments, each scanned.

Params for a segment are stacked along a leading 'layers' axis, so the
HLO contains one body per distinct block kind — essential to keep
compile time sane for the 88-layer/61-layer dry-run cells.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import blocks as blk
from repro.models.layers import ParamSpec, rms_norm, spec
from repro.models.partitioning import constrain
from repro.models.mamba2 import Mamba2Config
from repro.models.mla import MLAConfig
from repro.models.moe import MoEConfig

__all__ = ["LMConfig", "layout", "segments", "lm_specs", "lm_forward",
           "lm_loss", "lm_prefill", "lm_decode", "cache_specs"]


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int = 0             # 0 -> d_model // n_heads
    dtype: str = "bfloat16"
    # Block pattern:
    mixer: str = "attn"           # default mixer: attn | mla | mamba
    attn_every: int = 0           # jamba: one attn per this many layers
    attn_offset: int = 3
    ffn: str = "dense"            # dense | moe | none
    moe_every: int = 1            # moe on layers where idx % moe_every == moe_offset
    moe_offset: int = 0
    moe_start_layer: int = 0      # deepseek: dense layers before this index
    mamba: Optional[Mamba2Config] = None
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    # Embeddings / misc:
    gated_ffn: bool = True        # SwiGLU; False = plain GELU MLP (granite)
    tie_embeddings: bool = True
    q_block: int = 512
    kv_block: int = 1024
    ssd_chunk: int = 256
    remat: str = "full"           # full | none
    # Modality frontends (stubs; see vlm.py / configs):
    n_image_patches: int = 0
    d_vision: int = 0

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.d_model // max(self.n_heads, 1))


def layout(cfg: LMConfig) -> list:
    """Per-layer (mixer, ffn) kinds."""
    kinds = []
    for i in range(cfg.n_layers):
        if cfg.attn_every:
            mixer = "attn" if i % cfg.attn_every == cfg.attn_offset else cfg.mixer
        else:
            mixer = cfg.mixer
        ffn = cfg.ffn
        if cfg.ffn == "moe":
            is_moe = (i >= cfg.moe_start_layer
                      and i % cfg.moe_every == cfg.moe_offset)
            ffn = "moe" if is_moe else "dense"
        kinds.append(blk.LayerKind(mixer, ffn))
    return kinds


def segments(cfg: LMConfig) -> list:
    """[(period_kinds: tuple[LayerKind], steps: int), ...]."""
    kinds = layout(cfg)
    n = len(kinds)
    # Maximal uniform runs (homogeneous stacks, deepseek's dense prefix).
    segs = []
    i = 0
    while i < n:
        j = i
        while j < n and kinds[j] == kinds[i]:
            j += 1
        segs.append(((kinds[i],), j - i))
        i = j
    if len(segs) <= 4:
        return segs
    # Periodic hybrid (jamba): scan one period of block bodies.
    for p in range(2, min(16, n) + 1):
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)):
            return [(tuple(kinds[:p]), n // p)]
    return segs


def _stack_specs(specs: Any, steps: int) -> Any:
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((steps,) + s.shape, s.dtype, ("layers",) + s.axes,
                            s.init),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def lm_specs(cfg: LMConfig) -> dict:
    s: dict[str, Any] = {
        "embed": spec((cfg.vocab, cfg.d_model), ("vocab", "embed"), cfg.dtype,
                      init="embed"),
        "final_norm": spec((cfg.d_model,), ("embed",), "float32", init="ones"),
        "segments": [],
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = spec((cfg.d_model, cfg.vocab), ("embed", "vocab"),
                            cfg.dtype)
    if cfg.n_image_patches:
        s["img_proj"] = spec((cfg.d_vision, cfg.d_model), ("vision", "embed"),
                             cfg.dtype)
    for kinds, steps in segments(cfg):
        seg = [_stack_specs(blk.block_specs(cfg, kind), steps)
               for kind in kinds]
        s["segments"].append(seg)
    return s


def _maybe_remat(cfg: LMConfig, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    return fn


def _embed_tokens(cfg: LMConfig, params, batch) -> tuple:
    """Returns (x, positions).  Handles the VLM image-patch prefix."""
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.n_image_patches:
        img = batch["image_embeds"].astype(x.dtype)  # (B, P, d_vision)
        img = jnp.einsum("bpv,vd->bpd", img, params["img_proj"])
        x = jnp.concatenate([img, x], axis=1)
    x = constrain(x, "batch", "seq", "residual")
    b, l = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))
    return x, positions


def lm_forward(cfg: LMConfig, params, batch) -> tuple:
    """Returns (logits, aux_loss)."""
    x, positions = _embed_tokens(cfg, params, batch)

    aux = jnp.zeros((), jnp.float32)
    for seg_params, (kinds, steps) in zip(params["segments"], segments(cfg)):
        def body(carry, layer_params):
            x, aux = carry
            for kind, p in zip(kinds, layer_params):
                x, aux = blk.block_forward(cfg, kind, p, x, positions, aux)
            return (x, aux), None

        body = _maybe_remat(cfg, body)
        (x, aux), _ = jax.lax.scan(body, (x, aux), seg_params)

    x = rms_norm(x, params["final_norm"])
    if cfg.tie_embeddings:
        logits = jnp.einsum("bld,vd->blv", x, params["embed"])
    else:
        logits = jnp.einsum("bld,dv->blv", x, params["lm_head"])
    return constrain(logits, "batch", "seq", "vocab"), aux


def lm_loss(cfg: LMConfig, params, batch) -> tuple:
    """Next-token cross entropy; returns (loss, metrics)."""
    logits, aux = lm_forward(cfg, params, batch)
    labels = batch["labels"]
    if cfg.n_image_patches:
        logits = logits[:, cfg.n_image_patches:]  # loss on text positions only
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    mask = (labels >= 0).astype(jnp.float32)  # -1 labels = padding
    labels_safe = jnp.maximum(labels, 0)
    gold = jnp.take_along_axis(logits, labels_safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
    total = loss + 0.01 * aux
    return total, {"nll": loss, "aux": aux}


# ---------------------------------------------------------------------------
# Serving.
# ---------------------------------------------------------------------------


def cache_specs(cfg: LMConfig, batch: int, max_len: int) -> list:
    """Per-segment stacked cache ShapeDtypeStructs."""
    out = []
    for kinds, steps in segments(cfg):
        seg = []
        for kind in kinds:
            c = blk.block_cache_specs(cfg, kind, batch, max_len)
            seg.append(jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((steps,) + s.shape, s.dtype), c))
        out.append(seg)
    return out


def cache_axes(cfg: LMConfig) -> list:
    """Logical axes mirroring cache_specs (leading 'layers' stack dim)."""
    out = []
    for kinds, steps in segments(cfg):
        seg = []
        for kind in kinds:
            a = blk.block_cache_axes(cfg, kind)
            seg.append(jax.tree_util.tree_map(
                lambda ax: ("layers",) + ax, a,
                is_leaf=lambda x: isinstance(x, tuple) and all(
                    isinstance(e, str) for e in x)))
        out.append(seg)
    return out


def lm_prefill(cfg: LMConfig, params, batch, max_len: int,
               last_pos=None) -> tuple:
    """Full-sequence prefill: returns (last_logits, caches).

    ``last_pos`` (optional traced scalar) selects which position's
    logits to return instead of the literal last one — the serving
    engine's length-bucketed prefill right-pads prompts to a power-of
    -two length and needs the logits of the last *real* token (causal
    attention makes positions <= last_pos independent of the padding).
    """
    x, positions = _embed_tokens(cfg, params, batch)
    caches = []
    for seg_params, (kinds, steps) in zip(params["segments"], segments(cfg)):
        def body(carry, layer_params):
            x, aux = carry
            new_caches = []
            for kind, p in zip(kinds, layer_params):
                x, cache, aux = blk.block_prefill(cfg, kind, p, x, positions,
                                                  aux, max_len)
                new_caches.append(cache)
            return (x, aux), tuple(new_caches)

        (x, _), seg_cache = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), seg_params)
        caches.append(list(seg_cache))
    x = rms_norm(x, params["final_norm"])
    if last_pos is None:
        last = x[:, -1:]
    else:
        last = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bld,vd->blv", last, params["embed"])
    else:
        logits = jnp.einsum("bld,dv->blv", last, params["lm_head"])
    return constrain(logits, "batch", None, "vocab"), caches


def lm_decode(cfg: LMConfig, params, tokens, caches) -> tuple:
    """One decode step: tokens (B, 1) -> (logits (B,1,V), new caches)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    x = constrain(x, "batch", None, "residual")
    new_caches = []
    for seg_params, seg_cache, (kinds, steps) in zip(
            params["segments"], caches, segments(cfg)):
        def body(x, inputs):
            layer_params, layer_caches = inputs
            aux = jnp.zeros((), jnp.float32)
            new_lc = []
            for kind, p, c in zip(kinds, layer_params, layer_caches):
                x, c, aux = blk.block_decode(cfg, kind, p, x, c, aux)
                new_lc.append(c)
            return x, tuple(new_lc)

        x, new_seg = jax.lax.scan(body, x, (seg_params, tuple(seg_cache)))
        new_caches.append(list(new_seg))
    with jax.named_scope("model.head"):
        x = rms_norm(x, params["final_norm"])
        if cfg.tie_embeddings:
            logits = jnp.einsum("bld,vd->blv", x, params["embed"])
        else:
            logits = jnp.einsum("bld,dv->blv", x, params["lm_head"])
    return constrain(logits, "batch", None, "vocab"), new_caches
