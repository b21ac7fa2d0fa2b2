"""Layer-block combinators: (mixer, ffn) pairs covering every assigned arch.

mixer: 'attn' (GQA/MQA + RoPE) | 'mla' (DeepSeek latent) | 'mamba' (SSD)
ffn:   'dense' (SwiGLU) | 'moe' (top-k routed) | 'none' (pure-mamba blocks)

Each block is pre-norm residual.  The same block definitions serve
training forward, prefill (returning caches) and single-token decode
(consuming caches), so the three lowered programs share structure.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.layers import rms_norm, spec

__all__ = ["LayerKind", "block_specs", "block_forward", "block_prefill",
           "block_decode", "block_cache_specs"]


class LayerKind(NamedTuple):
    mixer: str
    ffn: str


def block_specs(cfg, kind: LayerKind) -> dict:
    s: dict[str, Any] = {
        "norm_mixer": spec((cfg.d_model,), ("embed",), "float32", init="ones"),
    }
    if kind.mixer == "attn":
        s["attn"] = attn_mod.attention_specs(cfg.d_model, cfg.n_heads,
                                             cfg.n_kv, cfg.head_dim, cfg.dtype)
    elif kind.mixer == "mla":
        s["mla"] = mla_mod.mla_specs(cfg.mla, cfg.dtype)
    elif kind.mixer == "mamba":
        s["mamba"] = mamba_mod.mamba2_specs(cfg.mamba, cfg.dtype)
    else:
        raise ValueError(kind.mixer)

    if kind.ffn != "none":
        s["norm_ffn"] = spec((cfg.d_model,), ("embed",), "float32", init="ones")
    if kind.ffn == "dense":
        s["ffn"] = moe_mod.ffn_specs(cfg.d_model, cfg.d_ff, cfg.dtype,
                                     gated=getattr(cfg, "gated_ffn", True))
    elif kind.ffn == "moe":
        s["moe"] = moe_mod.moe_specs(cfg.moe, cfg.dtype)
    return s


def _apply_ffn(cfg, kind: LayerKind, params, x, aux):
    if kind.ffn == "none":
        return x, aux
    h = rms_norm(x, params["norm_ffn"])
    if kind.ffn == "dense":
        return x + moe_mod.dense_ffn(params["ffn"], h), aux
    b, l, d = h.shape
    y, moe_aux = moe_mod.moe_ffn(cfg.moe, params["moe"], h.reshape(b * l, d))
    return x + y.reshape(b, l, d), aux + moe_aux


def block_forward(cfg, kind: LayerKind, params, x, positions, aux):
    h = rms_norm(x, params["norm_mixer"])
    if kind.mixer == "attn":
        x = x + attn_mod.attention(params["attn"], h, positions,
                                   q_block=cfg.q_block, kv_block=cfg.kv_block)
    elif kind.mixer == "mla":
        x = x + mla_mod.mla_attention(cfg.mla, params["mla"], h, positions,
                                      q_block=cfg.q_block,
                                      kv_block=cfg.kv_block)
    else:
        x = x + mamba_mod.mamba2_forward(cfg.mamba, params["mamba"], h,
                                         chunk=cfg.ssd_chunk)
    return _apply_ffn(cfg, kind, params, x, aux)


# ---------------------------------------------------------------------------
# Caches.
# ---------------------------------------------------------------------------


def block_cache_specs(cfg, kind: LayerKind, batch: int, max_len: int):
    if kind.mixer == "attn":
        return attn_mod.init_kv_cache_specs(batch, max_len, cfg.n_kv,
                                            cfg.head_dim, cfg.dtype)
    if kind.mixer == "mla":
        return mla_mod.init_mla_cache_specs(cfg.mla, batch, max_len, cfg.dtype)
    return mamba_mod.init_mamba2_state_specs(cfg.mamba, batch, cfg.dtype)


def block_cache_axes(cfg, kind: LayerKind):
    """Logical axes mirroring block_cache_specs (for the sharding planner)."""
    if kind.mixer == "attn":
        kv = ("batch", "seq", "kv_heads", "head_dim")
        return attn_mod.KVCache(k=kv, v=kv, length=())
    if kind.mixer == "mla":
        return mla_mod.MLACache(c_kv=("batch", "seq", "lora"),
                                k_pe=("batch", "seq", "head_dim"), length=())
    return mamba_mod.Mamba2State(ssm=("batch", "heads", "head_dim", "state"),
                                 conv=("batch", "conv_k", "mlp"), length=())


def _pad_to(x, max_len):
    """Pad (B, L, ...) along axis 1 up to max_len."""
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, max_len - x.shape[1])
    return jnp.pad(x, pad)


def block_prefill(cfg, kind: LayerKind, params, x, positions, aux, max_len):
    """Forward + produce this block's decode cache (padded to max_len)."""
    h = rms_norm(x, params["norm_mixer"])
    length = jnp.asarray(x.shape[1], jnp.int32)
    if kind.mixer == "attn":
        out, (k, v) = attn_mod.attention(params["attn"], h, positions,
                                         q_block=cfg.q_block,
                                         kv_block=cfg.kv_block, return_kv=True)
        x = x + out
        cache = attn_mod.KVCache(_pad_to(k.astype(jnp.dtype(cfg.dtype)), max_len),
                                 _pad_to(v.astype(jnp.dtype(cfg.dtype)), max_len),
                                 length)
    elif kind.mixer == "mla":
        out = mla_mod.mla_attention(cfg.mla, params["mla"], h, positions,
                                    q_block=cfg.q_block, kv_block=cfg.kv_block)
        c_kv, k_pe = mla_mod._project_kv_latent(cfg.mla, params["mla"], h,
                                                positions)
        x = x + out
        cache = mla_mod.MLACache(
            _pad_to(c_kv.astype(jnp.dtype(cfg.dtype)), max_len),
            _pad_to(k_pe.astype(jnp.dtype(cfg.dtype)), max_len), length)
    else:
        out, state = mamba_mod.mamba2_forward(cfg.mamba, params["mamba"], h,
                                              chunk=cfg.ssd_chunk,
                                              return_state=True)
        x = x + out
        # Conv rolling window = last (d_conv - 1) conv inputs.
        zxbcdt = jnp.einsum("bld,dp->blp", h, params["mamba"]["in_proj"])
        _, xbc, _ = mamba_mod._split_proj(cfg.mamba, zxbcdt)
        d_conv = cfg.mamba.d_conv
        conv_win = xbc[:, -(d_conv - 1):, :].astype(jnp.dtype(cfg.dtype))
        cache = mamba_mod.Mamba2State(state, conv_win, length)
    x, aux = _apply_ffn(cfg, kind, params, x, aux)
    return x, cache, aux


def block_decode(cfg, kind: LayerKind, params, x, cache, aux):
    # Named scopes mark the block's layers in the compiled HLO's op_name.
    with jax.named_scope("model.attention" if kind.mixer != "mamba"
                         else "model.mamba"):
        h = rms_norm(x, params["norm_mixer"])
        if kind.mixer == "attn":
            out, cache = attn_mod.decode_attention(params["attn"], h, cache)
        elif kind.mixer == "mla":
            out, cache = mla_mod.mla_decode(cfg.mla, params["mla"], h, cache)
        else:
            out, cache = mamba_mod.mamba2_decode(cfg.mamba, params["mamba"],
                                                 h, cache)
        x = x + out
    with jax.named_scope("model.ffn"):
        x, aux = _apply_ffn(cfg, kind, params, x, aux)
    return x, cache, aux
