"""Common model components + the ParamSpec infrastructure.

Every parameter is described by a :class:`ParamSpec` carrying its
shape, dtype and *logical axes* (MaxText-style).  Spec pytrees mirror
param pytrees, so:

  * the dry-run lowers against ``jax.ShapeDtypeStruct`` built straight
    from specs — a 671B model is never materialized;
  * the sharding planner maps logical axes -> mesh axes with
    divisibility checking (see :mod:`repro.launch.sharding`);
  * ``init_params`` materializes real (reduced-config) models for smoke
    tests, examples and CPU training.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["ParamSpec", "init_params", "shape_structs", "rms_norm",
           "layer_norm", "rope", "dense", "DEFAULT_DTYPE"]

DEFAULT_DTYPE = "bfloat16"


class ParamSpec(NamedTuple):
    shape: tuple
    dtype: str
    axes: tuple            # logical axis names, len(axes) == len(shape)
    init: str = "fan_in"   # fan_in | zeros | ones | embed

    def struct(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, jnp.dtype(self.dtype))


def spec(shape, axes, dtype=DEFAULT_DTYPE, init="fan_in") -> ParamSpec:
    assert len(shape) == len(axes), (shape, axes)
    return ParamSpec(tuple(int(s) for s in shape), dtype, tuple(axes), init)


def _init_leaf(key, s: ParamSpec) -> jax.Array:
    dtype = jnp.dtype(s.dtype)
    if s.init == "zeros":
        return jnp.zeros(s.shape, dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, dtype)
    if s.init == "embed":
        # Tied-embedding-friendly scale (0.02, GPT-style): keeps initial
        # logits near zero so loss starts at ~ln(vocab).
        return (jax.random.normal(key, s.shape, jnp.float32) * 0.02
                ).astype(dtype)
    # fan_in: truncated-normal-ish scaled by 1/sqrt(fan_in); fan_in is
    # the product of all dims except the last.
    fan_in = max(1, math.prod(s.shape[:-1]))
    scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, s.shape, jnp.float32) * scale).astype(dtype)


def init_params(specs: Any, key: jax.Array) -> Any:
    """Materialize a param pytree from a spec pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    # One jitted program per leaf: the f32 draw fuses into the cast, so
    # a full-size model never holds an f32 copy of its largest leaf.
    init = jax.jit(_init_leaf, static_argnums=1)
    params = [init(k, s) for k, s in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, params)


def shape_structs(specs: Any) -> Any:
    """Spec pytree -> ShapeDtypeStruct pytree (no allocation)."""
    return jax.tree_util.tree_map(
        lambda s: s.struct(), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


# ---------------------------------------------------------------------------
# Numerics.  Norms run in f32 and cast back (standard practice).
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    out = ((xf - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
           + bias.astype(jnp.float32))
    return out.astype(dtype)


def rope(x: jax.Array, positions: jax.Array, *, base: float = 10000.0) -> jax.Array:
    """Rotary embedding on (..., seq, heads, head_dim)."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freqs = jnp.exp(-math.log(base) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


def dense(x: jax.Array, w: jax.Array) -> jax.Array:
    """Matmul with f32 accumulation (bf16 inputs, MXU-style)."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)
