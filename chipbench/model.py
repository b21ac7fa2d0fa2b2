"""The benchmark's weights: made from the seed, on the device, in one
jitted call, in the type they are served in.

The weights belong to the benchmark, not to the program: the program
is handed them (``adapt``), and the reference (:mod:`reference`) makes
the same ones again after the program's state is freed.  Each matrix
is normal with standard deviation ``1 / sqrt(fan_in)``, ``fan_in``
counting one layer's input dimensions, so every layer adds to the
residual stream as much as the embedding holds and the logits are
those of a deep random network, not of the embedding alone.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# Input dimensions (after the layer axis) of each stacked matrix.
FAN_IN_DIMS = {"wq": 1, "wk": 1, "wv": 1, "wo": 2,
               "w_up": 1, "w_gate": 1, "w_down": 1, "lm_head": 1}
EMBED_STD = 0.02


def shapes(c: dict) -> dict:
    """name -> (shape, dtype) of every weight of the configuration."""
    L, d = c["num_hidden_layers"], c["hidden_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    ff, v, dt = c["intermediate_size"], c["vocab_size"], c["dtype"]
    out = {
        "embed": ((v, d), dt),
        "final_norm": ((d,), "float32"),
        "norm_mixer": ((L, d), "float32"),
        "norm_ffn": ((L, d), "float32"),
        "wq": ((L, d, h, hd), dt),
        "wk": ((L, d, kv, hd), dt),
        "wv": ((L, d, kv, hd), dt),
        "wo": ((L, h, hd, d), dt),
        "w_up": ((L, d, ff), dt),
        "w_down": ((L, ff, d), dt),
    }
    if c["hidden_act"] == "silu-gated":
        out["w_gate"] = ((L, d, ff), dt)
    if not c["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), dt)
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from all the bits of a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _make(key, c_items):
    c = dict(c_items)
    out = {}
    for i, (name, (shape, dtype)) in enumerate(sorted(shapes(c).items())):
        dt = jnp.dtype(dtype)
        if name.startswith("norm") or name == "final_norm":
            out[name] = jnp.ones(shape, dt)
            continue
        std = (EMBED_STD if name == "embed" else
               1.0 / math.sqrt(math.prod(shape[1:1 + FAN_IN_DIMS[name]])))
        out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32) * std).astype(dt)
    return out


def _frozen(c: dict) -> tuple:
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "dtype", "hidden_act", "tie_word_embeddings")
    return tuple((k, c[k]) for k in keys)


@functools.lru_cache(maxsize=None)
def _maker(c_items, device):
    return jax.jit(_make, static_argnums=1,
                   out_shardings=jax.sharding.SingleDeviceSharding(device))


def make_weights(c: dict, seed: int, device=None) -> dict:
    """Every weight of configuration ``c`` for ``seed``, on ``device``."""
    device = device or jax.devices()[0]
    items = _frozen(c)
    return _maker(items, device)(seed_key(seed), items)


def adapt(weights: dict, spec_tree):
    """The weights in the program's parameter tree.

    ``spec_tree`` is the program's own description of its parameters
    (one leaf per array, under the names the program gives them).  Each
    leaf takes the benchmark array of the same name, which must have the
    leaf's shape; nothing is copied."""
    def pick(path, leaf):
        name = path[-1].key
        arr = weights[name]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"{name}: benchmark shape {arr.shape} but the "
                             f"program asks for {leaf.shape}")
        return arr
    return jax.tree_util.tree_map_with_path(
        pick, spec_tree, is_leaf=lambda x: hasattr(x, "shape"))
