"""Shared helpers for the SeDA Pallas TPU kernels.

Every kernel works on **word planes**: an (N, W) array of per-block
u32 words is laid out as (W, rows, 128), word ``w`` of block ``b`` at
``[w, b // 128, b % 128]``.  One plane is a dense (rows, 128) tile, so
the per-block arithmetic (AES byte lanes, pad XOR, NH pairs) is plain
elementwise work across full vector registers: no lane reshapes, no
strided lane slices and no in-kernel gathers of the block's words.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["default_interpret", "cdiv", "LANES", "plane_rows", "to_planes",
           "from_planes", "pad_planes", "to_word_planes", "from_word_planes",
           "plane_spec", "SMEM_SPEC"]

LANES = 128

# Whole-array scalar operand (a key, a diversifier table) in SMEM.
SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def default_interpret() -> bool:
    """Pallas interpret mode on the CPU backend only.

    Tests run on CPU, where the kernel bodies execute through the
    interpreter; on a TPU (or any other backend) the kernels are
    compiled by Mosaic.
    """
    return jax.default_backend() == "cpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def plane_rows(n: int, tile_rows: int) -> tuple[int, int]:
    """(rows, tile) of the word planes holding ``n`` blocks.

    A grid step covers ``tile`` rows of 128 blocks.  Rows are padded to
    whole sublane tiles (8) and then to whole grid steps, so every
    block is (8k, 128)-aligned.
    """
    if tile_rows % 8:
        raise ValueError(f"tile_rows {tile_rows} is not a multiple of 8")
    rows = cdiv(cdiv(n, LANES), 8) * 8
    tile = min(tile_rows, rows)
    return cdiv(rows, tile) * tile, tile


def pad_planes(words: jax.Array, rows: int) -> jax.Array:
    """(W, N) words -> zero-padded (W, rows, 128) planes."""
    w, n = words.shape
    return jnp.pad(words, ((0, 0), (0, rows * LANES - n))).reshape(
        w, rows, LANES)


def to_planes(x: jax.Array, rows: int) -> jax.Array:
    """(N, W) per-block words -> zero-padded (W, rows, 128) planes."""
    return pad_planes(x.T, rows)


def from_planes(planes: jax.Array, n: int) -> jax.Array:
    """(W, rows, 128) planes -> (N, W) per-block words."""
    return planes.reshape(planes.shape[0], -1)[:, :n].T


_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def to_word_planes(values: jax.Array, words: int, rows: int) -> jax.Array:
    """Flat blocks of ``4 * words`` bytes -> (words, rows, 128) u32
    planes of their little-endian words.

    ``values`` holds the blocks' bytes as a 1-, 2- or 4-byte dtype (u8
    ciphertext, bf16 or f32 cache values).  The values are transposed
    first (position in the block major, block minor), so the word
    assembly is shift/or arithmetic on lane-dense rows: no array ever
    has the parts of one word as its minor dimension, which the TPU
    would pad to a full 128-lane tile.
    """
    size = jnp.dtype(values.dtype).itemsize
    per = 4 // size
    n = values.size // (words * per)
    v = values.reshape(n, words * per).T
    v = jax.lax.bitcast_convert_type(v, _UINT[size]).astype(jnp.uint32)
    v = v.reshape(words, per, n)
    w = v[:, 0]
    for i in range(1, per):
        w = w | (v[:, i] << (8 * size * i))
    return pad_planes(w, rows)


def from_word_planes(planes: jax.Array, n: int, dtype) -> jax.Array:
    """Inverse of :func:`to_word_planes`: the first ``n`` blocks as a
    flat array of ``dtype`` values."""
    size = jnp.dtype(dtype).itemsize
    per = 4 // size
    words = planes.shape[0]
    w = planes.reshape(words, -1)[:, :n]
    mask = jnp.uint32((1 << (8 * size)) - 1)
    parts = jnp.stack([(w >> (8 * size * i)) & mask for i in range(per)],
                      axis=1).astype(_UINT[size])
    v = jax.lax.bitcast_convert_type(parts.reshape(words * per, n), dtype)
    return v.T.reshape(-1)


def plane_spec(words: int, tile: int) -> pl.BlockSpec:
    """BlockSpec of a (words, rows, 128) operand tiled over rows."""
    return pl.BlockSpec((words, tile, LANES), lambda i: (0, i, 0))
