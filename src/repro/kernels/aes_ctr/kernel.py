"""Pallas TPU kernel: batched AES-128-CTR keystream generation.

This is SeDA's "AES Engine" (paper Fig. 2(b)) mapped to a TPU core.
Counter blocks arrive as word planes (see :mod:`repro.kernels.common`):
one grid step encrypts ``tile * 128`` blocks, held as a (16, tile, 128)
int32 state — one dense plane per state byte.

  HBM -> VMEM: counter planes (4, tile, 128) u32, S-box halves
               (2, 1, 128), round keys ((11, 16, 1, 128) byte rows, or
               per-block (44, tile, 128) u32 word planes for mixed keys)
  VMEM compute: AES rounds in a loop; ShiftRows reorders the byte
               planes and MixColumns is shift/xor arithmetic across
               them, so neither needs a gather
  VMEM -> HBM: OTP planes (4, tile, 128) u32, little-endian lanes

SubBytes is the only table lookup.  The 256-entry S-box is split into
two 128-entry rows and every byte gathers along the lane axis from
both (``take_along_axis`` on a 2D view — the in-register lane gather
Mosaic supports), picking by the byte's top bit.  Validated against
the FIPS-chained oracle in ref.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.aes import _SBOX_NP, _SHIFT_ROWS_PERM_NP
from repro.kernels import common
from repro.kernels.common import LANES, plane_rows, plane_spec

__all__ = ["aes_ctr_keystream", "aes_ctr_keystream_multi", "aes_planes"]

# S-box as its two 128-entry halves, one lane row each.
_SBOX_HALVES_NP = _SBOX_NP.astype("int32").reshape(2, 1, LANES)


def _xtime(x: jax.Array) -> jax.Array:
    """GF(2^8) doubling on int32 bytes."""
    return ((x << 1) ^ jnp.where((x & 0x80) != 0, 0x1B, 0)) & 0xFF


def _shift_rows(state: jax.Array) -> jax.Array:
    return jnp.concatenate([state[p:p + 1] for p in _SHIFT_ROWS_PERM_NP])


def _mix_columns(state: jax.Array) -> jax.Array:
    s = state.reshape((4, 4) + state.shape[1:])       # (col, row, ...)
    a0, a1, a2, a3 = s[:, 0], s[:, 1], s[:, 2], s[:, 3]
    x0, x1, x2, x3 = _xtime(a0), _xtime(a1), _xtime(a2), _xtime(a3)
    out = jnp.stack([x0 ^ (x1 ^ a1) ^ a2 ^ a3,
                     a0 ^ x1 ^ (x2 ^ a2) ^ a3,
                     a0 ^ a1 ^ x2 ^ (x3 ^ a3),
                     (x0 ^ a0) ^ a1 ^ a2 ^ x3], axis=1)
    return out.reshape(state.shape)


def _aes_ctr_kernel(ctr_ref, rk_ref, sbox_ref, out_ref, *, per_block: bool):
    tile = ctr_ref.shape[1]
    flat = (16 * tile, LANES)
    lo = jnp.broadcast_to(sbox_ref[0], flat)
    hi = jnp.broadcast_to(sbox_ref[1], flat)

    def sub_bytes(state):
        x = state.reshape(flat)
        idx = x & 0x7F
        y = jnp.where(x < 0x80, jnp.take_along_axis(lo, idx, axis=1),
                      jnp.take_along_axis(hi, idx, axis=1))
        return y.reshape(state.shape)

    def round_key(r):
        """Round key ``r`` as (16, 1 | tile, 128) int32 bytes."""
        if not per_block:
            return rk_ref[r]
        # Per-block schedules: 44 little-endian u32 words per block.
        words = rk_ref[pl.ds(4 * r, 4)]
        b = jnp.stack([(words >> (8 * i)) & 0xFF for i in range(4)], axis=1)
        return b.reshape(16, tile, LANES).astype(jnp.int32)

    # Counter words serialize big-endian: byte 4w + i = word w >> 24-8i.
    words = ctr_ref[...]
    state = jnp.stack([(words >> (24 - 8 * i)) & 0xFF for i in range(4)],
                      axis=1).reshape(16, tile, LANES).astype(jnp.int32)
    state = state ^ round_key(0)

    def full_round(r, state):
        return _mix_columns(_shift_rows(sub_bytes(state))) ^ round_key(r)

    state = jax.lax.fori_loop(1, 10, full_round, state)
    state = _shift_rows(sub_bytes(state)) ^ round_key(10)
    b = state.reshape(4, 4, tile, LANES).astype(jnp.uint32)
    out_ref[...] = b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24)


def aes_planes(ctr: jax.Array, round_keys: jax.Array, *,
               tile_rows: int = 32,
               interpret: bool | None = None) -> jax.Array:
    """AES-128 over counter word planes: (4, rows, 128) u32 -> OTP planes.

    ``round_keys`` is one (11, 16) u8 schedule, or per-block schedules
    as (44, rows, 128) u32 planes of little-endian words.
    """
    if interpret is None:
        interpret = common.default_interpret()
    rows = ctr.shape[1]
    tile = min(tile_rows, rows)
    per_block = round_keys.ndim == 3
    if per_block:
        rk, rk_spec = round_keys.astype(jnp.uint32), plane_spec(44, tile)
    else:
        rk = jnp.broadcast_to(
            round_keys.astype(jnp.int32)[:, :, None, None], (11, 16, 1, LANES))
        rk_spec = pl.BlockSpec((11, 16, 1, LANES), lambda i: (0, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_aes_ctr_kernel, per_block=per_block),
        grid=(rows // tile,),
        in_specs=[plane_spec(4, tile), rk_spec,
                  pl.BlockSpec((2, 1, LANES), lambda i: (0, 0, 0))],
        out_specs=plane_spec(4, tile),
        out_shape=jax.ShapeDtypeStruct((4, rows, LANES), jnp.uint32),
        interpret=interpret,
        name="aes_ctr_keystream" + ("_mixed" if per_block else ""),
    )(ctr.astype(jnp.uint32), rk, jnp.asarray(_SBOX_HALVES_NP))


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def aes_ctr_keystream(counter_words: jax.Array, round_keys: jax.Array, *,
                      tile_rows: int = 32,
                      interpret: bool | None = None) -> jax.Array:
    """(N, 4) u32 counters + (11, 16) u8 schedule -> (N, 4) u32 OTP lanes."""
    n = counter_words.shape[0]
    rows, _ = plane_rows(n, tile_rows)
    out = aes_planes(common.to_planes(counter_words, rows), round_keys,
                     tile_rows=tile_rows, interpret=interpret)
    return common.from_planes(out, n)


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def aes_ctr_keystream_multi(counter_words: jax.Array,
                            round_keys_per: jax.Array, *,
                            tile_rows: int = 32,
                            interpret: bool | None = None) -> jax.Array:
    """(N, 4) u32 counters + PER-BLOCK (N, 11, 16) u8 schedules ->
    (N, 4) u32 OTP lanes.  Mixed-key sibling of
    :func:`aes_ctr_keystream`; bit-identical to running the single-key
    kernel once per distinct schedule."""
    n = counter_words.shape[0]
    rows, _ = plane_rows(n, tile_rows)
    words = jax.lax.bitcast_convert_type(
        round_keys_per.astype(jnp.uint8).reshape(n, 44, 4), jnp.uint32)
    out = aes_planes(common.to_planes(counter_words, rows),
                     common.to_planes(words, rows),
                     tile_rows=tile_rows, interpret=interpret)
    return common.from_planes(out, n)
