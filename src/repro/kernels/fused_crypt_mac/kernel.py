"""Pallas TPU kernel: FUSED decrypt + integrity-hash (beyond-paper).

SeDA's read path touches every protected byte twice: once to XOR the
pad (Crypt Engine) and once to hash for the optBlk MAC (Integ Engine).
In hardware those are parallel engines on the same bus; on TPU, running
them as two kernels costs two HBM reads of the full tensor.  This
kernel fuses both into ONE VMEM visit per tile.  Operands are word
planes (see :mod:`repro.kernels.common`), ``L = S * 4`` words per
block of ``S`` 16-byte segments:

    HBM -> VMEM: data planes (L, tile, 128), base OTP planes (4, ...),
                 binding planes (8, ...), diversifiers and NH key
                 (SMEM scalars, or per-block planes for mixed keys)
    compute:     out[w] = data[w] ^ base[w % 4] ^ div[w]   (crypt engine)
                 nh = NH(ct ‖ bind)                       (integ engine)
    VMEM -> HBM: out planes (L, ...) + hash planes (2, ...) (hi, lo)

NH pairs words (2i, 2i+1), which are two planes, so the multiply and
the 64-bit accumulation are elementwise across planes — no strided
lane slices and no cross-lane reduction.

Memory-term saving vs. unfused: reads drop from 2x data to 1x data
(hashes/pads are negligible), i.e. ~33% less HBM traffic on the
read+verify path.

The WRITE direction is symmetric: a secure store encrypts the dirty
bytes and MACs the resulting ciphertext.  Unfused that is one kernel
producing ct and a second reading it back to hash — two VMEM visits of
the full tile.  ``fused_crypt_mac_write`` computes the pad XOR and the
NH compression of the just-produced ciphertext in one pass (the ct
never leaves VMEM between the engines), and the ``_mixed`` variants
carry per-block diversifiers + NH key rows so one dispatch serves
pages owned by different tenant-epoch bank rows.  One kernel body
serves all four: a key operand indexed by word is an SMEM scalar in
the single-key kernels and a per-block plane in the mixed ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common
from repro.kernels.common import LANES, SMEM_SPEC, plane_rows, plane_spec

__all__ = ["fused_crypt_mac", "fused_crypt_mac_mixed",
           "fused_crypt_mac_write", "fused_crypt_mac_write_mixed",
           "fused_planes"]


def _nh_planes(words: list, key: list) -> tuple[jax.Array, jax.Array]:
    """NH over word planes: sum_i (m_2i + k_2i) * (m_2i+1 + k_2i+1)
    mod 2^64 as (hi, lo) u32 planes.

    The 64-bit accumulation is carry-free: the low product words are
    summed as 16-bit halves (exact in u32 while pairs <= 2^16) and
    recombined with one explicit carry into the high word.
    """
    mask = jnp.uint32(0xFFFF)
    s0 = s1 = hi_acc = None
    for i in range(0, len(words), 2):
        a = words[i] + key[i]
        b = words[i + 1] + key[i + 1]
        a_lo, a_hi = a & mask, a >> 16
        b_lo, b_hi = b & mask, b >> 16
        ll = a_lo * b_lo
        lh = a_lo * b_hi
        mid = lh + a_hi * b_lo
        mid_carry = (mid < lh).astype(jnp.uint32)
        lo = ll + (mid << 16)
        lo_carry = (lo < ll).astype(jnp.uint32)
        hi = a_hi * b_hi + (mid >> 16) + (mid_carry << 16) + lo_carry
        terms = (lo & mask, lo >> 16, hi)
        if s0 is None:
            s0, s1, hi_acc = terms
        else:
            s0, s1, hi_acc = s0 + terms[0], s1 + terms[1], hi_acc + terms[2]
    t = (s0 >> 16) + s1
    return hi_acc + (t >> 16), (s0 & mask) | ((t & mask) << 16)


def _fused_kernel(data_ref, base_ref, div_ref, bind_ref, key_ref,
                  out_ref, nh_ref, *, write: bool):
    """Pad XOR of every word, then NH over ciphertext ‖ binding.

    Reads hash the incoming bytes (the ciphertext), writes hash the
    outgoing ones (the fresh ciphertext)."""
    n_words = data_ref.shape[0]
    ct = []
    for w in range(n_words):
        x = data_ref[w]
        y = x ^ base_ref[w % 4] ^ div_ref[w]
        out_ref[w] = y
        ct.append(y if write else x)
    m = ct + [bind_ref[i] for i in range(8)]
    hi, lo = _nh_planes(m, [key_ref[i] for i in range(len(m))])
    nh_ref[0] = hi
    nh_ref[1] = lo


def fused_planes(data: jax.Array, base: jax.Array, div: jax.Array,
                 bind: jax.Array, key: jax.Array, *, write: bool,
                 tile_rows: int = 32, interpret: bool | None = None):
    """The fused pass over word planes.

    ``data`` is (L, rows, 128), ``base`` (4, ...), ``bind`` (8, ...).
    Single key: ``div`` (L,) and ``key`` (L + 8,) u32, held in SMEM.
    Mixed keys: ``div`` (L, rows, 128) and ``key`` (L + 8, rows, 128)
    planes, one row per block.  Returns (out (L, ...), NH (2, ...)).
    """
    if interpret is None:
        interpret = common.default_interpret()
    lanes, rows = data.shape[0], data.shape[1]
    tile = min(tile_rows, rows)
    if div.ndim == 3:
        div_spec, key_spec = plane_spec(lanes, tile), plane_spec(lanes + 8,
                                                                 tile)
    else:
        div_spec = key_spec = SMEM_SPEC
    return pl.pallas_call(
        functools.partial(_fused_kernel, write=write),
        grid=(rows // tile,),
        in_specs=[plane_spec(lanes, tile), plane_spec(4, tile), div_spec,
                  plane_spec(8, tile), key_spec],
        out_specs=[plane_spec(lanes, tile), plane_spec(2, tile)],
        out_shape=[
            jax.ShapeDtypeStruct((lanes, rows, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((2, rows, LANES), jnp.uint32),
        ],
        interpret=interpret,
        name=("fused_crypt_mac_" + ("write" if write else "read")
              + ("_mixed" if div.ndim == 3 else "")),
    )(data, base, div.astype(jnp.uint32), bind, key.astype(jnp.uint32))


def _call(data_lanes, base_otp_lanes, div, bind_words, key, *, per_block,
          write, tile_rows, interpret):
    """(N, ...) layout of :func:`fused_planes` for the public kernels.

    ``per_block=False``: ``div`` is (S, 4) and ``key`` (L + 8,).
    ``per_block=True``: ``div`` is (N, S, 4) and ``key`` (N, L + 8)."""
    n, lanes = data_lanes.shape
    rows, _ = plane_rows(n, tile_rows)

    def planes(x):
        return common.to_planes(x.astype(jnp.uint32), rows)

    if per_block:
        assert div.shape == (n, lanes // 4, 4) and key.shape == (n, lanes + 8)
        div, key = planes(div.reshape(n, lanes)), planes(key)
    else:
        assert div.shape == (lanes // 4, 4) and key.shape == (lanes + 8,)
        div = div.reshape(lanes)
    out, nh = fused_planes(planes(data_lanes), planes(base_otp_lanes), div,
                           planes(bind_words), key, write=write,
                           tile_rows=tile_rows, interpret=interpret)
    return common.from_planes(out, n), common.from_planes(nh, n)


_STATIC = ("tile_rows", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def fused_crypt_mac(ct_lanes: jax.Array, base_otp_lanes: jax.Array,
                    div_lanes: jax.Array, bind_words: jax.Array,
                    key_u32: jax.Array, *, tile_rows: int = 32,
                    interpret: bool | None = None):
    """Returns (plaintext lanes (N, S*4) u32, NH hashes (N, 2) u32)."""
    return _call(ct_lanes, base_otp_lanes, div_lanes, bind_words, key_u32,
                 per_block=False, write=False, tile_rows=tile_rows,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def fused_crypt_mac_write(pt_lanes: jax.Array, base_otp_lanes: jax.Array,
                          div_lanes: jax.Array, bind_words: jax.Array,
                          key_u32: jax.Array, *, tile_rows: int = 32,
                          interpret: bool | None = None):
    """Single-key fused encrypt + NH: returns (ciphertext lanes
    (N, S*4) u32, NH hashes of the fresh ciphertext (N, 2) u32)."""
    return _call(pt_lanes, base_otp_lanes, div_lanes, bind_words, key_u32,
                 per_block=False, write=True, tile_rows=tile_rows,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def fused_crypt_mac_mixed(ct_lanes: jax.Array, base_otp_lanes: jax.Array,
                          div_lanes_per: jax.Array, bind_words: jax.Array,
                          key_per_u32: jax.Array, *, tile_rows: int = 32,
                          interpret: bool | None = None):
    """Mixed-key fused decrypt + NH: per-block diversifiers (N, S, 4)
    and per-block NH keys (N, S*4 + 8).  Returns (plaintext lanes
    (N, S*4) u32, NH hashes (N, 2) u32), bit-identical to vmapping
    :func:`fused_crypt_mac` over per-key groups."""
    return _call(ct_lanes, base_otp_lanes, div_lanes_per, bind_words,
                 key_per_u32, per_block=True, write=False,
                 tile_rows=tile_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def fused_crypt_mac_write_mixed(pt_lanes: jax.Array,
                                base_otp_lanes: jax.Array,
                                div_lanes_per: jax.Array,
                                bind_words: jax.Array,
                                key_per_u32: jax.Array, *,
                                tile_rows: int = 32,
                                interpret: bool | None = None):
    """Mixed-key fused encrypt + NH (the one-pass dirty-page reseal):
    returns (ciphertext lanes (N, S*4) u32, NH hashes of the FRESH
    ciphertext (N, 2) u32), bit-identical to encrypting and then
    hashing per key group."""
    return _call(pt_lanes, base_otp_lanes, div_lanes_per, bind_words,
                 key_per_u32, per_block=True, write=True,
                 tile_rows=tile_rows, interpret=interpret)
