"""Wrappers: fused secure-read AND secure-write for flat buffers.

``secure_crossing`` is the one boundary crossing the page pool calls:
base keystream (AES kernel), one fused pass of pad XOR + NH (the read
hashes the incoming ciphertext, the write the fresh one), and the AES
finalization of the NH hashes into optBlk MACs — all on word planes
(see :mod:`repro.kernels.common`).  With ``row_idx`` it gathers each
optBlk's AES schedule, B-AES diversifiers and NH key row from a device
key bank, so one dispatch serves pages owned by different
(tenant, epoch) rows.  ``secure_read_kernel*`` / ``secure_write_kernel*``
are the same crossing with (N, ...) counters and (N, 8) u8 MACs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import baes, mac
from repro.kernels import common
from repro.kernels.aes_ctr.kernel import aes_planes
from repro.kernels.fused_crypt_mac.kernel import (fused_crypt_mac,
                                                  fused_crypt_mac_mixed,
                                                  fused_crypt_mac_write,
                                                  fused_crypt_mac_write_mixed,
                                                  fused_planes)

__all__ = ["secure_crossing", "secure_read_kernel", "secure_read_kernel_mixed",
           "secure_write_kernel", "secure_write_kernel_mixed",
           "fused_crypt_mac", "fused_crypt_mac_mixed",
           "fused_crypt_mac_write", "fused_crypt_mac_write_mixed"]

TILE_ROWS = 32


def _div_lanes(round_keys: jax.Array, n_segments: int) -> jax.Array:
    """B-AES diversifiers as (S, 4) uint32 lanes (row 0 = zeros)."""
    div_u8 = baes.diversifiers(round_keys, n_segments)  # (S, 16) u8
    return jax.lax.bitcast_convert_type(
        div_u8.reshape(n_segments, 4, 4), jnp.uint32)


def secure_crossing(data: jax.Array, binding: mac.Binding,
                    counter_words: jax.Array, round_keys: jax.Array,
                    hash_key_u32: jax.Array, *, block_bytes: int,
                    write: bool, out_dtype=jnp.uint8,
                    row_idx: jax.Array | None = None,
                    interpret: bool | None = None):
    """One fused crossing of N optBlks.

    Args:
      data: the N blocks' bytes, flat, as u8 or as values of a 2- or
        4-byte dtype: ciphertext (read) or plaintext (write).
      binding: the blocks' RePA binding fields, broadcastable to (N,).
      counter_words: (4, N) u32 CTR counter words, one column per block.
      round_keys, hash_key_u32: one (11, 16) u8 schedule and (lanes,)
        u32 NH key; with ``row_idx`` a key bank of (K, 11, 16) and
        (K, lanes) rows instead.
      row_idx: optional (N,) int32 bank row per block (mixed keys).

    Returns (the output blocks, flat, as ``out_dtype`` values; (2, N)
    u32 MAC words — the little-endian lanes of each block's 8-byte MAC).
    """
    n_segments = block_bytes // 16
    if n_segments - 1 > 10:
        raise ValueError("kernel path supports narrow mode (<= 11 segments)")
    lanes = 4 * n_segments
    n = data.size * jnp.dtype(data.dtype).itemsize // block_bytes
    rows, _ = common.plane_rows(n, TILE_ROWS)
    kw = dict(tile_rows=TILE_ROWS, interpret=interpret)

    def gather_rows(bank_words):
        """(K, W) bank rows -> (W, rows, 128) planes, one per block."""
        return common.pad_planes(bank_words.T[:, row_idx], rows)

    if row_idx is None:
        rk = round_keys
        div = _div_lanes(round_keys, n_segments).reshape(lanes)
        key = hash_key_u32[: lanes + 8]
    else:
        k = round_keys.shape[0]
        rk = gather_rows(jax.lax.bitcast_convert_type(
            round_keys.astype(jnp.uint8).reshape(k, 44, 4), jnp.uint32))
        div = gather_rows(jax.vmap(lambda r: _div_lanes(r, n_segments))(
            round_keys).reshape(k, lanes))
        key = gather_rows(hash_key_u32[:, : lanes + 8].astype(jnp.uint32))

    fields = [jnp.broadcast_to(f, (n,)).astype(jnp.uint32) for f in binding]
    bind = common.pad_planes(
        jnp.stack(fields + [jnp.zeros((n,), jnp.uint32)] * (8 - len(fields))),
        rows)
    base = aes_planes(common.pad_planes(counter_words, rows), rk, **kw)
    out, nh = fused_planes(common.to_word_planes(data, lanes, rows), base,
                           div, bind, key, write=write, **kw)
    # mac.finalize_words, plane-wise: (hi, lo, pa ^ layer,
    # vn ^ fmap << 16 ^ blk) -> AES -> the first 8 bytes are the MAC.
    pa, vn, layer, fmap, blk = (bind[i] for i in range(5))
    fin = jnp.stack([nh[0], nh[1], pa ^ layer, vn ^ (fmap << 16) ^ blk])
    pads = aes_planes(fin, rk, **kw)
    return (common.from_word_planes(out, n, out_dtype),
            pads[:2].reshape(2, -1)[:, :n])


def _mac_bytes(mac_words: jax.Array) -> jax.Array:
    """(2, N) u32 MAC words -> (N, MAC_BYTES) u8."""
    return jax.lax.bitcast_convert_type(mac_words.T, jnp.uint8).reshape(
        -1, mac.MAC_BYTES)


def _crossing_bytes(data_u8, binding, round_keys, counter_words,
                    hash_key_u32, row_idx, *, block_bytes, write, interpret):
    out, words = secure_crossing(
        data_u8, binding, counter_words.astype(jnp.uint32).T, round_keys,
        hash_key_u32, block_bytes=block_bytes, write=write, row_idx=row_idx,
        interpret=interpret)
    return out.reshape(data_u8.shape), _mac_bytes(words)


def secure_read_kernel(ct_u8: jax.Array, binding: mac.Binding,
                       round_keys: jax.Array, counter_words: jax.Array,
                       hash_key_u32: jax.Array, *, block_bytes: int,
                       interpret: bool | None = None):
    """Kernel-backed secure read: returns (plaintext_u8, block_macs_u8).

    One pass over the ciphertext performs both the B-AES decrypt and
    the NH compression; the AES finalization of the MACs runs on the
    tiny hash list.  Bit-identical to the unfused core path.
    ``counter_words`` is (N, 4).
    """
    return _crossing_bytes(ct_u8, binding, round_keys, counter_words,
                           hash_key_u32, None, block_bytes=block_bytes,
                           write=False, interpret=interpret)


def secure_write_kernel(pt_u8: jax.Array, binding: mac.Binding,
                        round_keys: jax.Array, counter_words: jax.Array,
                        hash_key_u32: jax.Array, *, block_bytes: int,
                        interpret: bool | None = None):
    """Kernel-backed secure write: returns (ciphertext_u8, block_macs_u8).

    One pass over the plaintext performs both the B-AES encrypt and the
    NH compression of the fresh ciphertext; the AES finalization runs
    on the tiny hash list.  Bit-identical to encrypting via the unfused
    core path and then MACing the result.
    """
    return _crossing_bytes(pt_u8, binding, round_keys, counter_words,
                           hash_key_u32, None, block_bytes=block_bytes,
                           write=True, interpret=interpret)


def secure_read_kernel_mixed(ct_u8: jax.Array, binding: mac.Binding,
                             bank_round_keys: jax.Array,
                             counter_words: jax.Array,
                             bank_hash_key: jax.Array, row_idx: jax.Array, *,
                             block_bytes: int,
                             interpret: bool | None = None):
    """Mixed-key fused secure read: per-BLOCK keys gathered from a bank.

    Args:
      bank_round_keys: (K, 11, 16) u8 — the device key bank's schedules
        (one row per retained (tenant, epoch)).
      bank_hash_key: (K, n_lanes) u32 NH key rows.
      row_idx: (N,) int32 bank row per optBlk (a page's row repeated
        over its blocks).

    Every block is decrypted and NH-hashed under its OWN bank row in
    one fused pass — the route that keeps MIXED-row decode ticks on the
    fused kernels instead of falling back to the vmapped per-page
    reference.  Bit-identical to that vmapped path.
    """
    return _crossing_bytes(ct_u8, binding, bank_round_keys, counter_words,
                           bank_hash_key, row_idx, block_bytes=block_bytes,
                           write=False, interpret=interpret)


def secure_write_kernel_mixed(pt_u8: jax.Array, binding: mac.Binding,
                              bank_round_keys: jax.Array,
                              counter_words: jax.Array,
                              bank_hash_key: jax.Array, row_idx: jax.Array, *,
                              block_bytes: int,
                              interpret: bool | None = None):
    """Mixed-key fused secure write: per-BLOCK keys gathered from a bank.

    The write half of the mixed-key fused path: every block is
    encrypted and its fresh ciphertext NH-hashed under its OWN bank row
    in one fused pass — the route that keeps MIXED-row dirty-page
    reseals on the fused kernels instead of the vmapped per-page
    reference.  Bit-identical to that vmapped path.
    """
    return _crossing_bytes(pt_u8, binding, bank_round_keys, counter_words,
                           bank_hash_key, row_idx, block_bytes=block_bytes,
                           write=True, interpret=interpret)
