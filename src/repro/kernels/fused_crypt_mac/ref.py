"""Oracles for the fused crypt + NH kernels: the crypt half
(:func:`otp_xor_ref`), the hash half (:func:`nh_hash_ref`) and their
read/write, single/mixed-key compositions."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import mac
__all__ = ["otp_xor_ref", "nh_hash_ref", "fused_crypt_mac_ref", "fused_crypt_mac_mixed_ref",
           "fused_crypt_mac_write_ref", "fused_crypt_mac_write_mixed_ref"]


def otp_xor_ref(data_lanes: jax.Array, base_otp_lanes: jax.Array,
                div_lanes: jax.Array) -> jax.Array:
    """Apply per-segment diversified OTPs to wide blocks.

    Args:
      data_lanes: (N, S*4) uint32 — N wide blocks, S 16B segments each.
      base_otp_lanes: (N, 4) uint32 — one base OTP per block (AES output).
      div_lanes: (S, 4) uint32 — per-segment diversifiers (round keys;
        row 0 is zero so segment 0 keeps the base OTP).

    Returns (N, S*4) uint32 lanes:
      out[n, 4s+l] = data[n, 4s+l] ^ base[n, l] ^ div[s, l]
    """
    n, lanes = data_lanes.shape
    s = div_lanes.shape[0]
    d = data_lanes.reshape(n, s, 4)
    pads = base_otp_lanes[:, None, :] ^ div_lanes[None, :, :]
    return (d ^ pads).reshape(n, lanes)


def nh_hash_ref(payload_u32: jax.Array, key_u32: jax.Array) -> jax.Array:
    """(N, L) u32 payload + (L,) u32 key -> (N, 2) u32 (hi, lo)."""
    hi, lo = mac.nh_hash(payload_u32, key_u32)
    return jnp.stack([hi, lo], axis=-1)


def fused_crypt_mac_ref(ct_lanes: jax.Array, base_otp_lanes: jax.Array,
                        div_lanes: jax.Array, bind_words: jax.Array,
                        key_u32: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Decrypt wide blocks AND compute their NH hashes (over ciphertext).

    Args:
      ct_lanes: (N, S*4) u32 ciphertext lanes.
      base_otp_lanes: (N, 4) u32.
      div_lanes: (S, 4) u32.
      bind_words: (N, 8) u32 binding words appended to the NH payload.
      key_u32: (S*4 + 8,) u32 NH key.

    Returns (plaintext lanes (N, S*4), hashes (N, 2)).
    """
    pt = otp_xor_ref(ct_lanes, base_otp_lanes, div_lanes)
    payload = jnp.concatenate([ct_lanes, bind_words], axis=-1)
    return pt, nh_hash_ref(payload, key_u32)


def fused_crypt_mac_mixed_ref(ct_lanes: jax.Array, base_otp_lanes: jax.Array,
                              div_lanes_per: jax.Array, bind_words: jax.Array,
                              key_per_u32: jax.Array
                              ) -> tuple[jax.Array, jax.Array]:
    """Mixed-key oracle: one single-key ref evaluation per block.

    ``div_lanes_per`` is (N, S, 4) and ``key_per_u32`` (N, S*4 + 8) —
    each block carries its own diversifiers and NH key (pages owned by
    different tenant-epoch bank rows).
    """
    def one(ct1, base1, div1, bind1, key1):
        pt, nh = fused_crypt_mac_ref(ct1[None], base1[None], div1,
                                     bind1[None], key1)
        return pt[0], nh[0]

    return jax.vmap(one)(ct_lanes, base_otp_lanes, div_lanes_per,
                         bind_words, key_per_u32)


def fused_crypt_mac_write_ref(pt_lanes: jax.Array, base_otp_lanes: jax.Array,
                              div_lanes: jax.Array, bind_words: jax.Array,
                              key_u32: jax.Array
                              ) -> tuple[jax.Array, jax.Array]:
    """Write-direction oracle: encrypt, then NH over the FRESH
    ciphertext (same shapes as :func:`fused_crypt_mac_ref`; the hash
    input moves to the pad-XOR output)."""
    ct = otp_xor_ref(pt_lanes, base_otp_lanes, div_lanes)
    payload = jnp.concatenate([ct, bind_words], axis=-1)
    return ct, nh_hash_ref(payload, key_u32)


def fused_crypt_mac_write_mixed_ref(pt_lanes: jax.Array,
                                    base_otp_lanes: jax.Array,
                                    div_lanes_per: jax.Array,
                                    bind_words: jax.Array,
                                    key_per_u32: jax.Array
                                    ) -> tuple[jax.Array, jax.Array]:
    """Mixed-key write oracle: one single-key write ref per block."""
    def one(pt1, base1, div1, bind1, key1):
        ct, nh = fused_crypt_mac_write_ref(pt1[None], base1[None], div1,
                                           bind1[None], key1)
        return ct[0], nh[0]

    return jax.vmap(one)(pt_lanes, base_otp_lanes, div_lanes_per,
                         bind_words, key_per_u32)
