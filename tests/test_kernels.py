"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import baes, mac
from repro.core.secure_memory import SecureKeys
from repro.kernels.aes_ctr import kernel as aes_k
from repro.kernels.aes_ctr.ref import (aes_ctr_keystream_lanes_ref,
                                       aes_ctr_keystream_ref)
from repro.kernels.fused_crypt_mac.kernel import (fused_crypt_mac,
                                                  fused_crypt_mac_mixed,
                                                  fused_crypt_mac_write,
                                                  fused_crypt_mac_write_mixed)
from repro.kernels.fused_crypt_mac.ops import (secure_read_kernel,
                                               secure_read_kernel_mixed,
                                               secure_write_kernel,
                                               secure_write_kernel_mixed)
from repro.kernels.fused_crypt_mac.ref import (fused_crypt_mac_mixed_ref,
                                               fused_crypt_mac_write_mixed_ref,
                                               fused_crypt_mac_write_ref,
                                               nh_hash_ref, otp_xor_ref)


@pytest.fixture(scope="module")
def kkeys():
    return SecureKeys.derive(77)


def _aes_lanes_ref_multi(cw, rk_per):
    return jax.vmap(lambda c, rk: aes_ctr_keystream_lanes_ref(c[None], rk)[0])(
        cw, rk_per)


class TestAESCTRKernel:
    @pytest.mark.parametrize("n", [1, 7, 256, 1000])
    @pytest.mark.parametrize("keying", ["single", "per_block"])
    def test_vs_oracle(self, kkeys, n, keying):
        rng = np.random.default_rng(n)
        cw = jnp.asarray(rng.integers(0, 2**32, (n, 4), dtype=np.uint32))
        if keying == "single":
            got = aes_k.aes_ctr_keystream(cw, kkeys.round_keys)
            want = aes_ctr_keystream_lanes_ref(cw, kkeys.round_keys)
        else:
            rk_per = jnp.asarray(rng.integers(0, 256, (n, 11, 16),
                                              dtype=np.uint8))
            got = aes_k.aes_ctr_keystream_multi(cw, rk_per)
            want = _aes_lanes_ref_multi(cw, rk_per)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_bytes_layout(self, kkeys):
        cw = jnp.asarray([[0, 5, 0, 9]], dtype=jnp.uint32)
        lanes = aes_k.aes_ctr_keystream(cw, kkeys.round_keys)
        got = jax.lax.bitcast_convert_type(lanes, jnp.uint8).reshape(1, 16)
        want = aes_ctr_keystream_ref(cw, kkeys.round_keys)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("tile_rows", [8, 64, 512])
    def test_tile_sweep(self, kkeys, tile_rows):
        """Multi-step grids (and padded tails) agree with the oracle."""
        rng = np.random.default_rng(1)
        cw = jnp.asarray(rng.integers(0, 2**32, (20000, 4), dtype=np.uint32))
        got_t = aes_k.aes_ctr_keystream(cw, kkeys.round_keys,
                                        tile_rows=tile_rows)
        want = aes_ctr_keystream_lanes_ref(cw, kkeys.round_keys)
        np.testing.assert_array_equal(np.asarray(got_t), np.asarray(want))


def _rand_u32(rng, shape):
    return jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))


class TestFusedCryptHalf:
    """The crypt engine half of the fused kernels: the diversified pad
    XOR, against its oracle and against the core B-AES cipher."""

    @pytest.mark.parametrize("n,s", [(1, 2), (13, 4), (300, 8), (64, 32)])
    def test_vs_oracle(self, n, s):
        rng = np.random.default_rng(n * s)
        data = _rand_u32(rng, (n, s * 4))
        base = _rand_u32(rng, (n, 4))
        div = _rand_u32(rng, (s, 4))
        bind = _rand_u32(rng, (n, 8))
        key = _rand_u32(rng, (s * 4 + 8,))
        want = otp_xor_ref(data, base, div)
        for kernel in (fused_crypt_mac, fused_crypt_mac_write):
            got, _ = kernel(data, base, div, bind, key)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("block_bytes", [32, 64, 128])
    def test_full_baes_path_vs_core(self, kkeys, block_bytes):
        rng = np.random.default_rng(0)
        n = 40
        pt = jnp.asarray(rng.integers(0, 256, block_bytes * n, dtype=np.uint8))
        cw = jnp.asarray(np.stack(
            [np.zeros(n, np.uint32),
             np.arange(n, dtype=np.uint32) * (block_bytes // 16),
             np.zeros(n, np.uint32), np.full(n, 3, np.uint32)], -1))
        bind = mac.Binding.make(np.arange(n) * 4, 3, 0, 0, np.arange(n))
        got, _ = secure_write_kernel(pt, bind, kkeys.round_keys, cw,
                                     kkeys.hash_key, block_bytes=block_bytes)
        want = baes.baes_encrypt(pt, kkeys.round_keys, cw,
                                 block_bytes=block_bytes, key=kkeys.key)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestFusedHashHalf:
    """The integ engine half of the fused kernels: NH over
    ciphertext ‖ binding, and the finalized optBlk/layer MACs."""

    @pytest.mark.parametrize("n,s", [(1, 2), (50, 4), (200, 32)])
    def test_nh_vs_oracle(self, kkeys, n, s):
        rng = np.random.default_rng(n)
        ct = _rand_u32(rng, (n, s * 4))
        bind = _rand_u32(rng, (n, 8))
        key = kkeys.hash_key[: s * 4 + 8]
        _, got = fused_crypt_mac(ct, _rand_u32(rng, (n, 4)),
                                 _rand_u32(rng, (s, 4)), bind, key)
        want = nh_hash_ref(jnp.concatenate([ct, bind], axis=-1), key)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def _blocks(self, seed, n):
        rng = np.random.default_rng(seed)
        blocks = jnp.asarray(rng.integers(0, 256, (n, 64), dtype=np.uint8))
        cw = _rand_u32(rng, (n, 4))
        return blocks, cw

    def test_block_macs_bitexact_vs_core(self, kkeys):
        blocks, cw = self._blocks(2, 33)
        bind = mac.Binding.make(np.arange(33) * 4, 7, 2, 1, np.arange(33))
        _, got = secure_read_kernel(blocks.reshape(-1), bind,
                                    kkeys.round_keys, cw, kkeys.hash_key,
                                    block_bytes=64)
        want = mac.block_macs(blocks, bind, hash_key_u32=kkeys.hash_key,
                              round_keys=kkeys.round_keys, engine="nh")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_layer_mac_bitexact(self, kkeys):
        blocks, cw = self._blocks(3, 16)
        bind = mac.Binding.make(np.arange(16) * 4, 9, 0, 0, np.arange(16))
        _, macs = secure_read_kernel(blocks.reshape(-1), bind,
                                     kkeys.round_keys, cw, kkeys.hash_key,
                                     block_bytes=64)
        got = mac.xor_aggregate(macs)
        want = mac.layer_mac(blocks, bind, hash_key_u32=kkeys.hash_key,
                             round_keys=kkeys.round_keys)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestFusedCryptMac:
    @pytest.mark.parametrize("n_blocks", [4, 40])
    def test_fused_read_path(self, kkeys, n_blocks):
        rng = np.random.default_rng(4)
        bb = 64
        pt = jnp.asarray(rng.integers(0, 256, bb * n_blocks, dtype=np.uint8))
        cw = jnp.asarray(np.stack(
            [np.zeros(n_blocks, np.uint32),
             np.arange(n_blocks, dtype=np.uint32) * 4,
             np.zeros(n_blocks, np.uint32),
             np.full(n_blocks, 9, np.uint32)], -1))
        ct = baes.baes_encrypt(pt, kkeys.round_keys, cw, block_bytes=bb,
                               key=kkeys.key)
        bind = mac.Binding.make(np.arange(n_blocks) * 4, 9, 1, 0,
                                np.arange(n_blocks))
        pt2, macs = secure_read_kernel(ct, bind, kkeys.round_keys, cw,
                                       kkeys.hash_key, block_bytes=bb)
        np.testing.assert_array_equal(np.asarray(pt2), np.asarray(pt))
        want = mac.block_macs(ct.reshape(n_blocks, bb), bind,
                              hash_key_u32=kkeys.hash_key,
                              round_keys=kkeys.round_keys, engine="nh")
        np.testing.assert_array_equal(np.asarray(macs), np.asarray(want))

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 60))
    def test_fused_roundtrip_property(self, n_blocks):
        kkeys = SecureKeys.derive(55)
        rng = np.random.default_rng(n_blocks)
        pt = jnp.asarray(rng.integers(0, 256, 64 * n_blocks, dtype=np.uint8))
        cw = jnp.asarray(np.stack(
            [np.zeros(n_blocks, np.uint32),
             np.arange(n_blocks, dtype=np.uint32) * 4,
             np.zeros(n_blocks, np.uint32),
             np.full(n_blocks, 1, np.uint32)], -1))
        ct = baes.baes_encrypt(pt, kkeys.round_keys, cw, block_bytes=64,
                               key=kkeys.key)
        bind = mac.Binding.make(np.arange(n_blocks) * 4, 1, 0, 0,
                                np.arange(n_blocks))
        pt2, _ = secure_read_kernel(ct, bind, kkeys.round_keys, cw,
                                    kkeys.hash_key, block_bytes=64)
        np.testing.assert_array_equal(np.asarray(pt2), np.asarray(pt))


class TestFusedCryptMacMixed:
    """Mixed-key fused kernel: per-block bank rows, one fused pass."""

    def _bank(self, k_rows, seed=0):
        keys = [SecureKeys.derive(100 + seed * 16 + i) for i in range(k_rows)]
        return (jnp.stack([k.key for k in keys]),
                jnp.stack([k.round_keys for k in keys]),
                jnp.stack([k.hash_key for k in keys]), keys)

    @pytest.mark.parametrize("n,s", [(4, 2), (33, 4)])
    def test_mixed_kernel_vs_ref(self, n, s):
        rng = np.random.default_rng(n * s)
        ct = jnp.asarray(rng.integers(0, 2**32, (n, s * 4), dtype=np.uint32))
        base = jnp.asarray(rng.integers(0, 2**32, (n, 4), dtype=np.uint32))
        div = jnp.asarray(rng.integers(0, 2**32, (n, s, 4), dtype=np.uint32))
        bind = jnp.asarray(rng.integers(0, 2**32, (n, 8), dtype=np.uint32))
        key = jnp.asarray(rng.integers(0, 2**32, (n, s * 4 + 8),
                                       dtype=np.uint32))
        got_pt, got_nh = fused_crypt_mac_mixed(ct, base, div, bind, key)
        want_pt, want_nh = fused_crypt_mac_mixed_ref(ct, base, div, bind, key)
        np.testing.assert_array_equal(np.asarray(got_pt), np.asarray(want_pt))
        np.testing.assert_array_equal(np.asarray(got_nh), np.asarray(want_nh))

    @pytest.mark.parametrize("n_blocks", [5, 37])
    def test_mixed_secure_read_vs_per_key_reference(self, n_blocks):
        """Each block decrypts + MACs under its OWN bank row, matching
        the single-key path run once per row."""
        bb = 64
        rng = np.random.default_rng(n_blocks)
        bank_key, bank_rk, bank_hash, keys = self._bank(3, seed=n_blocks)
        rows = jnp.asarray(rng.integers(0, 3, n_blocks), jnp.int32)
        cw = jnp.asarray(rng.integers(0, 2**32, (n_blocks, 4),
                                      dtype=np.uint32))
        bind = mac.Binding.make(np.arange(n_blocks) * 4,
                                np.full(n_blocks, 7), np.full(n_blocks, 1),
                                np.full(n_blocks, 2), np.arange(n_blocks))
        ct = jnp.asarray(rng.integers(0, 256, n_blocks * bb, dtype=np.uint8))
        pt, macs = secure_read_kernel_mixed(ct, bind, bank_rk, cw, bank_hash,
                                            rows, block_bytes=bb)
        for i in range(n_blocks):
            r = int(rows[i])
            blk = ct.reshape(n_blocks, bb)[i]
            want_pt = baes.baes_encrypt(blk, keys[r].round_keys, cw[i:i + 1],
                                        block_bytes=bb, key=keys[r].key)
            b1 = mac.Binding(*(f[i:i + 1] for f in bind))
            want_mac = mac.block_macs(blk[None], b1,
                                      hash_key_u32=keys[r].hash_key,
                                      round_keys=keys[r].round_keys,
                                      engine="nh")
            np.testing.assert_array_equal(
                np.asarray(pt).reshape(n_blocks, bb)[i], np.asarray(want_pt))
            np.testing.assert_array_equal(np.asarray(macs[i]),
                                          np.asarray(want_mac[0]))

    def test_uniform_rows_match_single_key_kernel(self):
        """A mixed dispatch whose rows all agree is bit-identical to the
        single-key fused kernel."""
        bb = 64
        n = 12
        rng = np.random.default_rng(9)
        bank_key, bank_rk, bank_hash, keys = self._bank(2)
        rows = jnp.ones((n,), jnp.int32)
        cw = jnp.asarray(rng.integers(0, 2**32, (n, 4), dtype=np.uint32))
        bind = mac.Binding.make(np.arange(n) * 4, 3, 0, 1, np.arange(n))
        ct = jnp.asarray(rng.integers(0, 256, n * bb, dtype=np.uint8))
        got_pt, got_macs = secure_read_kernel_mixed(
            ct, bind, bank_rk, cw, bank_hash, rows, block_bytes=bb)
        want_pt, want_macs = secure_read_kernel(
            ct, bind, keys[1].round_keys, cw, keys[1].hash_key,
            block_bytes=bb)
        np.testing.assert_array_equal(np.asarray(got_pt), np.asarray(want_pt))
        np.testing.assert_array_equal(np.asarray(got_macs),
                                      np.asarray(want_macs))

    def test_multi_keystream_vs_single(self):
        """Per-block schedules equal to one schedule reproduce the
        single-key keystream kernel exactly."""
        kkeys = SecureKeys.derive(3)
        rng = np.random.default_rng(2)
        cw = jnp.asarray(rng.integers(0, 2**32, (50, 4), dtype=np.uint32))
        rk_per = jnp.broadcast_to(kkeys.round_keys[None], (50, 11, 16))
        got = aes_k.aes_ctr_keystream_multi(cw, rk_per)
        want = aes_k.aes_ctr_keystream(cw, kkeys.round_keys)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestFusedCryptMacWrite:
    """The write-direction kernels: encrypt + NH of the FRESH
    ciphertext in one pass (the one-pass dirty-page reseal)."""

    def _bank(self, k_rows, seed=0):
        keys = [SecureKeys.derive(200 + seed * 16 + i) for i in range(k_rows)]
        return (jnp.stack([k.round_keys for k in keys]),
                jnp.stack([k.hash_key for k in keys]), keys)

    @pytest.mark.parametrize("n,s", [(1, 2), (33, 4)])
    def test_write_kernel_vs_ref(self, n, s):
        rng = np.random.default_rng(n * s + 1)
        pt = jnp.asarray(rng.integers(0, 2**32, (n, s * 4), dtype=np.uint32))
        base = jnp.asarray(rng.integers(0, 2**32, (n, 4), dtype=np.uint32))
        div = jnp.asarray(rng.integers(0, 2**32, (s, 4), dtype=np.uint32))
        bind = jnp.asarray(rng.integers(0, 2**32, (n, 8), dtype=np.uint32))
        key = jnp.asarray(rng.integers(0, 2**32, (s * 4 + 8,),
                                       dtype=np.uint32))
        got_ct, got_nh = fused_crypt_mac_write(pt, base, div, bind, key)
        want_ct, want_nh = fused_crypt_mac_write_ref(pt, base, div, bind, key)
        np.testing.assert_array_equal(np.asarray(got_ct), np.asarray(want_ct))
        np.testing.assert_array_equal(np.asarray(got_nh), np.asarray(want_nh))

    @pytest.mark.parametrize("n,s", [(4, 2), (33, 4)])
    def test_mixed_write_kernel_vs_ref(self, n, s):
        rng = np.random.default_rng(n * s + 2)
        pt = jnp.asarray(rng.integers(0, 2**32, (n, s * 4), dtype=np.uint32))
        base = jnp.asarray(rng.integers(0, 2**32, (n, 4), dtype=np.uint32))
        div = jnp.asarray(rng.integers(0, 2**32, (n, s, 4), dtype=np.uint32))
        bind = jnp.asarray(rng.integers(0, 2**32, (n, 8), dtype=np.uint32))
        key = jnp.asarray(rng.integers(0, 2**32, (n, s * 4 + 8),
                                       dtype=np.uint32))
        got_ct, got_nh = fused_crypt_mac_write_mixed(pt, base, div, bind, key)
        want_ct, want_nh = fused_crypt_mac_write_mixed_ref(pt, base, div,
                                                           bind, key)
        np.testing.assert_array_equal(np.asarray(got_ct), np.asarray(want_ct))
        np.testing.assert_array_equal(np.asarray(got_nh), np.asarray(want_nh))

    @pytest.mark.parametrize("n_blocks", [4, 40])
    def test_secure_write_matches_encrypt_then_mac(self, kkeys, n_blocks):
        """ct bit-identical to the core B-AES encrypt, MACs bit-identical
        to mac.block_macs over that ciphertext — the exact unfused
        write-path composition the kernel replaces."""
        bb = 64
        rng = np.random.default_rng(n_blocks + 5)
        pt = jnp.asarray(rng.integers(0, 256, bb * n_blocks, dtype=np.uint8))
        cw = jnp.asarray(rng.integers(0, 2**32, (n_blocks, 4),
                                      dtype=np.uint32))
        bind = mac.Binding.make(np.arange(n_blocks) * 4,
                                np.full(n_blocks, 9), np.full(n_blocks, 1),
                                np.full(n_blocks, 0), np.arange(n_blocks))
        ct, macs = secure_write_kernel(pt, bind, kkeys.round_keys, cw,
                                       kkeys.hash_key, block_bytes=bb)
        want_ct = baes.baes_encrypt(pt, kkeys.round_keys, cw, block_bytes=bb,
                                    key=kkeys.key)
        np.testing.assert_array_equal(np.asarray(ct), np.asarray(want_ct))
        want_macs = mac.block_macs(want_ct.reshape(n_blocks, bb), bind,
                                   hash_key_u32=kkeys.hash_key,
                                   round_keys=kkeys.round_keys, engine="nh")
        np.testing.assert_array_equal(np.asarray(macs), np.asarray(want_macs))

    def test_write_then_read_roundtrip(self, kkeys):
        """A fused write's output verifies and decrypts through the
        fused read with the SAME binding/counters — the dirty page a
        tick reseals is readable (and checkable) next tick."""
        bb, n = 64, 12
        rng = np.random.default_rng(8)
        pt = jnp.asarray(rng.integers(0, 256, bb * n, dtype=np.uint8))
        cw = jnp.asarray(rng.integers(0, 2**32, (n, 4), dtype=np.uint32))
        bind = mac.Binding.make(np.arange(n) * 4, np.full(n, 3),
                                np.full(n, 0), np.full(n, 1), np.arange(n))
        ct, w_macs = secure_write_kernel(pt, bind, kkeys.round_keys, cw,
                                         kkeys.hash_key, block_bytes=bb)
        pt2, r_macs = secure_read_kernel(ct, bind, kkeys.round_keys, cw,
                                         kkeys.hash_key, block_bytes=bb)
        np.testing.assert_array_equal(np.asarray(pt2), np.asarray(pt))
        np.testing.assert_array_equal(np.asarray(r_macs), np.asarray(w_macs))

    @pytest.mark.parametrize("n_blocks", [5, 37])
    def test_mixed_secure_write_vs_per_key_reference(self, n_blocks):
        """Each block encrypts + MACs under its OWN bank row, matching
        the single-key path run once per row — the vmapped per-page
        write reference the mixed kernel replaces."""
        bb = 64
        rng = np.random.default_rng(n_blocks + 3)
        bank_rk, bank_hash, keys = self._bank(3, seed=n_blocks)
        rows = jnp.asarray(rng.integers(0, 3, n_blocks), jnp.int32)
        cw = jnp.asarray(rng.integers(0, 2**32, (n_blocks, 4),
                                      dtype=np.uint32))
        bind = mac.Binding.make(np.arange(n_blocks) * 4,
                                np.full(n_blocks, 7), np.full(n_blocks, 1),
                                np.full(n_blocks, 2), np.arange(n_blocks))
        pt = jnp.asarray(rng.integers(0, 256, n_blocks * bb, dtype=np.uint8))
        ct, macs = secure_write_kernel_mixed(pt, bind, bank_rk, cw,
                                             bank_hash, rows, block_bytes=bb)
        for i in range(n_blocks):
            r = int(rows[i])
            blk = pt.reshape(n_blocks, bb)[i]
            want_ct = baes.baes_encrypt(blk, keys[r].round_keys, cw[i:i + 1],
                                        block_bytes=bb, key=keys[r].key)
            b1 = mac.Binding(*(f[i:i + 1] for f in bind))
            want_mac = mac.block_macs(want_ct[None], b1,
                                      hash_key_u32=keys[r].hash_key,
                                      round_keys=keys[r].round_keys,
                                      engine="nh")
            np.testing.assert_array_equal(
                np.asarray(ct).reshape(n_blocks, bb)[i], np.asarray(want_ct))
            np.testing.assert_array_equal(np.asarray(macs[i]),
                                          np.asarray(want_mac[0]))

    def test_uniform_rows_match_single_key_write_kernel(self):
        """A mixed write whose rows all agree is bit-identical to the
        single-key fused write kernel."""
        bb, n = 64, 12
        rng = np.random.default_rng(10)
        bank_rk, bank_hash, keys = self._bank(2)
        rows = jnp.ones((n,), jnp.int32)
        cw = jnp.asarray(rng.integers(0, 2**32, (n, 4), dtype=np.uint32))
        bind = mac.Binding.make(np.arange(n) * 4, np.full(n, 3),
                                np.full(n, 0), np.full(n, 1), np.arange(n))
        pt = jnp.asarray(rng.integers(0, 256, n * bb, dtype=np.uint8))
        got_ct, got_macs = secure_write_kernel_mixed(
            pt, bind, bank_rk, cw, bank_hash, rows, block_bytes=bb)
        want_ct, want_macs = secure_write_kernel(
            pt, bind, keys[1].round_keys, cw, keys[1].hash_key,
            block_bytes=bb)
        np.testing.assert_array_equal(np.asarray(got_ct), np.asarray(want_ct))
        np.testing.assert_array_equal(np.asarray(got_macs),
                                      np.asarray(want_macs))
