"""Pallas TPU kernels for SeDA's perf-critical compute.

Each kernel package has kernel.py (pl.pallas_call + BlockSpec VMEM
tiling over word planes, see :mod:`repro.kernels.common`) and ref.py
(pure-jnp oracle); ``fused_crypt_mac/ops.py`` composes them into the
page crossing.  Tests run them in interpret mode on CPU against their
oracles, which chain back to FIPS-197 test vectors for everything
AES-derived; on a TPU Mosaic compiles them.

- aes_ctr         — AES-128-CTR keystream ("AES Engine"); SubBytes as
                    an in-register lane gather over the S-box halves
- fused_crypt_mac — B-AES diversify + pad XOR ("Crypt Engine") and the
                    NH hash of the optBlk MAC ("Integ Engine") in one
                    pass, for reads and writes, single or mixed keys
"""
