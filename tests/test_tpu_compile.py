"""The page-path Pallas kernels compile for a TPU v5e (Mosaic, no
interpret mode), at the shapes ``minitron-4b`` pages have under
``seda``.

The chip is described, not attached: ``jax.experimental.topologies``
gives v5e devices whose compiler is installed here, so these tests
catch what the chip's compiler refuses (layouts, gathers, VMEM) at no
chip time.  Nothing runs, so results are checked elsewhere
(``tests/test_kernels.py`` in interpret mode, ``chip_smoke.py`` on the
chip).  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU compiler library, and
every pytest-xdist worker imports this module, so only the worker that
runs these tests may describe the chip.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.kernels import common
from repro.kernels.aes_ctr import kernel as aes_k
from repro.kernels.fused_crypt_mac import kernel as fused_k
from repro.kernels.fused_crypt_mac import ops as fused_ops
from repro.models import lm as lm_mod
from repro.serve import kv_pages as kvp


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e 2x2 host, with JAX's persistent
    compilation cache off (its entries for a described chip could not
    be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def seda_page():
    """(blocks per page leaf, words per block) of minitron-4b under seda."""
    cfg = get_arch("minitron-4b").make_config()
    spec = kvp.build_page_spec(
        lm_mod.cache_specs(cfg, 1, 16), scheme="seda", page_tokens=16,
        n_pages=1, max_slots=1, max_len=16)
    return spec.leaves[0].n_blocks, spec.cfg.block_bytes // 4


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


U32, U8 = jnp.uint32, jnp.uint8
KERNELS = ["aes_ctr_keystream", "aes_ctr_keystream_multi", "fused_crypt_mac",
           "fused_crypt_mac_write", "fused_crypt_mac_mixed",
           "fused_crypt_mac_write_mixed"]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, seda_page, name):
    n, lanes = seda_page
    if name.startswith("aes"):
        rk = (((n, 11, 16), U8) if name.endswith("multi")
              else ((11, 16), U8))
        shapes = [((n, 4), U32), rk]
        kernel = getattr(aes_k, name)
    else:
        per_block = name.endswith("mixed")
        div = ((n, lanes // 4, 4), U32) if per_block else ((lanes // 4, 4),
                                                           U32)
        key = ((n, lanes + 8), U32) if per_block else ((lanes + 8,), U32)
        shapes = [((n, lanes), U32), ((n, 4), U32), div, ((n, 8), U32), key]
        kernel = getattr(fused_k, name)
    text = _compiled_text(lambda *a: kernel(*a, interpret=False), one_chip,
                          *shapes)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("write", [False, True], ids=["read", "write"])
def test_page_chunk_crossing_compiles_for_v5e(one_chip, seda_page, write):
    """The crossing the engine runs per chunk of pages: byte/value <->
    word-plane conversion, keystream, fused pass, MAC finalization."""
    blocks, lanes = seda_page
    pages = kvp._FUSED_CHUNK_BYTES // (blocks * lanes * 4)
    n = pages * blocks
    data = ((n * lanes * 2,), jnp.bfloat16) if write else ((n * lanes * 4,),
                                                          U8)

    def crossing(data, pa, vn, ctr, rk, hk):
        binding = (pa, vn, pa, vn, pa)
        return fused_ops.secure_crossing(
            data, binding, ctr, rk, hk, block_bytes=lanes * 4, write=write,
            out_dtype=U8 if write else jnp.bfloat16, interpret=False)

    text = _compiled_text(crossing, one_chip, data, ((n,), U32), ((n,), U32),
                          ((4, n), U32), ((11, 16), U8), ((lanes + 8,), U32))
    assert "tpu_custom_call" in text


def test_engine_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """A whole smoke-size decode step with the kernels on (as the
    engine runs it on a TPU) compiles for the chip."""
    from repro.models.layers import shape_structs
    from repro.serve.engine import SecureServingEngine

    monkeypatch.setattr(common, "default_interpret", lambda: False)
    arch = get_arch("minitron-4b")
    cfg = arch.make_smoke_config()
    params = shape_structs(lm_mod.lm_specs(cfg))
    eng = SecureServingEngine(arch, cfg, params, scheme="seda", max_slots=2,
                              page_tokens=4, pages_per_slot=4,
                              use_kernel=True)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        eng._decode_analysis_args(4))
    text = eng._decode_fn_for(4).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
