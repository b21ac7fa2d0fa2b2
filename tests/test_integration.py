"""End-to-end integration: training loop, resume, serving, dry-run infra."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


class TestTrainLoop:
    def test_secure_training_with_resume(self, tmp_path):
        from repro.launch import train
        args = ["--arch", "smollm-135m", "--smoke", "--global-batch", "4",
                "--seq-len", "32", "--scheme", "seda", "--log-every", "100",
                "--ckpt-dir", str(tmp_path), "--ckpt-every", "4"]
        out1 = train.main(args + ["--steps", "6"])
        assert out1["steps"] == 6
        assert np.isfinite(out1["last_loss"])
        # Resume: the final checkpoint is at step 6, so only 2 steps run.
        out2 = train.main(args + ["--steps", "8"])
        assert out2["steps"] == 2  # resumed from step 6 -> steps 6..7
        assert np.isfinite(out2["last_loss"])

    def test_insecure_loop_loss_decreases(self):
        from repro.launch import train
        out = train.main(["--arch", "smollm-135m", "--smoke", "--steps",
                          "150", "--global-batch", "8", "--seq-len", "64",
                          "--lr", "5e-3", "--log-every", "1000"])
        assert out["last_loss"] < out["first_loss"] - 0.1, (
            f"loss did not decrease: {out['first_loss']} -> "
            f"{out['last_loss']}")


class TestServing:
    def test_prefill_decode_roundtrip(self):
        from repro.configs import get_arch
        from repro.models import lm as lm_mod
        from repro.models.layers import init_params
        from repro.serve.serve_step import (greedy_sample, make_decode_step,
                                            make_prefill_step)
        arch = get_arch("olmoe-1b-7b")  # exercises the MoE decode path
        cfg = arch.make_smoke_config()
        params = init_params(lm_mod.lm_specs(cfg), jax.random.PRNGKey(0))
        prompts = jnp.ones((2, 8), jnp.int32)
        prefill = make_prefill_step(arch, cfg, max_len=16)
        decode = make_decode_step(arch, cfg)
        logits, caches = prefill(params, {"tokens": prompts})
        tok = greedy_sample(logits)
        for _ in range(3):
            logits, caches = decode(params, tok, caches)
            tok = greedy_sample(logits)
            assert tok.shape == (2, 1)
            assert bool(jnp.isfinite(logits).all())


class TestDryRunInfra:
    """The dry-run machinery itself, on an 8-device subprocess (the full
    512-device sweep runs via `python -m repro.launch.dryrun --all`;
    its 64-cell results are recorded in EXPERIMENTS.md)."""

    @pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
    def test_smoke_cell_lowers_and_compiles(self, shape):
        code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch.cells import build_cell
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cell = build_cell("smollm-135m", "{shape}", mesh, smoke=True)
compiled = cell.lower(mesh).compile()
assert compiled.cost_analysis() is not None
print("CELL_OK")
"""
        env = dict(os.environ, PYTHONPATH=SRC)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=500)
        assert "CELL_OK" in out.stdout, out.stderr[-2000:]

    def test_hlo_analysis_loop_awareness(self):
        """The analyzer multiplies scan-body flops by trip counts."""
        import jax
        from repro.launch.analysis import analyze_hlo

        def f(x):
            def body(c, _):
                return c @ c, None
            out, _ = jax.lax.scan(body, x, None, length=7)
            return out

        hlo = jax.jit(f).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile().as_text()
        stats = analyze_hlo(hlo)
        # 7 iterations x 2*64^3 flops each.
        assert stats.dot_flops == pytest.approx(7 * 2 * 64 ** 3, rel=0.01)
        assert 7 in stats.trip_counts
