"""Whole runs of the harness on the CPU at a tiny size: the window, the
check and the result line; the check failing on each fault the cells
can have; the float8 control, put in the program's place, failing it;
and no result without a TPU.

These drive everything a chip run drives except the look for a chip.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.tests.tiny import PEAKS, tiny_cell

ROOT = harness.ROOT
SEED = 2**31 + 77
# The tiny model is bfloat16 like the cells.  Over 16 seeds its served
# tokens lay at most 0.0143 below the reference's best, and the float8
# control's at least 0.0716 (m4b.seda.long-batch and m4b.seda.short-
# open, seeds 11-13 and 21-32): the limit lies between, as a cell's does.
TINY_LIMIT = 0.035


def _run(name, seconds=2.0, control=False, seed=SEED):
    cell = tiny_cell(name)
    cell.cell["check"]["logit_gap"] = TINY_LIMIT
    return harness.run_cell(cell, seed, seconds, False, jax.devices(),
                            PEAKS, time.perf_counter(), log=lambda m: None,
                            control=control)


@pytest.mark.parametrize("name", ["m4b.seda.long-batch",
                                  "m4b.seda.short-open",
                                  "m4b.off.long-batch"])
def test_tiny_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["window_compiles"]["value"] == 0
    assert "setup_s" in out["metrics"]
    assert ("decode_tok_s" in out["metrics"]) != ("ttft_p85_s" in
                                                 out["metrics"])
    json.dumps(out)


def _alter_tokens(monkeypatch):
    from repro.serve import engine
    base = engine.greedy_sample
    monkeypatch.setattr(engine, "greedy_sample",
                        lambda logits: (base(logits) + 1) % logits.shape[-1])


def _state_unchanged(monkeypatch):
    from repro.serve import kv_pages
    monkeypatch.setattr(kv_pages.PageIO, "write_dirty",
                        lambda self, pool, *a, **kw: pool)


def _half_batch(monkeypatch):
    from repro.serve import kv_pages
    base = kv_pages.PageIO.write_dirty

    def half(self, pool, table, leaves, lengths, active, *a, **kw):
        keep = jnp.arange(active.shape[0]) < active.shape[0] // 2
        return base(self, pool, table, leaves, lengths, active & keep,
                    *a, **kw)
    monkeypatch.setattr(kv_pages.PageIO, "write_dirty", half)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _half_batch],
                         ids=["token-altered", "state-unchanged",
                              "half-batch-left-out"])
def test_fault_fails_the_check(monkeypatch, fault):
    fault(monkeypatch)
    out = _run("m4b.seda.long-batch")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_fails_the_check(seed):
    """The float8 control in the program's place reads ``correct``
    false, where the program's own tokens of the same window pass."""
    out = _run("m4b.seda.long-batch", control=True, seed=seed)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["logit_gap"]["value"] > TINY_LIMIT
    assert out["program_logit_gap"] <= TINY_LIMIT, out


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "m4b.seda.long-batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_bare_benchmark_directory_has_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "m4b.seda.long-batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
