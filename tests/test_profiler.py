"""Protection-overhead profiler: HLO attribution and CostProfile.

The acceptance bar from the observability issue: for every scheme x
decode bucket on the kernel-capable smoke spec, the attributed
``protection + model`` cost must account for >= 95% of the compiled
decode fn's total HLO bytes-accessed and flops, the ``seda`` overhead
ratio must be nonzero, and ``off`` must be ~0.
"""

import json

import jax
import pytest

from repro.configs import get_arch
from repro.core.secure_exec import SCHEMES
from repro.models import lm as lm_mod
from repro.models.layers import init_params
from repro.obs.profiler import (CostProfile, attribute_hlo,
                                classify_source, profile_decode)
from repro.serve.cluster import ClusterEngine
from repro.serve.engine import SecureServingEngine


@pytest.fixture(scope="module")
def smoke():
    arch = get_arch("minitron-4b")
    cfg = arch.make_smoke_config()
    params = init_params(lm_mod.lm_specs(cfg), jax.random.PRNGKey(0))
    return arch, cfg, params


def _engine(smoke, **kw):
    arch, cfg, params = smoke
    kw.setdefault("max_slots", 1)
    kw.setdefault("page_tokens", 4)
    kw.setdefault("pages_per_slot", 2)
    return SecureServingEngine(arch, cfg, params, **kw)


class TestClassifySource:
    def test_kernel_and_core_files_are_protection(self):
        assert classify_source("/x/repro/kernels/aes_ctr/kernel.py", 10) \
            == "protection"
        assert classify_source("/x/repro/core/mac.py", 1) == "protection"
        assert classify_source("/x/repro/core/aes.py", 99) == "protection"

    def test_model_files_are_model(self):
        assert classify_source("/x/repro/models/layers.py", 5) == "model"
        assert classify_source("/x/repro/serve/engine.py", 5) == "model"

    def test_kv_pages_split_by_function_ranges(self):
        import inspect

        from repro.serve import kv_pages
        crypt_line = inspect.getsourcelines(kv_pages._crypt)[1] + 1
        assert classify_source(kv_pages.__file__, crypt_line) \
            == "protection"
        # Module line 1 (docstring) is paging glue, not protection.
        assert classify_source(kv_pages.__file__, 1) == "model"


class TestAttributeHlo:
    # A miniature module exercising the cascade: own metadata, a
    # metadata-less called computation (caller->callee inheritance),
    # and a fused body voted by its one attributed op.
    HLO = """\
HloModule test

FileNames
1 "/x/repro/core/aes.py"
2 "/x/repro/models/layers.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=5 end_line=5 column=1 end_column=2}
2 {file_name_id=2 function_name_id=1 line=9 end_line=9 column=1 end_column=2}
3 {file_name_id=1 function_name_id=1 line=7 end_line=7 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=3 parent_frame_id=1}

%fused_computation (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(f32[8]{0} %param_0.1, f32[8]{0} %param_0.1), metadata={op_name="mul" stack_frame_id=1}
}

%helper (a.1: f32[8]) -> f32[8] {
  %a.1 = f32[8]{0} parameter(0)
  ROOT %n = f32[8]{0} negate(f32[8]{0} %a.1)
}

ENTRY %main (p0: f32[8], p1: f32[4,4]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[4,4]{1,0} parameter(1)
  %d = f32[4,4]{1,0} dot(f32[4,4]{1,0} %p1, f32[4,4]{1,0} %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="mm" stack_frame_id=2}
  %f = f32[8]{0} fusion(f32[8]{0} %p0), kind=kLoop, calls=%fused_computation
  ROOT %c = f32[8]{0} call(f32[8]{0} %f), to_apply=%helper, metadata={op_name="bc" stack_frame_id=3}
}
"""

    def test_buckets_and_coverage(self):
        attr = attribute_hlo(self.HLO)
        total = attr["total"]
        assert total["bytes"] > 0 and total["flops"] > 0
        # Everything in the miniature module is attributable.
        assert attr["other"]["bytes"] == 0
        assert attr["other"]["flops"] == 0
        # dot: 2 * 16 out * 4 contract = 128 model flops.
        assert attr["model"]["flops"] == 128
        # multiply in the fused body (8) + negate in %helper (8).
        assert attr["protection"]["flops"] == 16
        # by_file strips the path up to the package root.
        assert set(attr["by_file"]) == {"core/aes.py", "models/layers.py"}

    def test_metadata_less_callee_inherits_from_call_site(self):
        attr = attribute_hlo(self.HLO)
        # %helper's negate carries no metadata anywhere; it must be
        # attributed through the call site's to_apply= (protection).
        assert attr["by_file"]["core/aes.py"]["flops"] >= 16


class TestProfileDecode:
    @pytest.mark.parametrize("scheme", sorted(SCHEMES))
    def test_coverage_all_schemes_and_buckets(self, smoke, scheme):
        eng = _engine(smoke, scheme=scheme, use_kernel=(scheme != "off"))
        for bucket in (1, 2):
            p = profile_decode(eng, bucket=bucket)
            assert isinstance(p, CostProfile)
            cov = p.coverage
            assert cov["bytes"] >= 0.95, (scheme, bucket, cov)
            assert cov["flops"] >= 0.95, (scheme, bucket, cov)
            if scheme == "off":
                assert p.overhead_bytes_ratio < 0.01
                assert p.overhead_flops_ratio < 0.01
            else:
                assert p.overhead_bytes_ratio > 0.01
                assert p.overhead_flops_ratio > 0.01

    def test_profile_export_and_gauges(self, smoke):
        eng = _engine(smoke, scheme="seda")
        out = eng.profile()
        assert out["scheme"] == "seda"
        assert len(out["profiles"]) == 1
        prof = out["profiles"][0]
        json.dumps(out)                     # JSON-serializable
        for key in ("protection", "model", "other", "total", "coverage",
                    "overhead_bytes_ratio", "roofline", "xla_cost"):
            assert key in prof
        # Gauges sample the cache (no compile at snapshot time).
        gauges = eng.metrics.snapshot()["gauges"]
        assert gauges["protection_overhead_ratio"] == {
            "2": pytest.approx(prof["overhead_bytes_ratio"])}
        assert "2" in gauges["protection_overhead_flops_ratio"]
        assert "2" in gauges["roofline_utilization"]

    def test_cluster_rollup(self, smoke):
        cluster = ClusterEngine(*smoke, shards=2, max_slots=1,
                                page_tokens=4, pages_per_slot=2,
                                scheme="seda")
        out = cluster.profile()
        assert out["scheme"] == "seda"
        assert [s["shard"] for s in out["shards"]] == [0, 1]
        roll = out["rollup"]
        assert roll["total"]["bytes"] == pytest.approx(sum(
            s["profiles"][0]["total"]["bytes"] for s in out["shards"]))
        assert roll["overhead_bytes_ratio"] > 0.01
        json.dumps(out)

    def test_roofline_fields(self, smoke):
        eng = _engine(smoke, scheme="off")
        p = profile_decode(eng, bucket=2)
        roof = p.roofline()
        assert roof["bound"] in ("compute", "memory")
        assert roof["roofline_s"] == pytest.approx(
            max(roof["compute_s"], roof["memory_s"]))
        # No measured ticks yet -> utilization omitted.
        assert "utilization" not in roof
