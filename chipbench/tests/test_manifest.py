"""BENCHMARK.json keeps to its contract, and every file it names is
there."""

import json
import os
import re

import pytest

from chipbench import harness, traffic

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["chipbench"]
    assert MANIFEST["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_check_fits_the_time_limit():
    runs = 2 + 14 * 24
    assert (runs * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200
            <= 43200)


@pytest.mark.parametrize("entry", MANIFEST["configs"] + MANIFEST["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_use_allowed_characters(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in entry.get("reduced", []):
        assert NAME.match(key)
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]


def test_names_are_unique():
    for group in (MANIFEST["configs"], MANIFEST["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entries(m):
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "metrics",
                                       m["name"] + ".py"))
    assert callable(harness.reader(m["name"]))
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        e2e = {e["name"] for e in MANIFEST["end_to_end"]}
        assert m["moves"] in e2e and m["layer"]
        allowed = [e for e in MANIFEST["end_to_end"] if e["name"] == m["moves"]]
        assert set(m["workloads"]) <= set(allowed[0].get("workloads", cells))
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


@pytest.mark.parametrize("w", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve(w):
    assert w["chips"] in (1, 4)
    for trace in (False, True):
        cell = harness.load_cell(w["name"], trace)
        assert cell.metrics
    e2e = {m["name"] for m in harness.load_cell(w["name"], False).metrics}
    assert "setup_s" in e2e and len(e2e) >= 2
    c = harness.load_cell(w["name"], False)
    assert c.cell["config"] == w["config"]
    assert c.cell["check"]["logit_gap"] is not None
    assert traffic.shapes(c.cell)["prompt"]
    if c.cell["loop"] == "open":
        assert c.cell["rate"] > 0


@pytest.mark.parametrize("c", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_files_resolve(c):
    path = os.path.join(ROOT, c["file"])
    assert c["file"].startswith("chipbench/")
    with open(path) as f:
        doc = json.load(f)
    assert doc["name"] == c["name"] and doc["source"] == c["source"]
    assert doc["reduced"] == c["reduced"]
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads")
    assert not set(c["reduced"]) & set(widths)
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert c["name"] in used


def test_peaks_table():
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.Unavailable):
        harness.peaks_for("TPU v9 imaginary")
