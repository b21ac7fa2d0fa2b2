"""A whole cell at a size a CPU test run can hold: the full-width
configuration and cell files, cut to a two-layer model, three slots and
short requests (used by the tests and by ``record_trace.py``)."""

import copy
import json
import os

from chipbench import harness

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell(name: str, trace: bool = False) -> harness.Cell:
    full = harness.load_cell(name, trace)
    c = dict(full.config, num_hidden_layers=2, hidden_size=128,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             intermediate_size=256, vocab_size=512)
    cell = copy.deepcopy(full.cell)
    cell.update(slots=3, backlog=6)
    if cell["loop"] == "closed":
        cell["pages_per_slot"] //= 8
    for key in ("median", "min", "max"):
        cell["prompt"][key] //= 8
    cell["prompt"]["round"] = max(1, cell["prompt"]["round"] // 8)
    cell["output"].update(min=max(1, cell["output"]["min"] // 8),
                          max=cell["output"]["max"] // 8)
    if cell["loop"] == "open":
        cell["rate"] = 4.0
    return harness.Cell(name, full.entry, cell, c, full.metrics)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)
