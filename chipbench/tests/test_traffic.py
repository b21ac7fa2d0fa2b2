"""The generator: the same seed gives the same requests; seeds differ
in order only, never in the multiset of sizes."""

import json
import os

import pytest

from chipbench import harness, traffic

CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(
    harness.HERE, "workloads")))
SEEDS = (7, 2**31 + 3, 2**33 + 11)


def _cell(name):
    with open(os.path.join(harness.HERE, "workloads", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CELLS)
def test_deterministic_per_seed(name):
    cell = _cell(name)
    a = traffic.make_requests(cell, SEEDS[1], 30, 1000)
    b = traffic.make_requests(cell, SEEDS[1], 30, 1000)
    assert [(r.prompt, r.max_new, r.due) for r in a] == \
        [(r.prompt, r.max_new, r.due) for r in b]


@pytest.mark.parametrize("name", CELLS)
def test_seeds_differ_in_order_not_in_sizes(name):
    cell = _cell(name)
    runs = [traffic.make_requests(cell, s, 30, 1000) for s in SEEDS]
    assert len({tuple(tuple(r.prompt) for r in rs) for rs in runs}) == 3
    d = cell["deck"]
    for rs in runs:
        full = len(rs) // d * d
        assert sorted(len(r.prompt) for r in rs[:full]) == sorted(
            traffic.deck(cell["prompt"], d) * (full // d))


@pytest.mark.parametrize("name", CELLS)
def test_lengths_fit_the_cell(name):
    cell = _cell(name)
    cap = cell["page_tokens"] * cell["pages_per_slot"]
    for r in traffic.make_requests(cell, 5, 30, 1000):
        assert cell["prompt"]["min"] <= len(r.prompt) <= cell["prompt"]["max"]
        assert len(r.prompt) % cell["prompt"]["round"] == 0
        assert 1 <= r.max_new <= cell["output"]["max"]
        assert len(r.prompt) + r.max_new <= cap
        assert all(1 <= t < 1000 for t in r.prompt)


def test_open_loop_arrivals():
    cell = _cell("m4b.seda.short-open")
    reqs = traffic.make_requests(cell, 3, 30, 1000)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    n = cell["deck"]
    block = dues[n - 1]
    assert block == pytest.approx(sum(traffic.exp_gaps(cell["rate"], n)))
    assert sum(1 for t in dues if t < 30) >= 0.8 * 30 * cell["rate"]


def test_closed_loop_start_is_staggered():
    cell = _cell("m4b.seda.long-batch")
    reqs = traffic.make_requests(cell, 3, 30, 1000)
    first = sorted(r.max_new for r in reqs[:cell["slots"]])
    assert first[0] < cell["output"]["min"] <= first[-1]
