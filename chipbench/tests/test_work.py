"""Work from shapes, against numbers worked out by hand."""

import json
import os

from chipbench import harness, work


def _config(name):
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_minitron_pages_and_params():
    c = _config("minitron-4b")
    assert work.kv_bytes_per_token(c) == 128 << 10
    assert work.page_bytes(c, 16) == 2 << 20
    # 32 x (attention 25.2M + plain FFN 56.6M) + untied head 786M; the
    # embedding (786M more) is read, not multiplied through.
    assert work.matmul_params(c) == 32 * (2 * 3072 * 3072 + 2 * 3072 * 1024
                                          + 2 * 3072 * 9216) + 3072 * 256000
    assert abs(2 * (work.matmul_params(c) + 3072 * 256000) / 1e9
               - 8.38) < 0.01


def test_granite_stage():
    c = _config("granite-34b.stage11")
    assert work.kv_bytes_per_token(c) == 11 * 512
    assert abs(work.matmul_params(c) / 1e9 - 4.47) < 0.01
    assert work.crossing_bytes(c, 16, 10) == 10 * (2 * 16 * 5632 + 32)


def test_flops():
    c = _config("minitron-4b")
    base = 2 * work.matmul_params(c)
    assert work.decode_flops(c, 0) == base
    assert work.decode_flops(c, 1000) - base == 4 * 32 * 24 * 128 * 1000
