#!/usr/bin/env python3
"""Record the small device trace the reduction's test reads: one second
of the tiny closed-loop cell on the chip, traced.  Run on a TPU host:

    python3 chipbench/tests/record_trace.py chipbench/tests/data
"""

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(out_dir: str) -> int:
    import jax

    from chipbench import harness, trace
    from chipbench.tests.tiny import PEAKS, tiny_cell

    cell = tiny_cell("m4b.seda.long-batch", trace=True)
    cell.cell["check"]["logit_gap"] = 1.0
    harness.TRACE_S = 1.0
    out = harness.run_cell(cell, 5, 1.0, True, jax.devices(), PEAKS,
                           time.perf_counter())
    src = trace.find_xplane(harness.TRACE_DIR)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(src, os.path.join(out_dir, "tiny.xplane.pb"))
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
