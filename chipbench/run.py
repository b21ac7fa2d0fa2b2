#!/usr/bin/env python3
"""The chip benchmark: run one cell once.

    python3 chipbench/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration and its
metrics are found by name (``BENCHMARK.json``, ``chipbench/workloads/``,
``chipbench/configs/``, ``chipbench/metrics/``).  Set-up makes the
weights and the traffic from ``--seed``, builds the serving engine and
runs every program the window will use; the window then drives the
engine for ``--seconds``; afterwards the tokens it served are compared
with a float32 reference.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of
the middle of the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: every number compared with its
limit); the last lines of standard error repeat the checks.  Without
a TPU, or with fewer chips than the cell needs, it exits 1 and prints
no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Import the benchmark as the package ``chipbench`` (its own directory
# on the path would let chipbench/trace.py shadow the standard library).
sys.path[0] = ROOT
# The TPU runtime's logs stay inside the checkout, not in a fixed /tmp
# directory that two checkouts on one machine would share.
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".chipbench",
                                                  "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    try:
        cell = harness.load_cell(args.workload, bool(args.trace))
        devices = harness.check_devices(cell.entry["chips"])
        peaks = harness.peaks_for(devices[0].device_kind)
    except (harness.Unavailable, OSError, KeyError) as e:
        print(f"[chipbench] cannot run: {e}", file=sys.stderr)
        return 1
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[chipbench] cannot run: no program under {src}",
              file=sys.stderr)
        return 1
    sys.path.insert(1, src)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices, peaks, T_START,
                           log=lambda m: print(m, flush=True))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
