#!/usr/bin/env python3
"""The control of a cell's check, on the chip: runs the cell once per
seed in one process with the float8 control in the program's place for
the check, and prints, per seed, the control's ``correct`` (which has to
be false) and widest logit gap beside the program's own gap on the same
window.

    python3 chipbench/control.py --workload <cell> --seconds <s> \\
        --seeds 101,102,103

The program's gaps over a dozen seeds or more are the lower reading of
the cell's ``logit_gap`` limit, the control's smallest gap its upper
reading (``PERF.md`` gives both).  The benchmark's runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from chipbench import harness
    cell = harness.load_cell(args.workload, False)
    devices = harness.check_devices(cell.entry["chips"])
    peaks = harness.peaks_for(devices[0].device_kind)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False, devices,
                               peaks, time.perf_counter(),
                               log=lambda m: print(m, flush=True),
                               control=True)
        rows.append({"seed": seed, "control_correct": out["correct"],
                     "control_gap": out["checks"]["logit_gap"]["value"],
                     "limit": out["checks"]["logit_gap"]["limit"],
                     "program_gap": out["program_logit_gap"],
                     "metrics": out["metrics"]})
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
