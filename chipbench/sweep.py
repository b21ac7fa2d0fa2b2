#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: run the cell at each
rate in turn (one process) and print, per rate, the tails and the queue
left at the window's end.  The knee is the highest rate whose queue
does not grow; the cell file then fixes its rate at 0.8 of it.

    python3 chipbench/sweep.py --workload <cell> --seconds <s> \\
        --rates 1,2,3,4 --seed 7
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    from chipbench import harness
    cell = harness.load_cell(args.workload, False)
    devices = harness.check_devices(cell.entry["chips"])
    peaks = harness.peaks_for(devices[0].device_kind)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    for rate in (float(r) for r in args.rates.split(",")):
        cell.cell["rate"] = rate
        lines = []
        out = harness.run_cell(cell, args.seed, args.seconds, False, devices,
                               peaks, time.perf_counter(),
                               log=lambda m: (lines.append(m),
                                              print(m, flush=True)))
        queue = [m for m in lines if "queue at" in m]
        print(json.dumps({"rate": rate, "metrics": out["metrics"],
                          "attempted": out["attempted"],
                          "queue": queue[0] if queue else None}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
