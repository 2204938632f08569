"""Readings that set a cell's limits: the program's over many seeds, and the
control's (the reference one precision down, fp8, in the program's place),
all in one process on the chip.

    python chipbench/control.py --workload phi4-chat --seconds 25 --seeds 11 12 13

For each seed it drives a short run of the cell as ``run.py`` does (set-up,
window, the program's check against the float32 reference), then reads the
control on the same inputs (``control`` of the cell's loop, ``harness/<kind>.py``)
and judges both by the comparison that decides ``correct``. One JSON line per
seed; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None, help="also append the lines to this file")
    args = ap.parse_args()

    import run as runpy
    from harness import spec

    runpy.setup_jax()
    cell = spec.find_cell(args.workload)
    device = runpy.device_info(cell.chips)
    peaks = spec.peaks(device["kind"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        res, run = runpy.execute(cell, seed, args.seconds, False, peaks)
        ctl = runpy.loop(cell).control(run, seed)
        line = {"workload": args.workload, "seed": seed,
                "program": {k: v for k, (v, _) in res["checks"].items()},
                "program_correct": runpy.judge(res["checks"]),
                "control": {k: v for k, (v, _) in ctl.items()},
                "control_correct": runpy.judge(ctl),
                "limits": {k: lim for k, (_, lim) in res["checks"].items()},
                "counters": run.counters, "e2e": res["e2e"],
                "memory_peak_bytes": res["memory_peak_bytes"],
                "wall_s": time.perf_counter() - t0}
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")


if __name__ == "__main__":
    main()
