#!/usr/bin/env python3
"""Readings for setting a cell's limit, on the chip, in one process.

    python3 chipbench/calibrate.py --workload alexnet-fp32-b8 \
        --seeds 12 --seconds 3

Runs the cell once per seed (seeds past 2**31), each a short window at
the cell's own load, and also judges the control on the same frames, by
the same comparison and limit: the reference computed at three bfloat16
passes (XLA's ``high``), the step below the float32 ``highest`` that
the configurations state, put in the program's place. Prints one JSON
line per seed, then the largest program reading and the smallest
control reading. Exits 1 where a program run is not correct or a
control run is. The benchmark's own runs never run the control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from chipbench import bench, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    spec = bench.cell_spec(args.workload)
    try:
        device, peak = run.find_chip(spec["cell"]["chips"])
    except run.Refused as e:
        print(f"calibrate: {args.workload}: {e}", file=sys.stderr)
        return 1
    prog, ctrl, wrong = [], [], 0
    t = T0
    for i in range(args.seeds):
        seed = args.first_seed + i
        r = bench.run_cell(spec, seed, args.seconds, False, t, peak=peak,
                           device=device, control=True)
        t = time.perf_counter()
        c = r["info"].pop("control")
        prog.append(r["checks"]["max_rel_err"]["value"])
        ctrl.append(c["checks"]["max_rel_err"]["value"])
        wrong += (not r["correct"]) + c["correct"]
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "max_rel_err": prog[-1],
                          "control_correct": c["correct"],
                          "control_max_rel_err": ctrl[-1],
                          "metrics": r["metrics"], "info": r["info"]}),
              flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_max": max(prog), "control_min": min(ctrl),
                      "limit": r["checks"]["max_rel_err"]["limit"]}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
