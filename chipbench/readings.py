"""Read a cell's two readings on the chip: what the comparison reads for the
program (the lower reading) and for the control put in its place (the upper),
over several seeds in one process, at the cell's own size with a short window.

    python3 -m chipbench.readings --workload <cell> --seeds 1,2,3 --seconds 8 --out chiprun_out/x.jsonl

Not part of a benchmark run: the limits in `chipbench/limits/<cell>.json` were
set from what this prints (PERF.md gives the readings). Exits with 1 where a
seed's program came out not correct, or its control correct."""
from __future__ import annotations

import argparse
import json
import sys

from . import run


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args(argv)
    cell, config, traffic = run.cell_files(run.load_bench(), args.workload)
    sound = True
    with open(args.out, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = run.execute(cell, config, traffic, seed, args.seconds, False, control=not args.no_control)
            control = res.get("control")
            sound = sound and res["correct"] and not (control and control["correct"])
            line = {"workload": args.workload, "seed": seed, "correct": res["correct"], "read": res["read"],
                    "control": control, "faults": res.get("faults"),
                    "metrics": res["metrics"], "window": res["window"], "device": res["device"]}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
