#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, taken on the
chip at a cell's own size, in one process.

    python3 chipbench/control.py --workload <cell> --seeds 12 \
        --control-seeds 3 --seconds 5 [--first-seed N] [--out DIR]

For each of ``--seeds`` seeds the program runs the cell: a short window
at the cell's own load, then the comparison with the plain reference.
For each of ``--control-seeds`` seeds the lower-precision control
(``chipbench/controls/<reference>.py``) takes the program's place. Each
run's numbers go to ``DIR/<cell>.jsonl``; the summary gives, for each
number, the largest reading of the program (the lower reading) and the
smallest of the control (the upper reading), beside the configuration's
limit. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default=os.path.join(run.ROOT, "chiprun_out",
                                                  "control"))
    args = ap.parse_args(argv)

    dev = run.device_info()
    if dev["platform"] != "tpu":
        run.say(f"JAX found {dev['platform']} ({dev['kind']}), not a TPU")
        return 2
    os.makedirs(args.out, exist_ok=True)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg_file = next(c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"])
    cfg = run.load_json(run.ROOT, cfg_file)
    control = run.load_module("controls", cfg["reference"]).Control(cfg)

    readings = {"program": {}, "control": {}}
    limits = {}
    path = os.path.join(args.out, f"{args.workload}.jsonl")
    with open(path, "w", encoding="utf-8") as fh:
        runs = [("program", None, k) for k in range(args.seeds)] + [
            ("control", control, k) for k in range(args.control_seeds)]
        for kind, program, k in runs:
            seed = args.first_seed + k
            line = run.run_cell(args.workload, seed, args.seconds, False,
                                bench=bench, program=program)
            rec = {"kind": kind, "seed": seed, "correct": line["correct"],
                   "attempted": line["attempted"], "failed": line["failed"],
                   "checks": line["checks"]}
            fh.write(json.dumps(rec) + "\n")
            fh.flush()
            print(json.dumps(rec), flush=True)
            for name, c in line["checks"].items():
                readings[kind].setdefault(name, []).append(c["value"])
                limits[name] = c["limit"]
    summary = {
        name: {"lower": max(readings["program"].get(name, [float("nan")])),
               "upper": min(readings["control"].get(name, [float("nan")])),
               "limit": limits[name]}
        for name in sorted(limits)}
    print(json.dumps({"workload": args.workload, "device": dev,
                      "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
