#!/usr/bin/env python3
"""Record the raw device trace of a cell's first requests, for reading by
hand and as a test fixture of the reduction.

    python3 chipbench/record_trace.py --workload <cell> --requests 1 \
        --out DIR

Runs the cell as ``run.py --trace 1`` does, with the profiler on for the
first ``--requests`` requests and a short window, and writes
``DIR/<cell>.xplane.pb``, ``DIR/<cell>.events.json.gz`` (every event:
plane, line, name, start and duration in nanoseconds) and the result
line. The benchmark's own runs never call it.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

import run
import tracing


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--requests", type=int, default=1)
    ap.add_argument("--seed", type=int, default=3_100_000_000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    raw = os.path.join(args.out, f"{args.workload}.xplane.pb")
    tracing.Profiler.keep = raw
    line = run.run_cell(args.workload, args.seed, 0.0, True,
                        traffic={"trace_requests": args.requests})
    events = [[e.plane, e.line, e.name, e.start_ns, e.dur_ns]
              for e in tracing.read_xplane(raw)]
    with gzip.open(os.path.join(args.out, f"{args.workload}.events.json.gz"),
                   "wt", encoding="utf-8") as fh:
        json.dump(events, fh)
    planes = sorted({(e[0], e[1]) for e in events})
    run.say("planes and lines: " + json.dumps(planes))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
