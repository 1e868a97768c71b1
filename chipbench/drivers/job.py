"""Traffic ``job``: cold measured offload jobs back to back, one caller.

Each request is one job on the system's normal path,
``Offloader(OffloadSpec(<program>, fidelity="measured", ...)).run()``: the
all-host baseline wall-clocked in analyze, the GA over placements with
each unique candidate wall-clocked on the host or the chip, the winner
re-measured and checked by PCAST in verify, and the report. A job starts
cold: its artifact, trace and fitness cache live in a directory of its
own under the run's scratch, removed when the job ends, and its GA seed
is drawn from ``--seed``. A job whose verify fails (its PCAST check among
it) raises, and counts as a failed request.

A job's answer is its winner's placed run at the measured scale, the
output that its PCAST check compared with the host path's
(``OffloadResult.answer``). Answers are kept and compared as the
``placed`` traffic keeps and compares a placed run's: this driver is
``placed.Driver`` with one job where that has one run. Each job leaves a
record in ``cell.jobs``, printed to standard error before the check.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile

import run

placed = run.load_module("drivers", "placed")

# the OffloadSpec fields the traffic sets; the program is the
# configuration's, the GA budget the spec's own
SPEC_KEYS = ("fidelity", "measured_scale", "repeats", "hw")


def under(program, fn):
    """The run fn ``fn`` with ``program.run`` in place of its own ``run``.

    A control (``chipbench/controls/``) has nothing but ``run``; this
    gives it the rest of a run fn (the genes it reads, its cache key,
    its tag), so that every run of the search, the verify re-measure and
    the PCAST pair is the control's."""

    class Under(type(fn)):
        def run(self, offloaded):
            return program.run(offloaded)

    return Under(**{f.name: getattr(fn, f.name)
                    for f in dataclasses.fields(fn)})


def record(res) -> dict:
    """What one job did: its seed, each stage's seconds, the search's
    measurements and cache hits, where the hot loop went, and PCAST."""
    from repro.offload import programs

    search = res.stage("search").payload
    pcast = res.stage("verify").payload["pcast"]
    hot = programs.RUNNABLE[res.spec.program][0]
    return {
        "seed": res.spec.seed,
        "stages_s": {name: rec.wall_s for name, rec in res.stages.items()},
        "baseline_s": res.baseline_time_s,
        "best_s": res.best_time_s,
        "measurements": search["evaluations"],
        "cache_hits": search["cache_hits"],
        "hot_loop": search["placement"][hot],
        "pcast_max_rel": pcast["max_rel"],
    }


class Job:
    """``run`` makes one cold job and returns its answer."""

    def __init__(self, cell, run_fn):
        self.cell = cell
        self.run_fn = run_fn  # None: the spec's own measured-scale row
        self.spec = {k: cell.traffic[k] for k in SPEC_KEYS}
        self.rng = cell.rng(2)

    def run(self, offloaded=True):
        from repro.offload import Offloader, OffloadSpec

        seed = int(self.rng.integers(0, 2 ** 31))
        home = tempfile.mkdtemp(prefix="job-", dir=self.cell.scratch)
        try:
            spec = OffloadSpec(program=self.cell.config["program"],
                               seed=seed, **self.spec,
                               cache=os.path.join(home, "fitness.jsonl"))
            res = Offloader(spec,
                            artifact_path=os.path.join(home,
                                                       "job.offload.json"),
                            run_fn=self.run_fn).run()
        finally:
            shutil.rmtree(home, ignore_errors=True)
        if res.answer is None:
            raise RuntimeError("the job's result carries no answer")
        self.cell.jobs.append(record(res))
        return res.answer


class Driver(placed.Driver):
    def __init__(self, cell):
        super().__init__(cell)
        # placed.Driver took the configuration's run fn; a program given
        # in its place (the control) runs inside each job instead
        self.own = placed.run_fn(cell.config)
        self.fn = Job(cell, under(cell.program, self.own)
                      if cell.program is not None else None)

    def setup(self) -> None:
        from repro.offload import programs
        from repro.offload.result import OffloadResult

        row = programs.measured_run_fn(self.cell.config["program"],
                                       self.cell.traffic["measured_scale"])
        if row != self.own:
            raise RuntimeError(
                f"the search measures {row}, the configuration runs "
                f"{self.own}: the cell would time another size")
        if "answer" not in {f.name for f in
                            dataclasses.fields(OffloadResult)}:
            raise RuntimeError("an offload result carries no answer here: "
                               "nothing of the job's own to compare")
        super().setup()
        self.cell.jobs.clear()  # the warm jobs are set-up, not requests

    def check(self):
        for job in self.cell.jobs:
            print(f"job {json.dumps(job)}", file=sys.stderr, flush=True)
        return super().check()
