"""CLI for the staged offload pipeline.

  python -m repro.offload run --program himeno --mode binary
  python -m repro.offload run --program hetero --mode mixed \\
      --destinations cpu,gpu,fpga --warm-start --cache /tmp/hetero.jsonl
  python -m repro.offload run --program hetero --mode mixed --blocks
  python -m repro.offload run --program himeno --fidelity measured \\
      --population 4 --generations 2
  python -m repro.offload run --program himeno --smoke   # CI gate
  python -m repro.offload calibrate --base quadro-p4000 \\
      --out p4000.calib.json
  python -m repro.offload run --program hetero --mode mixed \\
      --calibration p4000.calib.json --hw quadro-p4000-calibrated
  python -m repro.offload resume --artifact himeno-binary.offload.json
  python -m repro.offload report --artifact himeno-binary.offload.json
  python -m repro.offload trace --artifact himeno-binary.offload.json
  python -m repro.offload sweep --smoke            # CI fast tier
  python -m repro.offload sweep --workers 4        # the full model zoo

``run`` executes every stage (calibrate -> analyze -> seed -> search ->
verify -> report) and saves the artifact after each one; a failed stage
(e.g. the PCAST result-difference check) exits non-zero with the failure
recorded in the artifact. ``resume`` continues a saved artifact, skipping
its completed stages — an interrupted *search* additionally resumes warm
through the spec's persistent fitness cache. ``report`` pretty-prints an
artifact (partial ones included) without running anything. ``trace``
loads the structured JSONL trace written next to the artifact
(docs/observability.md), verifies it against the digest embedded in the
artifact, and renders the span tree plus a per-stage budget-attribution
table. ``calibrate``
measures the probe set, fits the machine constants, and saves a
``.calib.json`` that ``--calibration`` installs in later invocations
(docs/fidelity.md). ``sweep`` runs the programs x machines x modes
matrix cell-by-cell (resumable), appends one trajectory point to
``BENCH_sweep.json``, renders the leaderboard, and flags regressions
against the previous point (docs/benchmarks.md).

Every verb documents its exit codes in its ``--help`` epilog; the table
itself lives in :data:`EXIT_CODES` (asserted in tests/test_docs.py).
Argparse usage errors exit 2 on every verb, as usual.
"""
from __future__ import annotations

import argparse
import sys
from typing import Dict, List, Optional, Tuple

from repro.offload.pipeline import Offloader, render_report
from repro.offload.result import STAGES, OffloadResult, StageFailure
from repro.offload.spec import (
    FIDELITIES,
    GAControls,
    METHODS,
    MIXED_SMOKE_BUDGET,
    MODES,
    OffloadSpec,
)
from repro.runtime.compile_cache import enable_compile_cache


# exit codes per verb, rendered into each subparser's --help epilog and
# asserted verbatim in tests/test_docs.py. 2 is argparse's own usage-
# error code on every verb; the sweep's regression flag deliberately
# takes a code of its own (3) so nightly CI can tell "a cell's pipeline
# broke" (1) from "everything ran but got slower" (3).
EXIT_CODES: Dict[str, Tuple[Tuple[int, str], ...]] = {
    "run": (
        (0, "every stage up to --until completed"),
        (1, "a stage failed (PCAST mismatch, verify drift, ...); the "
            "failure is recorded in the artifact"),
        (2, "usage error"),
    ),
    "resume": (
        (0, "every remaining stage up to --until completed"),
        (1, "a stage failed; the failure is recorded in the artifact"),
        (2, "usage error"),
    ),
    "report": (
        (0, "artifact loaded and printed (partial artifacts included)"),
        (2, "usage error"),
    ),
    "trace": (
        (0, "trace loaded, validated, digest-checked against the "
            "artifact, and rendered"),
        (1, "trace file missing or malformed, or its digest does not "
            "match the one embedded in the artifact"),
        (2, "usage error"),
    ),
    "calibrate": (
        (0, "probe set measured, constants fitted, .calib.json saved"),
        (2, "usage error (incl. an unknown --base registry)"),
    ),
    "sweep": (
        (0, "every cell ran (or resumed complete); no regression vs the "
            "previous trajectory point"),
        (1, "at least one cell's pipeline failed (its error is recorded "
            "in the trajectory point; remaining cells still ran)"),
        (2, "usage error"),
        (3, "all cells ok, but at least one regressed beyond --tolerance "
            "vs the previous trajectory point"),
    ),
    "serve": (
        (0, "action completed: spec submitted (or coalesced onto an "
            "existing job), queue drained with every job DONE/CANCELLED, "
            "or status/result/cancel served"),
        (1, "unknown job id, at least one job FAILED during the drain, "
            "or the drain died on an injected crash (--fault crash-*)"),
        (2, "usage error"),
    ),
}


def _epilog(verb: str) -> str:
    rows = "\n".join(f"  {code}  {what}" for code, what in EXIT_CODES[verb])
    return f"exit codes:\n{rows}"


def _add_verb(sub, name: str, help_: str) -> argparse.ArgumentParser:
    """A subparser whose --help epilog is the verb's exit-code table."""
    return sub.add_parser(
        name, help=help_, epilog=_epilog(name),
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )


def _add_spec_args(p: argparse.ArgumentParser) -> None:
    """The OffloadSpec-building flags, shared verbatim by ``run`` and
    ``serve submit`` (consumed by :func:`_spec_from_args`)."""
    p.add_argument("--program", required=True,
                   help="miniapp name (himeno/nasft/hetero) or "
                        "arch:<name>")
    p.add_argument("--mode", choices=list(MODES), default="binary")
    p.add_argument("--method", choices=sorted(METHODS),
                   default="proposed", help="binary-mode configuration")
    p.add_argument("--destinations", default="cpu,gpu,fpga",
                   help="mixed-mode destination subset (host first)")
    p.add_argument("--hw", default="quadro-p4000")
    p.add_argument("--fidelity", choices=list(FIDELITIES),
                   default="modeled",
                   help="how candidates are priced: the analytic model "
                        "(modeled), real in-process wall clocks on the "
                        "device JAX uses (measured), or the model under "
                        "constants fitted to this machine (calibrated)")
    p.add_argument("--repeats", type=int, default=1,
                   help="measurement repeats per individual/probe "
                        "(measured/calibrated fidelity)")
    p.add_argument("--population", type=int, default=None)
    p.add_argument("--generations", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timeout-s", type=float, default=None)
    p.add_argument("--warm-start", action="store_true",
                   help="mixed mode: seed the k-ary population with "
                        "single-destination bests")
    p.add_argument("--blocks", action="store_true",
                   help="mixed mode: match loop chains against the "
                        "kernel library and let the genome substitute "
                        "tuned implementations (docs/blocks.md)")
    p.add_argument("--workers", type=int, default=1)
    p.add_argument("--executor", choices=("thread", "process"),
                   default="thread",
                   help="measurement executor (measured fidelity "
                        "requires thread: it measures in this process)")
    p.add_argument("--cache", default=None, metavar="PATH",
                   help="persistent JSONL fitness cache (resume rides "
                        "on it; `serve` overrides it with the queue "
                        "directory's shared store)")
    p.add_argument("--rel-tol", type=float, default=None,
                   help="PCAST relative tolerance override")
    p.add_argument("--abs-tol", type=float, default=None,
                   help="PCAST absolute tolerance override")
    p.add_argument("--diversity", type=float, default=None,
                   help="fitness-sharing strength for GA selection "
                        "(default 0 = off: plain roulette selection)")
    p.add_argument("--stability-seeds", type=int, default=None,
                   metavar="K",
                   help="pass@k winner-stability seeds re-searched by "
                        "the report stage (default 3; <=1 disables)")
    p.add_argument("--stability-window", type=float, default=None,
                   help="relative window a seed's best must land in to "
                        "'pass' (default 0.02)")
    p.add_argument("--stability-gate", type=float, default=None,
                   help="fail the report stage when the winners' "
                        "relative spread exceeds this (default: no "
                        "gate)")
    p.add_argument("--rank-probe", action="store_true",
                   help="wall-clock the two winner projections so even "
                        "modeled/calibrated runs record modeled-vs-"
                        "measured rank correlation")
    p.add_argument("--steady-state", action="store_true",
                   help="asynchronous steady-state GA: breed offspring "
                        "per free worker lane instead of idling at the "
                        "generation barrier (docs/pipeline.md)")
    p.add_argument("--batch-eval", action="store_true",
                   help="mixed mode: price whole populations in one "
                        "vectorized pass (scalar evaluator stays the "
                        "verify oracle)")
    p.add_argument("--smoke", action="store_true",
                   help="CI-sized budget (small GA)")


def _default_artifact(spec: OffloadSpec) -> str:
    tag = spec.program.replace(":", "-")
    return f"{tag}-{spec.mode}.offload.json"


def _spec_from_args(args: argparse.Namespace) -> OffloadSpec:
    kw = dict(
        program=args.program,
        mode=args.mode,
        method=args.method,
        destinations=tuple(args.destinations.split(",")),
        hw=args.hw,
        fidelity=args.fidelity,
        repeats=args.repeats,
        population=args.population,
        generations=args.generations,
        seed=args.seed,
        timeout_s=args.timeout_s,
        warm_start=args.warm_start,
        blocks=args.blocks,
        workers=args.workers,
        executor=args.executor,
        cache=args.cache,
        rel_tol=args.rel_tol,
        abs_tol=args.abs_tol,
    )
    if args.smoke and args.mode == "mixed":
        # binary paper-rule budgets are already seconds-scale on the
        # analytic evaluator; only the mixed budget needs trimming
        kw["population"] = kw["population"] or MIXED_SMOKE_BUDGET[0]
        kw["generations"] = kw["generations"] or MIXED_SMOKE_BUDGET[1]
    ga_kw = {}
    if args.diversity is not None:
        ga_kw["diversity"] = args.diversity
    if args.stability_seeds is not None:
        ga_kw["stability_seeds"] = args.stability_seeds
    if args.stability_window is not None:
        ga_kw["stability_window"] = args.stability_window
    if args.stability_gate is not None:
        ga_kw["stability_gate"] = args.stability_gate
    if args.rank_probe:
        ga_kw["rank_probe"] = True
    if args.steady_state:
        ga_kw["steady_state"] = True
    if args.batch_eval:
        ga_kw["batch"] = True
    if ga_kw:
        kw["ga"] = GAControls(**ga_kw)
    return OffloadSpec(**kw)


def _progress(stats) -> None:
    print(f"  gen {stats.generation:2d}: best {stats.best_time_s:.4g}s "
          f"(hit-rate {stats.hit_rate:.0%})")


def _cmd_sweep(ap: argparse.ArgumentParser, args: argparse.Namespace) -> int:
    """The sweep verb: enumerate the matrix, run it resumably, append a
    trajectory point, print the leaderboard, exit by EXIT_CODES."""
    from repro.offload import sweep as sw

    out = args.out or sw.DEFAULT_TRAJECTORY
    tol = args.tolerance if args.tolerance is not None \
        else sw.DEFAULT_REL_TOLERANCE
    if args.report_only:
        try:
            traj = sw.Trajectory.load(out)
        except ValueError as e:
            ap.error(str(e))
        print(sw.render_leaderboard(traj, tol))
        if traj.last is None:
            return 0
        return 3 if sw.flag_regressions(traj.previous, traj.last, tol) \
            else 0

    if args.smoke:
        cells, skipped = sw.smoke_matrix()
    else:
        try:
            cells, skipped = sw.enumerate_matrix(
                args.programs.split(",") if args.programs else None,
                args.machines.split(",") if args.machines else None,
                tuple(args.modes.split(",")),
            )
        except ValueError as e:
            ap.error(str(e))
    if not cells:
        ap.error("matrix has no feasible cells (every combination was "
                 "skipped); widen --programs/--machines/--modes")
    sweep_dir = args.sweep_dir or (
        sw.DEFAULT_SMOKE_DIR if args.smoke else sw.DEFAULT_SWEEP_DIR
    )
    point = sw.run_sweep(
        cells, skipped, out_dir=sweep_dir, cache=args.cache,
        workers=args.workers, smoke=args.smoke, seed=args.seed,
        label=args.label, progress=None if args.quiet else print,
    )
    if args.no_append:
        traj = sw.Trajectory.load(out)
        prev = traj.last  # the point was not persisted; compare to last
        traj.points.append(point)  # in-memory, for the leaderboard only
    else:
        traj = sw.append_point(out, point)
        prev = traj.previous
    print(sw.render_leaderboard(traj, tol))
    if not args.no_append:
        print(f"trajectory: {out} ({len(traj.points)} points)")
    if point["totals"]["n_failed"]:
        return 1
    return 3 if sw.flag_regressions(prev, point, tol) else 0


def _cmd_serve(ap: argparse.ArgumentParser, args: argparse.Namespace) -> int:
    """The serve verb: drive an OffloadService over --dir. Exit codes
    per EXIT_CODES['serve']."""
    from repro.serve.admission import AdmissionPolicy
    from repro.serve.jobs import FAILED, JobError
    from repro.serve.offload_service import (
        FaultPlan,
        OffloadService,
        ServiceCrash,
    )

    policy_kw = {}
    for field in ("max_in_flight", "max_generations", "max_population",
                  "max_workers", "max_stability_seeds"):
        v = getattr(args, field, None)
        if v is not None:
            policy_kw[field] = v
    fault = None
    if getattr(args, "fault", None):
        try:
            fault = FaultPlan.parse(args.fault)
        except ValueError as e:
            ap.error(str(e))
    try:
        policy = AdmissionPolicy(**policy_kw)
    except ValueError as e:
        ap.error(str(e))
    svc = OffloadService(args.dir, policy=policy, fault=fault)

    if args.action == "submit":
        try:
            spec = _spec_from_args(args)
        except ValueError as e:
            ap.error(str(e))
        receipt = svc.submit(spec, force=args.force)
        if args.quiet:
            print(receipt.job_id)
        elif receipt.coalesced:
            print(f"coalesced onto existing job {receipt.job_id} "
                  f"(spec digest {receipt.digest})")
        else:
            line = f"queued {receipt.job_id} (spec digest {receipt.digest})"
            if receipt.clamped:
                clamps = ", ".join(
                    f"{k} {req}->{got}"
                    for k, (req, got) in sorted(receipt.clamped.items())
                )
                line += f"; admission clamped: {clamps}"
            print(line)
        return 0

    if args.action == "run":
        try:
            jobs = svc.run()
        except ServiceCrash as e:
            print(f"service crashed: {e}", file=sys.stderr)
            return 1
        failed = 0
        for j in jobs:
            extra = f"  !! {j.error}" if j.error else ""
            dup = svc.store.coalesced_count(j.id)
            dup_txt = f"  (+{dup} coalesced)" if dup else ""
            print(f"{j.id:24s} {j.state:9s} restarts={j.restarts}"
                  f"{dup_txt}{extra}")
            failed += j.state == FAILED
        return 1 if failed else 0

    try:
        if args.action == "status":
            if args.job:
                j = svc.status(args.job)
                print(f"{j.id}: {j.state} (seq {j.seq}, restarts "
                      f"{j.restarts}, digest {j.digest}, "
                      f"{svc.store.coalesced_count(j.id)} coalesced)")
                if j.clamped:
                    for k, (req, got) in sorted(j.clamped.items()):
                        print(f"  clamped {k}: {req} -> {got}")
                if j.error:
                    print(f"  error: {j.error}")
            else:
                for j in svc.jobs():
                    print(f"{j.id:24s} {j.state:9s} restarts={j.restarts}")
        elif args.action == "result":
            art = svc.result(args.job)
            print(art.summary())
            print(f"artifact: {svc.store.artifact_path(args.job)}")
            print(f"trace: {svc.store.trace_path(args.job)}")
        else:  # cancel
            svc.cancel(args.job)
            print(f"cancel requested: {args.job}")
    except JobError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.offload",
        description="staged offload pipeline: analyze -> seed -> search "
                    "-> verify -> report",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    run = _add_verb(sub, "run", "run the pipeline for a new spec")
    _add_spec_args(run)
    run.add_argument("--calibration", default=None, metavar="PATH",
                     help="install a saved .calib.json before building "
                          "the spec, so --hw can name its entry")
    run.add_argument("--artifact", default=None, metavar="PATH",
                     help="artifact path (default <program>-<mode>"
                          ".offload.json)")
    run.add_argument("--until", choices=STAGES, default="report")
    run.add_argument("--no-trace", action="store_true",
                     help="skip writing the JSONL trace next to the "
                          "artifact")
    run.add_argument("--quiet", action="store_true")

    res = _add_verb(sub, "resume", "continue a saved artifact")
    res.add_argument("--artifact", required=True, metavar="PATH")
    res.add_argument("--until", choices=STAGES, default="report")
    res.add_argument("--calibration", default=None, metavar="PATH",
                     help="install a saved .calib.json first (needed when "
                          "the artifact's spec names a calibrated machine "
                          "that is not embedded in the artifact itself)")
    res.add_argument("--no-trace", action="store_true",
                     help="skip continuing the JSONL trace next to the "
                          "artifact")
    res.add_argument("--quiet", action="store_true")

    rep = _add_verb(sub, "report", "pretty-print a saved artifact")
    rep.add_argument("--artifact", required=True, metavar="PATH")

    trc = _add_verb(
        sub, "trace",
        "validate and render an artifact's JSONL trace: span tree, "
        "per-generation telemetry, budget attribution",
    )
    trc.add_argument("--artifact", required=True, metavar="PATH")
    trc.add_argument("--trace", default=None, metavar="PATH",
                     help="trace file (default: the artifact path with "
                          ".json swapped for .trace.jsonl)")

    cal = _add_verb(
        sub, "calibrate",
        "measure the probe set, fit machine constants, save a "
        ".calib.json entry usable via --calibration/--hw",
    )
    cal.add_argument("--base", default="quadro-p4000",
                     help="base machine registry to calibrate")
    cal.add_argument("--name", default=None,
                     help="entry name (default <base>-calibrated)")
    cal.add_argument("--repeats", type=int, default=3,
                     help="wall-clock repeats per probe (min kept; >1 "
                          "excludes one-time jit compiles)")
    cal.add_argument("--out", default=None, metavar="PATH",
                     help="where to save (default <name>.calib.json)")
    cal.add_argument("--kernels", action="store_true",
                     help="also time the block-substitution kernel "
                          "library against its oracles and fit "
                          "per-kernel gains (docs/blocks.md)")

    swp = _add_verb(
        sub, "sweep",
        "run the model-zoo matrix (programs x machines x modes), append "
        "a BENCH trajectory point, render the leaderboard, flag "
        "regressions",
    )
    swp.add_argument("--programs", default=None,
                     help="comma-separated programs (default: every "
                          "miniapp + every arch:<name>)")
    swp.add_argument("--machines", default=None,
                     help="comma-separated machine registries (default: "
                          "all)")
    swp.add_argument("--modes", default=",".join(MODES),
                     help="comma-separated modes (default: binary,mixed)")
    swp.add_argument("--smoke", action="store_true",
                     help="the fixed 3-cell CI matrix at smoke budgets "
                          "(overrides --programs/--machines/--modes)")
    swp.add_argument("--dir", dest="sweep_dir", default=None, metavar="DIR",
                     help="per-cell artifact + fitness-cache directory "
                          "(default .sweep, .sweep-smoke under --smoke); "
                          "re-running against the same directory resumes: "
                          "complete cells are skipped outright")
    swp.add_argument("--cache", default=None, metavar="PATH",
                     help="shared JSONL fitness cache (default "
                          "<dir>/fitness.jsonl)")
    swp.add_argument("--out", default=None, metavar="PATH",
                     help="trajectory file to append to (default "
                          "BENCH_sweep.json)")
    swp.add_argument("--label", default=None,
                     help="free-form label recorded in the point")
    swp.add_argument("--tolerance", type=float, default=None,
                     help="relative regression tolerance vs the previous "
                          "point (default 0.05; strictly-beyond flags)")
    swp.add_argument("--workers", type=int, default=1)
    swp.add_argument("--seed", type=int, default=0)
    swp.add_argument("--no-append", action="store_true",
                     help="run + report but leave the trajectory file "
                          "untouched (regressions compare against its "
                          "LAST point instead of the previous one)")
    swp.add_argument("--report-only", action="store_true",
                     help="no searches: render the leaderboard of the "
                          "saved trajectory's last point (vs its "
                          "previous) and exit by the regression verdict")
    swp.add_argument("--quiet", action="store_true",
                     help="suppress per-cell progress lines")

    srv = _add_verb(
        sub, "serve",
        "offload-as-a-service against a filesystem queue directory: "
        "submit specs, drain the queue concurrently over one shared "
        "fitness cache, query/cancel jobs (docs/serving.md)",
    )
    srv_sub = srv.add_subparsers(dest="action", required=True)

    def _srv_action(name: str, help_: str) -> argparse.ArgumentParser:
        p = srv_sub.add_parser(name, help=help_)
        p.add_argument("--dir", required=True, metavar="DIR",
                       help="the service queue directory (jobs, traces "
                            "and the shared fitness cache live under it)")
        return p

    ssub = _srv_action("submit", "admit one spec into the queue "
                                 "(duplicates coalesce onto the "
                                 "existing job)")
    _add_spec_args(ssub)
    ssub.add_argument("--force", action="store_true",
                      help="run a fresh job even if an identical spec "
                           "is already queued/running/done (it still "
                           "shares the fitness cache)")
    ssub.add_argument("--max-generations", type=int, default=None,
                      help="admission clamp on the GA generation budget")
    ssub.add_argument("--max-population", type=int, default=None,
                      help="admission clamp on the GA population")
    ssub.add_argument("--max-workers", type=int, default=None,
                      help="admission clamp on per-job eval workers")
    ssub.add_argument("--max-stability-seeds", type=int, default=None,
                      help="admission clamp on report-stage stability "
                           "re-searches")
    ssub.add_argument("--quiet", action="store_true",
                      help="print only the job id (shell capture)")

    srun = _srv_action("run", "recover + drain the queue: resume every "
                              "non-terminal job, run QUEUED jobs "
                              "concurrently")
    srun.add_argument("--max-in-flight", type=int, default=None,
                      help="concurrent jobs bound (default 2)")
    srun.add_argument("--fault", default=None, metavar="SPEC",
                      help="fault-injection harness: <kind>:<arg>"
                           "[@<job-match>], kinds raise-in-stage, "
                           "raise-in-search, crash-after-stage, "
                           "crash-in-search, kill-after-stage, "
                           "kill-in-search (docs/serving.md)")

    sstat = _srv_action("status", "job table, or one job's record")
    sstat.add_argument("--job", default=None, metavar="ID")

    sres = _srv_action("result", "print a job's artifact summary + "
                                 "artifact/trace paths")
    sres.add_argument("--job", required=True, metavar="ID")

    scan = _srv_action("cancel", "request cancellation (honored before "
                                 "the job's next pipeline stage)")
    scan.add_argument("--job", required=True, metavar="ID")

    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.cmd == "sweep":
        return _cmd_sweep(ap, args)

    if args.cmd == "serve":
        return _cmd_serve(ap, args)

    if args.cmd == "calibrate":
        from repro.offload import calibrate as cal_mod

        name = args.name or f"{args.base}-calibrated"
        try:
            cal_res = cal_mod.run_calibration(
                base=args.base, repeats=args.repeats, name=name,
                kernels=args.kernels,
            )
        except ValueError as e:
            ap.error(str(e))
        out = args.out or f"{name}.calib.json"
        cal_res.save(out)
        r = cal_res.residuals()
        print(f"calibrated {cal_res.base} -> {cal_res.name} "
              f"(hw {cal_res.hw_name}) on {cal_res.host}")
        for p in cal_res.probes:
            print(f"  {p['app']:7s} {p['dest']:5s} "
                  f"{'x'.join(map(str, p['grid'])):>10s} x{p['steps']}: "
                  f"measured {p['measured_s']:.4g}s fitted "
                  f"{p['fitted_s']:.4g}s ({p['rel_err']:+.1%})")
        print(f"residuals: max |{r['max_abs_rel']:.1%}| mean "
              f"|{r['mean_abs_rel']:.1%}| over {r['n']} probes; "
              f"pinned: {', '.join(cal_res.pinned)}")
        for k, g in sorted(cal_res.kernel_constants.items()):
            print(f"  kernel {k}: gain {g:.3g}x vs oracle")
        print(f"saved: {out}")
        print(f"use it:  python -m repro.offload run ... "
              f"--calibration {out} --hw {cal_res.name}")
        return 0

    if getattr(args, "calibration", None):
        from repro.offload import calibrate as cal_mod

        cal_mod.load_and_install(args.calibration)

    if args.cmd == "report":
        art = OffloadResult.load(args.artifact)
        print(art.summary())
        print()
        if art.completed("report"):
            print(art.stage("report").payload["text"])
        else:
            print(render_report(art))
        return 0

    if args.cmd == "trace":
        from repro.offload import trace as trace_mod

        art = OffloadResult.load(args.artifact)
        path = args.trace or trace_mod.default_trace_path(args.artifact)
        try:
            tr = trace_mod.load_trace(path)
        except (trace_mod.TraceError, OSError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        print(trace_mod.render_trace(tr, artifact=art))
        if art.trace is not None and art.trace.get("digest") != tr.digest:
            print("error: trace digest does not match the artifact's "
                  "embedded digest (stale or foreign trace file)",
                  file=sys.stderr)
            return 1
        return 0

    on_gen = None if args.quiet else _progress
    if args.cmd == "run":
        try:
            spec = _spec_from_args(args)
        except ValueError as e:
            ap.error(str(e))
        off = Offloader(spec, artifact_path=args.artifact
                        or _default_artifact(spec), on_generation=on_gen,
                        trace=not args.no_trace)
    else:  # resume
        off = Offloader.resume(args.artifact, on_generation=on_gen,
                               trace=not args.no_trace)

    try:
        result = off.run(until=args.until)
    except StageFailure as e:
        print(f"error: {e}", file=sys.stderr)
        print(f"artifact: {off.result.path}", file=sys.stderr)
        return 1
    if result.completed("report"):
        print(result.stage("report").payload["text"])
    else:
        print(render_report(result))
    print(f"artifact: {result.path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
