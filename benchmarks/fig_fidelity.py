"""Fidelity figure: how honest is the model, measured on this machine.

Every other figure prices candidates with the analytic model. This one
closes the loop the paper's method actually demands (each GA individual
is compiled and *timed* on the verification machine) in three sections:

1. **Calibration** — measure the designed probe set (himeno + nasft,
   several grids, host and accelerator paths), fit per-destination
   rate/setup/transfer constants by least squares, and print the probe
   table with fit residuals: the table IS the honesty statement for the
   modeled numbers every other figure reports.

2. **Calibrated search** — the same paper-flow pipeline at
   ``fidelity="calibrated"``: the search runs under the fitted machine,
   and the report's fidelity section states the predicted-vs-measured
   ratio per destination for the winner.

3. **Measured search** (``--measured``, also in ``--smoke``) — the
   paper's real measurement loop: ``fidelity="measured"`` wall-clocks
   every unique candidate in this process, on the device JAX uses.
   Slowest and most honest; tiny budget by design (the run-fn cache key
   collapses equivalent genomes to one real measurement each).
   ``--smoke`` wall-clocks toy grids (``measured_scale="small"``).

  PYTHONPATH=src python -m benchmarks.fig_fidelity
  PYTHONPATH=src python -m benchmarks.fig_fidelity --smoke --measured
"""
from __future__ import annotations

import argparse
import os
import tempfile

from benchmarks.common import add_common_args
from repro.offload import Offloader, OffloadSpec
from repro.offload import calibrate


def _fidelity_rows(result) -> str:
    fid = result.stage("verify").payload.get("fidelity", {})
    if "rows" not in fid:
        return f"  (skipped: {fid.get('skipped', 'no fidelity section')})"
    return "\n".join(
        f"  {r['destination']:>4s} {r['placement']:16s} predicted "
        f"{r['predicted_s']:.4g}s measured {r['measured_s']:.4g}s "
        f"-> ratio {r['ratio']:.2f}x"
        if "ratio" in r else
        f"  {r['placement']:16s} skipped ({r['skipped']})"
        for r in fid["rows"]
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--measured", action="store_true",
                    help="also run the measured-fidelity search "
                         "(in-process wall clocks; slowest section)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="wall-clock repeats per probe/individual")
    add_common_args(ap)
    args = ap.parse_args(argv)
    tmp = tempfile.mkdtemp(prefix="fig-fidelity-")

    # 1) calibration: probes, fit, residuals
    cal = calibrate.run_calibration(base="quadro-p4000",
                                    repeats=args.repeats)
    calibrate.install(cal)
    print(f"== calibration: quadro-p4000 -> {cal.name} on {cal.host} ==")
    print("csv:app,dest,grid,steps,measured_s,fitted_s,rel_err")
    for p in cal.probes:
        grid = "x".join(map(str, p["grid"]))
        print(f"  {p['app']:7s} {p['dest']:5s} {grid:>10s} x{p['steps']}: "
              f"measured {p['measured_s']:.4g}s fitted {p['fitted_s']:.4g}s "
              f"({p['rel_err']:+.1%})")
        print(f"csv:{p['app']},{p['dest']},{grid},{p['steps']},"
              f"{p['measured_s']:.6g},{p['fitted_s']:.6g},"
              f"{p['rel_err']:.4f}")
    r = cal.residuals()
    base = dict(cpu_flops=3.262e9, accel_flops_kernels=4.988e11)
    print(f"residuals: max |{r['max_abs_rel']:.1%}| mean "
          f"|{r['mean_abs_rel']:.1%}| over {r['n']} probes; "
          f"pinned: {', '.join(cal.pinned)}")
    print("fitted vs frozen: cpu "
          f"{cal.constants['cpu_flops']:.3g} vs {base['cpu_flops']:.3g} "
          f"flop/s, accel {cal.constants['accel_flops_kernels']:.3g} vs "
          f"{base['accel_flops_kernels']:.3g} flop/s (this container's "
          "numpy/XLA-CPU paths, not the paper's P4000 — divergence "
          "expected and now *quantified*)")

    # 2) calibrated pipeline: search under the fitted machine (the
    # section-1 calibration is injected — probes are measured ONCE)
    scale = "small" if args.smoke else "model"
    budget = dict(population=6, generations=4) if args.smoke else {}
    for app in ("himeno",) if args.smoke else ("himeno", "nasft"):
        spec = OffloadSpec(program=app, fidelity="calibrated",
                           repeats=args.repeats, seed=args.seed,
                           workers=args.workers, cache=args.cache,
                           measured_scale=scale, **budget)
        res = Offloader(
            spec, artifact_path=os.path.join(tmp, f"{app}-cal.json"),
            calibration=cal,
        ).run()
        print(f"\n== calibrated search: {app} ==")
        print(f"  winner {res.best_time_s:.4g}s, speedup "
              f"{res.speedup:.1f}x over all-host (both under the "
              "calibrated machine)")
        print(_fidelity_rows(res))
        fid = res.stage("verify").payload["fidelity"]
        print("csv:calibrated," + app + ","
              + ",".join(f"{r['ratio']:.4f}" for r in fid["rows"]))

    # 3) measured pipeline: real in-process wall clocks
    if args.measured or args.smoke:
        spec = OffloadSpec(program="himeno", fidelity="measured",
                           repeats=args.repeats, measured_scale=scale,
                           population=4,
                           generations=2, seed=args.seed,
                           cache=os.path.join(tmp, "measured.jsonl"))
        res = Offloader(
            spec, artifact_path=os.path.join(tmp, "himeno-meas.json")
        ).run()
        p = res.stage("search").payload
        print("\n== measured search: himeno (in-process wall clocks) ==")
        print(f"  winner {res.best_time_s:.4g}s from "
              f"{p['evaluations']} real measurements "
              f"({p['cache_hits']} cache hits)")
        print(_fidelity_rows(res))
        fid = res.stage("verify").payload["fidelity"]
        print("csv:measured,himeno,"
              + ",".join(f"{r['ratio']:.4f}" for r in fid["rows"]))


if __name__ == "__main__":
    main()
