"""Search-quality figure: is the GA's answer trustworthy, and does
fitness sharing buy anything (docs/observability.md)?

Two sections, both on the modeled pipeline (cheap, deterministic):

1. **Stability + rank fidelity** — the full pipeline per program with
   the report-stage quality metrics on: pass@k winner stability across
   GA seeds (window, spread, distinct winners) and, where a measured
   reference exists, the modeled-vs-measured rank correlation
   (spearman / kendall, via ``ga.rank_probe``).

2. **Diversity ablation** — the same searches with fitness sharing
   (``ga.diversity``) off vs on: winner time, stability spread, and
   final-population allele entropy side by side. Diversity trades a
   little convergence speed for selection pressure spread over distinct
   genomes; this table is where that trade is visible.

  PYTHONPATH=src python -m benchmarks.fig_quality
  PYTHONPATH=src python -m benchmarks.fig_quality --smoke --diversity 1.0
"""
from __future__ import annotations

import argparse
from typing import List, Optional

from benchmarks.common import add_common_args
from repro.offload import GAControls, Offloader, OffloadSpec
from repro.offload.quality import allele_entropy


def _spec(program: str, args, *, diversity: float = 0.0,
          rank_probe: bool = False) -> OffloadSpec:
    kw = dict(
        program=program,
        mode="binary",
        seed=args.seed,
        workers=args.workers,
        cache=args.cache,
        ga=GAControls(diversity=diversity, stability_seeds=args.k,
                      stability_window=args.window,
                      rank_probe=rank_probe),
    )
    if args.smoke:  # toy budget, and rank probes clock toy grids
        kw.update(population=6, generations=4, measured_scale="small")
    return OffloadSpec(**kw)


def _quality(spec: OffloadSpec):
    res = Offloader(spec).run()
    rep = res.stage("report").payload["quality"]
    search = res.stage("search").payload
    pop = [tuple(g) for g in search["final_population"]]
    alleles = max(2, len(search["ga"].get("allele_names", ())) or 2)
    return res, rep, allele_entropy(pop, alleles)


def _stability_line(st: dict) -> str:
    if "skipped" in st:
        return f"stability skipped ({st['skipped']})"
    return (f"pass@{st['k']} {st['pass_at_k']:.0%} "
            f"(window {st['window']:.1%}, spread +{st['rel_spread']:.1%}, "
            f"{st['distinct_winners']} distinct winner(s))")


def _rank_line(rk: dict) -> str:
    if "skipped" in rk:
        return f"rank skipped ({rk['skipped']})"
    if rk.get("spearman") is None:
        return f"rank undefined ({rk.get('note', 'constant side')})"
    kend = "n/a" if rk.get("kendall") is None else f"{rk['kendall']:+.2f}"
    return (f"spearman {rk['spearman']:+.2f} / kendall {kend} "
            f"over {rk['n']} candidates vs {rk['reference']}")


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    add_common_args(ap)
    ap.add_argument("--programs", default="himeno,nasft",
                    help="comma-separated miniapps")
    ap.add_argument("--k", type=int, default=3,
                    help="stability seeds (pass@k)")
    ap.add_argument("--window", type=float, default=0.02,
                    help="stability window (relative)")
    ap.add_argument("--diversity", type=float, default=1.0,
                    help="fitness-sharing exponent for the ablation's "
                         "ON arm")
    args = ap.parse_args(argv)
    programs = [p.strip() for p in args.programs.split(",") if p.strip()]

    print("\n== search quality: winner stability + rank fidelity ==")
    for prog in programs:
        res, rep, _ = _quality(_spec(prog, args, rank_probe=True))
        print(f"  {prog:8s} best {res.best_time_s:.4f}s "
              f"(speedup {res.speedup:.2f}x)")
        print(f"           {_stability_line(rep['stability'])}")
        print(f"           {_rank_line(rep['rank'])}")

    print(f"\n== diversity ablation: ga.diversity 0.0 vs "
          f"{args.diversity} ==")
    print("csv:program,diversity,best_time_s,rel_spread,entropy")
    for prog in programs:
        for div in (0.0, args.diversity):
            res, rep, ent = _quality(_spec(prog, args, diversity=div))
            st = rep["stability"]
            spread = st.get("rel_spread")
            spread_s = "n/a" if spread is None else f"+{spread:.1%}"
            print(f"  {prog:8s} diversity={div:<4g} "
                  f"best {res.best_time_s:.4f}s  spread {spread_s}  "
                  f"final-pop allele entropy {ent:.3f}")
            print(f"csv:{prog},{div:g},{res.best_time_s:.6f},"
                  f"{'' if spread is None else f'{spread:.6f}'},"
                  f"{ent:.4f}")


if __name__ == "__main__":
    main()
