"""The seam between the Offloader and its programs: every adapter kind
answers the whole ``ProgramAdapter`` interface, and the realised
projection — the one placement a runnable program can really run — sets
the hot loop's gene and nothing else."""
import random

import pytest

from repro.core import pcast
from repro.offload import Offloader, OffloadSpec, calibrate
from repro.offload import programs as op

_SMALL = dict(population=4, generations=2, measured_scale="small",
              repeats=1, ga={"stability_seeds": 1, "rank_probe": True})

KINDS = {
    "binary": dict(program="himeno"),
    "mixed": dict(program="himeno", mode="mixed"),
    "mixed-blocks": dict(program="himeno", mode="mixed", blocks=True),
    "measured": dict(program="himeno", fidelity="measured"),
    "calibrated": dict(program="himeno", fidelity="calibrated"),
    "arch": dict(program="arch:stablelm-3b"),
}

FIDELITY_ROW_KEYS = {"destination", "placement", "predicted_s",
                     "measured_s", "ratio"}
RANK_KEYS = {"n", "spearman", "kendall", "distinct_modeled",
             "distinct_measured", "scale", "reference"}


def _synthetic_measure(p, repeats):
    # an exact analytic "machine", so the calibration needs no clock
    f, b, c = calibrate._region_quantities(calibrate._probe_program(p))
    if p.dest == "host":
        return f / 2.0e9 + c * 1e-4
    return f / 1.0e11 + b / 5.0e9 + c * 5e-5


def _run(kind):
    spec = OffloadSpec(**{**_SMALL, **KINDS[kind]})
    cal = None
    if spec.fidelity == "calibrated":
        cal = calibrate.run_calibration(base=spec.hw,
                                        measure=_synthetic_measure)
    off = Offloader(spec, calibration=cal)
    return off, off.run()


def _genomes(adapter, n=12):
    rng = random.Random(0)
    out = [(0,) * adapter.gene_length]
    for _ in range(n):
        out.append(tuple(rng.randrange(adapter.alleles)
                         for _ in range(adapter.gene_length)))
    return out


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_adapter_answers_the_stage_questions(kind):
    off, res = _run(kind)
    ad = off.adapter
    assert isinstance(ad, op.ProgramAdapter)
    n = ad.gene_length
    assert len(ad.allele_names) == ad.alleles >= 2
    assert isinstance(ad.deterministic, bool)
    assert float(ad.baseline_time()) > 0
    assert ad.analyze_payload()["gene_length"] == n
    assert callable(ad.build_evaluator())
    best = tuple(res.best_genes)
    assert set(ad.placement(best).values()) <= set(ad.allele_names)
    extras = ad.search_extras(best)
    search = res.stage("search").payload
    for key, value in extras.items():
        assert search[key] == value
    report, answer = ad.verify(best)

    if ad.run_fn is None:  # no runnable implementation
        assert kind == "arch"
        assert (report, answer) == (None, None)
        assert ad.scale_model() is None
        assert ad.realised(best) is None
        assert "skipped" in res.stage("report").payload["quality"]["rank"]
        return

    assert isinstance(report, pcast.PcastReport) and report.ok
    assert (answer is not None) == (kind == "measured")
    model = ad.scale_model()
    hot, hot_loop = ad.run_fn.hot_gene, ad.run_fn.hot_loop
    assert hot == op.hot_gene_index(ad.spec.program)
    assert float(ad.clock()((0,) * n)) > 0
    for genes in _genomes(ad) + [best]:
        real = ad.realised(genes)
        offloaded = ad.placement(genes)[hot_loop] != ad.host
        assert real.offloaded == offloaded
        assert len(real.genes) == n
        assert all(g == 0 for i, g in enumerate(real.genes) if i != hot)
        assert bool(real.genes[hot]) == offloaded
        assert real.destination == ad.allele_names[real.genes[hot]]
        assert float(model(real.genes)) > 0

    # verify's fidelity rows price the winner's realised projection
    fid = res.stage("verify").payload.get("fidelity")
    if off.spec.fidelity == "modeled":
        assert fid is None
    else:
        real = ad.realised(best)
        host_row, win_row = fid["rows"]
        assert set(host_row) == set(win_row) == FIDELITY_ROW_KEYS
        assert (host_row["destination"], host_row["placement"]) == \
            (ad.host, "all-host")
        assert host_row["predicted_s"] == model((0,) * n)
        assert (win_row["destination"], win_row["placement"]) == \
            (real.destination, "winner:hot-loop")
        assert win_row["predicted_s"] == model(real.genes)
        assert fid["scale"] == ad.run_fn.tag
    # ...and the report's rank section ranks the final population
    rank = res.stage("report").payload["quality"]["rank"]
    assert RANK_KEYS <= set(rank) <= RANK_KEYS | {"note"}
    assert rank["n"] == len(search["final_population"])
    assert rank["scale"] == ad.run_fn.tag
