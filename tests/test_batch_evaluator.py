"""Fast-search substrate: batch-pricing parity + steady-state GA
properties (docs/pipeline.md "Fast search").

Seeded property tests (no hypothesis in the image; every random draw is
pinned by seed, so failures replay exactly):

- the vectorized :class:`BatchMixedEvaluator` prices random genomes
  identically to the scalar :class:`MixedEvaluator` oracle to round-off,
  over unbounded and capacity-bounded registries and over
  block-substitution genomes;
- cache identity (fingerprint + canonical keys) is unchanged by the
  batch subclass, so batch and scalar searches share one fitness cache;
- the steady-state GA spends its evaluation budget exactly, never loses
  the best-so-far genome, and emits the same one-row-per-generation
  telemetry/history shape as the generational loop;
- the ``OffloadSpec.ga`` fast-search knobs serialize only when set
  (knobs-off spec digests stay byte-identical to prior artifacts) and
  the full pipeline completes + verifies with both knobs on.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.blocks import BatchBlockMixedEvaluator, BlockMixedEvaluator
from repro.core import ga, miniapps
from repro.core.evalpool import EvalPool
from repro.destinations import (
    BatchMixedEvaluator,
    MixedEvaluator,
    get_registry,
)
from repro.offload import Offloader, OffloadSpec
from repro.offload.spec import GAControls

RTOL = 1e-12  # far under the pipeline's 1e-9 verify tolerance

REGISTRIES = ("quadro-p4000", "p4000-constrained", "tpu-v5e-host")
PROGRAMS = ("hetero", "himeno", "nasft")


def _genomes(rng, gene_length, k, n):
    return [
        tuple(int(x) for x in rng.integers(0, k, gene_length))
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# parity with the scalar oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("regname", REGISTRIES)
@pytest.mark.parametrize("pname", PROGRAMS)
def test_batch_pricing_matches_scalar_oracle(regname, pname):
    reg = get_registry(regname)
    names = tuple(d.name for d in reg.destinations)
    prog = miniapps.MINIAPPS[pname]()
    scalar = MixedEvaluator(prog, names, registry=reg)
    batch = BatchMixedEvaluator(prog, names, registry=reg)
    rng = np.random.default_rng(20260809)
    genomes = _genomes(rng, prog.gene_length, scalar.k, 48)
    got = batch.evaluate_batch(genomes)
    want = [scalar(g) for g in genomes]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g == pytest.approx(w, rel=RTOL)


def test_bounded_registry_falls_back_to_exact_scalar_pricing():
    # a capacity-bounded searched destination has per-genome eviction
    # state: the batch path degrades to per-genome scalar calls, so the
    # numbers are EQUAL, not just close
    reg = get_registry("p4000-constrained")
    names = tuple(d.name for d in reg.destinations)
    prog = miniapps.hetero_program()
    scalar = MixedEvaluator(prog, names, registry=reg)
    batch = BatchMixedEvaluator(prog, names, registry=reg)
    assert batch._scalar_only
    rng = np.random.default_rng(7)
    genomes = _genomes(rng, prog.gene_length, scalar.k, 16)
    assert batch.evaluate_batch(genomes) == [scalar(g) for g in genomes]


def test_batch_pricing_matches_scalar_on_block_genomes():
    scalar = BlockMixedEvaluator(miniapps.hetero_program())
    batch = BatchBlockMixedEvaluator(miniapps.hetero_program())
    assert batch.gene_length == scalar.gene_length
    rng = np.random.default_rng(99)
    genomes = _genomes(rng, scalar.gene_length, scalar.k, 48)
    got = batch.evaluate_batch(genomes)
    want = [scalar(g) for g in genomes]
    for g, w in zip(got, want):
        assert g == pytest.approx(w, rel=RTOL)


def test_batch_subclass_keeps_cache_identity():
    prog = miniapps.hetero_program()
    scalar = MixedEvaluator(prog)
    batch = BatchMixedEvaluator(prog)
    assert batch.fingerprint() == scalar.fingerprint()
    rng = np.random.default_rng(3)
    for genes in _genomes(rng, prog.gene_length, scalar.k, 8):
        assert batch.cache_key(genes) == scalar.cache_key(genes)
        # scalar __call__ is inherited untouched — the verify oracle
        assert batch(genes) == scalar(genes)


def test_batch_empty_population_and_subset_destinations():
    prog = miniapps.hetero_program()
    batch = BatchMixedEvaluator(prog, ("cpu", "gpu"))
    assert batch.evaluate_batch([]) == []
    scalar = MixedEvaluator(prog, ("cpu", "gpu"))
    rng = np.random.default_rng(11)
    genomes = _genomes(rng, prog.gene_length, 2, 16)
    got = batch.evaluate_batch(genomes)
    want = [scalar(g) for g in genomes]
    for g, w in zip(got, want):
        assert g == pytest.approx(w, rel=RTOL)


def test_evalpool_batch_path_agrees_with_scalar_pool():
    # one pool over the batch evaluator, one over the scalar: identical
    # per-generation times through evaluate_generation
    prog = miniapps.hetero_program()
    scalar = MixedEvaluator(prog)
    batch = BatchMixedEvaluator(prog)
    rng = np.random.default_rng(5)
    popn = _genomes(rng, prog.gene_length, scalar.k, 24)
    with EvalPool(scalar) as p1, EvalPool(batch) as p2:
        t1, tel1 = p1.evaluate_generation(popn, 1e6, 1000.0)
        t2, tel2 = p2.evaluate_generation(popn, 1e6, 1000.0)
    assert t1 == pytest.approx(t2, rel=RTOL)
    assert (tel1.submitted, tel1.unique, tel1.cache_hits) == \
        (tel2.submitted, tel2.unique, tel2.cache_hits)


# ---------------------------------------------------------------------------
# steady-state GA properties
# ---------------------------------------------------------------------------


def _steady_run(workers, seed=0, pop=10, gens=5):
    prog = miniapps.hetero_program()
    ev = MixedEvaluator(prog)
    pool = EvalPool(ev, workers=workers)
    params = ga.GAParams(population=pop, generations=gens, seed=seed,
                         alleles=ev.k, steady_state=True)
    res = ga.run_ga(None, prog.gene_length, params, pool=pool)
    return res, pool, params, ev


@pytest.mark.parametrize("workers", [1, 4])
def test_steady_state_budget_is_exact(workers):
    res, pool, params, _ = _steady_run(workers)
    tot = pool.totals()
    budget = params.population * params.generations
    assert tot.submitted == budget
    # every submission resolves to a fresh measurement or a hit — no
    # double counting, nothing dropped
    assert tot.evaluated + tot.cache_hits == tot.submitted
    assert res.evaluations == tot.evaluated


@pytest.mark.parametrize("workers", [1, 4])
def test_steady_state_never_loses_the_best(workers):
    res, pool, params, ev = _steady_run(workers, seed=2)
    bests = [h.best_time_s for h in res.history]
    assert all(b2 <= b1 for b1, b2 in zip(bests, bests[1:]))
    assert res.best_time_s == bests[-1]
    # the reported winner re-prices to exactly its reported time
    assert ev(res.best_genes) == pytest.approx(res.best_time_s, rel=RTOL)


def test_steady_state_history_shape_matches_generational():
    res, pool, params, _ = _steady_run(1)
    assert len(res.history) == params.generations
    assert len(pool.history) == params.generations
    for h in res.history:
        assert len(h.times) == params.population
        assert len(h.population) == params.population
    # telemetry rows carry the idle attribution key (rendered by the
    # trace CLI budget table)
    assert all("idle_wall_s" in t.row() for t in pool.history)


def test_steady_state_inline_is_deterministic():
    r1, *_ = _steady_run(1, seed=4)
    r2, *_ = _steady_run(1, seed=4)
    assert r1.best_genes == r2.best_genes
    assert r1.best_time_s == r2.best_time_s
    assert [h.best_time_s for h in r1.history] == \
        [h.best_time_s for h in r2.history]


def test_steady_state_single_generation_falls_back_to_barrier():
    # generations=1 has no steady tail; the dispatch must not engage
    prog = miniapps.hetero_program()
    ev = MixedEvaluator(prog)
    params = ga.GAParams(population=6, generations=1, seed=0,
                         alleles=ev.k, steady_state=True)
    res = ga.run_ga(ev, prog.gene_length, params)
    assert len(res.history) == 1


# ---------------------------------------------------------------------------
# spec serialization + full pipeline
# ---------------------------------------------------------------------------


def test_fast_search_knobs_serialize_only_when_set():
    off = OffloadSpec(program="hetero", mode="mixed")
    d = off.to_dict()
    # every field is serialised, the off state too, and round-trips
    assert d["ga"]["steady_state"] is False
    assert d["ga"]["batch"] is False
    assert OffloadSpec.from_dict(d) == off
    assert OffloadSpec.from_json(off.to_json()) == off

    on = OffloadSpec(program="hetero", mode="mixed",
                     ga=GAControls(steady_state=True, batch=True))
    d = on.to_dict()
    assert d["ga"]["steady_state"] is True
    assert d["ga"]["batch"] is True
    rt = OffloadSpec.from_dict(d)
    assert rt.ga.steady_state and rt.ga.batch
    assert rt == on


def test_pipeline_with_both_knobs_completes_and_verifies():
    spec = OffloadSpec(
        program="hetero", mode="mixed", population=10, generations=6,
        ga=GAControls(steady_state=True, batch=True, stability_seeds=0),
    )
    res = Offloader(spec).run()
    assert res.completed("verify")
    v = res.stage("verify").payload
    assert v["consistent"] is True
    s = res.stage("search").payload
    assert s["ga"]["steady_state"] is True
    assert s["ga"]["batch"] is True
    # the scalar oracle re-measured the batch-priced winner within the
    # pipeline's tolerance
    assert v["re_measured_s"] == pytest.approx(s["best_time_s"], rel=1e-9)


def test_batch_knob_alone_reproduces_the_scalar_search_winner():
    base = OffloadSpec(program="hetero", mode="mixed", population=10,
                       generations=6, ga=GAControls(stability_seeds=0))
    fast = OffloadSpec(program="hetero", mode="mixed", population=10,
                       generations=6,
                       ga=GAControls(batch=True, stability_seeds=0))
    r1 = Offloader(base).run(until="search").stage("search").payload
    r2 = Offloader(fast).run(until="search").stage("search").payload
    # same RNG stream, same (to round-off) fitness values -> same winner
    assert r1["best_genes"] == r2["best_genes"]
    assert r1["best_time_s"] == pytest.approx(r2["best_time_s"], rel=RTOL)
