"""repro.offload: spec/artifact lifecycle, stage semantics, CLI, and
parity of the facade's searches with a hand-wired ``run_ga`` over the
same evaluator."""
import json

import pytest

from repro.core import evaluator as ev
from repro.core import evalpool as ep
from repro.core import ga, miniapps
from repro.core import transfer as tr
from repro.offload import (
    Offloader,
    OffloadResult,
    OffloadSpec,
    StageFailure,
)
from repro.offload.__main__ import main as cli_main


# ---------------------------------------------------------------------------
# parity with the pre-redesign wiring
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("app,method", [
    ("himeno", "proposed"),
    ("himeno", "previous"),
    ("nasft", "proposed"),
])
def test_binary_search_parity(app, method):
    """Offloader reproduces the old fig4/fig5 wiring byte-identically:
    same evaluator, same paper-rule GAParams, same RNG stream."""
    from repro.offload.spec import METHODS

    prog = miniapps.MINIAPPS[app]()
    n = prog.gene_length
    kw = METHODS[method]
    e = ev.MiniappEvaluator(
        prog, tr.TransferMode(kw["transfer"]), staged=kw["staged"],
        kernels_only=kw["kernels_only"],
    )
    params = ga.GAParams.for_gene_length(n, seed=0)
    with ep.EvalPool(e) as pool:
        old = ga.run_ga(None, n, params, pool=pool)

    res = Offloader(
        OffloadSpec(program=app, mode="binary", method=method)
    ).run(until="search")
    assert res.best_genes == old.best_genes
    assert res.best_time_s == old.best_time_s
    # and the baseline matches the old scripts' cpu reference
    assert res.baseline_time_s == pytest.approx(
        ev.predict_time(prog, (0,) * n).total_s, rel=1e-12
    )


def test_mixed_search_parity():
    """Offloader reproduces the old fig_mixed_destinations wiring."""
    from repro.destinations import MixedEvaluator

    prog = miniapps.hetero_program()
    e = MixedEvaluator(prog, ("cpu", "gpu", "fpga"))
    params = ga.GAParams(population=10, generations=8, seed=0,
                         timeout_s=1e6, alleles=e.k)
    with ep.EvalPool(e) as pool:
        old = ga.run_ga(None, prog.gene_length, params, pool=pool)

    res = Offloader(
        OffloadSpec(program="hetero", mode="mixed",
                    population=10, generations=8)
    ).run(until="search")
    assert res.best_genes == old.best_genes
    assert res.best_time_s == old.best_time_s


def test_arch_search_parity():
    """The arch adapter reproduces the old ga_arch_search analytic path
    (same evaluator math, same min(n,10) budget)."""
    from repro.offload.programs import ArchPlanEvaluator

    e = ArchPlanEvaluator("stablelm-3b")
    n = Offloader(
        OffloadSpec(program="arch:stablelm-3b")
    ).adapter.gene_length
    params = ga.GAParams(population=min(n, 10), generations=min(n, 10),
                         seed=0, timeout_s=1e6)
    old = ga.run_ga(e, n, params)

    res = Offloader(OffloadSpec(program="arch:stablelm-3b")).run(
        until="search"
    )
    assert res.best_genes == old.best_genes
    assert res.best_time_s == old.best_time_s
    # fingerprint kept from the pre-redesign closure (cache continuity)
    assert e.fingerprint() == "analytic-plan:stablelm-3b"


def test_run_ga_no_seeds_is_byte_identical():
    """seeds=[] / None must not perturb the RNG stream."""
    e = ev.MiniappEvaluator(miniapps.himeno_program())
    params = ga.GAParams.for_gene_length(13, seed=3)
    a = ga.run_ga(e, 13, params)
    b = ga.run_ga(e, 13, params, seeds=[])
    assert a.best_genes == b.best_genes and a.best_time_s == b.best_time_s


def test_run_ga_seed_validation():
    e = ev.MiniappEvaluator(miniapps.himeno_program())
    params = ga.GAParams.for_gene_length(13, seed=0)
    with pytest.raises(ValueError, match="length"):
        ga.run_ga(e, 13, params, seeds=[(1, 0)])
    with pytest.raises(ValueError, match="alleles"):
        ga.run_ga(e, 13, params, seeds=[(7,) * 13])


# ---------------------------------------------------------------------------
# spec validation + serialization
# ---------------------------------------------------------------------------


def test_spec_json_roundtrip():
    spec = OffloadSpec(program="hetero", mode="mixed",
                       destinations=("cpu", "fpga"), population=5,
                       warm_start=True, cache="/tmp/x.jsonl", rel_tol=1e-4)
    assert OffloadSpec.from_json(spec.to_json()) == spec


@pytest.mark.parametrize("kw,msg", [
    (dict(program="himeno", mode="hybrid"), "mode"),
    (dict(program="himeno", method="bogus"), "method"),
    (dict(program="himeno", mode="mixed", destinations=("cpu",)),
     "destinations"),
    (dict(program="arch:stablelm-3b", mode="mixed"), "arch"),
    (dict(program="himeno", warm_start=True), "warm_start"),
    (dict(program="himeno", executor="fork"), "executor"),
])
def test_spec_validation(kw, msg):
    with pytest.raises(ValueError, match=msg):
        OffloadSpec(**kw)


def test_spec_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown"):
        OffloadSpec.from_dict({"program": "himeno", "wat": 1})


def test_unknown_program_fails_at_analyze():
    off = Offloader(OffloadSpec(program="nope"))
    with pytest.raises(ValueError, match="unknown miniapp"):
        off.run(until="analyze")
    assert off.result.stages["analyze"].status == "failed"


# ---------------------------------------------------------------------------
# artifact lifecycle: save -> reload -> resume
# ---------------------------------------------------------------------------


def _mixed_spec(tmp_path, **kw):
    kw.setdefault("population", 10)
    kw.setdefault("generations", 8)
    kw.setdefault("cache", str(tmp_path / "fitness.jsonl"))
    return OffloadSpec(program="hetero", mode="mixed", **kw)


def test_artifact_roundtrip(tmp_path):
    path = str(tmp_path / "art.json")
    spec = _mixed_spec(tmp_path)
    res = Offloader(spec, artifact_path=path).run(until="search")
    loaded = OffloadResult.load(path)
    assert loaded.spec == spec
    assert loaded.completed("analyze") and loaded.completed("search")
    assert loaded.best_genes == res.best_genes
    assert loaded.best_time_s == res.best_time_s
    assert loaded.stage("search").payload == res.stage("search").payload


def test_resume_skips_completed_stages(tmp_path):
    path = str(tmp_path / "art.json")
    spec = _mixed_spec(tmp_path)
    Offloader(spec, artifact_path=path).run(until="seed")

    # plant a sentinel: if resume re-ran analyze, it would be lost
    art = json.load(open(path))
    for st in art["stages"]:
        if st["name"] == "analyze":
            st["payload"]["sentinel"] = "untouched"
    json.dump(art, open(path, "w"))

    res = Offloader.resume(path).run()
    assert res.stage("analyze").payload["sentinel"] == "untouched"
    for stage in ("analyze", "seed", "search", "verify", "report"):
        assert res.completed(stage)


def test_older_artifact_loads_and_resumes(tmp_path):
    """An artifact written in the older form — the spec without the keys
    that were once dropped at their defaults (``blocks``,
    ``measured_scale``, ``ga.steady_state``, ``ga.batch``), the search
    payload without ``steady_state`` / ``batch`` — loads as the same spec
    and resumes to the end."""
    path = str(tmp_path / "old.json")
    spec = _mixed_spec(tmp_path)
    Offloader(spec, artifact_path=path).run(until="search")
    art = json.load(open(path))
    for key in ("blocks", "measured_scale"):
        del art["spec"][key]
    for key in ("steady_state", "batch"):
        del art["spec"]["ga"][key]
        for st in art["stages"]:
            if st["name"] == "search":
                del st["payload"]["ga"][key]
    json.dump(art, open(path, "w"))

    assert OffloadResult.load(path).spec == spec
    res = Offloader.resume(path).run()
    for stage in ("verify", "report"):
        assert res.completed(stage)
    assert "steady_state" not in res.stage("search").payload["ga"]
    assert res.stage("verify").payload["consistent"]


def test_killed_search_resumes_from_fitness_cache(tmp_path):
    """The acceptance criterion: a killed run resumed via the artifact
    reaches the same winner WITHOUT re-measuring cached individuals."""
    spec = _mixed_spec(tmp_path)
    first = Offloader(spec, artifact_path=str(tmp_path / "a.json")).run(
        until="search"
    )
    # simulate the kill: a fresh artifact for the same spec (the search
    # stage record was lost) but the fitness cache survived on disk
    second = Offloader(spec, artifact_path=str(tmp_path / "b.json")).run(
        until="search"
    )
    p = second.stage("search").payload
    assert second.best_genes == first.best_genes
    assert second.best_time_s == first.best_time_s
    assert p["evaluations"] == 0  # everything answered from the cache
    assert p["cache_resumed"] > 0


def test_artifact_version_guard(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"v": 99, "spec": {}, "stages": []}))
    with pytest.raises(ValueError, match="version"):
        OffloadResult.load(str(path))


# ---------------------------------------------------------------------------
# warm-start seeding (genome-aware, mixed mode)
# ---------------------------------------------------------------------------


def test_warm_start_seeds_recorded_and_win_gen0(tmp_path):
    spec = _mixed_spec(tmp_path, warm_start=True)
    res = Offloader(spec).run(until="search")
    seed_p = res.stage("seed").payload
    assert seed_p["warm_start"] and len(seed_p["seeds"]) == 2
    assert [i["device"] for i in seed_p["seed_info"]] == ["gpu", "fpga"]
    best_single = min(i["best_time_s"] for i in seed_p["seed_info"])
    history = res.stage("search").payload["history"]
    # the re-expressed seeds are IN generation 0, so its best is at
    # least the best single-destination placement
    assert history[0]["best_time_s"] <= best_single * (1 + 1e-9)
    assert res.best_time_s <= best_single * (1 + 1e-9)
    # re-expression really lands in the k-ary alphabet
    assert any(g == 2 for s in seed_p["seeds"] for g in s)


def test_warm_start_gen0_beats_cold_gen0(tmp_path):
    cold = Offloader(_mixed_spec(tmp_path)).run(until="search")
    warm = Offloader(_mixed_spec(tmp_path, warm_start=True)).run(
        until="search"
    )
    c0 = cold.stage("search").payload["history"][0]["best_time_s"]
    w0 = warm.stage("search").payload["history"][0]["best_time_s"]
    assert w0 < c0


# ---------------------------------------------------------------------------
# verify stage + CLI exit codes
# ---------------------------------------------------------------------------


def test_cli_run_report_binary(tmp_path):
    """Full pipeline (incl. the PCAST check on the runnable Himeno
    implementation) through the CLI: exit 0 and a complete artifact."""
    path = str(tmp_path / "himeno.json")
    rc = cli_main(["run", "--program", "himeno", "--mode", "binary",
                   "--smoke", "--quiet", "--artifact", path])
    assert rc == 0
    art = OffloadResult.load(path)
    for stage in ("analyze", "seed", "search", "verify", "report"):
        assert art.completed(stage)
    assert art.stage("verify").payload["pcast"]["ok"]
    assert "PCAST PASS" in art.stage("report").payload["text"]
    assert cli_main(["report", "--artifact", path]) == 0


def test_cli_pcast_failure_exits_nonzero(tmp_path):
    """A PCAST result-difference failure (zero tolerance makes the f32
    jit-vs-numpy difference fatal) surfaces as a non-zero CLI exit with
    the failure recorded in the artifact."""
    path = str(tmp_path / "fail.json")
    rc = cli_main(["run", "--program", "himeno", "--mode", "binary",
                   "--smoke", "--quiet", "--artifact", path,
                   "--rel-tol", "0", "--abs-tol", "0"])
    assert rc == 1
    art = OffloadResult.load(path)
    assert art.stages["verify"].status == "failed"
    assert "PCAST" in art.stages["verify"].error
    assert not art.stages["verify"].payload["pcast"]["ok"]
    assert not art.completed("report")

    # resuming with the failure recorded re-runs verify and fails again
    rc2 = cli_main(["resume", "--artifact", path, "--quiet"])
    assert rc2 == 1


def test_verify_reports_pcast_skipped_for_hetero(tmp_path):
    res = Offloader(_mixed_spec(tmp_path)).run()
    assert "skipped" in res.stage("verify").payload["pcast"]
    assert res.completed("report")


def test_verify_rejects_evaluator_mismatch(tmp_path):
    """An artifact searched with an injected evaluator must not verify
    against a different one (e.g. compiled-arch artifact resumed without
    re-injection): clear failure, not a spurious 'drifted' one."""
    spec = OffloadSpec(program="arch:stablelm-3b", population=4,
                       generations=3)
    injected = lambda genes: 1.0 + 0.001 * sum(genes)  # noqa: E731
    injected.fingerprint = lambda: "injected:toy"
    path = str(tmp_path / "arch.json")
    Offloader(spec, artifact_path=path, evaluator=injected).run(
        until="search"
    )
    with pytest.raises(StageFailure, match="evaluator .* differs"):
        Offloader.resume(path).run(until="verify")
    art = OffloadResult.load(path)
    assert art.stages["verify"].status == "failed"
    # the failed record renders (re_measured_s is None on this path)
    from repro.offload import render_report

    assert "FAILED" in render_report(art)
    # re-injecting the evaluator verifies cleanly, without redundantly
    # re-running the (potentially expensive) injected measurement
    Offloader.resume(path, evaluator=injected).run(until="verify")
    art2 = OffloadResult.load(path)
    assert art2.completed("verify")
    assert art2.stage("verify").payload["re_measured_s"] is None
    assert "skipped" in art2.stage("verify").payload["note"]


def test_stage_failure_recorded_before_raise(tmp_path):
    """A corrupted search record makes verify's re-measurement drift:
    the failure must be recorded AND saved before the raise."""
    path = str(tmp_path / "drift.json")
    off = Offloader(_mixed_spec(tmp_path), artifact_path=path)
    off.run(until="search")
    off.result.stage("search").payload["best_time_s"] /= 2  # corrupt
    with pytest.raises(StageFailure, match="drifted"):
        off.run_stage("verify")
    art = OffloadResult.load(path)
    assert art.stages["verify"].status == "failed"
    assert "drifted" in art.stages["verify"].error
