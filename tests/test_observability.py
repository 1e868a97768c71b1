"""Observability layer (ISSUE 7): structured pipeline tracing
(src/repro/offload/trace.py), search-quality metrics in the report stage
(src/repro/offload/quality.py via the Offloader), the ga.diversity
selection knob, and the `python -m repro.offload trace` CLI verb.

The load-bearing guarantees:

- two identical modeled runs produce traces with IDENTICAL content
  digests (timing is excluded by construction), and the artifact embeds
  that digest;
- with tracing on and ga.diversity unset, the search payload (winner,
  history, evaluator fingerprint) is byte-identical to an untraced run —
  observability must never perturb the search;
- a zero-generation search records an explicit no-winner payload and the
  report renders a clear "no generations" line;
- the report stage carries pass@k winner stability and rank fidelity,
  and the stability gate turns excessive spread into a stage failure.
"""
import dataclasses
import itertools
import json
import os

import pytest

from repro.core import ga
from repro.offload import trace as tm
from repro.offload.__main__ import main
from repro.offload.pipeline import Offloader, render_report
from repro.offload.result import OffloadResult, StageFailure
from repro.offload.spec import GAControls, OffloadSpec


def _clock():
    """A deterministic injected clock: 0.0, 0.5, 1.0, ..."""
    c = itertools.count()
    return lambda: next(c) * 0.5


def _run(tmp_path, name, spec, **kw):
    path = str(tmp_path / f"{name}.offload.json")
    off = Offloader(spec, artifact_path=path, trace_clock=_clock(), **kw)
    off.run()
    return off.result, path


SPEC = OffloadSpec(program="himeno", mode="binary")


# ---------------------------------------------------------------------------
# trace determinism + round-trip
# ---------------------------------------------------------------------------


def test_two_identical_runs_same_digest(tmp_path):
    r1, p1 = _run(tmp_path / "a", "x", SPEC)
    r2, p2 = _run(tmp_path / "b", "x", SPEC)
    t1 = tm.load_trace(tm.default_trace_path(p1))
    t2 = tm.load_trace(tm.default_trace_path(p2))
    assert t1.digest == t2.digest
    # record-by-record: identical modulo the clock-derived keys
    assert [tm.strip_timing(r) for r in t1.records] == \
           [tm.strip_timing(r) for r in t2.records]
    # the artifact embeds exactly this digest
    assert r1.trace["digest"] == t1.digest
    assert r1.trace["records"] == len(t1.records)
    assert r1.trace["path"] == os.path.basename(tm.default_trace_path(p1))
    # and it survives the artifact's own JSON round-trip
    assert OffloadResult.load(p1).trace == r1.trace


def test_trace_structure_and_span_order(tmp_path):
    _, path = _run(tmp_path, "x", SPEC)
    tr = tm.load_trace(tm.default_trace_path(path))
    assert tr.records[0]["kind"] == "run"
    assert tr.records[0]["schema"] == tm.TRACE_SCHEMA
    assert tr.records[0]["resumed"] is False
    assert [s["name"] for s in tr.spans()] == [
        "calibrate", "analyze", "seed", "search", "verify", "report"]
    assert all(s["status"] == "done" for s in tr.spans())
    # one generation event per GA generation, telemetry attached
    gens = [e for e in tr.events("search") if e["name"] == "generation"]
    n_gens = len(tr.spans()[3]["attrs"])  # sanity: attrs present
    assert n_gens > 0
    assert len(gens) == tr.spans()[3]["attrs"]["generations"]
    for e in gens:
        a = e["attrs"]
        for key in ("generation", "best_time_s", "median_time_s",
                    "best_fitness", "median_fitness", "allele_entropy",
                    "evaluated", "cache_hits", "dedup_ratio"):
            assert key in a, key
        assert 0.0 <= a["allele_entropy"] <= 1.0
        # the pool's generation wall clock is timing, never attrs
        assert "wall_s" in e.get("timing", {})
    # the report stage evented its stability re-searches
    assert any(e["name"] == "stability_search" for e in tr.events("report"))


def _scrub_wall(obj):
    """Drop measured wall-clock fields — the only legitimately
    nondeterministic payload content."""
    if isinstance(obj, dict):
        return {k: _scrub_wall(v) for k, v in obj.items()
                if "wall_s" not in k}
    if isinstance(obj, list):
        return [_scrub_wall(v) for v in obj]
    return obj


def test_tracing_does_not_perturb_search(tmp_path):
    traced, _ = _run(tmp_path / "on", "x", SPEC)
    off = Offloader(SPEC, artifact_path=str(tmp_path / "off.offload.json"),
                    trace=False)
    untraced = off.run()
    assert not os.path.exists(
        tm.default_trace_path(str(tmp_path / "off.offload.json")))
    assert untraced.trace is None
    assert _scrub_wall(traced.stage("search").payload) == \
        _scrub_wall(untraced.stage("search").payload)


def test_resume_appends_second_run_header(tmp_path):
    path = str(tmp_path / "x.offload.json")
    off = Offloader(SPEC, artifact_path=path, trace_clock=_clock())
    off.run(until="seed")
    off2 = Offloader.resume(path, trace_clock=_clock())
    res = off2.run()
    tr = tm.load_trace(tm.default_trace_path(path))
    runs = [r for r in tr.records if r["kind"] == "run"]
    assert [r["resumed"] for r in runs] == [False, True]
    # seq stayed contiguous across processes and the digest matches
    assert res.trace["digest"] == tr.digest
    rendered = tm.render_trace(tr, artifact=res)
    assert "run 2 (resumed" in rendered
    assert "matches" in rendered


def test_load_trace_rejects_corruption(tmp_path):
    path = str(tmp_path / "t.trace.jsonl")
    w = tm.TraceWriter(path, clock=_clock())
    w.run_header(program="p", mode="binary", fidelity="modeled",
                 spec_digest="d", resumed=False)
    w.span("analyze", 0.0, 1.0, "done")
    w.close()
    recs = tm.load_trace(path).records  # sane baseline

    with open(path, "a", encoding="utf-8") as fh:  # truncated tail line
        fh.write('{"seq": 2, "kind": "span"')
    with pytest.raises(tm.TraceError):
        tm.load_trace(path)

    bad = str(tmp_path / "gap.trace.jsonl")
    with open(bad, "w", encoding="utf-8") as fh:
        fh.write(json.dumps(recs[0]) + "\n")
        skipped = dict(recs[1], seq=5)
        fh.write(json.dumps(skipped) + "\n")
    with pytest.raises(tm.TraceError, match="seq"):
        tm.load_trace(bad)

    empty = str(tmp_path / "empty.trace.jsonl")
    open(empty, "w").close()
    with pytest.raises(tm.TraceError, match="empty"):
        tm.load_trace(empty)

    noheader = str(tmp_path / "nh.trace.jsonl")
    with open(noheader, "w", encoding="utf-8") as fh:
        fh.write(json.dumps({"seq": 0, "kind": "span", "name": "x",
                             "status": "done", "t0": 0, "t1": 1}) + "\n")
    with pytest.raises(tm.TraceError, match="run header"):
        tm.load_trace(noheader)


def test_default_trace_path():
    assert tm.default_trace_path("a/b.offload.json") == \
        "a/b.offload.trace.jsonl"
    assert tm.default_trace_path("plain") == "plain.trace.jsonl"


def test_in_memory_artifact_traces_nothing():
    off = Offloader(SPEC)  # no artifact path, no trace path
    res = off.run()
    assert res.trace is None  # silently disabled, pipeline unharmed
    assert res.completed("report")


# ---------------------------------------------------------------------------
# satellite: telemetry persisted in the search payload
# ---------------------------------------------------------------------------


def test_generation_telemetry_persisted(tmp_path):
    res, path = _run(tmp_path, "x", SPEC)
    p = res.stage("search").payload
    tel = p["telemetry"]
    assert len(tel) == len(p["history"]) > 0
    for row in tel:  # row index == generation index
        for key in ("submitted", "unique", "cache_hits", "evaluated",
                    "timeouts", "dedup_ratio", "hit_rate"):
            assert key in row, key
    assert sum(r["evaluated"] for r in tel) == p["evaluations"]
    # the final population (and its times) round-trip for rank metrics
    assert len(p["final_population"]) == p["ga"]["population"]
    assert len(p["final_times_s"]) == p["ga"]["population"]
    assert p["ga"]["allele_names"] == ["cpu", "gpu"]
    loaded = OffloadResult.load(path)
    assert loaded.stage("search").payload == p


# ---------------------------------------------------------------------------
# satellite: zero-generation searches
# ---------------------------------------------------------------------------


def test_zero_generation_report(tmp_path, capsys):
    spec = dataclasses.replace(SPEC, generations=0)
    res, path = _run(tmp_path, "zg", spec)
    p = res.stage("search").payload
    assert p["best_time_s"] is None
    assert p["best_genes"] == []
    assert p["history"] == [] and p["final_population"] == []
    assert res.best_time_s is None and res.speedup is None
    assert "no winner to verify" in res.stage("verify").payload["note"]
    text = res.stage("report").payload["text"]
    assert "no generations" in text
    assert "placement:" not in text
    q = res.stage("report").payload["quality"]
    assert "zero generations" in q["stability"]["skipped"]
    assert "skipped" in q["rank"]
    # the CLI report verb renders the same line from the saved artifact
    assert main(["report", "--artifact", path]) == 0
    assert "no generations" in capsys.readouterr().out
    # render_report also handles a LOADED artifact (quality from payload)
    assert "no generations" in render_report(OffloadResult.load(path))


# ---------------------------------------------------------------------------
# quality metrics in the report stage
# ---------------------------------------------------------------------------


def test_stability_section_contents(tmp_path):
    res, _ = _run(tmp_path, "x", SPEC)
    q = res.stage("report").payload["quality"]
    st = q["stability"]
    assert st["k"] == 3  # default GAControls.stability_seeds
    assert st["reused_recorded"] is True  # seed 0 came from the search
    assert st["winners"][0]["reused"] is True
    assert st["winners"][0]["best_time_s"] == res.best_time_s
    assert {w["seed"] for w in st["winners"]} == {0, 1, 2}
    assert 0.0 <= st["pass_at_k"] <= 1.0
    assert st["rel_spread"] >= 0.0
    assert "pass@" in res.stage("report").payload["text"]


def test_stability_gate_fails_report_stage(tmp_path):
    base, _ = _run(tmp_path / "base", "x", SPEC)
    spread = base.stage("report").payload["quality"]["stability"][
        "rel_spread"]
    assert spread > 0.0  # deterministic modeled search: pinned behavior
    spec = dataclasses.replace(SPEC, ga=GAControls(stability_gate=spread / 2))
    path = str(tmp_path / "gated.offload.json")
    off = Offloader(spec, artifact_path=path, trace_clock=_clock())
    with pytest.raises(StageFailure, match="stability gate"):
        off.run()
    rec = off.result.stages["report"]
    assert rec.status == "failed"
    # the quality numbers are still recorded alongside the failure
    assert rec.payload["quality"]["stability"]["rel_spread"] == spread
    # and a permissive gate passes
    ok_spec = dataclasses.replace(SPEC, ga=GAControls(stability_gate=1.0))
    res, _ = _run(tmp_path / "ok", "x", ok_spec)
    assert res.completed("report")


def test_stability_disabled_and_injected_evaluator_skips(tmp_path):
    spec = dataclasses.replace(SPEC, ga=GAControls(stability_seeds=1))
    res, _ = _run(tmp_path / "off", "x", spec)
    st = res.stage("report").payload["quality"]["stability"]
    assert "skipped" in st and "stability_seeds" in st["skipped"]

    calls = []

    def injected(genes):
        calls.append(tuple(genes))
        return 1.0 + sum(genes) * 0.01

    off = Offloader(SPEC, evaluator=injected)
    res = off.run()
    q = res.stage("report").payload["quality"]
    assert "injected" in q["stability"]["skipped"]
    assert "injected" in q["rank"]["skipped"]


def test_rank_probe_measures_two_projections(tmp_path):
    spec = dataclasses.replace(SPEC, ga=GAControls(rank_probe=True),
                               measured_scale="small")
    res, path = _run(tmp_path, "rp", spec)
    rk = res.stage("report").payload["quality"]["rank"]
    assert "skipped" not in rk
    assert rk["n"] == res.stage("search").payload["ga"]["population"]
    assert rk["spearman"] is not None
    assert -1.0 <= rk["spearman"] <= 1.0
    assert rk["distinct_measured"] <= 2  # two wall-clocked projections
    assert rk["reference"] == "model:quadro-p4000"
    tr = tm.load_trace(tm.default_trace_path(path))
    probes = [e for e in tr.events("report") if e["name"] == "rank_probe"]
    assert 1 <= len(probes) <= 2
    assert "rank fidelity spearman" in res.stage("report").payload["text"]


def test_rank_skipped_without_probe_or_implementation(tmp_path):
    res, _ = _run(tmp_path / "a", "x", SPEC)
    rk = res.stage("report").payload["quality"]["rank"]
    assert "rank_probe" in rk["skipped"]
    arch = OffloadSpec(program="arch:stablelm-3b", mode="binary",
                       ga=GAControls(rank_probe=True))
    res, _ = _run(tmp_path / "b", "x", arch)
    rk = res.stage("report").payload["quality"]["rank"]
    assert "no runnable implementation" in rk["skipped"]


# ---------------------------------------------------------------------------
# ga.diversity: off by default, byte-identical when unset
# ---------------------------------------------------------------------------


def _toy_pool_run(diversity):
    params = ga.GAParams.for_gene_length(
        6, seed=7, timeout_s=1e6, penalty_time_s=1e6, alleles=2,
        diversity=diversity,
    )
    evaluate = lambda genes: 1.0 + sum(genes) * 0.1  # noqa: E731
    return ga.run_ga(evaluate, 6, params)


def test_diversity_zero_is_byte_identical():
    a = _toy_pool_run(0.0)
    b = _toy_pool_run(0.0)
    assert a.best_genes == b.best_genes
    assert [h.population for h in a.history] == \
           [h.population for h in b.history]
    # the dataclass default IS 0.0: an unset spec changes nothing
    assert ga.GAParams.for_gene_length(6, seed=7, timeout_s=1, penalty_time_s=1).diversity == 0.0
    assert OffloadSpec(program="himeno", mode="binary").ga.diversity == 0.0


def test_diversity_changes_selection_only_when_set():
    base = _toy_pool_run(0.0)
    shared = _toy_pool_run(1.5)
    # same RNG stream, same generation 0 (selection happens after)
    assert base.history[0].population == shared.history[0].population
    # ...but fitness sharing must steer later generations differently
    assert [h.population for h in base.history] != \
           [h.population for h in shared.history]
    with pytest.raises(ValueError, match="diversity"):
        _toy_pool_run(-0.5)


def test_diversity_threads_through_the_spec(tmp_path):
    spec = dataclasses.replace(SPEC, ga=GAControls(diversity=1.0))
    res, _ = _run(tmp_path, "div", spec)
    assert res.stage("search").payload["ga"]["diversity"] == 1.0
    # spec JSON round-trip keeps the knob (dict -> GAControls coercion)
    spec2 = OffloadSpec.from_dict(json.loads(spec.to_json()))
    assert spec2.ga == GAControls(diversity=1.0)
    assert spec2 == spec


def test_fast_search_knobs_thread_through_the_spec(tmp_path):
    spec = dataclasses.replace(
        SPEC, ga=GAControls(steady_state=True), generations=4)
    res, _ = _run(tmp_path, "steady", spec)
    ga_payload = res.stage("search").payload["ga"]
    assert ga_payload["steady_state"] is True
    spec2 = OffloadSpec.from_dict(json.loads(spec.to_json()))
    assert spec2.ga.steady_state and spec2 == spec
    # knobs-off searches must not even carry the keys: payload and spec
    # JSON stay byte-identical to pre-fast-search artifacts
    base, _ = _run(tmp_path, "base", SPEC)
    assert "steady_state" not in base.stage("search").payload["ga"]
    assert "batch" not in base.stage("search").payload["ga"]
    d = json.loads(SPEC.to_json())
    assert "steady_state" not in d["ga"] and "batch" not in d["ga"]


# ---------------------------------------------------------------------------
# the trace CLI verb
# ---------------------------------------------------------------------------


def test_trace_cli_renders_budget_attribution(tmp_path, capsys):
    _, path = _run(tmp_path, "x", SPEC)
    assert main(["trace", "--artifact", path]) == 0
    out = capsys.readouterr().out
    assert "budget attribution:" in out
    assert "measurement concentration" in out
    assert "artifact digest" in out and "matches" in out
    for stage in ("calibrate", "analyze", "seed", "search", "verify",
                  "report"):
        assert stage in out
    # the evalpool's per-generation clocks (recorded under the events'
    # digest-exempt timing sub-dict) must actually be RENDERED: the
    # barrier-idle / lane-starvation column was recorded but invisible
    assert "idle_s" in out
    assert "eval_s" in out


def test_trace_cli_exit_codes(tmp_path, capsys):
    _, path = _run(tmp_path, "x", SPEC)
    trace_path = tm.default_trace_path(path)

    os.rename(trace_path, trace_path + ".gone")
    assert main(["trace", "--artifact", path]) == 1  # missing file
    os.rename(trace_path + ".gone", trace_path)

    with open(trace_path, "a", encoding="utf-8") as fh:
        fh.write("not json\n")
    assert main(["trace", "--artifact", path]) == 1  # malformed

    # a VALID but foreign/stale trace: digest mismatch against the
    # artifact's embedded digest
    other = str(tmp_path / "other.trace.jsonl")
    w = tm.TraceWriter(other, clock=_clock())
    w.run_header(program="himeno", mode="binary", fidelity="modeled",
                 spec_digest="feedface", resumed=False)
    w.close()
    capsys.readouterr()
    assert main(["trace", "--artifact", path, "--trace", other]) == 1
    assert "does not match" in capsys.readouterr().err
