"""Fault-injection + concurrency suite for the offload service
(src/repro/serve): the serving features land together with the tests
that prove their behavior under crashes, cancels and contention.

Covers the ISSUE 9 acceptance criteria directly:

- crash mid-search -> restart -> the job completes via resume with ZERO
  fresh measurements and the same winner as an uninterrupted run
  (simulated crash in the fast tier; a real SIGKILL subprocess variant
  runs @slow in the nightly tier);
- a forced duplicate submission reports a >=90% fitness-cache hit rate
  in its job trace; the coalescing path returns the first job's id;
- an injected evaluator exception FAILS that job while siblings finish;
- cancellation between pipeline stages stops the job with the terminal
  state recorded and no further stage executed;
- with the service unused (imported, even built), an Offloader run keeps
  its winner, best time and trace digest.
"""
import json
import os
import subprocess
import sys
import threading

import pytest

import repro
from repro.offload import trace as trace_mod
from repro.offload.pipeline import Offloader, _spec_digest
from repro.offload.spec import OffloadSpec
from repro.serve import jobs as jb
from repro.serve.admission import AdmissionPolicy
from repro.serve.offload_service import (
    FaultPlan,
    OffloadService,
    ServiceCrash,
)

# the suite's canonical job: hetero mixed, analytic evaluator, tiny GA —
# a full six-stage pipeline in well under a second
_SPEC_KW = dict(program="hetero", mode="mixed", population=6, generations=4,
                ga={"stability_seeds": 2})
_LAST_GEN = _SPEC_KW["generations"] - 1  # crash here = everything cached


def _spec(**kw) -> OffloadSpec:
    return OffloadSpec(**{**_SPEC_KW, **kw})


def _svc(tmp_path, **kw) -> OffloadService:
    return OffloadService(str(tmp_path / "q"), **kw)


def _search(art):
    return art.stages["search"].payload


def _winner(art):
    return (art.best_genes, art.best_time_s,
            _search(art)["placement"],
            [h["best_time_s"] for h in _search(art)["history"]])


def _terminal_event(svc, job_id):
    tr = trace_mod.load_trace(svc.store.trace_path(job_id))
    events = [e for e in tr.events("service") if e["name"] == "job_terminal"]
    assert events, "job trace records no terminal event"
    return events[-1]["attrs"]


# ---------------------------------------------------------------------------
# submission: coalescing + admission
# ---------------------------------------------------------------------------


def test_duplicate_submission_coalesces_onto_anchor(tmp_path):
    svc = _svc(tmp_path)
    r1 = svc.submit(_spec())
    r2 = svc.submit(_spec())
    assert not r1.coalesced and r2.coalesced
    assert r2.job_id == r1.job_id  # the first job's artifact id
    # cache path + workers are result-neutral: they coalesce too
    r3 = svc.submit(_spec(workers=4, cache="/elsewhere/f.jsonl"))
    assert r3.coalesced and r3.job_id == r1.job_id
    # a genuinely different spec gets its own job
    r4 = svc.submit(_spec(seed=1))
    assert not r4.coalesced and r4.job_id != r1.job_id
    assert svc.store.coalesced_count(r1.job_id) == 2
    assert [j.state for j in svc.jobs()] == [jb.QUEUED, jb.QUEUED]


def test_coalescing_still_applies_after_done_and_skips_failed(tmp_path):
    svc = _svc(tmp_path)
    r1 = svc.submit(_spec())
    svc.run()
    assert svc.status(r1.job_id).state == jb.DONE
    # DONE anchors absorb repeats: the search is never paid twice
    r2 = svc.submit(_spec())
    assert r2.coalesced and r2.job_id == r1.job_id
    # FAILED/CANCELLED anchors do NOT absorb: resubmit = retry
    svc.cancel(r1.job_id)  # terminal job ignores it; make a failed one
    bad = _svc(tmp_path, fault=FaultPlan.parse("raise-in-search:0@-r2"))
    rf = bad.submit(_spec(), force=True)
    bad.run()
    assert bad.status(rf.job_id).state == jb.FAILED
    r3 = bad.submit(_spec(seed=0), force=False)
    assert r3.coalesced and r3.job_id == r1.job_id  # DONE anchor wins


def test_admission_clamps_are_applied_and_recorded(tmp_path):
    svc = _svc(tmp_path, policy=AdmissionPolicy(
        max_in_flight=1, max_generations=2, max_population=4,
        max_stability_seeds=1))
    r = svc.submit(_spec())
    assert r.clamped == {"generations": [4, 2], "population": [6, 4],
                         "stability_seeds": [2, 1]}
    job = svc.status(r.job_id)
    assert job.clamped == r.clamped
    art = svc.result(r.job_id)
    assert art.spec.generations == 2 and art.spec.population == 4
    assert art.spec.ga.stability_seeds == 1
    svc.run()
    assert len(_search(svc.result(r.job_id))["history"]) == 2


def test_concurrent_identical_submissions_yield_one_job(tmp_path):
    svc = _svc(tmp_path)
    receipts = []
    lock = threading.Lock()

    def submit():
        r = svc.submit(_spec())
        with lock:
            receipts.append(r)

    threads = [threading.Thread(target=submit) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len({r.job_id for r in receipts}) == 1
    assert sum(not r.coalesced for r in receipts) == 1
    assert len(svc.jobs()) == 1


# ---------------------------------------------------------------------------
# fault injection: crash-resume, evaluator exception, cancellation
# ---------------------------------------------------------------------------


def test_crash_mid_search_restart_resumes_with_zero_measurements(tmp_path):
    # reference: the same spec, uninterrupted, in its own directory
    ref_svc = OffloadService(str(tmp_path / "ref"))
    ref = ref_svc.submit(_spec())
    ref_svc.run()
    ref_art = ref_svc.result(ref.job_id)

    # crash AFTER the last generation's measurements hit the shared
    # cache, BEFORE the search stage records: the worst-case kill point
    svc = _svc(tmp_path,
               fault=FaultPlan.parse(f"crash-in-search:{_LAST_GEN}"))
    r = svc.submit(_spec())
    with pytest.raises(ServiceCrash):
        svc.run()
    assert svc.status(r.job_id).state == jb.RUNNING  # the crash state

    # restart = a fresh service over the same directory, no fault
    svc2 = _svc(tmp_path)
    svc2.run()
    job = svc2.status(r.job_id)
    assert job.state == jb.DONE and job.restarts == 1
    art = svc2.result(r.job_id)
    p = _search(art)
    assert p["evaluations"] == 0, "resume must re-measure nothing"
    assert p["cache_resumed"] > 0
    assert _winner(art) == _winner(ref_art)
    # the trace survives the crash: validates whole, digest matches the
    # artifact's embedded one, and records the requeue + terminal events
    tr = trace_mod.load_trace(svc2.store.trace_path(r.job_id))
    assert art.trace["digest"] == tr.digest
    names = [e["name"] for e in tr.events("service")]
    assert "job_requeued" in names and names[-1] == "job_terminal"
    term = _terminal_event(svc2, r.job_id)
    assert term["restarts"] == 1 and term["evaluations"] == 0


def test_evaluator_exception_fails_job_while_sibling_completes(tmp_path):
    svc = _svc(tmp_path, policy=AdmissionPolicy(max_in_flight=2),
               fault=FaultPlan.parse("raise-in-search:1@-r2"))
    ra = svc.submit(_spec())
    rb = svc.submit(_spec(), force=True)  # gets id ...-r2 -> the fault
    jobs = {j.id: j for j in svc.run()}
    assert jobs[ra.job_id].state == jb.DONE
    assert jobs[rb.job_id].state == jb.FAILED
    assert "fault injected" in jobs[rb.job_id].error
    term = _terminal_event(svc, rb.job_id)
    assert term["state"] == jb.FAILED and "error" in term
    # a failed job's artifact still validates against its trace
    art = svc.result(rb.job_id)
    tr = trace_mod.load_trace(svc.store.trace_path(rb.job_id))
    assert art.trace["digest"] == tr.digest


def test_cancel_queued_job_runs_no_stage(tmp_path):
    svc = _svc(tmp_path)
    r = svc.submit(_spec())
    svc.cancel(r.job_id)
    svc.run()
    job = svc.status(r.job_id)
    assert job.state == jb.CANCELLED
    assert svc.result(r.job_id).stages == {}
    assert _terminal_event(svc, r.job_id)["state"] == jb.CANCELLED


def test_cancel_running_job_stops_between_stages(tmp_path, monkeypatch):
    svc = _svc(tmp_path)
    r = svc.submit(_spec())
    orig = Offloader.run_stage

    def run_stage_then_cancel(self, name):
        orig(self, name)
        if name == "seed":  # job is RUNNING; cancel lands mid-pipeline
            svc.cancel(r.job_id)

    monkeypatch.setattr(Offloader, "run_stage", run_stage_then_cancel)
    svc.run()
    job = svc.status(r.job_id)
    assert job.state == jb.CANCELLED
    assert "before stage 'search'" in job.error
    art = svc.result(r.job_id)
    assert art.completed("seed")
    assert "search" not in art.stages, "no stage may run past a cancel"
    assert _terminal_event(svc, r.job_id)["state"] == jb.CANCELLED


def test_recover_repairs_torn_trace_tail(tmp_path):
    svc = _svc(tmp_path,
               fault=FaultPlan.parse(f"crash-in-search:{_LAST_GEN}"))
    r = svc.submit(_spec())
    with pytest.raises(ServiceCrash):
        svc.run()
    # a SIGKILL mid-write leaves half a JSON line; recovery drops it
    trace_path = svc.store.trace_path(r.job_id)
    with open(trace_path, "a", encoding="utf-8") as fh:
        fh.write('{"seq": 99, "kind": "ev')
    with pytest.raises(trace_mod.TraceError):
        trace_mod.load_trace(trace_path)
    svc2 = _svc(tmp_path)
    svc2.run()
    assert svc2.status(r.job_id).state == jb.DONE
    trace_mod.load_trace(trace_path)  # validates whole again


# ---------------------------------------------------------------------------
# shared cache: forced duplicates are nearly free
# ---------------------------------------------------------------------------


def test_forced_duplicate_reports_cache_hit_rate(tmp_path):
    svc = _svc(tmp_path)
    r1 = svc.submit(_spec())
    svc.run()
    r2 = svc.submit(_spec(), force=True)
    assert not r2.coalesced and r2.job_id != r1.job_id
    svc.run()
    art1, art2 = svc.result(r1.job_id), svc.result(r2.job_id)
    assert _winner(art2) == _winner(art1)
    assert _search(art2)["evaluations"] == 0  # pure cache replay
    term = _terminal_event(svc, r2.job_id)
    assert term["hit_rate"] >= 0.9  # the acceptance bar; actual: 1.0
    assert term["evaluations"] == 0


def test_cross_subset_submissions_share_the_store(tmp_path):
    # different destination subsets share the subset-independent mixed
    # fingerprint: the cpu+gpu job re-uses cpu+gpu+fpga measurements
    svc = _svc(tmp_path)
    r1 = svc.submit(_spec())
    svc.run()
    r2 = svc.submit(_spec(destinations=("cpu", "gpu")))
    svc.run()
    p = _search(svc.result(r2.job_id))
    assert p["cache_hits"] > 0


# ---------------------------------------------------------------------------
# concurrency stress: coalescing + bound + serial parity
# ---------------------------------------------------------------------------


def test_concurrency_stress_matches_serial_runs(tmp_path):
    distinct = [
        _spec(),
        _spec(destinations=("cpu", "gpu")),
        _spec(destinations=("cpu", "fpga")),
        _spec(seed=1),
    ]
    svc = _svc(tmp_path, policy=AdmissionPolicy(max_in_flight=2))
    receipts = []
    lock = threading.Lock()

    def submit(spec):
        r = svc.submit(spec)
        with lock:
            receipts.append(r)

    # 8 threads, every distinct spec submitted twice: the duplicates
    # must coalesce, the distinct ones must all run
    threads = [threading.Thread(target=submit, args=(s,))
               for s in distinct * 2]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(svc.jobs()) == len(distinct)
    assert sum(r.coalesced for r in receipts) == len(distinct)

    jobs = svc.run()
    assert all(j.state == jb.DONE for j in jobs)
    assert svc.max_in_flight_seen <= 2, "admission bound exceeded"

    # serial reference: each spec through a plain Offloader, alone
    by_digest = {jb.coalesce_key(svc.normalize(s)): s for s in distinct}
    for j in jobs:
        spec = by_digest[j.digest]
        ref_dir = tmp_path / f"serial-{j.digest}"
        ref = Offloader(
            OffloadSpec(**{**_SPEC_KW,
                           **{k: getattr(spec, k)
                              for k in ("destinations", "seed")}}),
            artifact_path=str(ref_dir / "ref.offload.json"),
        ).run()
        assert _winner(svc.result(j.id)) == _winner(ref), j.id


# ---------------------------------------------------------------------------
# state machine guard rails (the persisted store side)
# ---------------------------------------------------------------------------


def test_illegal_transition_raises_and_leaves_record_untouched(tmp_path):
    svc = _svc(tmp_path)
    r = svc.submit(_spec())
    art = svc.store.load(r.job_id)
    with pytest.raises(jb.JobError):
        svc.store.transition(art, jb.DONE)  # QUEUED -> DONE is illegal
    assert svc.status(r.job_id).state == jb.QUEUED
    svc.run()
    art = svc.store.load(r.job_id)
    for target in (jb.RUNNING, jb.QUEUED, jb.FAILED, jb.CANCELLED):
        with pytest.raises(jb.JobError):
            svc.store.transition(art, target)  # DONE is terminal
    assert svc.status(r.job_id).state == jb.DONE


def test_unknown_job_and_duplicate_create_raise(tmp_path):
    svc = _svc(tmp_path)
    with pytest.raises(jb.JobError):
        svc.status("jb-0000000000")
    r = svc.submit(_spec())
    with pytest.raises(jb.JobError):
        svc.store.create(svc.normalize(_spec()),
                         jb.Job(id=r.job_id, state=jb.QUEUED,
                                digest=r.digest, seq=99))


# ---------------------------------------------------------------------------
# CLI: the filesystem queue is fully drivable without sockets
# ---------------------------------------------------------------------------


def test_cli_serve_roundtrip(tmp_path, capsys):
    from repro.offload.__main__ import main

    q = str(tmp_path / "q")
    spec_args = ["--program", "hetero", "--mode", "mixed",
                 "--population", "6", "--generations", "4",
                 "--stability-seeds", "2"]
    assert main(["serve", "submit", "--dir", q, *spec_args,
                 "--quiet"]) == 0
    job_id = capsys.readouterr().out.strip()
    assert job_id.startswith("jb-")
    assert main(["serve", "submit", "--dir", q, *spec_args]) == 0
    assert f"coalesced onto existing job {job_id}" in capsys.readouterr().out
    assert main(["serve", "run", "--dir", q]) == 0
    out = capsys.readouterr().out
    assert job_id in out and "done" in out and "(+1 coalesced)" in out
    assert main(["serve", "status", "--dir", q, "--job", job_id]) == 0
    assert "done" in capsys.readouterr().out
    assert main(["serve", "result", "--dir", q, "--job", job_id]) == 0
    out = capsys.readouterr().out
    assert "OffloadResult[hetero/mixed" in out and "artifact:" in out
    # the job's trace renders + digest-checks through the trace verb
    art_path = os.path.join(q, "jobs", f"{job_id}.offload.json")
    assert main(["trace", "--artifact", art_path]) == 0
    assert "service::job_terminal" in capsys.readouterr().out
    # unknown job ids exit 1 on every query verb
    assert main(["serve", "status", "--dir", q, "--job", "jb-nope"]) == 1
    assert main(["serve", "result", "--dir", q, "--job", "jb-nope"]) == 1
    assert main(["serve", "cancel", "--dir", q, "--job", "jb-nope"]) == 1
    capsys.readouterr()


def test_cli_serve_run_reports_failed_jobs(tmp_path, capsys):
    from repro.offload.__main__ import main

    q = str(tmp_path / "q")
    spec_args = ["--program", "hetero", "--mode", "mixed",
                 "--population", "6", "--generations", "4",
                 "--stability-seeds", "2"]
    assert main(["serve", "submit", "--dir", q, *spec_args,
                 "--quiet"]) == 0
    capsys.readouterr()
    assert main(["serve", "run", "--dir", q, "--fault",
                 "raise-in-stage:search"]) == 1
    assert "failed" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# the service layer is invisible when unused
# ---------------------------------------------------------------------------


def _pinned_run(path):
    """(winner, best time, trace digest) of one Offloader run of the
    suite's spec under a pinned trace clock."""
    import itertools

    clock = itertools.count(0.0, 0.25)
    art = Offloader(_spec(), artifact_path=str(path),
                    trace_clock=lambda c=clock: next(c)).run()
    return art.best_genes, art.best_time_s, art.trace["digest"]


# runs the suite's spec in a fresh process before and after importing
# repro.serve and building an unused OffloadService
_UNUSED_SERVICE = """
import itertools, json, sys
from repro.offload.pipeline import Offloader
from repro.offload.spec import OffloadSpec

def run(path):
    clock = itertools.count(0.0, 0.25)
    art = Offloader(OffloadSpec.from_json(sys.argv[1]), artifact_path=path,
                    trace_clock=lambda c=clock: next(c)).run()
    return [list(art.best_genes), art.best_time_s, art.trace["digest"]]

assert "repro.serve" not in sys.modules
before = run(sys.argv[2] + "/before.offload.json")
from repro.serve.offload_service import OffloadService
OffloadService(sys.argv[2] + "/q")
after = run(sys.argv[2] + "/after.offload.json")
print(json.dumps([before, after]))
"""


def test_offloader_runs_repeat_under_a_pinned_clock(tmp_path):
    first = _pinned_run(tmp_path / "a.offload.json")
    assert first[0] and first[1] is not None
    assert _pinned_run(tmp_path / "b.offload.json") == first


def test_unused_service_keeps_offloader_byte_identical(tmp_path):
    env = dict(os.environ)
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", _UNUSED_SERVICE, _spec().to_json(),
         str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    before, after = json.loads(out.stdout.strip().splitlines()[-1])
    assert after == before
    # and the same three as this process's own run of the spec
    genes, best_s, digest = _pinned_run(tmp_path / "here.offload.json")
    assert before == [list(genes), best_s, digest]


def test_offloader_artifact_carries_no_job_field(tmp_path):
    _pinned_run(tmp_path / "plain.offload.json")
    saved = json.loads((tmp_path / "plain.offload.json").read_text())
    assert "job" not in saved


def test_spec_json_round_trips_and_equal_specs_digest_equal():
    for spec in (_spec(), OffloadSpec(program="himeno"),
                 OffloadSpec(program="hetero", mode="mixed")):
        again = OffloadSpec.from_json(spec.to_json())
        assert again == spec
        assert _spec_digest(again) == _spec_digest(spec)
    assert _spec_digest(_spec()) == _spec_digest(_spec())
    assert _spec_digest(_spec()) != _spec_digest(_spec(seed=1))


# ---------------------------------------------------------------------------
# the real thing: SIGKILL the service process, restart, resume (@slow)
# ---------------------------------------------------------------------------


def _serve_cli(args, **kw):
    env = dict(os.environ)
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.offload", "serve", *args],
        env=env, capture_output=True, text=True, timeout=600, **kw)


@pytest.mark.slow
def test_sigkill_service_process_restart_resumes(tmp_path):
    q = str(tmp_path / "q")
    spec_args = ["--program", "hetero", "--mode", "mixed",
                 "--population", "6", "--generations", "4",
                 "--stability-seeds", "2"]
    sub = _serve_cli(["submit", "--dir", q, *spec_args, "--quiet"])
    assert sub.returncode == 0, sub.stderr
    job_id = sub.stdout.strip()

    # the service process SIGKILLs ITSELF at the last generation: no
    # cleanup, no atexit — the artifact says RUNNING, the cache is warm
    killed = _serve_cli(["run", "--dir", q, "--fault",
                         f"kill-in-search:{_LAST_GEN}"])
    assert killed.returncode == -9, (killed.returncode, killed.stderr)
    svc = OffloadService(q)
    assert svc.status(job_id).state == jb.RUNNING

    restarted = _serve_cli(["run", "--dir", q])
    assert restarted.returncode == 0, restarted.stderr
    job = svc.status(job_id)
    assert job.state == jb.DONE and job.restarts == 1
    art = svc.result(job_id)
    assert _search(art)["evaluations"] == 0
    # same winner as an uninterrupted run of the same spec
    ref_svc = OffloadService(str(tmp_path / "ref"))
    ref = ref_svc.submit(_spec())
    ref_svc.run()
    assert _winner(art) == _winner(ref_svc.result(ref.job_id))
