"""Offload-as-a-service: concurrent `Offloader` runs over one shared
fitness-cache store, with admission control and crash-safe jobs.

The paper's end state is environment-adaptive software as a *service*
(arXiv:2002.12115 §6): users submit once-written code, the platform
converts/verifies/places it per environment. This module is that shape
for the repro pipeline: an :class:`OffloadService` accepts
:class:`~repro.offload.spec.OffloadSpec` submissions into a
filesystem-backed queue directory (:class:`~repro.serve.jobs.JobStore`),
admits them under an :class:`~repro.serve.admission.AdmissionPolicy`
(budget clamps, duplicate coalescing), and drains the queue over a
bounded worker pool — every job one full `Offloader` pipeline, all jobs
multiplexed over ONE shared JSONL fitness-cache store through an
:class:`~repro.core.evalpool.EvalBroker` (cache keys are evaluator-
fingerprinted, so cross-user sharing is safe and is the whole point: a
repeat submission is mostly cache hits).

Crash safety: the artifact IS the job record (:mod:`repro.serve.jobs`),
the cache store survives any kill, and :meth:`OffloadService.recover`
re-queues every artifact left RUNNING — so *restart = resume every
non-terminal job*, with zero re-measurement of anything already paid
for. Because a server is only as good as its behavior under crashes,
fault injection is built in (:class:`FaultPlan`): the test suite — and
``--fault`` on the CLI — can raise inside a stage, crash the service
after a stage, or SIGKILL it mid-search at a chosen generation
(docs/serving.md#fault-injection).

Nothing here is imported by the pipeline: importing this module and
building an unused service leaves an `Offloader` run's winner, best time
and trace digest unchanged (tested in tests/test_offload_service.py).
"""
from __future__ import annotations

import dataclasses
import json
import os
import signal
import threading
import time
from concurrent.futures import FIRST_EXCEPTION, ThreadPoolExecutor, wait
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.core.evalpool import EvalBroker, evaluator_fingerprint
from repro.offload import trace as trace_mod
from repro.offload.pipeline import Offloader, _spec_digest
from repro.offload.result import STAGES, OffloadResult
from repro.offload.spec import OffloadSpec
from repro.serve.admission import AdmissionPolicy, admit
from repro.serve import jobs as jb


class ServiceCrash(RuntimeError):
    """Simulated process death (fault injection): the service run loop
    aborts WITHOUT transitioning the job — on disk it stays RUNNING,
    exactly as a SIGKILL would leave it, so recovery paths are testable
    in-process in the fast tier."""


# fault kinds: where the failure fires and what it does there.
#   raise-in-stage:<stage>    raise before entering <stage> -> job FAILED
#   raise-in-search:<gen>     raise from the GA loop at generation <gen>
#                             (the "evaluator blew up" fault) -> FAILED
#   crash-after-stage:<stage> ServiceCrash after <stage> completes
#   crash-in-search:<gen>     ServiceCrash from the GA loop at <gen>
#   kill-after-stage:<stage>  SIGKILL self after <stage> completes
#   kill-in-search:<gen>      SIGKILL self from the GA loop at <gen>
_FAULT_KINDS = (
    "raise-in-stage", "raise-in-search",
    "crash-after-stage", "crash-in-search",
    "kill-after-stage", "kill-in-search",
)


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """One injected fault, parsed from ``<kind>:<arg>[@<job-match>]``
    (e.g. ``crash-in-search:7``, ``raise-in-stage:verify@jb-ab12``).
    ``job-match`` is a substring filter on the job id; omitted = every
    job. The search-generation faults fire from the Offloader's
    per-generation callback, i.e. *after* that generation's measurements
    are in the shared cache — which is what makes kill-at-last-generation
    the canonical "resume must re-measure nothing" scenario."""

    kind: str
    arg: str
    match: str = ""

    @classmethod
    def parse(cls, text: str) -> "FaultPlan":
        body, _, match = text.partition("@")
        kind, sep, arg = body.partition(":")
        if not sep or kind not in _FAULT_KINDS:
            raise ValueError(
                f"fault must be <kind>:<arg>[@<job-match>] with kind in "
                f"{_FAULT_KINDS}; got {text!r}"
            )
        if kind.endswith("-in-search"):
            int(arg)  # generation number; raise early on junk
        return cls(kind=kind, arg=arg, match=match)

    def applies_to(self, job_id: str) -> bool:
        return self.match in job_id

    def _fire(self, what: str) -> None:
        if self.kind.startswith("raise-"):
            raise RuntimeError(f"fault injected: {what}")
        if self.kind.startswith("crash-"):
            raise ServiceCrash(f"fault injected: {what}")
        os.kill(os.getpid(), signal.SIGKILL)  # kill-*: no cleanup at all

    def before_stage(self, job_id: str, stage: str) -> None:
        if self.kind == "raise-in-stage" and self.arg == stage \
                and self.applies_to(job_id):
            self._fire(f"{self.kind}:{stage}")

    def after_stage(self, job_id: str, stage: str) -> None:
        if self.kind in ("crash-after-stage", "kill-after-stage") \
                and self.arg == stage and self.applies_to(job_id):
            self._fire(f"{self.kind}:{stage}")

    def on_generation(self, job_id: str, generation: int) -> None:
        if self.kind.endswith("-in-search") and self.applies_to(job_id) \
                and generation == int(self.arg):
            self._fire(f"{self.kind}:{generation}")


@dataclasses.dataclass(frozen=True)
class SubmitReceipt:
    """What :meth:`OffloadService.submit` hands back."""

    job_id: str
    coalesced: bool  # True: job_id is an EXISTING job covering this spec
    digest: str  # the spec's coalesce key
    clamped: Dict[str, List[int]]  # admission clamps applied (may be {})


def _cache_stats(art: OffloadResult) -> Tuple[int, int]:
    """(cache hits, fresh measurements) this artifact's recorded search
    work paid — the per-job cache hit-rate the trace reports."""
    hits = evals = 0
    if "seed" in art.stages:
        for info in art.stages["seed"].payload.get("seed_info", []):
            hits += int(info.get("cache_hits", 0))
            evals += int(info.get("evaluations", 0))
    if "search" in art.stages:
        p = art.stages["search"].payload
        hits += int(p.get("cache_hits", 0))
        evals += int(p.get("evaluations", 0))
    return hits, evals


class OffloadService:
    """The queue-fed offload search service (docs/serving.md).

    Parameters
    ----------
    root:
        The queue directory (:class:`~repro.serve.jobs.JobStore` layout).
        Everything the service is — jobs, artifacts, traces, the shared
        fitness-cache store — lives under it; a second construction over
        the same directory (after a crash, in another process) sees the
        same service.
    policy:
        Admission policy; defaults to :class:`AdmissionPolicy` defaults
        (2 in-flight, no budget bounds, coalescing on).
    fault:
        Optional :class:`FaultPlan` — the fault-injection harness the
        test suite and ``serve run --fault`` use.
    trace_clock:
        Injected clock for the service's trace records (tests pin it;
        timing never enters trace digests either way).
    """

    def __init__(
        self,
        root: str,
        policy: Optional[AdmissionPolicy] = None,
        fault: Optional[FaultPlan] = None,
        trace_clock: Optional[Callable[[], float]] = None,
    ):
        self.store = jb.JobStore(root)
        self.policy = policy or AdmissionPolicy()
        self.fault = fault
        self._trace_clock = trace_clock
        # one submission at a time: concurrent identical submissions
        # must see each other (coalesce), not race to the anchor id
        self._submit_lock = threading.Lock()
        self._gauge_lock = threading.Lock()
        self._in_flight = 0
        self.max_in_flight_seen = 0  # high-water mark (stress tests)

    # -- submission --------------------------------------------------------

    def normalize(self, spec: OffloadSpec) -> OffloadSpec:
        """The spec as the service runs it: the fitness cache pinned to
        the service's shared store (every job shares it — including the
        report stage's stability re-searches, which open it by path)."""
        if spec.cache == self.store.cache_path:
            return spec
        return dataclasses.replace(spec, cache=self.store.cache_path)

    def submit(self, spec: OffloadSpec, force: bool = False) -> SubmitReceipt:
        """Admit one spec. Duplicate submissions (same coalesce key as a
        live or DONE job) return that job's id instead of searching
        twice; ``force=True`` runs a fresh job anyway (it still shares
        the fitness cache, so it is mostly hits). FAILED/CANCELLED
        anchors never absorb a submission — resubmitting is the retry
        path."""
        decision = admit(self.normalize(spec), self.policy)
        digest = jb.coalesce_key(decision.spec)
        with self._submit_lock:
            return self._submit_admitted(decision, digest, force)

    def _submit_admitted(self, decision, digest: str,
                         force: bool) -> SubmitReceipt:
        if self.policy.coalesce and not force:
            live = [j for j in self.store.by_digest(digest)
                    if j.state not in (jb.FAILED, jb.CANCELLED)]
            if live:
                anchor = live[0]  # lowest seq: the original submission
                self.store.record_coalesced(anchor.id, digest)
                return SubmitReceipt(job_id=anchor.id, coalesced=True,
                                     digest=digest,
                                     clamped=decision.clamped)
        job_id = self.store.allocate_id(digest)
        job = jb.Job(
            id=job_id, state=jb.QUEUED, digest=digest,
            seq=self.store.next_seq(), clamped=decision.clamped,
            submitted_ts=time.time(),
        )
        self.store.create(decision.spec, job)
        # the job's trace starts here: the service writes the run header
        # (so the file validates stand-alone even if the job never runs)
        # plus the admission record; the Offloader appends its own header
        # and spans later. All wall clocks go under `timing` — service
        # records must not perturb trace digests' determinism rules.
        with trace_mod.TraceWriter(self.store.trace_path(job_id),
                                   clock=self._trace_clock) as w:
            w.run_header(
                program=decision.spec.program, mode=decision.spec.mode,
                fidelity=decision.spec.fidelity,
                spec_digest=_spec_digest(decision.spec), resumed=False,
            )
            w.event("job_submitted", span="service",
                    attrs={"job": job_id, "digest": digest,
                           "seq": job.seq, "forced": bool(force)},
                    timing={"submitted_ts": job.submitted_ts})
            w.event("admission", span="service", attrs={
                "max_in_flight": self.policy.max_in_flight,
                "clamped": {k: list(v)
                            for k, v in sorted(decision.clamped.items())},
            })
        return SubmitReceipt(job_id=job_id, coalesced=False, digest=digest,
                             clamped=decision.clamped)

    # -- queries / control -------------------------------------------------

    def status(self, job_id: str) -> jb.Job:
        return self.store.job(job_id)

    def jobs(self) -> List[jb.Job]:
        return self.store.list_jobs()

    def result(self, job_id: str) -> OffloadResult:
        return self.store.load(job_id)

    def cancel(self, job_id: str) -> jb.Job:
        """Request cancellation. A QUEUED job is finalized by the next
        scheduler pass before it starts; a RUNNING job stops at the next
        stage boundary; a terminal job ignores the request."""
        return self.store.request_cancel(job_id)

    # -- crash recovery ----------------------------------------------------

    def recover(self) -> List[str]:
        """Re-queue every job a dead service left RUNNING (the artifact
        is the job record, so this is a directory scan + transition).
        Also repairs a torn trailing trace line a SIGKILL mid-write can
        leave. Returns the re-queued job ids."""
        out: List[str] = []
        for j in self.store.list_jobs():
            if j.state != jb.RUNNING:
                continue
            _repair_trace_tail(self.store.trace_path(j.id))
            art = self.store.load(j.id)
            with trace_mod.TraceWriter(self.store.trace_path(j.id),
                                       clock=self._trace_clock) as w:
                w.event("job_requeued", span="service",
                        attrs={"job": j.id, "restarts": j.restarts + 1})
                art.trace = w.summary()
            self.store.transition(art, jb.QUEUED, restarted=True)
            out.append(j.id)
        return out

    # -- the scheduler -----------------------------------------------------

    def run(self) -> List[jb.Job]:
        """Recover, then drain the queue: every QUEUED job runs exactly
        once, at most ``policy.max_in_flight`` concurrently, in
        admission order. Returns the final job list. A ServiceCrash
        fault aborts the drain mid-flight (pending jobs stay QUEUED,
        the crashed one stays RUNNING) and re-raises — callers treat it
        as process death."""
        self.recover()
        broker = EvalBroker(self.store.cache_path)
        ex = ThreadPoolExecutor(max_workers=self.policy.max_in_flight)
        try:
            futs = [
                ex.submit(self._run_job_gauged, j.id, broker)
                for j in self.store.list_jobs() if j.state == jb.QUEUED
            ]
            done, _ = wait(futs, return_when=FIRST_EXCEPTION)
            for f in done:
                exc = f.exception()
                if exc is not None:
                    raise exc  # ServiceCrash: simulated death, mid-drain
            ex.shutdown(wait=True)
        except BaseException:
            ex.shutdown(wait=False, cancel_futures=True)
            raise
        finally:
            broker.close()
        return self.store.list_jobs()

    def _run_job_gauged(self, job_id: str, broker: EvalBroker) -> None:
        with self._gauge_lock:
            self._in_flight += 1
            self.max_in_flight_seen = max(self.max_in_flight_seen,
                                          self._in_flight)
        try:
            self._run_job(job_id, broker)
        finally:
            with self._gauge_lock:
                self._in_flight -= 1

    def _run_job(self, job_id: str, broker: EvalBroker) -> None:
        art = self.store.load(job_id)
        job = jb.Job.from_dict(art.job)
        if job.state != jb.QUEUED:
            return  # raced to terminal, or owned elsewhere
        if self.store.cancel_requested(job_id):
            self._finalize(art, jb.CANCELLED,
                           error="cancelled before start")
            return
        self.store.transition(art, jb.RUNNING)
        with trace_mod.TraceWriter(self.store.trace_path(job_id),
                                   clock=self._trace_clock) as w:
            w.event("job_started", span="service",
                    attrs={"job": job_id, "restarts": job.restarts},
                    timing={"queue_wait_s":
                            max(0.0, time.time() - job.submitted_ts)})

        fault = self.fault

        def on_generation(gs) -> None:
            if fault is not None:
                fault.on_generation(job_id, int(gs.generation))

        # the Offloader appends to the same trace file (its writer
        # replays the service's records and continues the sequence); the
        # service writes NOTHING more until the pipeline is done — two
        # live writers on one trace would fork the seq numbering.
        off = Offloader(
            art.spec, artifact=art,
            artifact_path=self.store.artifact_path(job_id),
            trace_path=self.store.trace_path(job_id),
            trace_clock=self._trace_clock,
            on_generation=on_generation,
            cache_factory=lambda ev: broker.open_cache(
                evaluator_fingerprint(ev)),
        )
        try:
            for name in STAGES:
                if art.completed(name):
                    continue
                if self.store.cancel_requested(job_id):
                    self._finalize(art, jb.CANCELLED,
                                   error=f"cancelled before stage {name!r}")
                    return
                if fault is not None:
                    fault.before_stage(job_id, name)
                off.run_stage(name)
                if fault is not None:
                    fault.after_stage(job_id, name)
        except ServiceCrash:
            raise  # job stays RUNNING on disk: that IS the crash state
        except Exception as e:  # noqa: BLE001 — any stage/injected error
            self._finalize(art, jb.FAILED, error=repr(e))
            return
        self._finalize(art, jb.DONE)

    def _finalize(self, art: OffloadResult, state: str,
                  error: Optional[str] = None) -> jb.Job:
        """Terminal bookkeeping: append the ``job_terminal`` trace event
        (with the job's cache hit-rate), re-embed the trace summary, and
        persist the terminal transition — one save, via the store's
        state machine."""
        job_id = art.job["id"]
        hits, evals = _cache_stats(art)
        attrs: Dict[str, Any] = {
            "job": job_id, "state": state,
            "cache_hits": hits, "evaluations": evals,
            "restarts": int(art.job.get("restarts", 0)),
        }
        if hits + evals:
            attrs["hit_rate"] = round(hits / (hits + evals), 4)
        if error is not None:
            attrs["error"] = error
        with trace_mod.TraceWriter(self.store.trace_path(job_id),
                                   clock=self._trace_clock) as w:
            w.event("job_terminal", span="service", attrs=attrs)
            art.trace = w.summary()
        return self.store.transition(art, state, error=error)


def _repair_trace_tail(path: str) -> None:
    """Drop a torn trailing line a SIGKILL mid-write can leave in a
    trace file (every earlier line was flushed whole). Corruption
    anywhere else is NOT repaired — load_trace will reject it loudly."""
    if not os.path.exists(path):
        return
    with open(path, "r", encoding="utf-8") as fh:
        lines = fh.readlines()
    if not lines:
        return
    tail = lines[-1]
    try:
        json.loads(tail)
        complete = tail.endswith("\n")
    except (json.JSONDecodeError, ValueError):
        complete = False
    if complete:
        return
    with open(path, "w", encoding="utf-8") as fh:
        fh.writelines(lines[:-1])
