"""The Offloader facade: the paper's whole flow as one staged pipeline.

Stages (in order, each recorded into the :class:`OffloadResult` artifact):

- **calibrate** — fidelity="calibrated" only: measure the designed probe
  set on this machine, fit per-destination constants by least squares
  (:mod:`repro.offload.calibrate`), install the resulting named machine
  entry, and record the fit residuals. Every other fidelity records the
  stage as not applicable.
- **analyze** — load the program, assign directives per loop/unit (the
  paper's Clang-parse + pgcc-classification step), price the all-host
  baseline (a REAL wall-clocked run under fidelity="measured").
- **seed** — build the initial-population seeds. With
  ``spec.warm_start`` (mixed mode), runs one quick binary GA per
  non-host destination and re-expresses each single-destination best in
  the full k-ary alphabet (genome-aware seeding); the pre-searches share
  the spec's fitness cache with the main search (the mixed fingerprint
  is subset-independent).
- **search** — the GA over an :class:`EvalPool` with the persistent
  JSONL fitness cache; a killed search re-run resumes warm from the
  cache without re-measuring anything already paid for.
- **verify** — re-measure the winner against the recorded best (exact
  for the analytic evaluators) and run the PCAST result-difference check
  of the offloaded implementation vs the CPU reference, where the
  program has a runnable implementation.
- **report** — render the human-readable summary into the artifact.

Completed stages are skipped when re-running from a loaded artifact, so
``Offloader.resume(path).run()`` continues a killed pipeline exactly
where it stopped. A stage failure is recorded (status ``failed``) and
saved *before* :class:`StageFailure` propagates, so the artifact always
reflects what actually happened.

With ``spec`` defaults, the facade's searches are byte-identical to the
pre-redesign hand-wired paths (parity-tested in
tests/test_offload_pipeline.py): same GAParams, same pool construction,
same RNG stream.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Dict, Optional, Sequence

from repro.core import ga
from repro.core.evalpool import (
    EvalPool,
    FitnessCache,
    evaluator_fingerprint,
)
from repro.core.evaluator import HardwareModel
from repro.offload import programs
from repro.offload import quality as qual
from repro.offload import trace as trace_mod
from repro.offload.result import (
    STAGES,
    OffloadResult,
    StageFailure,
    timed,
)
from repro.offload.spec import OffloadSpec

# relative mismatch tolerated when re-measuring the winner with a
# deterministic (analytic) evaluator
_REMEASURE_RTOL = 1e-9


def _spec_digest(spec: OffloadSpec) -> str:
    """Short content digest of the spec (trace run headers)."""
    return hashlib.sha256(spec.to_json().encode("utf-8")).hexdigest()[:16]


def _evaluator_label(evaluator) -> str:
    """The evaluator's fingerprint, or an explicit ``injected:`` marker
    for fingerprint-less injected callables. This labels stage payloads
    for the resume drift guard only — persistent fitness-cache keying
    always goes through ``evaluator_fingerprint``, which refuses
    fingerprint-less evaluators outright."""
    if callable(getattr(evaluator, "fingerprint", None)):
        return evaluator_fingerprint(evaluator)
    mod = getattr(evaluator, "__module__", type(evaluator).__module__)
    name = getattr(evaluator, "__qualname__", type(evaluator).__qualname__)
    return f"injected:{mod}.{name}"


def _span_attrs(name: str, payload: Dict[str, Any]) -> Dict[str, Any]:
    """Deterministic data attrs for a stage span, derived from the stage
    payload alone (wall clocks stay out — they belong to span timing,
    which the trace digest ignores)."""
    a: Dict[str, Any] = {}
    if name == "calibrate":
        a["applicable"] = bool(payload.get("applicable"))
        if payload.get("entry"):
            a["entry"] = payload["entry"]
    elif name == "analyze":
        if "gene_length" in payload:
            a["gene_length"] = int(payload["gene_length"])
        if "baseline_s" in payload:
            a["baseline_s"] = float(payload["baseline_s"])
        if "blocks" in payload:  # key only present on block-enabled runs
            a["block_matches"] = len(payload["blocks"].get("matches", []))
    elif name == "seed":
        a["seeds"] = len(payload.get("seeds", []))
    elif name == "search":
        a["evaluations"] = int(payload.get("evaluations", 0))
        a["cache_hits"] = int(payload.get("cache_hits", 0))
        a["timeouts"] = int(payload.get("timeouts", 0))
        a["generations"] = len(payload.get("history", []))
        if payload.get("best_time_s") is not None:
            a["best_time_s"] = float(payload["best_time_s"])
        if "substitutions" in payload:  # block-enabled runs only
            a["substitutions"] = sum(
                1 for s in payload["substitutions"] if s.get("active")
            )
    elif name == "verify":
        pc = payload.get("pcast") or {}
        a["pcast"] = "skipped" if "skipped" in pc else (
            "ok" if pc.get("ok") else "fail") if pc else "none"
        a["consistent"] = bool(payload.get("consistent", False))
        if "block_oracles" in payload:  # block-enabled runs only
            a["block_oracles"] = "ok" if all(
                r.get("ok") for r in payload["block_oracles"]
            ) else "fail"
    elif name == "report":
        # NOTE: no "evaluations" attr here — the report span's
        # stability_search / rank_probe EVENTS carry the measurement
        # counts, and the budget table counts events only when the
        # span has no count of its own (else it would double-count)
        q = payload.get("quality") or {}
        st = q.get("stability") or {}
        if "pass_at_k" in st:
            a["pass_at_k"] = st["pass_at_k"]
            a["stability_k"] = st["k"]
        rk = q.get("rank") or {}
        if rk.get("spearman") is not None:
            a["spearman"] = round(float(rk["spearman"]), 4)
    return a


class Offloader:
    """Facade running the staged pipeline for one :class:`OffloadSpec`.

    Parameters
    ----------
    spec:
        The declarative pipeline input.
    artifact:
        An existing :class:`OffloadResult` to continue (its completed
        stages are skipped). Defaults to a fresh artifact for ``spec``.
    artifact_path:
        Where to save the artifact after every stage (None = in-memory).
    evaluator:
        Injected evaluator for the search/verify stages, overriding the
        adapter's (e.g. a ``CompiledEvaluator``, or a calibration
        candidate). Injection is process-local: resuming such an
        artifact in a new process needs the same injection again.
    hw:
        Injected :class:`HardwareModel` overriding the ``spec.hw``
        registry lookup (calibration sweeps score unregistered
        candidate models).
    calibration:
        A pre-built ``CalibrationResult`` for fidelity="calibrated"
        specs: the calibrate stage records and installs it instead of
        re-measuring the probe set (calibrate once, search many apps).
        Its ``base`` must match ``spec.hw``.
    on_generation:
        Optional per-generation callback forwarded to ``run_ga``.
    trace:
        Write a structured JSONL trace next to the artifact
        (:mod:`repro.offload.trace`). On by default; a no-op for
        in-memory artifacts unless ``trace_path`` names a file. The
        trace never feeds back into any stage, so search results and
        cache fingerprints are byte-identical with tracing on or off.
    trace_path:
        Explicit trace file path (default: artifact path with
        ``.json`` swapped for ``.trace.jsonl``).
    trace_clock:
        Injected monotonic clock for the trace spans (tests pin it to
        make whole trace files deterministic; timing never enters the
        trace digest either way).
    cache_factory:
        Injected ``evaluator -> FitnessCache`` opener overriding the
        default per-stage ``FitnessCache(spec.cache, fingerprint)``
        construction. The serving layer (repro.serve) passes an
        :class:`~repro.core.evalpool.EvalBroker` view opener here so
        concurrent jobs share one in-memory store; the stage still calls
        ``close()`` on what it gets back, so factories must hand out
        refcounted views. ``None`` (the default) keeps single-run
        behavior byte-identical to the pre-serving pipeline.
    """

    def __init__(
        self,
        spec: OffloadSpec,
        artifact: Optional[OffloadResult] = None,
        artifact_path: Optional[str] = None,
        evaluator: Optional[Callable[[Sequence[int]], float]] = None,
        hw: Optional[HardwareModel] = None,
        calibration=None,
        on_generation: Optional[Callable[[ga.GenerationStats], None]] = None,
        trace: bool = True,
        trace_path: Optional[str] = None,
        trace_clock: Optional[Callable[[], float]] = None,
        cache_factory: Optional[
            Callable[[Callable], Optional[FitnessCache]]
        ] = None,
    ):
        if artifact is not None and artifact.spec != spec:
            raise ValueError("artifact was produced by a different spec; "
                             "use Offloader.resume to continue it")
        self.spec = spec
        self.result = artifact or OffloadResult(spec=spec)
        if artifact_path is not None:
            self.result.path = artifact_path
        self._evaluator = evaluator
        self._hw = hw
        self._on_generation = on_generation
        self._trace_enabled = trace
        self._trace_path = trace_path
        self._trace_clock = trace_clock
        self._cache_factory = cache_factory
        self._tracer: Optional[trace_mod.TraceWriter] = None
        self._trace_header_written = False
        self._adapter = None  # built lazily (adapters may import jax-side)
        # CalibrationResult (fidelity="calibrated" only); an injected one
        # is recorded by the calibrate stage in place of a fresh sweep
        if calibration is not None and calibration.base != spec.hw:
            raise ValueError(
                f"injected calibration was fitted for base "
                f"{calibration.base!r}, spec.hw is {spec.hw!r}"
            )
        self._injected_cal = calibration
        self._cal = None

    @classmethod
    def resume(
        cls,
        artifact_path: str,
        evaluator: Optional[Callable[[Sequence[int]], float]] = None,
        hw: Optional[HardwareModel] = None,
        on_generation: Optional[Callable[[ga.GenerationStats], None]] = None,
        trace: bool = True,
        trace_path: Optional[str] = None,
        trace_clock: Optional[Callable[[], float]] = None,
        cache_factory: Optional[
            Callable[[Callable], Optional[FitnessCache]]
        ] = None,
    ) -> "Offloader":
        """Continue a saved artifact: its spec is authoritative and its
        completed stages are skipped on the next :meth:`run`. An
        existing trace file is continued, not truncated (the resumed
        process appends a second run header)."""
        art = OffloadResult.load(artifact_path)
        return cls(art.spec, artifact=art, artifact_path=artifact_path,
                   evaluator=evaluator, hw=hw, on_generation=on_generation,
                   trace=trace, trace_path=trace_path,
                   trace_clock=trace_clock, cache_factory=cache_factory)

    # -- plumbing ----------------------------------------------------------

    @property
    def adapter(self):
        if self._adapter is None:
            self._adapter = programs.resolve_adapter(
                self._effective_spec(), self._hw
            )
        return self._adapter

    def _effective_spec(self) -> OffloadSpec:
        """The spec the adapters see. fidelity="calibrated" resolves to a
        MODELED spec pointing at the installed calibrated machine entry —
        downstream stages price candidates exactly like any other modeled
        search, just under the fitted constants (whose fingerprints carry
        the calibration digest). The artifact keeps the original spec."""
        if self.spec.fidelity != "calibrated":
            return self.spec
        cal = self._ensure_calibration()
        return dataclasses.replace(
            self.spec, fidelity="modeled", hw=cal.name
        )

    def _ensure_calibration(self):
        """The CalibrationResult for this run, installed in-process.
        After the calibrate stage it is cached; on resume it is rebuilt
        from the stage payload (same constants -> same digest -> same
        fingerprints, so resumed searches keep their cache hits) without
        re-measuring anything."""
        if self._cal is not None:
            return self._cal
        from repro.offload import calibrate

        if not self.result.completed("calibrate"):
            raise StageFailure(
                "calibrate",
                "fidelity='calibrated' needs the calibrate stage to run "
                "before any adapter-facing stage (run() orders this)",
            )
        payload = self.result.stage("calibrate").payload
        cal = calibrate.CalibrationResult.from_dict(payload["calibration"])
        calibrate.install(cal, replace=True)
        self._cal = cal
        return cal

    def _search_evaluator(self):
        return self._evaluator if self._evaluator is not None \
            else self.adapter.build_evaluator()

    def _open_cache(self, evaluator) -> Optional[FitnessCache]:
        if self._cache_factory is not None:
            # serving-side injection: a refcounted shared-store view
            # (the stage's close() releases its reference only)
            return self._cache_factory(evaluator)
        if not self.spec.cache:
            return None
        return FitnessCache(self.spec.cache,
                            fingerprint=evaluator_fingerprint(evaluator))

    def _trace(self) -> Optional[trace_mod.TraceWriter]:
        """The lazily-built TraceWriter, or None when tracing is off (or
        there is nowhere to write: in-memory artifact, no trace_path).
        Emits exactly one run header per process, flagged ``resumed``
        when any stage was already complete at construction."""
        if not self._trace_enabled:
            return None
        if self._tracer is None:
            path = self._trace_path
            if path is None:
                if self.result.path is None:
                    return None
                path = trace_mod.default_trace_path(self.result.path)
            self._tracer = trace_mod.TraceWriter(
                path, clock=self._trace_clock
            )
        if not self._trace_header_written:
            self._tracer.run_header(
                program=self.spec.program,
                mode=self.spec.mode,
                fidelity=self.spec.fidelity,
                spec_digest=_spec_digest(self.spec),
                resumed=any(self.result.completed(s) for s in STAGES),
            )
            self._trace_header_written = True
        return self._tracer

    # -- driver ------------------------------------------------------------

    def run(self, until: str = "report") -> OffloadResult:
        """Run every not-yet-completed stage up to and including
        ``until``, saving the artifact after each one."""
        if until not in STAGES:
            raise ValueError(f"unknown stage {until!r}; have {STAGES}")
        for name in STAGES[: STAGES.index(until) + 1]:
            if self.result.completed(name):
                continue
            self.run_stage(name)
        return self.result

    def run_stage(self, name: str) -> None:
        tr = self._trace()
        t0 = tr.clock() if tr is not None else 0.0
        fn = getattr(self, f"_stage_{name}")
        try:
            payload, wall = timed(fn)
        except StageFailure as e:
            if tr is not None:
                tr.span(name, t0, tr.clock(), "failed", error=str(e))
                self.result.trace = tr.summary()
            raise
        except Exception as e:  # noqa: BLE001 — record, then propagate
            if tr is not None:
                tr.span(name, t0, tr.clock(), "failed", error=repr(e))
                self.result.trace = tr.summary()
            self.result.record(name, {}, 0.0, status="failed",
                               error=repr(e))
            self.result.save()
            raise
        status = "done"
        error = payload.pop("_error", None)
        if error is not None:
            status = "failed"
        if tr is not None:
            tr.span(name, t0, tr.clock(), status,
                    attrs=_span_attrs(name, payload), error=error)
            self.result.trace = tr.summary()
        self.result.record(name, payload, wall, status=status, error=error)
        self.result.save()
        if error is not None:
            raise StageFailure(name, error)

    # -- stages ------------------------------------------------------------

    def _stage_calibrate(self) -> Dict[str, Any]:
        if self.spec.fidelity != "calibrated":
            return {"fidelity": self.spec.fidelity, "applicable": False}
        from repro.offload import calibrate

        cal = self._injected_cal
        if cal is None:
            cal = calibrate.run_calibration(
                base=self.spec.hw, repeats=self.spec.repeats,
                kernels=self.spec.blocks,
            )
        calibrate.install(cal, replace=True)
        self._cal = cal
        return {
            "fidelity": "calibrated",
            "applicable": True,
            "provided": self._injected_cal is not None,
            "base": cal.base,
            "entry": cal.name,
            "hw_name": cal.hw_name,
            "host": cal.host,
            "pinned": list(cal.pinned),
            "residuals": cal.residuals(),
            "calibration": cal.to_dict(),
        }

    def _stage_analyze(self) -> Dict[str, Any]:
        payload = self.adapter.analyze_payload()
        payload["baseline_s"] = float(self.adapter.baseline_time())
        blocks = payload.get("blocks")
        if blocks and blocks.get("matches"):
            tracer = self._trace()
            if tracer is not None:
                for m in blocks["matches"]:
                    tracer.event("block_match", span="analyze", attrs={
                        "entry": m["entry"],
                        "loops": "+".join(m["loops"]),
                        "n_loops": len(m["loops"]),
                    })
        return payload

    def _stage_seed(self) -> Dict[str, Any]:
        payload: Dict[str, Any] = {
            "warm_start": bool(self.spec.warm_start),
            "seeds": [],
            "seed_info": [],
        }
        if not self.spec.warm_start:
            return payload
        # mixed-mode genome-aware seeding: one quick binary GA per
        # non-host destination, bests re-expressed in the k-ary alphabet
        adapter = self.adapter
        host = self.spec.destinations[0]
        n = adapter.gene_length
        for device in self.spec.destinations[1:]:
            sub = adapter.sub_evaluator((host, device))
            params = ga.GAParams.for_gene_length(
                n,
                seed=self.spec.seed,
                timeout_s=self.spec.timeout_s
                if self.spec.timeout_s is not None else 1e6,
                penalty_time_s=self.spec.penalty_time_s,
                alleles=sub.k,
            )
            cache = self._open_cache(sub)
            try:
                with EvalPool(sub, workers=self.spec.workers,
                              executor=self.spec.executor,
                              cache=cache) as pool:
                    res = ga.run_ga(None, n, params, pool=pool)
                    tot = pool.totals()
            finally:
                if cache is not None:
                    cache.close()
            seed_genes = adapter.reexpress(res.best_genes, device)
            payload["seeds"].append([int(g) for g in seed_genes])
            payload["seed_info"].append({
                "device": device,
                "best_time_s": float(res.best_time_s),
                "evaluations": int(tot.evaluated),
                "cache_hits": int(tot.cache_hits),
            })
        return payload

    def _stage_search(self) -> Dict[str, Any]:
        adapter = self.adapter
        evaluator = self._search_evaluator()
        n = adapter.gene_length
        params = self.spec.ga_params(n, adapter.alleles)
        seeds = [
            tuple(int(g) for g in s)
            for s in self.result.stage("seed").payload.get("seeds", [])
        ]
        cache = self._open_cache(evaluator)
        resumed = len(cache) if cache is not None else 0
        tracer = self._trace()
        pool: Optional[EvalPool] = None

        def on_generation(gs: ga.GenerationStats) -> None:
            # per-generation trace event: population shape + the pool's
            # GenerationTelemetry for this generation. The pool's wall
            # clock is real time -> "timing" (digest-exempt); everything
            # else is deterministic data -> "attrs".
            if tracer is not None:
                attrs: Dict[str, Any] = {
                    "generation": int(gs.generation),
                    "best_time_s": float(gs.best_time_s),
                    "mean_time_s": float(gs.mean_time_s),
                    "best_fitness": ga.fitness_of_time(gs.best_time_s),
                }
                if gs.times:
                    med = qual.median(gs.times)
                    attrs["median_time_s"] = med
                    attrs["median_fitness"] = ga.fitness_of_time(med)
                if gs.population is not None:
                    attrs["allele_entropy"] = round(qual.allele_entropy(
                        gs.population, params.alleles), 6)
                timing = None
                if pool is not None and pool.history:
                    tel = pool.history[-1]
                    attrs.update(
                        submitted=int(tel.submitted),
                        unique=int(tel.unique),
                        cache_hits=int(tel.cache_hits),
                        evaluated=int(tel.evaluated),
                        timeouts=int(tel.timeouts),
                        dedup_ratio=round(tel.dedup_ratio, 4),
                        hit_rate=round(tel.hit_rate, 4),
                    )
                    # timing keys are digest-exempt on the trace side;
                    # idle_s is the barrier-stall / lane-starvation
                    # attribution the trace CLI's budget table renders
                    timing = {"wall_s": tel.wall_s, "idle_s": tel.idle_s}
                tracer.event("generation", span="search", attrs=attrs,
                             timing=timing)
            if self._on_generation is not None:
                self._on_generation(gs)

        try:
            with EvalPool(evaluator, workers=self.spec.workers,
                          executor=self.spec.executor, cache=cache) as pool:
                res = ga.run_ga(
                    None, n, params, pool=pool,
                    on_generation=on_generation,
                    seeds=seeds or None,
                )
                tot = pool.totals()
                telemetry = [t.row() for t in pool.history]
        finally:
            if cache is not None:
                cache.close()
        if res.history:
            best_genes = [int(g) for g in res.best_genes]
            best_t: Optional[float] = float(res.best_time_s)
            placement = adapter.placement(res.best_genes)
            stats_fn = getattr(adapter, "schedule_stats", None)
            residency = stats_fn(res.best_genes) if stats_fn is not None \
                else None
            subs_fn = getattr(adapter, "substitutions", None)
            substitutions = subs_fn(res.best_genes) \
                if subs_fn is not None else None
            last = res.history[-1]
            final_population = [[int(g) for g in ind]
                                for ind in (last.population or [])]
            final_times = [float(t) for t in (last.times or [])]
        else:
            # a zero-generation budget evaluates nothing: record an
            # explicit no-winner search instead of a fake one
            best_genes, best_t, placement, residency = [], None, {}, None
            final_population, final_times = [], []
            substitutions = None
        return {
            "best_genes": best_genes,
            "best_time_s": best_t,
            **({"residency": residency} if residency is not None else {}),
            **({"substitutions": substitutions}
               if substitutions is not None else {}),
            "wall_s": float(res.wall_s),
            "evaluations": int(tot.evaluated),
            "cache_hits": int(tot.cache_hits),
            "timeouts": int(tot.timeouts),
            "cache_resumed": int(resumed),
            "evaluator": _evaluator_label(evaluator),
            "telemetry": telemetry,
            "final_population": final_population,
            "final_times_s": final_times,
            "ga": {
                "population": params.population,
                "generations": params.generations,
                "alleles": params.alleles,
                "allele_names": list(getattr(adapter, "allele_names",
                                             ()) or ()),
                "seed": params.seed,
                "seeded": len(seeds),
                "diversity": float(params.diversity),
                # recorded only when on: knobs-off payloads stay
                # byte-identical to pre-fast-search artifacts
                **({"steady_state": True} if params.steady_state else {}),
                **({"batch": True} if self.spec.ga.batch else {}),
            },
            "placement": placement,
            "history": [
                {
                    "generation": h.generation,
                    "best_time_s": float(h.best_time_s),
                    "mean_time_s": float(h.mean_time_s),
                    "gen_wall_s": float(h.gen_wall_s),
                    "dedup_ratio": float(h.dedup_ratio),
                    "hit_rate": float(h.hit_rate),
                }
                for h in res.history
            ],
        }

    def _stage_verify(self) -> Dict[str, Any]:
        adapter = self.adapter
        search = self.result.stage("search").payload
        if search.get("best_time_s") is None:
            # zero-generation search: nothing was evaluated, no winner
            return {
                "re_measured_s": None,
                "search_best_s": None,
                "consistent": True,
                "note": "search recorded zero generations; "
                        "no winner to verify",
                "pcast": {"skipped": "no winner to check"},
            }
        best = tuple(int(g) for g in search["best_genes"])
        best_t = float(search["best_time_s"])

        evaluator = self._search_evaluator()
        # guard against evaluator drift across resume: the search stage
        # recorded its evaluator's fingerprint, and re-measuring the
        # winner with a DIFFERENT one (e.g. a compiled-evaluator
        # artifact resumed without re-injecting it) would either fail
        # spuriously or silently bless an unverified number
        searched_fp = search.get("evaluator")
        verify_fp = _evaluator_label(evaluator)
        if searched_fp is not None and searched_fp != verify_fp:
            return {
                "re_measured_s": None,
                "search_best_s": best_t,
                "pcast": {"skipped": "evaluator mismatch"},
                "_error": (
                    f"verify evaluator {verify_fp!r} differs from the one "
                    f"the search used ({searched_fp!r}); resume with the "
                    "same evaluator injection (Offloader.resume(path, "
                    "evaluator=...))"
                ),
            }
        if self._evaluator is not None:
            # injected evaluators (compiled / measured): a re-measurement
            # would redo the expensive per-individual work (an AOT
            # compile, a wall-clocked run) outside the pool/cache for a
            # number that could not be held to exactness anyway — skip it
            payload: Dict[str, Any] = {
                "re_measured_s": None,
                "search_best_s": best_t,
                "consistent": True,
                "note": "injected evaluator: re-measurement skipped",
            }
            consistent = True
        else:
            re_t = float(evaluator(best))
            exact = adapter.deterministic
            mismatch = abs(re_t - best_t) / max(best_t, 1e-300)
            consistent = (not exact) or mismatch <= _REMEASURE_RTOL
            payload = {
                "re_measured_s": re_t,
                "search_best_s": best_t,
                "mismatch_rel": mismatch,
                "consistent": bool(consistent),
            }
        report = adapter.pcast_check(best)
        if report is None:
            payload["pcast"] = {
                "skipped": "no runnable reference implementation",
            }
        else:
            payload["pcast"] = {
                "ok": bool(report.ok),
                "max_rel": float(report.max_rel),
                "n_leaves": len(report.leaves),
                "detail": report.describe(),
            }
        fid = self._fidelity_section(best, best_t)
        if fid is not None:
            payload["fidelity"] = fid
        oracles = self._block_oracles(adapter, best)
        if oracles is not None:
            payload["block_oracles"] = oracles
        if not consistent:
            payload["_error"] = (
                f"winner re-measurement drifted: "
                f"{payload['re_measured_s']:.6g}s vs recorded "
                f"{best_t:.6g}s (rel {payload['mismatch_rel']:.3g})"
            )
        elif report is not None and not report.ok:
            payload["_error"] = (
                f"PCAST result-difference check FAILED "
                f"(max_rel {report.max_rel:.3e})"
            )
        elif oracles is not None and not all(r["ok"] for r in oracles):
            bad = [r for r in oracles if not r["ok"]]
            payload["_error"] = (
                "block substitution oracle check FAILED: "
                + "; ".join(
                    f"{r['kernel']} vs {r['oracle']} "
                    f"(max_abs {r['max_abs_err']:.3e} > tol {r['tol']:.3e})"
                    for r in bad
                )
            )
        return payload

    def _block_oracles(self, adapter, best) -> Optional[list]:
        """Kernel-oracle checks for every substitution the winner
        activates: the substituted implementation (the real kernel,
        compiled on a TPU and interpreted elsewhere) vs its
        ``kernels/ref.py`` oracle on a tiny
        seeded input — the block analogue of the PCAST placement check.
        None when the run has no block genome (blocks-off byte parity)."""
        subs_fn = getattr(adapter, "substitutions", None)
        if subs_fn is None:
            return None
        subs = subs_fn(best)
        if subs is None:
            return None
        from repro import blocks as blocks_mod

        tracer = self._trace()
        rows = []
        for s in subs:
            if not s.get("active"):
                continue
            entry = adapter.library.get(s["entry"])
            row = blocks_mod.oracle_check(entry, seed=self.spec.seed)
            row["destination"] = s["destination"]
            row["loops"] = list(s["loops"])
            rows.append(row)
            if tracer is not None:
                tracer.event("block_substitution", span="verify", attrs={
                    "entry": s["entry"],
                    "destination": s["destination"],
                    "loops": "+".join(s["loops"]),
                    "oracle_ok": bool(row["ok"]),
                    "max_abs_err": float(row["max_abs_err"]),
                })
        return rows

    def _scale_model(self) -> Callable[[Sequence[int]], float]:
        """The analytic model of the effective spec's machine AT THE
        MEASURED SCALE — what fidelity/rank sections compare real wall
        clocks against (a paper-scale prediction would be off by the
        problem-size ratio, not by model error)."""
        from repro.core import evaluator as ev
        from repro.core import transfer as tr

        spec = self.spec
        if spec.fidelity == "measured":
            return self.adapter.model_evaluator()
        eff = self._effective_spec()
        scale_prog = programs.measured_run_fn(
            spec.program, spec.measured_scale).program()
        if spec.mode == "mixed":
            from repro.destinations import MixedEvaluator, get_registry

            reg = get_registry(eff.hw)
            if getattr(self.adapter, "matches", ()):
                # block-enabled genomes carry block genes; price them
                # with a block evaluator over the scale program (same
                # loop structure -> same matches)
                from repro.blocks import BlockMixedEvaluator

                return BlockMixedEvaluator(
                    scale_prog, eff.destinations, registry=reg,
                    library=self.adapter.library,
                )
            return MixedEvaluator(scale_prog, eff.destinations,
                                  registry=reg)
        method = programs.METHODS[eff.method]
        return ev.MiniappEvaluator(
            scale_prog,
            tr.TransferMode(method["transfer"]),
            staged=method["staged"],
            hw=programs.resolve_hw(eff),
            kernels_only=method["kernels_only"],
        )

    def _fidelity_section(self, best, best_t: float) -> Optional[Dict]:
        """Predicted-vs-measured honesty check of the winner (and the
        all-host baseline), one row per destination involved. Modeled
        runs skip it (nothing was measured, and the pipeline must stay
        byte-identical to the pre-fidelity artifacts); programs without
        a runnable implementation record why.

        - fidelity="measured": predicted comes from the analytic model
          of the spec's machine AT THE MEASURED SCALE; measured numbers
          are the search's own wall clocks (no extra runs).
        - fidelity="calibrated": predicted comes from the calibrated
          model at the measured scale; the winner and baseline are
          freshly wall-clocked in-process.
        """
        from repro.core import evaluator as ev
        from repro.offload.spec import MEASURED_PROGRAMS

        spec = self.spec
        if spec.fidelity == "modeled":
            return None
        if spec.program not in MEASURED_PROGRAMS:
            return {
                "level": spec.fidelity,
                "skipped": "no runnable implementation to measure "
                           "(calibration residuals still recorded in the "
                           "calibrate stage)",
            }
        adapter = self.adapter
        n = adapter.gene_length
        zeros = (0,) * n
        run_fn = programs.measured_run_fn(spec.program, spec.measured_scale)
        model = self._scale_model()

        if spec.fidelity == "measured":
            reference = f"model:{adapter.hw.name}"
            meas_host = float(
                self.result.stage("analyze").payload["baseline_s"]
            )
            meas_win = float(best_t)
        else:  # calibrated
            reference = f"calibrated:{self._ensure_calibration().hw_name}"
            m = ev.MeasuredEvaluator(run_fn, repeats=spec.repeats,
                                     tag=run_fn.tag)
            meas_host = float(m(zeros))
            meas_win = float(m(best))

        # the runnable implementations realize exactly ONE placement
        # switch (the hot loop on the generic jit/accelerator path), so
        # the winner row compares the model and the clock on the
        # REALIZABLE projection of the winner — anything else would
        # price loops (or backends, for k-ary genomes: the run_fn jits
        # for ANY nonzero allele) the measurement cannot move
        hot = programs.hot_gene_index(spec.program)
        hot_name = programs.RUNNABLE[spec.program][0]
        host = "cpu"
        hot_offloaded = adapter.placement(best).get(hot_name, host) != host
        if spec.mode == "mixed":
            dests = adapter.build_evaluator().dests
            accel = next((i for i, d in enumerate(dests)
                          if d.kind in ("gpu", "tpu")), None)
        else:
            dests, accel = None, 1

        def row(dest: str, label: str, pred: float, meas: float) -> Dict:
            return {
                "destination": dest,
                "placement": label,
                "predicted_s": float(pred),
                "measured_s": float(meas),
                "ratio": float(pred / meas) if meas > 0 else float("inf"),
            }

        rows = [row(host, "all-host", model(zeros), meas_host)]
        if hot_offloaded and accel is None:
            # e.g. a cpu+fpga subset: the jit path the clock runs has no
            # counterpart destination in the model — say so, don't fake it
            rows.append({
                "destination": "?",
                "placement": "winner:hot-loop",
                "skipped": "searched subset has no gpu/tpu-kind "
                           "destination matching the jit measurement",
            })
        else:
            allele = accel if hot_offloaded else 0
            realized = tuple(
                allele if i == hot else 0 for i in range(n)
            )
            win_dest = dests[allele].name if dests is not None \
                else ("gpu" if allele else host)
            rows.append(row(win_dest, "winner:hot-loop",
                            model(realized), meas_win))
        return {
            "level": spec.fidelity,
            "scale": run_fn.tag,
            "reference": reference,
            "rows": rows,
        }

    def _stage_report(self) -> Dict[str, Any]:
        quality = self._quality_section()
        payload: Dict[str, Any] = {}
        if quality is not None:
            payload["quality"] = quality
        payload["text"] = render_report(self.result, quality=quality)
        gate = self.spec.ga.stability_gate
        st = (quality or {}).get("stability") or {}
        if gate is not None and st.get("rel_spread", 0.0) > gate:
            payload["_error"] = (
                f"winner stability gate: relative spread "
                f"{st['rel_spread']:.1%} across {st['k']} GA seeds exceeds "
                f"the gate {gate:.1%} (ga.stability_gate)"
            )
        return payload

    # -- search-quality metrics (report stage; never feed the search) ------

    def _quality_section(self) -> Optional[Dict[str, Any]]:
        """pass@k winner stability + modeled-vs-measured rank fidelity
        (repro.offload.quality), computed in the REPORT stage only: by
        construction nothing here can perturb the recorded search."""
        if not self.result.completed("search"):
            return None
        search = self.result.stage("search").payload
        return {
            "stability": self._stability_section(search),
            "rank": self._rank_section(search),
        }

    def _stability_section(self, search: Dict[str, Any]) -> Dict[str, Any]:
        knobs = self.spec.ga
        if knobs.stability_seeds <= 1:
            return {"skipped": "disabled (ga.stability_seeds <= 1)"}
        if not search.get("history"):
            return {"skipped": "search recorded zero generations"}
        if self._evaluator is not None:
            return {"skipped": "injected evaluator (a re-search could be "
                               "arbitrarily expensive; call "
                               "quality.winner_stability directly)"}
        adapter = self.adapter
        # re-searches always run the cheap MODELED evaluator: for
        # fidelity="measured" that is the analytic model at measured
        # scale, not the wall-clocking run_fn
        model_fn = getattr(adapter, "model_evaluator", None)
        evaluator = model_fn() if callable(model_fn) \
            else self._search_evaluator()
        fp = evaluator_fingerprint(evaluator)
        recorded = None
        if search.get("evaluator") == fp \
                and search.get("best_time_s") is not None:
            # the recorded search IS the k=0 member (same evaluator)
            recorded = (search["best_genes"], search["best_time_s"])
        n = adapter.gene_length
        params = self.spec.ga_params(n, adapter.alleles)
        seeds = [
            tuple(int(g) for g in s)
            for s in self.result.stage("seed").payload.get("seeds", [])
        ]
        tracer = self._trace()

        def on_search(row: Dict[str, Any]) -> None:
            if tracer is not None:
                tracer.event("stability_search", span="report", attrs={
                    "seed": row["seed"],
                    "best_time_s": row["best_time_s"],
                    "evaluations": row["evaluations"],
                    "cache_hits": row["cache_hits"],
                })

        st = qual.winner_stability(
            evaluator, n, params,
            k=knobs.stability_seeds,
            window=knobs.stability_window,
            seeds=seeds or None,
            workers=self.spec.workers,
            cache_path=self.spec.cache,
            recorded=recorded,
            on_search=on_search,
        )
        st["evaluator"] = fp
        st["reused_recorded"] = recorded is not None
        return st

    def _rank_section(self, search: Dict[str, Any]) -> Dict[str, Any]:
        from repro.core import evaluator as ev
        from repro.offload.spec import MEASURED_PROGRAMS

        spec = self.spec
        knobs = spec.ga
        final = search.get("final_population") or []
        times = search.get("final_times_s") or []
        if not final:
            return {"skipped": "no final population recorded "
                               "(zero generations, or an artifact from "
                               "before tracing)"}
        if spec.is_arch or spec.program not in MEASURED_PROGRAMS:
            return {"skipped": "no runnable implementation to measure "
                               "against"}
        if self._evaluator is not None:
            return {"skipped": "injected evaluator"}
        if spec.fidelity != "measured" and not knobs.rank_probe:
            return {"skipped": "rank probe off (ga.rank_probe=false; "
                               "measured fidelity ranks for free)"}
        adapter = self.adapter
        n = adapter.gene_length
        run_fn = programs.measured_run_fn(spec.program, spec.measured_scale)
        model = self._scale_model()
        pop = [tuple(int(g) for g in ind) for ind in final]
        modeled = [float(model(g)) for g in pop]
        tracer = self._trace()

        if spec.fidelity == "measured":
            # the final generation's times ARE wall clocks — free
            if len(times) != len(pop):
                return {"skipped": "final population and times out of "
                                   "sync in the search payload"}
            measured = [float(t) for t in times]
        else:
            # two wall-clocked projections cover every candidate: the
            # runnable implementations realize exactly one placement
            # switch (hot loop on the jit path or not), so measurement
            # can only ever distinguish those two classes
            hot = programs.hot_gene_index(spec.program)
            hot_name = programs.RUNNABLE[spec.program][0]
            host = "cpu"
            if spec.mode == "mixed":
                dests = adapter.build_evaluator().dests
                accel = next((i for i, d in enumerate(dests)
                              if d.kind in ("gpu", "tpu")), None)
            else:
                accel = 1
            m = ev.MeasuredEvaluator(run_fn, repeats=spec.repeats,
                                     tag=run_fn.tag)
            zeros = (0,) * n
            t_host = float(m(zeros))
            if tracer is not None:
                tracer.event("rank_probe", span="report", attrs={
                    "projection": "all-host", "evaluations": 1,
                    "measured_s": t_host,
                })
            offloaded = [
                adapter.placement(g).get(hot_name, host) != host
                for g in pop
            ]
            t_off = None
            if any(offloaded):
                on_genome = tuple(
                    (accel if accel is not None else 1) if i == hot else 0
                    for i in range(n)
                )
                t_off = float(m(on_genome))
                if tracer is not None:
                    tracer.event("rank_probe", span="report", attrs={
                        "projection": "hot-offloaded", "evaluations": 1,
                        "measured_s": t_off,
                    })
            measured = [t_off if off else t_host for off in offloaded]
        eff = self._effective_spec()
        return qual.rank_section(
            modeled, measured,
            scale=run_fn.tag,
            reference=f"model:{eff.hw}",
        )


def render_report(result: OffloadResult,
                  quality: Optional[Dict[str, Any]] = None) -> str:
    """Human-readable end-to-end summary from artifact payloads alone
    (used by the report stage AND ``python -m repro.offload report`` on
    loaded artifacts, partial ones included). ``quality`` is the
    search-quality section the report stage just computed; for loaded
    artifacts it falls back to the recorded report payload."""
    spec = result.spec
    tag = spec.method if spec.mode == "binary" and not spec.is_arch \
        else "+".join(spec.destinations) if spec.mode == "mixed" \
        else "plan-search"
    if spec.fidelity != "modeled":
        tag += f"/{spec.fidelity}"
    rows = [f"== repro.offload report: {spec.program} [{spec.mode}/{tag}] =="]

    if result.completed("calibrate"):
        c = result.stage("calibrate").payload
        if c.get("applicable"):
            r = c["residuals"]
            rows.append(
                f"calibrate: {c['base']} -> {c['entry']} on {c['host']} "
                f"({r['n']} probes, |resid| max {r['max_abs_rel']:.1%} / "
                f"mean {r['mean_abs_rel']:.1%}; "
                f"pinned: {', '.join(c['pinned'])})"
            )
    if result.completed("analyze"):
        a = result.stage("analyze").payload
        rows.append(
            f"analyze: {a.get('description', spec.program)} — "
            f"{a['gene_length']} genes"
            + (f" / {a['n_loops']} loops" if "n_loops" in a else "")
            + f"; all-host baseline {a['baseline_s']:.4g}s"
        )
        d = a.get("device")
        if d:  # measured fidelity: what the clocks ran on
            rows.append(
                f"device: {d['platform']} ({d['device_kind']}) x{d['count']}"
                f", measured at {a['measured_scale']}"
            )
    if result.completed("seed"):
        s = result.stage("seed").payload
        if s.get("seeds"):
            info = ", ".join(
                f"{i['device']} {i['best_time_s']:.4g}s"
                for i in s["seed_info"]
            )
            rows.append(f"seed: warm-start with {len(s['seeds'])} "
                        f"single-destination bests ({info})")
        else:
            rows.append("seed: random initial population")
    if result.completed("search"):
        p = result.stage("search").payload
        if p.get("best_time_s") is None:
            rows.append(
                "search: no generations run (generations=0 budget); "
                "nothing evaluated, no winner recorded"
            )
        else:
            line = (
                f"search: best {p['best_time_s']:.4g}s in "
                f"{p['ga']['generations']} generations "
                f"({p['evaluations']} measurements, {p['cache_hits']} cache "
                f"hits, wall {p['wall_s']:.2f}s)"
            )
            if result.speedup:
                line += f"; speedup {result.speedup:.1f}x over all-host"
            rows.append(line)
            moved = {u: d for u, d in p["placement"].items()
                     if d not in ("cpu", "host")}
            rows.append(f"placement: {len(moved)}/{len(p['placement'])} "
                        "units offloaded")
            for u, d in moved.items():
                rows.append(f"    {u:24s} -> {d}")
            subs = p.get("substitutions")
            if subs is not None:
                act = [s for s in subs if s.get("active")]
                rows.append(f"blocks: {len(act)}/{len(subs)} matched "
                            "blocks substituted (docs/blocks.md)")
                for s in act:
                    rows.append(
                        f"    [{s['entry']}] {'+'.join(s['loops'])} "
                        f"-> {s['destination']}"
                    )
            r = p.get("residency")
            if r and r.get("capacities"):
                caps = ", ".join(f"{n} {b/1e6:.0f} MB"
                                 for n, b in sorted(r["capacities"].items()))
                line = (f"residency: evicted "
                        f"{r['evicted_bytes']/1e6:.1f} MB, "
                        f"streamed {r['spilled_bytes']/1e6:.1f} MB "
                        f"under capacities [{caps}]")
                if r.get("oversubscribed"):
                    line += ("; oversubscribed: "
                             + ", ".join(r["oversubscribed"]))
                rows.append(line)
    if "verify" in result.stages:
        v = result.stages["verify"]
        pc = v.payload.get("pcast", {})
        if "skipped" in pc:
            pc_txt = f"PCAST skipped ({pc['skipped']})"
        elif pc:
            pc_txt = (f"PCAST {'PASS' if pc['ok'] else 'FAIL'} "
                      f"(max_rel {pc['max_rel']:.3e}, "
                      f"{pc['n_leaves']} tensors)")
        else:
            pc_txt = "PCAST not run"
        ok = "ok" if v.done else f"FAILED: {v.error}"
        re_t = v.payload.get("re_measured_s")
        re_txt = "re-measurement skipped" if re_t is None \
            else f"re-measured {re_t:.4g}s"
        rows.append(f"verify: {ok}; {re_txt}; {pc_txt}")
        bo = v.payload.get("block_oracles")
        if bo:
            parts = ", ".join(
                f"{r['kernel']}@{r['destination']} "
                f"{'PASS' if r['ok'] else 'FAIL'} "
                f"(max_abs {r['max_abs_err']:.2e} vs {r['oracle']})"
                for r in bo
            )
            rows.append(f"block oracles: {parts}")
        fid = v.payload.get("fidelity")
        if fid and "skipped" in fid:
            rows.append(f"fidelity[{fid['level']}]: skipped "
                        f"({fid['skipped']})")
        elif fid:
            parts = ", ".join(
                f"{r['destination']}/{r['placement']} "
                f"{r['ratio']:.2f}x ({r['predicted_s']:.4g}s vs "
                f"{r['measured_s']:.4g}s)"
                if "ratio" in r else
                f"{r['placement']} skipped ({r['skipped']})"
                for r in fid["rows"]
            )
            rows.append(
                f"fidelity[{fid['level']} @ {fid['scale']}]: "
                f"predicted/measured {parts}"
            )
    q = quality
    if q is None and "report" in result.stages:
        q = result.stages["report"].payload.get("quality")
    if q:
        st = q.get("stability") or {}
        if "skipped" in st:
            rows.append(f"quality: stability skipped ({st['skipped']})")
        elif st:
            rows.append(
                f"quality: winner stability pass@{st['k']} "
                f"{st['pass_at_k']:.0%} (window {st['window']:.1%}, "
                f"spread +{st['rel_spread']:.1%}, "
                f"{st['distinct_winners']} distinct winner(s))"
            )
        rk = q.get("rank") or {}
        if "skipped" in rk:
            rows.append(f"quality: rank fidelity skipped ({rk['skipped']})")
        elif rk:
            if rk.get("spearman") is None:
                rows.append(
                    f"quality: rank fidelity undefined over {rk['n']} "
                    f"final candidates ({rk.get('note', 'degenerate')})"
                )
            else:
                kd = rk.get("kendall")
                kd_txt = f"{kd:+.2f}" if kd is not None else "n/a"
                rows.append(
                    f"quality: rank fidelity spearman "
                    f"{rk['spearman']:+.2f} / kendall {kd_txt} "
                    f"over {rk['n']} final candidates vs "
                    f"{rk.get('reference', 'model')}"
                    + (f" @ {rk['scale']}" if rk.get("scale") else "")
                )
    return "\n".join(rows)
