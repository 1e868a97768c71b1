"""OffloadResult: the pipeline's JSON-serializable, resumable artifact.

One artifact per end-to-end run: the :class:`OffloadSpec` plus one
:class:`StageRecord` per completed (or failed) stage, in pipeline order.
``save``/``load`` round-trip the whole thing through JSON, and the
:class:`~repro.offload.pipeline.Offloader` skips stages already recorded
as done — so a killed run resumed from its artifact re-enters the
pipeline exactly where it stopped, and a *search* interrupted mid-GA
resumes warm through the spec's persistent JSONL fitness cache (the
stage re-runs, but every already-measured genome is a cache hit).

Stage payloads are plain JSON values (genes as lists of ints) so the
artifact is greppable/diffable and survives module refactors.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, Optional, Tuple

from repro.offload.spec import OffloadSpec

_ARTIFACT_VERSION = 1

# pipeline order; Offloader runs exactly these, in this order. The
# calibrate stage comes FIRST: with spec.fidelity="calibrated" it
# measures + fits the machine the analyze baseline and the search both
# price against; for every other fidelity it records itself as not
# applicable (so artifacts stay uniform and resume stays positional).
STAGES: Tuple[str, ...] = (
    "calibrate", "analyze", "seed", "search", "verify", "report"
)


class StageFailure(RuntimeError):
    """A pipeline stage failed (recorded in the artifact before raising)."""

    def __init__(self, stage: str, message: str):
        super().__init__(f"stage {stage!r} failed: {message}")
        self.stage = stage


@dataclasses.dataclass
class StageRecord:
    name: str
    status: str  # "done" | "failed"
    wall_s: float
    payload: Dict[str, Any]
    error: Optional[str] = None

    @property
    def done(self) -> bool:
        return self.status == "done"

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "StageRecord":
        return cls(
            name=str(d["name"]),
            status=str(d["status"]),
            wall_s=float(d.get("wall_s", 0.0)),
            payload=dict(d.get("payload", {})),
            error=d.get("error"),
        )


@dataclasses.dataclass
class OffloadResult:
    """Spec + per-stage records; the unit of save/reload/resume."""

    spec: OffloadSpec
    stages: Dict[str, StageRecord] = dataclasses.field(default_factory=dict)
    path: Optional[str] = None  # where save() writes (None = in-memory)
    # trace summary ({"path", "digest", "records"}) maintained by the
    # Offloader when tracing is on: the digest is the trace file's
    # content digest (timing-stripped; repro.offload.trace), so the
    # `trace` CLI verb can prove a trace file belongs to this artifact
    trace: Optional[Dict[str, Any]] = None
    # serving-layer job record (repro.serve.jobs, docs/serving.md): when
    # the artifact is owned by the offload service, its lifecycle state
    # (queued/running/done/failed/cancelled), restarts, admission clamps
    # etc. live HERE — the resumable artifact IS the job-state record,
    # which is what makes crash recovery "resume every artifact whose
    # job is non-terminal". Additive: None for every non-service run,
    # keeping those artifact bytes identical to pre-serving ones.
    job: Optional[Dict[str, Any]] = None
    # the winner's answer at measured fidelity: the output of its placed
    # run that verify's PCAST compared against the host path, at the
    # measured scale. In memory only: never saved, loaded or traced, and
    # left out of equality.
    answer: Optional[Dict[str, Any]] = dataclasses.field(
        default=None, compare=False, repr=False)

    # -- stage bookkeeping --------------------------------------------------

    def completed(self, stage: str) -> bool:
        rec = self.stages.get(stage)
        return rec is not None and rec.done

    def stage(self, name: str) -> StageRecord:
        if name not in self.stages:
            raise KeyError(
                f"stage {name!r} not in artifact (have "
                f"{[s for s in STAGES if s in self.stages]})"
            )
        return self.stages[name]

    def record(self, name: str, payload: Dict[str, Any], wall_s: float,
               status: str = "done", error: Optional[str] = None
               ) -> StageRecord:
        assert name in STAGES, name
        rec = StageRecord(name=name, status=status, wall_s=wall_s,
                          payload=payload, error=error)
        self.stages[name] = rec
        return rec

    # -- convenience accessors ---------------------------------------------

    @property
    def best_genes(self) -> Optional[Tuple[int, ...]]:
        if not self.completed("search"):
            return None
        return tuple(int(g) for g in self.stage("search").payload["best_genes"])

    @property
    def best_time_s(self) -> Optional[float]:
        if not self.completed("search"):
            return None
        t = self.stage("search").payload["best_time_s"]
        # a zero-generation search records no winner (best_time_s=None)
        return float(t) if t is not None else None

    @property
    def baseline_time_s(self) -> Optional[float]:
        if not self.completed("analyze"):
            return None
        return float(self.stage("analyze").payload["baseline_s"])

    @property
    def speedup(self) -> Optional[float]:
        if self.best_time_s and self.baseline_time_s:
            return self.baseline_time_s / self.best_time_s
        return None

    @property
    def calibration(self) -> Optional[Dict[str, Any]]:
        """The embedded calibration dict (constants, probes, residuals)
        when this artifact ran at fidelity='calibrated'; None otherwise."""
        if not self.completed("calibrate"):
            return None
        return self.stage("calibrate").payload.get("calibration")

    # -- persistence --------------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "v": _ARTIFACT_VERSION,
            "spec": self.spec.to_dict(),
            "stages": [self.stages[s].to_dict()
                       for s in STAGES if s in self.stages],
        }
        if self.trace is not None:  # additive: v1 artifacts stay loadable
            out["trace"] = self.trace
        if self.job is not None:  # additive: service-owned artifacts only
            out["job"] = self.job
        return out

    def save(self, path: Optional[str] = None) -> Optional[str]:
        """Atomically write the artifact JSON; returns the path written
        (None when the artifact is in-memory only)."""
        path = path or self.path
        if path is None:
            return None
        self.path = path
        return atomic_json_save(path, self.to_dict())

    @classmethod
    def load(cls, path: str) -> "OffloadResult":
        with open(path, "r", encoding="utf-8") as fh:
            d = json.load(fh)
        if d.get("v") != _ARTIFACT_VERSION:
            raise ValueError(
                f"unsupported artifact version {d.get('v')!r} in {path}"
            )
        out = cls(spec=OffloadSpec.from_dict(d["spec"]), path=path,
                  trace=d.get("trace"), job=d.get("job"))
        for rec in d.get("stages", []):
            sr = StageRecord.from_dict(rec)
            if sr.name in STAGES:
                out.stages[sr.name] = sr
        return out

    # -- display ------------------------------------------------------------

    def summary(self) -> str:
        rows = [f"OffloadResult[{self.spec.program}/{self.spec.mode}"
                + (f"/{self.spec.method}" if self.spec.mode == "binary"
                   else f"/{'+'.join(self.spec.destinations)}") + "]"]
        for s in STAGES:
            if s in self.stages:
                r = self.stages[s]
                flag = "done" if r.done else f"FAILED ({r.error})"
                rows.append(f"  {s:8s} {flag} ({r.wall_s:.2f}s)")
            else:
                rows.append(f"  {s:8s} -")
        return "\n".join(rows)


def atomic_json_save(path: str, obj: Dict[str, Any]) -> str:
    """Write ``obj`` as pretty JSON via tmp-file + rename, so readers
    never observe a torn file (shared by OffloadResult and
    CalibrationResult saves)."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(obj, fh, indent=1, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)
    return path


def timed(fn, *args, **kw):
    """(result, wall seconds) of ``fn(*args, **kw)``."""
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0
