"""repro.offload — the paper's whole flow as one staged pipeline API.

The source paper's contribution is a single automated sequence: extract
loop statements, assign directives, GA-search placements against a
verification environment, reduce transfers, and check results (PCAST).
This package is that sequence as a reusable surface:

- :class:`OffloadSpec` — one frozen, JSON-round-trippable description of
  a search (program, binary/mixed mode, method, GA budget, pool/cache
  settings, verify tolerances);
- :class:`Offloader` — the facade running the named stages ``analyze ->
  seed -> search -> verify -> report``;
- :class:`OffloadResult` — the per-stage artifact that saves, reloads,
  and resumes (completed stages skip; interrupted searches resume warm
  through the persistent JSONL fitness cache);
- ``python -m repro.offload`` — the CLI (``run`` / ``resume`` /
  ``report`` / ``trace`` / ``calibrate`` / ``sweep``, ``--smoke`` for
  CI; every verb's ``--help`` epilog documents its exit codes);
- :mod:`repro.offload.sweep` — the model-zoo sweep driver: the
  programs x machines x modes matrix run resumably cell-by-cell, the
  append-only ``BENCH_sweep.json`` trajectory, the leaderboard and the
  regression flagger (docs/benchmarks.md);
- :mod:`repro.offload.trace` / :mod:`repro.offload.quality` — the
  observability layer (docs/observability.md): a deterministic JSONL
  trace of span/event records written next to every artifact and
  embedded in it by digest, plus the pass@k winner-stability and
  modeled-vs-measured rank-correlation metrics the report stage and
  every sweep cell surface;
- :mod:`repro.offload.calibrate` — measured model calibration behind
  ``OffloadSpec.fidelity`` (imported lazily: modeled pipelines never
  touch it).

Every example, benchmark and calibration script drives this facade; with
spec defaults its searches match a hand-wired ``run_ga`` over the same
evaluator (parity-tested in tests/test_offload_pipeline.py).
"""
from repro.offload.pipeline import Offloader, render_report
from repro.offload.result import (
    STAGES,
    OffloadResult,
    StageFailure,
    StageRecord,
)
from repro.offload.spec import (
    FIDELITIES,
    GAControls,
    METHODS,
    MODES,
    OffloadSpec,
)

__all__ = [
    "FIDELITIES",
    "GAControls",
    "METHODS",
    "MODES",
    "Offloader",
    "OffloadResult",
    "OffloadSpec",
    "STAGES",
    "StageFailure",
    "StageRecord",
    "render_report",
]
