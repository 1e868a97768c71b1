"""Model-zoo sweep: the cross-product evaluation matrix + BENCH trajectory.

The paper's core claim is breadth — the improved offload method works
"in multiple applications" — so the repo needs a driver that runs *all*
of them, on *every* modeled machine, in one resumable invocation:

    {miniapps + arch:<name> programs} x {machine registries} x {modes}

Each feasible cell runs through the ordinary :class:`Offloader` pipeline
into its own ``OffloadResult`` artifact under a sweep directory, with
one shared persistent JSONL fitness cache (evaluator fingerprints keep
foreign entries apart, so sharing one file is safe and is the point: a
re-sweep is mostly cache hits, and a killed sweep resumes cell-by-cell
— completed artifacts are skipped outright with zero fresh
measurements).

Every sweep appends exactly one schema-versioned **trajectory point** to
a ``BENCH_sweep.json`` file (default: repo root): git hash, timestamp,
the matrix, one summary record per cell (winner fitness, speedup vs
all-host, search cost, cache-hit rate, residency pressure, block
substitutions) and
aggregate totals. The trajectory is append-only — points are never
rewritten — which makes it the PR-over-PR perf record the ROADMAP's
re-anchor process reads.

On top of the trajectory, :func:`render_leaderboard` renders the
best placement per program per machine with deltas against the previous
point, and :func:`flag_regressions` compares consecutive points
cell-by-cell: a cell whose winner fitness worsened by strictly more
than ``rel_tolerance`` (default 5%) is flagged, and the CLI
(``python -m repro.offload sweep``) turns flags into a nonzero exit
code so nightly CI fails loudly. See docs/benchmarks.md for the full
schema table and the cookbook.

Feasibility rules (recorded per skipped cell, never silent):

- ``arch:<name>`` programs are binary-only (``OffloadSpec`` rejects
  mixed mode for them) and their analytic plan evaluator is
  machine-independent, so each arch runs once, pinned to the default
  machine — the other (machine, arch) cells are recorded as skipped
  duplicates rather than tripling the budget for identical searches.
- Binary miniapp cells price against a :class:`HardwareModel`, so they
  only exist on machines whose registry name is also a hardware-model
  name (``p4000-constrained`` shares the P4000's rate constants and is
  skipped in binary mode).
- Mixed cells search the machine's full destination set (host first),
  taken from its registry.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import subprocess
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.offload.pipeline import Offloader
from repro.offload.result import (
    OffloadResult,
    StageFailure,
    atomic_json_save,
)
from repro.offload.spec import (
    GAControls,
    MEASURED_PROGRAMS,
    MIXED_SMOKE_BUDGET,
    MODES,
    OffloadSpec,
)

SWEEP_SCHEMA = "repro.offload.sweep"
SWEEP_SCHEMA_VERSION = 1

# per-POINT schema version (the FILE schema above stays 1 so existing
# trajectories keep loading). v2 points additionally carry a per-cell
# "quality" key — the report stage's pass@k winner stability and
# modeled-vs-measured rank correlation (docs/observability.md) — and
# append cleanly after v1 points: readers treat a missing "v" as 1.
# v3 points additionally carry a per-cell "blocks" key — the
# function-block substitution summary (matched blocks, substituted
# count, kernel@destination rows; docs/blocks.md), None for cells the
# feature does not apply to.
# v4 points additionally carry "throughput" inside each ok cell's
# "search" summary — modeled-search genomes/sec (population x
# generations / search wall), the number the fast-search knobs
# (OffloadSpec.ga.batch / .steady_state) exist to raise.
SWEEP_POINT_VERSION = 4

# default trajectory file (repo root when invoked from there) and the
# default per-cell artifact directories; smoke and full matrices get
# separate directories so a smoke artifact can never satisfy (and
# silently shrink) a full-budget cell on resume
DEFAULT_TRAJECTORY = "BENCH_sweep.json"
DEFAULT_SWEEP_DIR = ".sweep"
DEFAULT_SMOKE_DIR = ".sweep-smoke"

# a cell regresses when its winner fitness worsens by STRICTLY more
# than this relative tolerance vs the previous point (exactly at the
# edge is not a regression — modeled searches are deterministic, so the
# tolerance only absorbs intentional small model/constant changes)
DEFAULT_REL_TOLERANCE = 0.05

# the machine every machine-independent arch search is pinned to, and
# the default machine of the smoke matrix
DEFAULT_MACHINE = "quadro-p4000"

# CI fast-tier smoke matrix: one binary miniapp, one mixed (k-ary,
# warm-started) miniapp, one arch program — the three adapter families
# through the whole pipeline in seconds
SMOKE_CELLS: Tuple[Tuple[str, str, str], ...] = (
    ("himeno", DEFAULT_MACHINE, "binary"),
    ("hetero", DEFAULT_MACHINE, "mixed"),
    ("arch:stablelm-3b", DEFAULT_MACHINE, "binary"),
)


@dataclasses.dataclass(frozen=True)
class SweepCell:
    """One matrix cell: a program searched on a machine in a mode."""

    program: str
    hw: str
    mode: str

    @property
    def id(self) -> str:
        return f"{self.program}:{self.hw}:{self.mode}"

    @property
    def slug(self) -> str:
        """Filesystem-safe artifact stem for this cell."""
        return self.id.replace(":", "-").replace("/", "-")


# ---------------------------------------------------------------------------
# matrix enumeration
# ---------------------------------------------------------------------------


def default_programs() -> List[str]:
    """Every sweepable program: the paper miniapps plus the whole
    model zoo as ``arch:<name>`` plan searches."""
    from repro.configs import ARCH_IDS
    from repro.core import miniapps

    return sorted(miniapps.MINIAPPS) + [f"arch:{a}" for a in ARCH_IDS]


def default_machines() -> List[str]:
    from repro.destinations import REGISTRIES

    return sorted(REGISTRIES)


def enumerate_matrix(
    programs: Optional[Sequence[str]] = None,
    machines: Optional[Sequence[str]] = None,
    modes: Sequence[str] = MODES,
) -> Tuple[List[SweepCell], List[Dict[str, str]]]:
    """The cross product as (feasible cells, skipped cells with reasons).

    Every (program, machine, mode) combination appears in exactly one of
    the two lists — infeasible cells are recorded, never dropped
    silently.
    """
    from repro.configs import ARCH_IDS
    from repro.core import miniapps
    from repro.destinations import REGISTRIES
    from repro.offload.programs import HW_MODELS

    programs = list(programs) if programs is not None else default_programs()
    machines = list(machines) if machines is not None else default_machines()
    for m in modes:
        if m not in MODES:
            raise ValueError(f"unknown mode {m!r}; have {MODES}")
    known_progs = set(miniapps.MINIAPPS) | {f"arch:{a}" for a in ARCH_IDS}
    unknown = [p for p in programs if p not in known_progs]
    if unknown:
        raise ValueError(
            f"unknown programs {unknown}; have {sorted(known_progs)}"
        )
    unknown = [m for m in machines if m not in REGISTRIES
               and m not in HW_MODELS]
    if unknown:
        raise ValueError(
            f"unknown machines {unknown}; have registries "
            f"{sorted(REGISTRIES)} and hardware models {sorted(HW_MODELS)}"
        )
    cells: List[SweepCell] = []
    skipped: List[Dict[str, str]] = []
    for prog in programs:
        for hw in machines:
            for mode in modes:
                cell = SweepCell(prog, hw, mode)
                reason = None
                if prog.startswith("arch:"):
                    if mode == "mixed":
                        reason = "arch programs are binary-only"
                    elif hw != DEFAULT_MACHINE and DEFAULT_MACHINE in machines:
                        reason = (
                            "arch plan evaluator is machine-independent; "
                            f"scored once on {DEFAULT_MACHINE}"
                        )
                elif mode == "binary" and hw not in HW_MODELS:
                    reason = (
                        "binary mode prices against a HardwareModel; "
                        f"registry {hw!r} has no rate-constant entry"
                    )
                if reason is None:
                    cells.append(cell)
                else:
                    skipped.append({"id": cell.id, "reason": reason})
    return cells, skipped


def smoke_matrix() -> Tuple[List[SweepCell], List[Dict[str, str]]]:
    """The fixed 3-cell CI fast-tier matrix (one per adapter family)."""
    return [SweepCell(*c) for c in SMOKE_CELLS], []


def cell_spec(
    cell: SweepCell,
    *,
    smoke: bool = False,
    cache: Optional[str] = None,
    workers: int = 1,
    seed: int = 0,
) -> OffloadSpec:
    """The :class:`OffloadSpec` a cell runs under. Mixed cells search
    the machine's full destination set (host first) warm-started, with
    the smoke budget trim under ``smoke``; binary/arch budgets are
    already seconds-scale on the analytic evaluators."""
    kw: Dict[str, Any] = dict(
        program=cell.program,
        mode=cell.mode,
        hw=cell.hw,
        cache=cache,
        workers=workers,
        seed=seed,
    )
    if cell.mode == "mixed":
        from repro.destinations import get_registry

        reg = get_registry(cell.hw)
        kw["destinations"] = tuple(d.name for d in reg.destinations)
        kw["warm_start"] = True
        # mixed cells search with the block-substitution dimension on:
        # the sweep's job is the best placement the toolchain can find,
        # and v3 points record what substitution bought per cell
        kw["blocks"] = True
        if smoke:
            kw["population"], kw["generations"] = MIXED_SMOKE_BUDGET
    if cell.program in MEASURED_PROGRAMS:
        # runnable programs: wall-clock the two winner projections so
        # every sweep point records modeled-vs-measured rank fidelity
        kw["ga"] = GAControls(rank_probe=True)
        if smoke:  # the CI matrix wall-clocks toy grids
            kw["measured_scale"] = "small"
    return OffloadSpec(**kw)


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------


def _git_hash() -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except (OSError, subprocess.TimeoutExpired):
        return None
    return out.stdout.strip() or None if out.returncode == 0 else None


def _quality_summary(art: Optional[OffloadResult]) -> Optional[Dict]:
    """Compact per-cell copy of the report stage's quality section
    (pass@k stability + rank correlation), the v2 trajectory field. A
    gate-failed report stage still recorded its payload, so its quality
    numbers surface here too."""
    if art is None or "report" not in art.stages:
        return None
    q = art.stages["report"].payload.get("quality")
    if not q:
        return None
    out: Dict[str, Any] = {}
    st = q.get("stability") or {}
    out["stability"] = {"skipped": st["skipped"]} if "skipped" in st else {
        k: st[k] for k in ("k", "pass_at_k", "rel_spread",
                           "distinct_winners") if k in st
    }
    rk = q.get("rank") or {}
    out["rank"] = {"skipped": rk["skipped"]} if "skipped" in rk else {
        k: rk.get(k) for k in ("n", "spearman", "kendall")
    }
    return out


def _blocks_summary(art: Optional[OffloadResult]) -> Optional[Dict]:
    """Compact per-cell block-substitution record (docs/blocks.md), the
    v3 trajectory field: how many library blocks matched, how many the
    winner substituted, and which kernel landed where. None when the
    cell ran without the feature (binary/arch cells, zero-match mixed
    programs)."""
    if art is None or "analyze" not in art.stages:
        return None
    blocks = art.stages["analyze"].payload.get("blocks")
    if not blocks:
        return None
    out: Dict[str, Any] = {
        "matches": len(blocks.get("matches", ())),
        "substituted": 0,
        "kernels": [],
    }
    if "search" in art.stages:
        subs = art.stages["search"].payload.get("substitutions") or ()
        act = [s for s in subs if s.get("active")]
        out["substituted"] = len(act)
        out["kernels"] = [f"{s['entry']}@{s['destination']}" for s in act]
    return out


def _cell_record(
    cell: SweepCell,
    art: Optional[OffloadResult],
    *,
    status: str,
    fresh: int,
    resumed: bool,
    wall_s: float,
    error: Optional[str] = None,
) -> Dict[str, Any]:
    rec: Dict[str, Any] = {
        "id": cell.id,
        "program": cell.program,
        "hw": cell.hw,
        "mode": cell.mode,
        "status": status,  # "ok" | "failed"
        "resumed": resumed,  # artifact was already complete: cell skipped
        "fresh_measurements": int(fresh),  # paid in THIS invocation
        "wall_s": float(wall_s),
        "error": error,
        "best_time_s": None,
        "baseline_s": None,
        "speedup": None,
        "search": None,
        "residency": None,
        "quality": _quality_summary(art),
        "blocks": _blocks_summary(art),
    }
    if art is None:
        return rec
    rec["best_time_s"] = art.best_time_s
    rec["baseline_s"] = art.baseline_time_s
    rec["speedup"] = art.speedup
    if art.completed("search"):
        s = art.stage("search").payload
        looked_up = int(s["evaluations"]) + int(s["cache_hits"])
        rec["search"] = {
            "evaluations": int(s["evaluations"]),
            "cache_hits": int(s["cache_hits"]),
            "hit_rate": float(s["cache_hits"]) / looked_up
            if looked_up else 0.0,
            "wall_s": float(s["wall_s"]),
            "generations": int(s["ga"]["generations"]),
            "population": int(s["ga"]["population"]),
            # genomes/sec the search sustained (submissions, not fresh
            # measurements: cache hits are part of the sustained rate)
            "throughput": (
                int(s["ga"]["generations"]) * int(s["ga"]["population"])
                / float(s["wall_s"])
            ) if float(s["wall_s"]) > 0 else None,
        }
        r = s.get("residency")
        if r is not None:
            rec["residency"] = {
                "evicted_bytes": float(r["evicted_bytes"]),
                "spilled_bytes": float(r["spilled_bytes"]),
                "oversubscribed": list(r.get("oversubscribed", ())),
            }
    return rec


def _totals(cells: List[Dict[str, Any]], wall_s: float) -> Dict[str, Any]:
    ok = [c for c in cells if c["status"] == "ok"]
    speedups = [c["speedup"] for c in ok if c["speedup"]]
    fresh = sum(c["fresh_measurements"] for c in cells)
    hits = sum(c["search"]["cache_hits"] for c in ok if c["search"])
    looked_up = fresh + hits
    return {
        "n_cells": len(cells),
        "n_ok": len(ok),
        "n_failed": sum(1 for c in cells if c["status"] == "failed"),
        "n_resumed": sum(1 for c in cells if c["resumed"]),
        "fresh_measurements": int(fresh),
        "cache_hits": int(hits),
        "hit_rate": float(hits) / looked_up if looked_up else 0.0,
        "geomean_speedup": float(
            math.exp(sum(math.log(s) for s in speedups) / len(speedups))
        ) if speedups else None,
        "wall_s": float(wall_s),
    }


def run_sweep(
    cells: Sequence[SweepCell],
    skipped: Sequence[Dict[str, str]] = (),
    *,
    out_dir: str = DEFAULT_SWEEP_DIR,
    cache: Optional[str] = None,
    workers: int = 1,
    smoke: bool = False,
    seed: int = 0,
    label: Optional[str] = None,
    progress: Optional[Callable[[str], None]] = None,
) -> Dict[str, Any]:
    """Run every cell (resumably) and return one trajectory point.

    Per cell, in order:

    - an existing COMPLETE artifact under ``out_dir`` short-circuits the
      cell entirely (``resumed=True``, zero fresh measurements);
    - an existing partial artifact is continued via
      :meth:`Offloader.resume` (its embedded spec is authoritative);
    - otherwise a fresh pipeline runs under :func:`cell_spec`.

    All cells share one JSONL fitness cache (default
    ``<out_dir>/fitness.jsonl``); evaluator fingerprints keep entries
    from crossing between cells that must not share. A cell's
    :class:`StageFailure` is recorded (status="failed") and the sweep
    continues — one bad cell must not lose the rest of the matrix.
    """
    say = progress or (lambda _line: None)
    os.makedirs(out_dir, exist_ok=True)
    cache = cache or os.path.join(out_dir, "fitness.jsonl")
    t0 = time.perf_counter()
    records: List[Dict[str, Any]] = []
    for i, cell in enumerate(cells):
        c0 = time.perf_counter()
        art_path = os.path.join(out_dir, f"{cell.slug}.offload.json")
        art: Optional[OffloadResult] = None
        if os.path.exists(art_path):
            art = OffloadResult.load(art_path)
        if art is not None and art.completed("report"):
            rec = _cell_record(cell, art, status="ok", fresh=0,
                               resumed=True,
                               wall_s=time.perf_counter() - c0)
            records.append(rec)
            say(f"[{i + 1}/{len(cells)}] {cell.id}: already complete "
                f"(best {rec['best_time_s']:.4g}s) — skipped")
            continue
        if art is not None:
            off = Offloader.resume(art_path)
        else:
            spec = cell_spec(cell, smoke=smoke, cache=cache,
                             workers=workers, seed=seed)
            off = Offloader(spec, artifact_path=art_path)
        status, error = "ok", None
        try:
            off.run()
        except StageFailure as e:
            status, error = "failed", str(e)
        except Exception as e:  # noqa: BLE001 — sweep must finish
            status, error = "failed", repr(e)
        fresh = 0
        if off.result.completed("search"):
            fresh = int(off.result.stage("search").payload["evaluations"])
        rec = _cell_record(cell, off.result, status=status, fresh=fresh,
                           resumed=False, error=error,
                           wall_s=time.perf_counter() - c0)
        records.append(rec)
        if status == "ok":
            say(f"[{i + 1}/{len(cells)}] {cell.id}: best "
                f"{rec['best_time_s']:.4g}s "
                f"({rec['speedup']:.1f}x over all-host, "
                f"{fresh} fresh measurements)")
        else:
            say(f"[{i + 1}/{len(cells)}] {cell.id}: FAILED — {error}")
    return {
        "v": SWEEP_POINT_VERSION,
        "git": _git_hash(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "label": label,
        "smoke": bool(smoke),
        "matrix": {
            "cells": [c.id for c in cells],
            "skipped": list(skipped),
        },
        "cells": records,
        "totals": _totals(records, time.perf_counter() - t0),
    }


# ---------------------------------------------------------------------------
# trajectory persistence (BENCH_sweep.json)
# ---------------------------------------------------------------------------

_POINT_KEYS = ("git", "timestamp", "label", "smoke", "matrix", "cells",
               "totals")
_CELL_KEYS = ("id", "program", "hw", "mode", "status", "resumed",
              "fresh_measurements", "wall_s", "best_time_s", "baseline_s",
              "speedup")


def validate_point(point: Dict[str, Any]) -> None:
    """Raise ``ValueError`` naming every missing field — the writer-side
    schema gate (``Trajectory.append`` runs it on every point)."""
    problems = [f"point missing key {k!r}" for k in _POINT_KEYS
                if k not in point]
    cells = point.get("cells")
    if not isinstance(cells, list):
        problems.append("point 'cells' must be a list")
        cells = []
    v = point.get("v", 1)  # v1 points predate the "v" key
    for i, c in enumerate(cells):
        problems += [f"cell[{i}] missing key {k!r}" for k in _CELL_KEYS
                     if k not in c]
        if c.get("status") not in ("ok", "failed"):
            problems.append(f"cell[{i}] status must be ok|failed: "
                            f"{c.get('status')!r}")
        if v >= 2 and "quality" not in c:
            problems.append(f"cell[{i}] missing key 'quality' "
                            f"(required for v{v} points)")
        if v >= 3 and "blocks" not in c:
            problems.append(f"cell[{i}] missing key 'blocks' "
                            f"(required for v{v} points)")
        if (v >= 4 and c.get("status") == "ok"
                and isinstance(c.get("search"), dict)
                and "throughput" not in c["search"]):
            problems.append(f"cell[{i}] search missing key 'throughput' "
                            f"(required for v{v} points)")
    if problems:
        raise ValueError("invalid trajectory point: " + "; ".join(problems))


@dataclasses.dataclass
class Trajectory:
    """The append-only BENCH trajectory: an ordered list of points."""

    points: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    path: Optional[str] = None

    @classmethod
    def load(cls, path: str) -> "Trajectory":
        """Load a trajectory file; a missing file is an empty trajectory
        (the first sweep creates it), anything else must carry the
        schema tag + version."""
        if not os.path.exists(path):
            return cls(points=[], path=path)
        with open(path, "r", encoding="utf-8") as fh:
            d = json.load(fh)
        if d.get("schema") != SWEEP_SCHEMA or \
                d.get("v") != SWEEP_SCHEMA_VERSION:
            raise ValueError(
                f"{path} is not a {SWEEP_SCHEMA}/v{SWEEP_SCHEMA_VERSION} "
                f"trajectory (schema={d.get('schema')!r}, v={d.get('v')!r})"
            )
        return cls(points=list(d.get("points", [])), path=path)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "schema": SWEEP_SCHEMA,
            "v": SWEEP_SCHEMA_VERSION,
            "points": self.points,
        }

    def save(self, path: Optional[str] = None) -> Optional[str]:
        path = path or self.path
        if path is None:
            return None
        self.path = path
        return atomic_json_save(path, self.to_dict())

    @property
    def last(self) -> Optional[Dict[str, Any]]:
        return self.points[-1] if self.points else None

    @property
    def previous(self) -> Optional[Dict[str, Any]]:
        return self.points[-2] if len(self.points) >= 2 else None


def append_point(path: str, point: Dict[str, Any]) -> Trajectory:
    """Validate ``point``, merge it onto whatever is on disk at ``path``
    right now (append-only: existing points are never rewritten or
    dropped), save atomically, and return the merged trajectory."""
    validate_point(point)
    traj = Trajectory.load(path)
    traj.points.append(point)
    traj.save()
    return traj


# ---------------------------------------------------------------------------
# regression flagging + leaderboard
# ---------------------------------------------------------------------------


def flag_regressions(
    prev: Optional[Dict[str, Any]],
    new: Dict[str, Any],
    rel_tolerance: float = DEFAULT_REL_TOLERANCE,
) -> List[Dict[str, Any]]:
    """Cells of ``new`` whose winner fitness worsened by strictly more
    than ``rel_tolerance`` relative to the same cell id in ``prev``.

    Semantics (documented in docs/benchmarks.md, tested at the edges):

    - only cells with status="ok" and a recorded winner in BOTH points
      compare — a failed or new cell is never a *regression* (failures
      carry their own exit code);
    - ``new_s > prev_s * (1 + tol)`` flags; equality at the boundary
      does not;
    - improvements are never flagged, whatever their size.
    """
    if prev is None:
        return []
    if rel_tolerance < 0:
        raise ValueError(f"rel_tolerance must be >= 0: {rel_tolerance}")
    prev_by_id = {
        c["id"]: c for c in prev.get("cells", ())
        if c.get("status") == "ok" and c.get("best_time_s")
    }
    flags = []
    for c in new.get("cells", ()):
        if c.get("status") != "ok" or not c.get("best_time_s"):
            continue
        p = prev_by_id.get(c["id"])
        if p is None:
            continue
        prev_s, new_s = float(p["best_time_s"]), float(c["best_time_s"])
        if new_s > prev_s * (1.0 + rel_tolerance):
            flags.append({
                "id": c["id"],
                "prev_best_s": prev_s,
                "new_best_s": new_s,
                "ratio": new_s / prev_s,
                "rel_tolerance": rel_tolerance,
            })
    return flags


def _delta_text(prev_cell: Optional[Dict[str, Any]],
                cell: Dict[str, Any]) -> str:
    if prev_cell is None or not prev_cell.get("best_time_s") \
            or not cell.get("best_time_s"):
        return "new"
    rel = cell["best_time_s"] / prev_cell["best_time_s"] - 1.0
    return f"{rel:+.1%}"


def render_leaderboard(
    traj: Trajectory,
    rel_tolerance: float = DEFAULT_REL_TOLERANCE,
) -> str:
    """The best placement per program per machine from the trajectory's
    last point, with per-cell deltas against the previous point and the
    regression verdict (the same comparison the exit code reflects)."""
    point = traj.last
    if point is None:
        return "BENCH trajectory is empty — run a sweep first."
    prev = traj.previous
    prev_by_id = {c["id"]: c for c in (prev or {}).get("cells", ())}
    ok = [c for c in point["cells"] if c["status"] == "ok"]
    rows = [
        f"== BENCH leaderboard @ {point.get('git') or 'unknown'} "
        f"({point['timestamp']}, point {len(traj.points)}"
        + (f", label {point['label']!r}" if point.get("label") else "")
        + (", smoke matrix" if point.get("smoke") else "")
        + ") =="
    ]
    for hw in sorted({c["hw"] for c in ok}):
        rows.append(f"machine {hw}:")
        rows.append(f"  {'program':28s} {'mode':7s} {'best_s':>10s} "
                    f"{'speedup':>8s} {'vs prev':>8s}")
        by_prog: Dict[str, Dict[str, Any]] = {}
        for c in ok:
            if c["hw"] != hw:
                continue
            cur = by_prog.get(c["program"])
            if cur is None or (c["best_time_s"] or float("inf")) < \
                    (cur["best_time_s"] or float("inf")):
                by_prog[c["program"]] = c
        for prog in sorted(
            by_prog, key=lambda p: -(by_prog[p]["speedup"] or 0.0)
        ):
            c = by_prog[prog]
            rows.append(
                f"  {prog:28s} {c['mode']:7s} {c['best_time_s']:10.4g} "
                f"{(c['speedup'] or 0.0):7.1f}x "
                f"{_delta_text(prev_by_id.get(c['id']), c):>8s}"
            )
    quality_lines = []
    for c in ok:
        q = c.get("quality") or {}
        st = q.get("stability") or {}
        rk = q.get("rank") or {}
        parts = []
        if "pass_at_k" in st:
            parts.append(f"pass@{st['k']} {st['pass_at_k']:.0%} "
                         f"(spread +{st['rel_spread']:.1%}, "
                         f"{st['distinct_winners']} winners)")
        if rk.get("spearman") is not None:
            parts.append(f"spearman {rk['spearman']:+.2f} "
                         f"over {rk['n']}")
        if parts:
            quality_lines.append(f"  {c['id']}: " + ", ".join(parts))
    if quality_lines:
        rows.append("search quality (v2 points; docs/observability.md):")
        rows.extend(quality_lines)
    block_lines = []
    for c in ok:
        b = c.get("blocks")
        if not b or not b.get("matches"):
            continue
        kern = ", ".join(b.get("kernels", ())) or "none"
        block_lines.append(
            f"  {c['id']}: {b.get('substituted', 0)}/{b['matches']} "
            f"blocks substituted ({kern})"
        )
    if block_lines:
        rows.append("block substitutions (v3 points; docs/blocks.md):")
        rows.extend(block_lines)
    failed = [c for c in point["cells"] if c["status"] == "failed"]
    for c in failed:
        rows.append(f"FAILED {c['id']}: {c.get('error')}")
    tot = point["totals"]
    rows.append(
        f"totals: {tot['n_ok']}/{tot['n_cells']} cells ok"
        + (f", {tot['n_resumed']} resumed" if tot["n_resumed"] else "")
        + f", {tot['fresh_measurements']} fresh measurements, "
        f"hit-rate {tot['hit_rate']:.0%}"
        + (f", geomean speedup {tot['geomean_speedup']:.2f}x"
           if tot.get("geomean_speedup") else "")
        + f", wall {tot['wall_s']:.1f}s"
    )
    flags = flag_regressions(prev, point, rel_tolerance)
    if flags:
        rows.append(f"REGRESSIONS (tolerance {rel_tolerance:.0%}):")
        for f in flags:
            rows.append(
                f"  {f['id']}: {f['prev_best_s']:.4g}s -> "
                f"{f['new_best_s']:.4g}s ({f['ratio']:.3f}x)"
            )
    elif prev is not None:
        rows.append(f"regressions (tolerance {rel_tolerance:.0%}): none")
    else:
        rows.append("regressions: no previous point to compare against")
    return "\n".join(rows)
