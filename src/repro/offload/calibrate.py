"""Model calibration against real measurements (docs/fidelity.md).

The analytic ``HardwareModel`` constants were frozen once against the
paper's published end-points; nothing on THIS container ever checked
them against a wall clock. This module closes that loop, the way the
FPGA companion work (arXiv:2004.08548) insists modeled rates must be:

- **measure** a small designed probe set — the runnable miniapps
  (himeno, nasft) at several grid/iteration configs, each on both the
  host (numpy) and the accelerator (jitted JAX) path, wall-clocked by
  :class:`~repro.core.evaluator.MeasuredEvaluator`;
- **fit** per-destination constants by linear least squares:
  ``t ≈ flops/rate + bytes/link_bw + calls*setup`` (host probes have no
  transfer column). Two apps with different flops/bytes ratios keep the
  columns independent; non-positive coefficients are dropped and their
  constants *pinned* to the base model (recorded, never silent);
- **emit** a named registry entry (e.g. ``quadro-p4000-calibrated``)
  selectable via ``OffloadSpec.hw`` in every mode — the fitted
  :class:`HardwareModel` for binary/arch searches plus a
  :func:`~repro.destinations.profiles.calibrated_registry` for mixed
  searches — and record every probe's fit residual in the artifact.

Cache identity: the emitted ``HardwareModel.name`` is
``<entry>-<8-hex digest of the fitted constants>``, and the calibrated
registry fingerprints every constant, so a re-calibration deliberately
invalidates fitness caches while the modeled machines' fingerprints
stay untouched.

With ``kernels=True`` (``--kernels`` on the CLI, automatic when
``OffloadSpec.blocks`` is set) the probe set extends to the block
kernel library: each entry's implementation is wall-clocked against its
``ref.py`` oracle and the measured speedup lands in
``kernel_constants``, which :func:`install` registers as per-kernel
gains so block-substitution pricing (docs/blocks.md) is fitted, not
assumed.

Single constants cannot be split into a compute/bandwidth pair by one
wall clock, so the fit keeps the base machine's compute:bandwidth
*balance*: ``cpu_membw``/``accel_membw`` scale with the fitted rates
(recorded under ``pinned``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import evaluator as ev
from repro.core import miniapps
from repro.core.evaluator import loop_bytes
from repro.core.loopir import LoopClass, LoopProgram
from repro.destinations import (
    Registry,
    calibrated_registry,
    get_registry,
    register_registry,
)
from repro.offload import programs

_CAL_VERSION = 1


# ---------------------------------------------------------------------------
# the designed probe set
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Probe:
    """One designed measurement: app config x destination path."""

    app: str  # "himeno" | "nasft"
    grid: Tuple[int, int, int]
    steps: int  # nn (himeno) / niter (nasft)
    dest: str  # "host" | "accel"


def _configs() -> List[Tuple[str, Tuple[int, int, int], int]]:
    # grids big enough that per-call compute rises above dispatch noise
    # (at toy grids the jit path is dispatch-dominated and the rate
    # column of the fit is unidentifiable), small enough that the whole
    # sweep stays a few seconds
    return [
        ("himeno", (17, 17, 33), 2),
        ("himeno", (17, 17, 33), 4),
        ("himeno", (33, 33, 65), 2),
        ("himeno", (33, 33, 65), 4),
        ("nasft", (16, 16, 16), 2),
        ("nasft", (16, 16, 16), 4),
        ("nasft", (32, 32, 32), 2),
        ("nasft", (32, 32, 32), 4),
    ]


# both apps at several scales, each on both paths: himeno and nasft have
# different flops/bytes ratios, which is what keeps the rate and
# transfer columns of the least-squares system independent
DEFAULT_PROBES: Tuple[Probe, ...] = tuple(
    Probe(app, grid, steps, dest)
    for app, grid, steps in _configs()
    for dest in ("host", "accel")
)


def _probe_run_fn(p: Probe):
    if p.app == "himeno":
        return miniapps.HimenoRunFn(grid=p.grid, nn=p.steps)
    return miniapps.NasftRunFn(grid=p.grid, niter=p.steps)


def _probe_program(p: Probe) -> LoopProgram:
    return _probe_run_fn(p).program()


def _region_quantities(prog: LoopProgram) -> Tuple[float, float, float]:
    """(flops, bytes, calls) of the program's sequential-region loops —
    the work the runnable implementations actually execute per run."""
    flops = byts = 0.0
    for loop in prog.loops:
        if loop.parent_seq is None:
            continue
        execs = prog.region_trip(loop.parent_seq)
        flops += loop.total_flops * execs
        byts += loop_bytes(prog, loop) * execs
    calls = float(max((r.trip for r in prog.seq_regions), default=1))
    return flops, byts, calls


def _measure_probe(p: Probe, repeats: int) -> float:
    """Wall-clock one probe in-process (the calibrate flow measures a
    handful of designed points, not a GA population — subprocess
    isolation buys nothing here). One untimed warm-up run precedes the
    timed repeats: calibration fits steady-state rates by definition,
    so a one-time jit compile must never land in a probe even at
    repeats=1."""
    fn = _probe_run_fn(p)
    m = ev.MeasuredEvaluator(fn, repeats=repeats, tag=fn.tag)
    n = miniapps.MINIAPPS[p.app]().gene_length
    genes = [0] * n
    if p.dest == "accel":
        genes[fn.hot_gene] = 1
    fn(genes)  # warm-up (compile cache), not timed
    return float(m(genes))


# ---------------------------------------------------------------------------
# the least-squares fit
# ---------------------------------------------------------------------------


def _nonneg_lstsq(
    A: np.ndarray, b: np.ndarray
) -> Tuple[Optional[np.ndarray], List[int]]:
    """Least squares with non-positive coefficients dropped (their
    columns zeroed and refit), column 0 (the rate term) mandatory.
    Returns (coefficients | None when even the rate fit fails, dropped
    column indices). Columns are norm-scaled before the solve."""
    active = list(range(A.shape[1]))
    dropped: List[int] = []
    while True:
        sub = A[:, active]
        scale = np.linalg.norm(sub, axis=0)
        scale[scale == 0.0] = 1.0
        coef_s, *_ = np.linalg.lstsq(sub / scale, b, rcond=None)
        coef = coef_s / scale
        bad = [i for i, c in zip(active, coef) if c <= 0.0 and i != 0]
        if not bad:
            if coef[0] <= 0.0:
                return None, dropped  # unusable: pin to the base model
            out = np.zeros(A.shape[1])
            for i, c in zip(active, coef):
                out[i] = c
            return out, dropped
        # drop the worst offender and refit the rest
        worst = min(bad, key=lambda i: coef[active.index(i)])
        active.remove(worst)
        dropped.append(worst)


def _base_hw_from_registry(reg: Registry) -> ev.HardwareModel:
    """Derive the base HardwareModel constants from a registry's host
    and first GPU/TPU-kind destination (works for any registry, named
    calibrations included)."""
    host = reg.host
    accel = next(
        (d for d in reg.destinations if d.kind in ("gpu", "tpu")), None
    )
    if accel is None:
        raise ValueError(
            f"registry {reg.name!r} has no GPU/TPU-kind destination to "
            "calibrate against"
        )
    link = reg.link(host.name, accel.name)
    assert link is not None, (host.name, accel.name)
    rates = dict(accel.rates)
    return ev.HardwareModel(
        name=f"base-of-{reg.name}",
        cpu_flops=dict(host.rates)[LoopClass.TIGHT],
        cpu_membw=host.membw,
        accel_flops_kernels=rates[LoopClass.TIGHT],
        accel_flops_parallel=rates[LoopClass.NON_TIGHT],
        accel_flops_vector=rates[LoopClass.VECTOR_ONLY],
        accel_membw=accel.membw,
        link_bw=link.bw,
        link_latency=link.latency,
        launch_latency=accel.launch_latency,
    )


# ---------------------------------------------------------------------------
# the calibration artifact
# ---------------------------------------------------------------------------


_CONSTANT_FIELDS = (
    "cpu_flops", "cpu_membw", "accel_flops_kernels", "accel_flops_parallel",
    "accel_flops_vector", "accel_membw", "link_bw", "link_latency",
    "launch_latency",
)


@dataclasses.dataclass
class CalibrationResult:
    """Fitted constants + per-probe residuals for one machine.

    Saved as ``<name>.calib.json`` by the CLI (git-ignored: calibrations
    are machine-local facts, like fitness caches) and embedded verbatim
    in the pipeline's ``calibrate`` stage payload, so resuming a
    calibrated artifact reconstructs the identical machine without
    re-measuring anything.
    """

    name: str  # spec-facing registry/hw entry name
    base: str  # the base registry that was calibrated
    host: str  # where the clocks ran
    repeats: int
    constants: Dict[str, float]  # the _CONSTANT_FIELDS values
    pinned: Tuple[str, ...]  # constants NOT determined by the fit
    probes: Tuple[Dict[str, Any], ...]  # measured/fitted/residual rows
    # per-kernel speedup of each block-library implementation over its
    # ref.py oracle on THIS host (``run_calibration(kernels=True)``,
    # docs/blocks.md); empty unless kernel probes ran
    kernel_constants: Dict[str, float] = dataclasses.field(
        default_factory=dict
    )

    @property
    def digest(self) -> str:
        blob = json.dumps(self.constants, sort_keys=True)
        if self.kernel_constants:
            # appended only when present: kernel-free calibrations keep
            # their pre-blocks digests (and cache identities) unchanged
            blob += json.dumps(self.kernel_constants, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:8]

    @property
    def hw_name(self) -> str:
        """The HardwareModel name: entry name + constants digest, so a
        re-calibration can never silently share fitness-cache entries
        with its predecessor (binary-mode fingerprints key on it)."""
        return f"{self.name}-{self.digest}"

    def hardware_model(self) -> ev.HardwareModel:
        return ev.HardwareModel(name=self.hw_name, **self.constants)

    def residuals(self) -> Dict[str, float]:
        errs = [abs(float(p["rel_err"])) for p in self.probes]
        return {
            "n": len(errs),
            "max_abs_rel": max(errs) if errs else 0.0,
            "mean_abs_rel": float(np.mean(errs)) if errs else 0.0,
        }

    # -- (de)serialization --------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        return {
            "v": _CAL_VERSION,
            "name": self.name,
            "base": self.base,
            "host": self.host,
            "repeats": self.repeats,
            "constants": dict(self.constants),
            "pinned": list(self.pinned),
            "probes": [dict(p) for p in self.probes],
            **({"kernel_constants": dict(self.kernel_constants)}
               if self.kernel_constants else {}),
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "CalibrationResult":
        v = d.get("v", _CAL_VERSION)
        if v != _CAL_VERSION:
            raise ValueError(f"unsupported calibration version {v!r}")
        return cls(
            name=str(d["name"]),
            base=str(d["base"]),
            host=str(d.get("host", "")),
            repeats=int(d.get("repeats", 1)),
            constants={k: float(v) for k, v in d["constants"].items()},
            pinned=tuple(d.get("pinned", ())),
            probes=tuple(dict(p) for p in d.get("probes", ())),
            kernel_constants={
                k: float(v)
                for k, v in d.get("kernel_constants", {}).items()
            },
        )

    def save(self, path: str) -> str:
        from repro.offload.result import atomic_json_save

        return atomic_json_save(path, self.to_dict())

    @classmethod
    def load(cls, path: str) -> "CalibrationResult":
        with open(path, "r", encoding="utf-8") as fh:
            return cls.from_dict(json.load(fh))


# ---------------------------------------------------------------------------
# the flow: measure -> fit -> emit
# ---------------------------------------------------------------------------


def run_calibration(
    base: str = "quadro-p4000",
    repeats: int = 3,
    name: Optional[str] = None,
    probes: Optional[Sequence[Probe]] = None,
    measure: Optional[Callable[[Probe, int], float]] = None,
    kernels: bool = False,
    kernel_measure: Optional[
        Callable[[Any], Tuple[float, float]]
    ] = None,
) -> CalibrationResult:
    """Measure the probe set and fit the calibrated constants.

    ``measure`` is injectable for tests (a synthetic clock makes the fit
    deterministic); the default wall-clocks in-process.

    ``kernels=True`` additionally times every block-library kernel
    (docs/blocks.md) against its ``ref.py`` oracle and records the
    measured speedup as a per-kernel gain in ``kernel_constants``, so a
    ``fidelity="calibrated"`` blocks run prices substitutions from this
    host's clocks instead of the modeled defaults. ``kernel_measure``
    is the injectable probe: ``entry -> (oracle_s, impl_s)``.
    """
    base_reg = get_registry(base)
    base_hw = _base_hw_from_registry(base_reg)
    name = name or f"{base}-calibrated"
    probes = tuple(probes if probes is not None else DEFAULT_PROBES)
    measure = measure or _measure_probe
    if not any(p.dest == "host" for p in probes) or \
            not any(p.dest == "accel" for p in probes):
        raise ValueError("probe set needs both host and accel probes")

    rows: List[Dict[str, Any]] = []
    for p in probes:
        flops, byts, calls = _region_quantities(_probe_program(p))
        rows.append({
            "app": p.app,
            "dest": p.dest,
            "grid": list(p.grid),
            "steps": p.steps,
            "flops": flops,
            "bytes": byts,
            "calls": calls,
            "measured_s": float(measure(p, repeats)),
        })

    pinned: List[str] = ["link_latency"]  # one wall clock can't see it

    # host fit: t ~ flops/rate + calls*overhead (no transfer column; the
    # per-call overhead is interpreter dispatch, recorded but unused)
    hrows = [r for r in rows if r["dest"] == "host"]
    A = np.array([[r["flops"], r["calls"]] for r in hrows])
    b = np.array([r["measured_s"] for r in hrows])
    coef, _ = _nonneg_lstsq(A, b)
    if coef is None:
        cpu_flops = base_hw.cpu_flops
        pinned.append("cpu_flops")
        coef = np.array([1.0 / cpu_flops, 0.0])
    else:
        cpu_flops = 1.0 / coef[0]
    for r in hrows:
        r["fitted_s"] = float(coef[0] * r["flops"] + coef[1] * r["calls"])

    # accel fit: t ~ flops/rate + bytes/link_bw + calls*launch
    arows = [r for r in rows if r["dest"] == "accel"]
    A = np.array([[r["flops"], r["bytes"], r["calls"]] for r in arows])
    b = np.array([r["measured_s"] for r in arows])
    coef, dropped = _nonneg_lstsq(A, b)
    if coef is None:
        accel_flops = base_hw.accel_flops_kernels
        link_bw = base_hw.link_bw
        launch = base_hw.launch_latency
        pinned += ["accel_flops_kernels", "link_bw", "launch_latency"]
        coef = np.array([1.0 / accel_flops, 1.0 / link_bw, launch])
    else:
        accel_flops = 1.0 / coef[0]
        link_bw = 1.0 / coef[1] if 1 not in dropped else base_hw.link_bw
        launch = float(coef[2]) if 2 not in dropped \
            else base_hw.launch_latency
        if 1 in dropped:
            pinned.append("link_bw")
        if 2 in dropped:
            pinned.append("launch_latency")
    for r in arows:
        r["fitted_s"] = float(
            coef[0] * r["flops"] + coef[1] * r["bytes"]
            + coef[2] * r["calls"]
        )

    for r in rows:
        r["rel_err"] = float(
            (r["fitted_s"] - r["measured_s"]) / max(r["measured_s"], 1e-12)
        )

    # a single rate per destination cannot split compute from bandwidth:
    # keep the base machine's balance (membw scales with the rate) and
    # its directive-rate ratios
    pinned += ["cpu_membw", "accel_membw", "accel_flops_parallel",
               "accel_flops_vector"]
    constants = {
        "cpu_flops": float(cpu_flops),
        "cpu_membw": float(
            base_hw.cpu_membw * cpu_flops / base_hw.cpu_flops
        ),
        "accel_flops_kernels": float(accel_flops),
        "accel_flops_parallel": float(
            accel_flops * base_hw.accel_flops_parallel
            / base_hw.accel_flops_kernels
        ),
        "accel_flops_vector": float(
            accel_flops * base_hw.accel_flops_vector
            / base_hw.accel_flops_kernels
        ),
        "accel_membw": float(
            base_hw.accel_membw * accel_flops
            / base_hw.accel_flops_kernels
        ),
        "link_bw": float(link_bw),
        "link_latency": float(base_hw.link_latency),
        "launch_latency": float(launch),
    }
    assert set(constants) == set(_CONSTANT_FIELDS)

    kernel_constants: Dict[str, float] = {}
    if kernels:
        from repro.blocks import library as blk

        kmeasure = kernel_measure or (
            lambda entry: blk.time_kernel(entry, repeats=repeats)
        )
        for entry in blk.default_library().entries:
            oracle_s, impl_s = kmeasure(entry)
            gain = float(oracle_s) / max(float(impl_s), 1e-12)
            # a kernel that measures slower than its oracle keeps a
            # sub-1 gain: the search then prices substitution as a loss
            # and the genome learns to leave the block alone
            kernel_constants[entry.name] = max(gain, 1e-6)

    return CalibrationResult(
        name=name,
        base=base,
        host=ev._local_host(),
        repeats=repeats,
        constants=constants,
        pinned=tuple(pinned),
        probes=tuple(rows),
        kernel_constants=kernel_constants,
    )


def install(cal: CalibrationResult,
            replace: bool = True) -> ev.HardwareModel:
    """Register the calibration as a named machine in THIS process:
    ``OffloadSpec.hw = cal.name`` then selects the fitted HardwareModel
    (binary/arch) or the calibrated registry (mixed). Registration is
    process-local — other processes re-install from the saved
    ``.calib.json`` or from the artifact's calibrate-stage payload."""
    hw = cal.hardware_model()
    programs.register_hw_model(hw, name=cal.name, replace=replace)

    def factory(base: str = cal.base, hw: ev.HardwareModel = hw,
                name: str = cal.name) -> Registry:
        return calibrated_registry(get_registry(base), hw, name)

    register_registry(cal.name, factory, replace=replace)
    if cal.kernel_constants:
        from repro.blocks import library as blk

        blk.register_kernel_gains(cal.name, dict(cal.kernel_constants))
    return hw


def load_and_install(path: str, replace: bool = True) -> CalibrationResult:
    """``install(CalibrationResult.load(path))`` — the CLI's
    ``--calibration`` flag."""
    cal = CalibrationResult.load(path)
    install(cal, replace=replace)
    return cal
