"""Verification-environment evaluators: genes -> processing time (seconds).

Three evaluators, one per fidelity level:

- ``MiniappEvaluator`` — analytic cost model over a LoopProgram. Per-loop
  time = max(arithmetic, memory-traffic) on the executing side + kernel
  launch latency; transfers priced from ``core.transfer``'s schedule.
  Hardware constants model the paper's verification machine (Quadro P4000
  over PCIe3 x16); a TPU-v5e-host profile is provided for the adapted
  system. Constants were calibrated once against the paper's measured
  end-points (see ``calibration`` note below) and then frozen.

- ``MeasuredEvaluator`` — actually runs a miniapp implementation in this
  process and wall-clocks it on the device JAX gives it (the paper's real
  measurement loop, with timeout -> penalty handled by the GA).

- ``CompiledEvaluator`` — framework level: genes -> ExecutionPlan ->
  AOT ``.lower().compile()`` on the production mesh -> three-term roofline
  ``t_step``. Compile failure plays the role of a pgcc compile error
  (penalty). Used by the beyond-paper architecture offload search.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

from repro.core import transfer as tr
from repro.core.loopir import Loop, LoopClass, LoopProgram


# ---------------------------------------------------------------------------
# hardware model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Effective (not peak) rates; calibrated, see module docstring."""

    name: str
    cpu_flops: float  # scalar/autovec CPU pipeline
    cpu_membw: float  # CPU stream bandwidth through cache misses
    accel_flops_kernels: float  # `kernels`-directive loops (tight nests)
    accel_flops_parallel: float  # `parallel loop` (non-tight: slightly worse)
    accel_flops_vector: float  # `parallel loop vector` (VPU-rate only)
    accel_membw: float
    link_bw: float  # CPU<->accelerator (PCIe / host-HBM)
    link_latency: float  # per transfer batch
    launch_latency: float  # per kernel launch


# Paper verification machine: i5-7500 + Quadro P4000 (PCIe3 x16).
# Calibration (scripts/calibrate_miniapps.py, frozen 2026-07-16): constants
# chosen so the PROPOSED and PREVIOUS pipelines run through the full GA land
# on the paper's measured fig. 5 speedups:
#   paper   Himeno 4.8x / 15.4x   NAS.FT 5.4x / 10.0x
#   model   Himeno 5.0x / 15.3x   NAS.FT 4.6x /  9.7x
QUADRO_P4000 = HardwareModel(
    name="quadro-p4000",
    cpu_flops=3.262e9,
    cpu_membw=5.464e9,
    accel_flops_kernels=4.988e11,
    accel_flops_parallel=3.99e11,  # paper: kernels beats parallel on PGI
    accel_flops_vector=3.325e10,
    accel_membw=9.301e10,
    link_bw=7.694e9,
    link_latency=2.0e-5,
    launch_latency=8.0e-6,
)

# TPU adaptation of the same verification loop: v5e chip fed from host RAM.
TPU_V5E_HOST = HardwareModel(
    name="tpu-v5e-host",
    cpu_flops=6.0e9,
    cpu_membw=2.0e10,
    accel_flops_kernels=1.97e14,  # bf16 MXU
    accel_flops_parallel=1.6e14,
    accel_flops_vector=4.0e12,  # VPU-rate
    accel_membw=8.19e11,
    link_bw=3.2e10,  # PCIe gen4-ish host link
    link_latency=1.0e-5,
    launch_latency=3.0e-6,
)


# ---------------------------------------------------------------------------
# analytic model
# ---------------------------------------------------------------------------


_DIRECTIVE_RATE = {
    LoopClass.TIGHT: "accel_flops_kernels",
    LoopClass.NON_TIGHT: "accel_flops_parallel",
    LoopClass.VECTOR_ONLY: "accel_flops_vector",
}


def loop_bytes(prog: LoopProgram, loop: Loop) -> float:
    """Memory traffic of one nest execution: every touched array streamed
    once (true for the miniapps' loops, which sweep their arrays).
    Public: shared with :mod:`repro.destinations`' per-backend models."""
    return float(sum(prog.var(v).nbytes for v in loop.touched()))


def loop_time(
    prog: LoopProgram, loop: Loop, offloaded: bool, hw: HardwareModel
) -> float:
    """Time for ONE execution of the full nest (all trips of this loop)."""
    flops = loop.total_flops
    byts = loop_bytes(prog, loop)
    if not offloaded:
        return max(flops / hw.cpu_flops, byts / hw.cpu_membw)
    rate = getattr(hw, _DIRECTIVE_RATE[loop.klass])
    if loop.sequential_carry:
        rate = hw.accel_flops_vector  # no parallelism to exploit
    return max(flops / rate, byts / hw.accel_membw) + hw.launch_latency


@dataclasses.dataclass
class TimeBreakdown:
    cpu_s: float = 0.0
    accel_s: float = 0.0
    transfer_s: float = 0.0
    launch_s: float = 0.0  # included in accel_s; reported for analysis

    @property
    def total_s(self) -> float:
        return self.cpu_s + self.accel_s + self.transfer_s


def predict_time(
    prog: LoopProgram,
    genes: Sequence[int],
    mode: tr.TransferMode = tr.TransferMode.BULK,
    staged: bool = True,
    hw: HardwareModel = QUADRO_P4000,
) -> TimeBreakdown:
    offload = prog.genes_to_offloads(genes)
    bd = TimeBreakdown()
    for loop in prog.loops:
        execs = prog.region_trip(loop.parent_seq)
        t = loop_time(prog, loop, offload[loop.name], hw) * execs
        if offload[loop.name]:
            bd.accel_s += t
            bd.launch_s += hw.launch_latency * execs
        else:
            bd.cpu_s += t
    sched = tr.build_schedule(prog, genes, mode=mode, staged=staged)
    bd.transfer_s = (
        sched.total_bytes / hw.link_bw + sched.total_events * hw.link_latency
    )
    return bd


class MiniappEvaluator:
    """genes -> predicted seconds, under a transfer mode + staging flag."""

    def __init__(
        self,
        prog: LoopProgram,
        mode: tr.TransferMode = tr.TransferMode.BULK,
        staged: bool = True,
        hw: HardwareModel = QUADRO_P4000,
        kernels_only: bool = False,
    ):
        self.prog = prog
        self.mode = mode
        self.staged = staged
        self.hw = hw
        # previous method [33]: only `kernels`-class loops may be offloaded
        self.kernels_only = kernels_only

    def admissible(self, genes: Sequence[int]) -> Tuple[int, ...]:
        if not self.kernels_only:
            return tuple(genes)
        return tuple(
            g if l.klass == LoopClass.TIGHT else 0
            for g, l in zip(genes, self.prog.offloadable_loops)
        )

    def __call__(self, genes: Sequence[int]) -> float:
        return predict_time(
            self.prog, self.admissible(genes), self.mode, self.staged, self.hw
        ).total_s

    def fingerprint(self) -> str:
        """Configuration key for the persistent fitness cache (evalpool):
        two evaluators share measurements iff their fingerprints match.
        Keys on the program's structural digest, not its name — the same
        app at another grid size must not share cached times."""
        return (
            f"miniapp:{self.prog.fingerprint()}:{self.mode.value}"
            f":{'staged' if self.staged else 'unstaged'}:{self.hw.name}"
            f"{':kernels-only' if self.kernels_only else ''}"
        )

    def cpu_only_time(self) -> float:
        return predict_time(
            self.prog, (0,) * self.prog.gene_length, self.mode, True, self.hw
        ).total_s


# ---------------------------------------------------------------------------
# measured evaluator (the real verification environment)
# ---------------------------------------------------------------------------

# One measurement at a time on the device. A chip belongs to one process,
# so every measurement runs in the process that holds it; the runnable
# implementations place their arrays on the default device, and two
# clocks sharing it (concurrent service jobs) would time each other.
DEVICE_LANE = threading.Lock()


def device_info() -> Dict[str, object]:
    """What JAX measures on: ``{"platform", "device_kind", "count"}``."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs)}


class MeasuredEvaluator:
    """Wall-clocks ``run_fn(genes)``; the GA applies the timeout penalty.

    Measurements are machine-bound facts: the fingerprint carries the
    *measurement identity* — run_fn, repeat count, config tag, the host
    the clock ran on AND the device (platform and kind) — so a persistent
    fitness cache can hold modeled and measured entries side by side
    without ever serving one machine's (or the analytic model's, or the
    CPU's) numbers to another.

    ``measures_device``: the clock runs work on this process's device, so
    an :class:`~repro.core.evalpool.EvalPool` runs it in-line, one at a
    time (a child would find the chip held), and a measurement that
    raises fails the run — unlike a compile error, a crashed clock says
    nothing about the placement.
    """

    measures_device = True

    def __init__(self, run_fn: Callable[[Sequence[int]], None],
                 repeats: int = 1, tag: str = "default",
                 host: Optional[str] = None, device: Optional[str] = None):
        self.run_fn = run_fn
        self.repeats = repeats
        # qualnames don't distinguish lambdas/partials/closures that differ
        # only in captured state; set tag to the app/config identity when
        # sharing a persistent fitness cache
        self.tag = tag
        self.host = host if host is not None else _local_host()
        if device is None:
            d = device_info()
            device = f"{d['platform']}:{d['device_kind']}"
        self.device = device

    def __call__(self, genes: Sequence[int]) -> float:
        best = float("inf")
        with DEVICE_LANE:  # the clock starts once the device is ours
            for _ in range(self.repeats):
                t0 = time.perf_counter()
                self.run_fn(genes)
                best = min(best, time.perf_counter() - t0)
        return best

    def cache_key(self, genes: Sequence[int]) -> str:
        """Delegates to the run_fn's canonicalization when it has one
        (``HimenoRunFn``/``NasftRunFn`` collapse to the genes their
        implementation actually distinguishes, so equivalent genomes
        share one real measurement); digit-string otherwise."""
        ck = getattr(self.run_fn, "cache_key", None)
        if callable(ck):
            return str(ck(genes))
        return "".join(str(int(g)) for g in genes)

    def fingerprint(self) -> str:
        name = getattr(self.run_fn, "__qualname__", None) \
            or type(self.run_fn).__name__
        mod = getattr(self.run_fn, "__module__", "")
        return (f"measured:{mod}.{name}:r{self.repeats}:{self.tag}"
                f"@{self.host}:{self.device}")


def _local_host() -> str:
    import platform

    return platform.node() or "localhost"


# ---------------------------------------------------------------------------
# compiled evaluator (framework level, beyond-paper)
# ---------------------------------------------------------------------------


class CompiledEvaluator:
    """genes -> plan -> AOT compile -> roofline t_step (seconds).

    ``build_and_score(genes)`` must lower+compile the cell under the genes'
    ExecutionPlan and return predicted step seconds; it is injected (from
    ``launch.dryrun``) to keep core/ free of launch-time imports. Compile
    errors are the pgcc-compile-error analogue -> penalty (returned as inf,
    which the GA maps to the penalty time).

    ``evaluate_batch`` is the evalpool's batched AOT-compile path: a whole
    generation's unique, uncached genomes are compiled with up to
    ``compile_workers`` concurrent lower+compile pipelines (XLA compilation
    releases the GIL, so threads overlap the C++ compile work).
    """

    def __init__(
        self,
        build_and_score: Callable[[Tuple[int, ...]], float],
        verbose: bool = False,
        compile_workers: int = 1,
        tag: str = "default",
    ):
        self.build_and_score = build_and_score
        self.verbose = verbose
        self.compile_workers = max(1, int(compile_workers))
        self.tag = tag
        self.failures: Dict[Tuple[int, ...], str] = {}

    def __call__(self, genes: Sequence[int]) -> float:
        key = tuple(genes)
        try:
            t = float(self.build_and_score(key))
        except Exception as e:  # noqa: BLE001 — compile error == penalty
            self.failures[key] = repr(e)
            if self.verbose:
                print(f"[compiled-eval] {key} failed: {e!r}")
            return float("inf")
        if self.verbose:
            print(f"[compiled-eval] {key} -> {t*1e3:.2f} ms")
        return t

    def evaluate_batch(
        self, genes_list: Sequence[Sequence[int]]
    ) -> "list[float]":
        from repro.core.evalpool import parallel_map

        return parallel_map(self, list(genes_list), self.compile_workers)

    def fingerprint(self) -> str:
        name = getattr(self.build_and_score, "__qualname__", None) \
            or type(self.build_and_score).__name__
        mod = getattr(self.build_and_score, "__module__", "")
        return f"compiled:{mod}.{name}:{self.tag}"
