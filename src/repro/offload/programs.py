"""Program adapters: one interface from an OffloadSpec to the pieces the
pipeline stages need.

A *program* is whatever the offload genome indexes into:

- a **miniapp** ``LoopProgram`` (the paper's applications — Himeno,
  NAS.FT, and the heterogeneous pipeline), searched either in the
  paper's binary CPU/GPU mode (``MiniappEvaluator`` under a METHODS
  configuration) or in the mixed-destination k-ary mode
  (``MixedEvaluator`` over a destination subset);
- a **model architecture** (``"arch:<name>"``), the beyond-paper
  framework-level search where genes toggle stage-group offload in an
  ExecutionPlan, scored by the analytic plan evaluator (or an injected
  ``CompiledEvaluator`` for real AOT-compile scoring).

Each adapter knows its gene length and allele count, builds its
evaluator, computes the all-host baseline, renders a genome as a
{unit: destination} placement, and (for miniapps with runnable JAX
implementations) produces the PCAST result-difference check of the
offloaded path against the CPU reference.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from repro.core import evaluator as ev
from repro.core import miniapps
from repro.core import pcast
from repro.core import transfer as tr
from repro.core.loopir import LoopClass, LoopProgram
from repro.offload.spec import (
    MEASURED_PROGRAMS,
    MEASURED_SCALES,
    METHODS,
    OffloadSpec,
)

# HardwareModel registry (spec.hw); Offloader may inject an unregistered
# candidate model (calibration sweeps) via its ``hw=`` override.
HW_MODELS: Dict[str, ev.HardwareModel] = {
    ev.QUADRO_P4000.name: ev.QUADRO_P4000,
    ev.TPU_V5E_HOST.name: ev.TPU_V5E_HOST,
}

_BUILTIN_HW_MODELS = frozenset(HW_MODELS)


def register_hw_model(hw: ev.HardwareModel, name: Optional[str] = None,
                      replace: bool = False) -> None:
    """Make a :class:`HardwareModel` selectable as ``OffloadSpec.hw`` in
    binary/arch mode (calibrated machines register here under their
    entry name; the model's OWN name carries the constants digest that
    keys fitness-cache fingerprints). Built-ins cannot be replaced."""
    name = name or hw.name
    if name in _BUILTIN_HW_MODELS:
        raise ValueError(f"cannot replace built-in hardware model {name!r}")
    if name in HW_MODELS and not replace:
        raise ValueError(
            f"hardware model {name!r} already registered; pass "
            "replace=True to re-register"
        )
    HW_MODELS[name] = hw

# paper directive per pgcc-style loop class (§3.3)
DIRECTIVES: Dict[LoopClass, str] = {
    LoopClass.TIGHT: "acc kernels",
    LoopClass.NON_TIGHT: "acc parallel loop",
    LoopClass.VECTOR_ONLY: "acc parallel loop vector",
    LoopClass.NOT_OFFLOADABLE: "(excluded: not offloadable)",
}


def resolve_hw(spec: OffloadSpec,
               override: Optional[ev.HardwareModel] = None
               ) -> ev.HardwareModel:
    if override is not None:
        return override
    if spec.hw not in HW_MODELS:
        raise ValueError(
            f"unknown hardware model {spec.hw!r}; have {sorted(HW_MODELS)}"
        )
    return HW_MODELS[spec.hw]


# ---------------------------------------------------------------------------
# runnable programs: hot loop, run fns, PCAST pairs
# ---------------------------------------------------------------------------

# The measured scale, in one table: the run fn (grid + iteration count)
# every wall clock and measured-fidelity PCAST check of a runnable program
# uses, per OffloadSpec.measured_scale. "model" runs the grid the searched
# LoopProgram models — Himeno class M 128x128x256 (~235 MB of float32
# state), NAS.FT class A 256x256x128 complex64 — with Himeno's Jacobi
# iterations cut from the modeled 100 to 20 for run time (the host path
# is numpy; NAS.FT keeps its 6). "small" is a toy grid for CPU tests.
MEASURED_RUN_FNS: Dict[str, Dict[str, Any]] = {
    "model": {
        "himeno": miniapps.HimenoRunFn(grid=(128, 128, 256), nn=20),
        "nasft": miniapps.NasftRunFn(grid=(256, 256, 128), niter=6),
    },
    "small": {
        "himeno": miniapps.HimenoRunFn(grid=(9, 9, 17), nn=2),
        "nasft": miniapps.NasftRunFn(grid=(8, 8, 8), niter=2),
    },
}

# miniapp name -> (hot loop whose gene selects the accelerator path,
# PCAST pair builder of the MODELED adapters: a correctness check of the
# implementation at a small grid, since nothing is timed there). Apps
# absent here have no runnable implementation; their verify stage
# records the PCAST check as skipped.
RUNNABLE: Dict[str, Tuple[str, Callable[[bool], Tuple[Any, Any]]]] = {
    "himeno": ("jacobi_stencil",
               miniapps.HimenoRunFn(grid=(17, 17, 33), nn=4).pair),
    "nasft": ("evolve", miniapps.NasftRunFn(grid=(16, 16, 16), niter=2).pair),
}

assert set(MEASURED_RUN_FNS) == set(MEASURED_SCALES), \
    "spec.MEASURED_SCALES must list exactly the measured-scale table rows"
assert all(set(fns) == set(RUNNABLE) == set(MEASURED_PROGRAMS)
           for fns in MEASURED_RUN_FNS.values()), \
    "spec.MEASURED_PROGRAMS must list exactly the runnable miniapps"


def measured_run_fn(program: str, scale: str):
    """The run fn of a runnable program at a measured scale."""
    return MEASURED_RUN_FNS[scale][program]


def hot_gene_index(name: str) -> int:
    """Gene index of the runnable implementation's hot loop — the one
    gene the measured path actually realizes (docs/fidelity.md)."""
    prog = miniapps.MINIAPPS[name]()
    return miniapps._gene_index(prog, RUNNABLE[name][0])


def _pcast(pair: Callable[[bool], Tuple[Any, Any]], offloaded: bool,
           spec: OffloadSpec) -> pcast.PcastReport:
    """Run a PCAST pair on the device lane (never beside a measurement)
    and compare it under the spec's tolerances."""
    with ev.DEVICE_LANE:
        ref, off = pair(offloaded)
    return pcast.compare(ref, off, rel_tol=spec.rel_tol,
                         abs_tol=spec.abs_tol)


# ---------------------------------------------------------------------------
# adapters
# ---------------------------------------------------------------------------


class MiniappBinaryAdapter:
    """The paper's binary CPU/GPU search under a METHODS configuration."""

    kind = "miniapp-binary"
    deterministic = True  # analytic evaluator: re-measuring is exact

    def __init__(self, spec: OffloadSpec,
                 hw: Optional[ev.HardwareModel] = None):
        if spec.program not in miniapps.MINIAPPS:
            raise ValueError(
                f"unknown miniapp {spec.program!r}; have "
                f"{sorted(miniapps.MINIAPPS)}"
            )
        self.spec = spec
        self.hw = resolve_hw(spec, hw)
        self.prog: LoopProgram = miniapps.MINIAPPS[spec.program]()
        self.method = METHODS[spec.method]

    @property
    def gene_length(self) -> int:
        return self.prog.gene_length

    @property
    def alleles(self) -> int:
        return 2

    @property
    def allele_names(self) -> Tuple[str, ...]:
        return ("cpu", "gpu")

    def build_evaluator(self) -> ev.MiniappEvaluator:
        return ev.MiniappEvaluator(
            self.prog,
            tr.TransferMode(self.method["transfer"]),
            staged=self.method["staged"],
            hw=self.hw,
            kernels_only=self.method["kernels_only"],
        )

    def baseline_time(self) -> float:
        # all loops on the CPU, priced exactly as the fig4/fig5 scripts
        # did (default BULK/staged args are transfer-free at zero genes)
        return ev.predict_time(
            self.prog, (0,) * self.gene_length, hw=self.hw
        ).total_s

    def analyze_payload(self) -> Dict[str, Any]:
        return {
            "program": self.prog.name,
            "description": self.prog.description,
            "gene_length": self.gene_length,
            "n_loops": len(self.prog.loops),
            "kernels_only": bool(self.method["kernels_only"]),
            "loops": [
                {
                    "name": l.name,
                    "class": l.klass.value,
                    "directive": DIRECTIVES[l.klass],
                    "offloadable": l.offloadable,
                }
                for l in self.prog.loops
            ],
        }

    def placement(self, genes: Sequence[int]) -> Dict[str, str]:
        adm = self.build_evaluator().admissible(genes)
        out = {l.name: "cpu" for l in self.prog.loops}
        for g, l in zip(adm, self.prog.offloadable_loops):
            out[l.name] = "gpu" if g else "cpu"
        return out

    def pcast_check(self, genes: Sequence[int]
                    ) -> Optional[pcast.PcastReport]:
        hot = RUNNABLE.get(self.prog.name)
        if hot is None:
            return None
        loop_name, pair = hot
        offloaded = self.placement(genes)[loop_name] != "cpu"
        return _pcast(pair, offloaded, self.spec)


class MiniappMeasuredAdapter:
    """Measured fidelity: the paper's REAL measurement loop — every
    candidate wall-clocked by running the miniapp's implementation, not
    priced by the analytic model.

    The genome still indexes the paper-scale LoopProgram (gene length
    13/65), but fitness comes from ``MeasuredEvaluator`` wall-clocking
    the run_fn at the spec's measured scale (``MEASURED_RUN_FNS``), one
    measurement at a time, in this process: a chip belongs to one
    process, so the process that runs the Offloader is the one that
    measures. The device is read once, here, and recorded in the analyze
    payload and the measurement fingerprint. The run_fn's ``cache_key``
    collapses genomes to the genes the implementation actually
    distinguishes (the hot loop), so equivalent placements share one real
    measurement exactly as the paper's §5.2 cache intends.
    ``model_evaluator()`` exposes the analytic model AT THE MEASURED
    SCALE for the verify stage's predicted-vs-measured fidelity section.
    """

    kind = "miniapp-measured"
    deterministic = False  # wall clocks jitter; re-measure can't be exact

    def __init__(self, spec: OffloadSpec,
                 hw: Optional[ev.HardwareModel] = None):
        assert spec.fidelity == "measured", spec.fidelity
        self.spec = spec
        self.hw = resolve_hw(spec, hw)  # the MODEL the fidelity section
        # compares against; never used to price candidates
        self.prog: LoopProgram = miniapps.MINIAPPS[spec.program]()
        self.run_fn = measured_run_fn(spec.program, spec.measured_scale)
        self.method = METHODS[spec.method]
        self.device = ev.device_info()

    @property
    def gene_length(self) -> int:
        return self.prog.gene_length

    @property
    def alleles(self) -> int:
        return 2

    @property
    def allele_names(self) -> Tuple[str, ...]:
        return ("cpu", "gpu")

    def build_evaluator(self) -> ev.MeasuredEvaluator:
        return ev.MeasuredEvaluator(
            self.run_fn, repeats=self.spec.repeats, tag=self.run_fn.tag,
            device=f"{self.device['platform']}:{self.device['device_kind']}",
        )

    def model_evaluator(self) -> ev.MiniappEvaluator:
        """The analytic model at the measured scale, under the spec's
        method configuration and modeled machine."""
        return ev.MiniappEvaluator(
            self.run_fn.program(),
            tr.TransferMode(self.method["transfer"]),
            staged=self.method["staged"],
            hw=self.hw,
            kernels_only=self.method["kernels_only"],
        )

    def baseline_time(self) -> float:
        # a REAL all-host measurement, compared against other wall
        # clocks, not against model output
        return float(self.build_evaluator()((0,) * self.gene_length))

    def analyze_payload(self) -> Dict[str, Any]:
        e = self.build_evaluator()
        return {
            "program": self.prog.name,
            "description": self.prog.description,
            "gene_length": self.gene_length,
            "n_loops": len(self.prog.loops),
            "fidelity": "measured",
            "measured_scale": self.run_fn.tag,
            "host": e.host,
            "device": dict(self.device),
            "repeats": self.spec.repeats,
            "loops": [
                {
                    "name": l.name,
                    "class": l.klass.value,
                    "directive": DIRECTIVES[l.klass],
                    "offloadable": l.offloadable,
                }
                for l in self.prog.loops
            ],
        }

    def placement(self, genes: Sequence[int]) -> Dict[str, str]:
        # raw gene -> path mapping: measured fidelity has no admissibility
        # model to mask through — the implementation either jits the loop
        # or it doesn't
        out = {l.name: "cpu" for l in self.prog.loops}
        for g, l in zip(genes, self.prog.offloadable_loops):
            out[l.name] = "gpu" if int(g) else "cpu"
        return out

    def pcast_check(self, genes: Sequence[int]
                    ) -> Optional[pcast.PcastReport]:
        # at the measured scale: the check covers what was timed
        loop_name = RUNNABLE[self.prog.name][0]
        offloaded = self.placement(genes)[loop_name] != "cpu"
        return _pcast(self.run_fn.pair, offloaded, self.spec)


class MiniappMixedAdapter:
    """Mixed-destination k-ary search (arXiv:2011.12431 direction)."""

    kind = "miniapp-mixed"
    deterministic = True

    def __init__(self, spec: OffloadSpec,
                 hw: Optional[ev.HardwareModel] = None):
        from repro.destinations import (
            REGISTRIES,
            MixedEvaluator,
            default_registry,
            get_registry,
        )

        if spec.program not in miniapps.MINIAPPS:
            raise ValueError(
                f"unknown miniapp {spec.program!r}; have "
                f"{sorted(miniapps.MINIAPPS)}"
            )
        self.spec = spec
        # ``spec.hw`` selects the modeled MACHINE here, not just rate
        # constants: a named Registry carries per-destination memory
        # capacities, so freezing the name in the spec freezes them too.
        # ``self.machine`` is the spec-facing name: for spec-resolved
        # machines it can be fed straight back into ``OffloadSpec.hw``
        # (the registry's INTERNAL name may differ, e.g. "p4000-fpga" —
        # renaming it would move every unbounded cache fingerprint); an
        # injected HardwareModel (calibration sweeps) is process-local
        # and not name-addressable, so its artifact says so explicitly
        # instead of claiming a name the spec would reject.
        if hw is not None:
            self.registry = default_registry(hw)
            self.machine = f"injected:{hw.name}"
        elif spec.hw in REGISTRIES:
            self.registry = get_registry(spec.hw)
            self.machine = spec.hw
        elif spec.hw in HW_MODELS:
            self.registry = default_registry(HW_MODELS[spec.hw])
            self.machine = spec.hw
        else:
            raise ValueError(
                f"unknown machine {spec.hw!r} for mixed mode; have "
                f"registries {sorted(REGISTRIES)} and hardware models "
                f"{sorted(HW_MODELS)}"
            )
        known = {d.name for d in self.registry.destinations}
        missing = [n for n in spec.destinations if n not in known]
        if missing:
            raise ValueError(
                f"destinations {missing} do not exist on machine "
                f"{self.machine!r} (its destinations: {sorted(known)}); "
                "set OffloadSpec.destinations (CLI: --destinations) to "
                "match the registry"
            )
        self.prog: LoopProgram = miniapps.MINIAPPS[spec.program]()
        self._mixed_cls = MixedEvaluator
        # function-block substitution (docs/blocks.md): with spec.blocks
        # and at least one library match, the evaluator grows one gene
        # per matched block. Zero matches fall back to the plain
        # evaluator so the search (and its cache fingerprint) stays
        # byte-identical to a blocks-off run.
        self.library = None
        self.matches: Tuple[Any, ...] = ()
        if spec.blocks:
            from repro.blocks import default_library, match_blocks

            self.library = default_library(hw=self.machine)
            self.matches = match_blocks(self.prog, self.library)
        # spec.ga.batch swaps in the vectorized-population subclasses
        # for the MAIN search evaluator. Scalar __call__, fingerprint
        # and cache keys are inherited, so the verify-stage re-measure
        # stays the oracle and batch/scalar searches share one cache;
        # the warm-start sub_evaluators stay scalar (tiny populations,
        # not worth the table builds).
        if self.matches:
            from repro.blocks import (
                BatchBlockMixedEvaluator,
                BlockMixedEvaluator,
            )

            block_cls = (
                BatchBlockMixedEvaluator if spec.ga.batch
                else BlockMixedEvaluator
            )
            self._evaluator = block_cls(
                self.prog, spec.destinations, registry=self.registry,
                library=self.library, matches=self.matches,
            )
        else:
            from repro.destinations import BatchMixedEvaluator

            mixed_cls = BatchMixedEvaluator if spec.ga.batch \
                else MixedEvaluator
            self._evaluator = mixed_cls(
                self.prog, spec.destinations, registry=self.registry
            )

    @property
    def gene_length(self) -> int:
        return self.prog.gene_length + len(self.matches)

    @property
    def alleles(self) -> int:
        return self._evaluator.k

    @property
    def allele_names(self) -> Tuple[str, ...]:
        return self._evaluator.allele_names()

    def build_evaluator(self):
        return self._evaluator

    def sub_evaluator(self, subset: Sequence[str]):
        """A single-destination (host + one device) evaluator sharing
        this machine's registry — the warm-start pre-searches. Its
        fingerprint equals the mixed one (subset-independent), so the
        pre-searches and the main search share one fitness-cache file.
        Under ``spec.blocks`` the sub-evaluator is block-aware over the
        SAME matches, so pre-search genomes keep the full ``n + m``
        length and ``reexpress`` maps block genes like loop genes."""
        if self.matches:
            from repro.blocks import BlockMixedEvaluator

            return BlockMixedEvaluator(
                self.prog, tuple(subset), registry=self.registry,
                library=self.library, matches=self.matches,
            )
        return self._mixed_cls(self.prog, tuple(subset),
                               registry=self.registry)

    def substitutions(self, genes: Sequence[int]) -> Optional[list]:
        """Per-block decision rows for a genome (None when the run has
        no block genome — keeps blocks-off payloads byte-identical)."""
        fn = getattr(self._evaluator, "substitutions", None)
        return fn(genes) if fn is not None else None

    def reexpress(self, genes: Sequence[int], device: str) -> Tuple[int, ...]:
        """A binary (host, device) genome re-expressed in the full k-ary
        alphabet of ``spec.destinations``."""
        idx = self.spec.destinations.index(device)
        return tuple(idx if int(g) else 0 for g in genes)

    def baseline_time(self) -> float:
        return self._evaluator.host_only_time()

    def _capacities(self) -> Dict[str, float]:
        """Bounded device memories of the searched subset (empty when
        the whole machine is unbounded)."""
        return {
            d.name: float(d.memory_bytes)
            for d in self._evaluator.dests if d.bounded
        }

    def analyze_payload(self) -> Dict[str, Any]:
        dests = {d.name: d for d in self._evaluator.dests}
        out: Dict[str, Any] = {
            "program": self.prog.name,
            "description": self.prog.description,
            "gene_length": self.gene_length,
            "n_loops": len(self.prog.loops),
            "machine": self.machine,
            "destinations": [d.name for d in self._evaluator.dests],
            "capacities": self._capacities(),
        }
        if self.spec.blocks:
            out["blocks"] = {
                "library": [e.name for e in self.library.entries],
                "library_fingerprint": self.library.fingerprint(),
                "matches": [
                    {
                        "entry": m.entry,
                        "loops": list(m.loops),
                        "parent_seq": m.parent_seq,
                        "atom": m.atom,
                    }
                    for m in self.matches
                ],
            }
        out["loops"] = [
            {
                "name": l.name,
                "class": l.klass.value,
                "directive": DIRECTIVES[l.klass],
                "offloadable": l.offloadable,
                "admissible": [
                    n for n, d in dests.items() if d.accepts(l.klass)
                ] if l.offloadable else [],
            }
            for l in self.prog.loops
        ]
        return out

    def placement(self, genes: Sequence[int]) -> Dict[str, str]:
        return self._evaluator.placement(genes)

    def schedule_stats(self, genes: Sequence[int]) -> Dict[str, Any]:
        """Residency pressure of a genome's transfer schedule — recorded
        in the search payload so the report stage can state eviction and
        streaming traffic without re-running anything."""
        bd = self._evaluator.breakdown(genes)
        s = bd.schedule
        return {
            "transfer_s": float(bd.transfer_s),
            "transfer_bytes": float(s.total_bytes),
            "evicted_bytes": float(s.total_evicted_bytes),
            "evict_bytes_by_dest": {
                k: float(v) for k, v in sorted(s.evict_bytes_by_dest.items())
            },
            "spilled_bytes": float(s.total_spilled_bytes),
            "spill_bytes_by_dest": {
                k: float(v) for k, v in sorted(s.spill_bytes_by_dest.items())
            },
            "oversubscribed": list(s.oversubscribed),
            "capacities": self._capacities(),
        }

    def pcast_check(self, genes: Sequence[int]
                    ) -> Optional[pcast.PcastReport]:
        hot = RUNNABLE.get(self.prog.name)
        if hot is None:
            return None
        loop_name, pair = hot
        host = self._evaluator.dests[0].name
        offloaded = self.placement(genes)[loop_name] != host
        return _pcast(pair, offloaded, self.spec)


class ArchPlanEvaluator:
    """Analytic per-unit roofline for the framework-level search
    (moved verbatim from examples/ga_arch_search.py): offloaded units
    run TP-sharded, baseline units replicated (x16 compute), collectives
    charged per offloaded unit boundary."""

    def __init__(self, arch: str):
        from repro.configs import get_arch

        self.arch = arch
        self.cfg = get_arch(arch)

    def __call__(self, genes: Sequence[int]) -> float:
        from repro.configs.base import TRAIN_4K
        from repro.core import analysis
        from repro.launch.roofline import model_flops

        plan = analysis.build_plan(self.cfg, None, genes=tuple(genes))
        t = 0.0
        flops = model_flops(self.cfg, TRAIN_4K) / 256
        per_unit = flops / max(len(plan.units), 1)
        for u in plan.units:
            rate = 197e12
            t += per_unit / rate / (1.0 if u.offload else 16.0) * 16.0 \
                if not u.offload else per_unit / rate
            if u.offload:
                t += 2 * self.cfg.d_model * 4096 * 2 / 50e9 / 1e3  # reshard
        return t

    def fingerprint(self) -> str:
        # kept identical to the pre-redesign closure's fingerprint so
        # existing persistent caches keep hitting
        return f"analytic-plan:{self.arch}"


class ArchAdapter:
    """Beyond-paper: genes toggle stage-group offload in an ExecutionPlan.

    The default evaluator is the instant analytic one; the Offloader's
    ``evaluator=`` injection swaps in a ``CompiledEvaluator`` for real
    AOT-compile scoring (examples/ga_arch_search.py --compiled).
    """

    kind = "arch"
    deterministic = True

    def __init__(self, spec: OffloadSpec,
                 hw: Optional[ev.HardwareModel] = None):
        from repro.configs import get_arch
        from repro.core import analysis

        self.spec = spec
        self.cfg = get_arch(spec.arch_name)
        self.units = analysis.build_units(self.cfg, None)

    @property
    def gene_length(self) -> int:
        return len(self.units)

    @property
    def alleles(self) -> int:
        return 2

    @property
    def allele_names(self) -> Tuple[str, ...]:
        return ("cpu", "accel")

    def build_evaluator(self) -> ArchPlanEvaluator:
        return ArchPlanEvaluator(self.spec.arch_name)

    def baseline_time(self) -> float:
        return self.build_evaluator()((0,) * self.gene_length)

    def analyze_payload(self) -> Dict[str, Any]:
        from repro.core import analysis

        return {
            "program": self.spec.program,
            "description": f"{self.spec.arch_name} execution plan",
            "gene_length": self.gene_length,
            "units": [
                {"name": u.name, "directive": u.directive.value}
                for u in self.units
            ],
            "applicability": analysis.applicability_notes(self.cfg, None),
        }

    def placement(self, genes: Sequence[int]) -> Dict[str, str]:
        return {
            u.name: "accel" if g else "cpu"
            for g, u in zip(genes, self.units)
        }

    def describe_plan(self, genes: Sequence[int]) -> str:
        from repro.core import analysis

        return analysis.build_plan(
            self.cfg, None, genes=tuple(genes)
        ).describe()

    def pcast_check(self, genes: Sequence[int]) -> None:
        return None  # no runnable reference pair at the plan level


def resolve_adapter(spec: OffloadSpec,
                    hw: Optional[ev.HardwareModel] = None):
    if spec.is_arch:
        return ArchAdapter(spec, hw)
    if spec.fidelity == "measured":
        return MiniappMeasuredAdapter(spec, hw)
    if spec.mode == "mixed":
        return MiniappMixedAdapter(spec, hw)
    return MiniappBinaryAdapter(spec, hw)
