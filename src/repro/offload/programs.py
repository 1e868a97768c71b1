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

Every adapter implements :class:`ProgramAdapter`, the questions the
Offloader's stages ask: gene length and alleles, the search evaluator,
the all-host baseline, a genome's {unit: destination} placement, and —
for miniapps with runnable JAX implementations — the PCAST check of the
offloaded path against the CPU reference, the model at the measured
scale, and the one placement the runnable program can really run.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

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
# runnable programs: run fns at each scale, hot loops, PCAST pairs
# ---------------------------------------------------------------------------

# The measured scale, in one table: the run fn (grid + iteration count)
# every wall clock and measured-fidelity PCAST check of a runnable program
# uses, per OffloadSpec.measured_scale. "model" runs the published sizes —
# Himeno size M, 129x129x257 arrays (himenobmtxpa.c: mimax=129,
# mjmax=129, mkmax=257; 239 MB of float32 state on the device), NAS.FT
# class A 256x256x128 complex64 — with Himeno's Jacobi iterations cut from
# the modeled 100 to 20 for run time (the host path is numpy; NAS.FT keeps
# its 6). "small" is a toy grid for CPU tests.
MEASURED_RUN_FNS: Dict[str, Dict[str, Any]] = {
    "model": {
        "himeno": miniapps.HimenoRunFn(grid=(129, 129, 257), nn=20),
        "nasft": miniapps.NasftRunFn(grid=(256, 256, 128), niter=6),
    },
    "small": {
        "himeno": miniapps.HimenoRunFn(grid=(9, 9, 17), nn=2),
        "nasft": miniapps.NasftRunFn(grid=(8, 8, 8), niter=2),
    },
}

# The run fns of the MODELED adapters' PCAST check: a correctness check
# of the implementation at a small grid, since nothing is timed there.
# Apps absent here have no runnable implementation; their verify stage
# records the PCAST check as skipped.
_PCAST_RUN_FNS: Dict[str, Any] = {
    "himeno": miniapps.HimenoRunFn(grid=(17, 17, 33), nn=4),
    "nasft": miniapps.NasftRunFn(grid=(16, 16, 16), niter=2),
}

# miniapp name -> (hot loop whose gene selects the accelerator path,
# PCAST pair of the modeled adapters), read off the run fns
RUNNABLE: Dict[str, Tuple[str, Callable[[bool], Tuple[Any, Any]]]] = {
    name: (fn.hot_loop, fn.pair) for name, fn in _PCAST_RUN_FNS.items()
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
    return _PCAST_RUN_FNS[name].hot_gene


def _pcast(pair: Callable[[bool], Tuple[Any, Any]], offloaded: bool,
           spec: OffloadSpec) -> pcast.PcastReport:
    """Run a PCAST pair on the device lane (never beside a measurement)
    and compare it under the spec's tolerances."""
    with ev.DEVICE_LANE:
        ref, off = pair(offloaded)
    return pcast.compare(ref, off, rel_tol=spec.rel_tol,
                         abs_tol=spec.abs_tol)


def _loop_rows(prog: LoopProgram) -> list:
    """The analyze payload's per-loop rows: class and paper directive."""
    return [
        {
            "name": l.name,
            "class": l.klass.value,
            "directive": DIRECTIVES[l.klass],
            "offloadable": l.offloadable,
        }
        for l in prog.loops
    ]


# ---------------------------------------------------------------------------
# adapters
# ---------------------------------------------------------------------------


class Realised(NamedTuple):
    """A genome as the runnable program really runs it. The runnable
    implementations realize exactly ONE placement switch (the hot loop on
    the jit path or not), so ``genes`` sets the hot loop's gene to the
    accelerator allele when the genome offloads that loop (0 when not)
    and every other gene to 0. ``destination`` is where the model prices
    that run: None when the hot loop is offloaded but the searched
    destinations have no gpu/tpu-kind one matching the jit path (the
    genes then carry allele 1, which the run fn reads as offloaded)."""

    genes: Tuple[int, ...]
    offloaded: bool
    destination: Optional[str]


class ProgramAdapter:
    """The one interface between the Offloader's stages and a program.

    Every adapter has ``spec``, ``gene_length``, ``alleles`` and
    ``allele_names``, and answers ``build_evaluator()`` (the search's
    evaluator), ``baseline_time()`` (the all-host time),
    ``analyze_payload()`` and ``placement(genes)`` ({unit: destination}).
    The defaults below mean "not applicable":

    - ``deterministic`` — may a re-measure of the winner be held to
      exactness (analytic evaluators: yes);
    - ``run_fn`` — the runnable program at the spec's measured scale, or
      None when the program has no runnable implementation;
    - ``verify(genes)`` — ``(PcastReport | None, answer | None)``: the
      PCAST check of the genome's placement, and the placed run's output
      where the check ran at the measured scale;
    - ``search_extras(genes)`` — extra search-payload entries of the
      winner;
    - ``scale_model()`` — the analytic model AT THE MEASURED SCALE, what
      the fidelity and rank sections compare wall clocks against;
    - ``realised(genes)`` — the :class:`Realised` projection, or None
      without a runnable implementation;
    - ``clock()`` — a wall clock of ``run_fn`` outside any pool or cache.

    Mixed mode's warm start also calls ``sub_evaluator`` and
    ``reexpress``; the spec keeps ``warm_start`` to mixed mode.
    """

    deterministic = True
    run_fn: Any = None
    library = None  # the block library, where blocks are matched
    host = "cpu"  # the host's destination name
    accel: Optional[int] = 1  # the allele of the jit path's destination
    spec: OffloadSpec
    gene_length: int
    allele_names: Tuple[str, ...]

    def placement(self, genes: Sequence[int]) -> Dict[str, str]:
        raise NotImplementedError

    def verify(self, genes: Sequence[int]
               ) -> Tuple[Optional[pcast.PcastReport], Any]:
        # the modeled adapters check the implementation at a small grid
        # (_PCAST_RUN_FNS): nothing is timed there, so it hands back no
        # answer
        real = self.realised(genes)
        if real is None:
            return None, None
        pair = _PCAST_RUN_FNS[self.spec.program].pair
        return _pcast(pair, real.offloaded, self.spec), None

    def search_extras(self, genes: Sequence[int]) -> Dict[str, Any]:
        return {}

    def scale_model(self) -> Optional[Callable[[Sequence[int]], float]]:
        return None

    def realised(self, genes: Sequence[int]) -> Optional[Realised]:
        if self.run_fn is None:
            return None
        place = self.placement(genes).get(self.run_fn.hot_loop, self.host)
        offloaded = place != self.host
        if not offloaded:
            allele, dest = 0, self.host
        elif self.accel is None:
            allele, dest = 1, None
        else:
            allele, dest = self.accel, self.allele_names[self.accel]
        hot = self.run_fn.hot_gene
        genes = tuple(allele if i == hot else 0
                      for i in range(self.gene_length))
        return Realised(genes, offloaded, dest)

    def clock(self) -> ev.MeasuredEvaluator:
        return ev.MeasuredEvaluator(self.run_fn, repeats=self.spec.repeats,
                                    tag=self.run_fn.tag)


class MiniappBinaryAdapter(ProgramAdapter):
    """The paper's binary CPU/GPU search under a METHODS configuration."""

    alleles = 2
    allele_names = ("cpu", "gpu")

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
        self.run_fn = MEASURED_RUN_FNS[spec.measured_scale].get(spec.program)

    @property
    def gene_length(self) -> int:
        return self.prog.gene_length

    def _evaluator_of(self, prog: LoopProgram) -> ev.MiniappEvaluator:
        return ev.MiniappEvaluator(
            prog,
            tr.TransferMode(self.method["transfer"]),
            staged=self.method["staged"],
            hw=self.hw,
            kernels_only=self.method["kernels_only"],
        )

    def build_evaluator(self) -> ev.MiniappEvaluator:
        return self._evaluator_of(self.prog)

    def scale_model(self) -> Optional[ev.MiniappEvaluator]:
        """The analytic model at the measured scale, under the spec's
        method configuration and modeled machine."""
        if self.run_fn is None:
            return None
        return self._evaluator_of(self.run_fn.program())

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
            "loops": _loop_rows(self.prog),
        }

    def placement(self, genes: Sequence[int]) -> Dict[str, str]:
        adm = self.build_evaluator().admissible(genes)
        out = {l.name: "cpu" for l in self.prog.loops}
        for g, l in zip(adm, self.prog.offloadable_loops):
            out[l.name] = "gpu" if g else "cpu"
        return out


class MiniappMeasuredAdapter(MiniappBinaryAdapter):
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
    measurement exactly as the paper's §5.2 cache intends. ``hw`` is the
    MODEL the fidelity section compares against (``scale_model``), never
    used to price candidates. ``run_fn``, where given, takes the place of
    the table's row: the search, the verify re-measure and the PCAST pair
    all run it.
    """

    deterministic = False  # wall clocks jitter; re-measure can't be exact

    def __init__(self, spec: OffloadSpec,
                 hw: Optional[ev.HardwareModel] = None, run_fn=None):
        assert spec.fidelity == "measured", spec.fidelity
        super().__init__(spec, hw)
        if run_fn is not None:
            self.run_fn = run_fn
        self.device = ev.device_info()

    def build_evaluator(self) -> ev.MeasuredEvaluator:
        return ev.MeasuredEvaluator(
            self.run_fn, repeats=self.spec.repeats, tag=self.run_fn.tag,
            device=f"{self.device['platform']}:{self.device['device_kind']}",
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
            "loops": _loop_rows(self.prog),
        }

    def placement(self, genes: Sequence[int]) -> Dict[str, str]:
        # raw gene -> path mapping: measured fidelity has no admissibility
        # model to mask through — the implementation either jits the loop
        # or it doesn't
        out = {l.name: "cpu" for l in self.prog.loops}
        for g, l in zip(genes, self.prog.offloadable_loops):
            out[l.name] = "gpu" if int(g) else "cpu"
        return out

    def verify(self, genes: Sequence[int]
               ) -> Tuple[pcast.PcastReport, Dict[str, Any]]:
        """The PCAST check of a placement at the measured scale (so it
        covers what was timed), and that placement's answer: the output
        compared against the host path's. Each of the two runs is an
        ``offload.measure`` span."""
        offloaded = self.realised(genes).offloaded
        with ev.DEVICE_LANE:
            with ev.measure_span():
                ref = self.run_fn.run(False)
            with ev.measure_span():
                off = self.run_fn.run(offloaded)
        report = pcast.compare(ref, off, rel_tol=self.spec.rel_tol,
                               abs_tol=self.spec.abs_tol)
        return report, off


class MiniappMixedAdapter(ProgramAdapter):
    """Mixed-destination k-ary search (arXiv:2011.12431 direction)."""

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
        self.run_fn = MEASURED_RUN_FNS[spec.measured_scale].get(spec.program)
        self._mixed_cls = MixedEvaluator
        # function-block substitution (docs/blocks.md): with spec.blocks
        # and at least one library match, the evaluator grows one gene
        # per matched block. Zero matches fall back to the plain
        # evaluator, so the search (and its cache fingerprint) is the
        # loop-level one.
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

    @property
    def host(self) -> str:
        return self._evaluator.dests[0].name

    @property
    def accel(self) -> Optional[int]:
        """The first gpu/tpu-kind destination: the jit path's stand-in."""
        return next((i for i, d in enumerate(self._evaluator.dests)
                     if d.kind in ("gpu", "tpu")), None)

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

    def scale_model(self):
        """The scalar model at the measured scale; block-enabled genomes
        carry block genes, so they are priced by a block evaluator over
        the scale program (same loop structure -> same matches)."""
        if self.run_fn is None:
            return None
        prog = self.run_fn.program()
        if self.matches:
            from repro.blocks import BlockMixedEvaluator

            return BlockMixedEvaluator(
                prog, self.spec.destinations, registry=self.registry,
                library=self.library,
            )
        return self._mixed_cls(prog, self.spec.destinations,
                               registry=self.registry)

    def substitutions(self, genes: Sequence[int]) -> Optional[list]:
        """Per-block decision rows for a genome (None when the run has
        no block genome)."""
        return self._evaluator.substitutions(genes) if self.matches \
            else None

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
        out["loops"] = _loop_rows(self.prog)
        for row, l in zip(out["loops"], self.prog.loops):
            row["admissible"] = [
                n for n, d in dests.items() if d.accepts(l.klass)
            ] if l.offloadable else []
        return out

    def placement(self, genes: Sequence[int]) -> Dict[str, str]:
        return self._evaluator.placement(genes)

    def search_extras(self, genes: Sequence[int]) -> Dict[str, Any]:
        """The winner's residency pressure — so the report stage can
        state eviction and streaming traffic without re-running anything
        — and, on block-enabled runs, its substitutions."""
        bd = self._evaluator.breakdown(genes)
        s = bd.schedule
        out: Dict[str, Any] = {"residency": {
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
        }}
        subs = self.substitutions(genes)
        if subs is not None:
            out["substitutions"] = subs
        return out


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


class ArchAdapter(ProgramAdapter):
    """Beyond-paper: genes toggle stage-group offload in an ExecutionPlan.

    The default evaluator is the instant analytic one; the Offloader's
    ``evaluator=`` injection swaps in a ``CompiledEvaluator`` for real
    AOT-compile scoring (examples/ga_arch_search.py --compiled).
    """

    alleles = 2
    allele_names = ("cpu", "accel")

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


def resolve_adapter(spec: OffloadSpec,
                    hw: Optional[ev.HardwareModel] = None,
                    run_fn=None) -> ProgramAdapter:
    """The adapter of a spec; ``run_fn`` (measured fidelity only) is the
    caller's run fn in place of ``MEASURED_RUN_FNS``'s row."""
    if run_fn is not None and spec.fidelity != "measured":
        raise ValueError("a caller's run_fn is what measured fidelity "
                         f"wall-clocks; this spec is {spec.fidelity!r}")
    if spec.is_arch:
        return ArchAdapter(spec, hw)
    if spec.fidelity == "measured":
        return MiniappMeasuredAdapter(spec, hw, run_fn)
    if spec.mode == "mixed":
        return MiniappMixedAdapter(spec, hw)
    return MiniappBinaryAdapter(spec, hw)
