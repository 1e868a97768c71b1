"""OffloadSpec: the one declarative input of the staged offload pipeline.

Everything the paper's flow needs to run end to end — which program,
which search mode (the paper's binary CPU/GPU placements or the
mixed-destination k-ary follow-up), which method configuration, GA
budget, evaluation-pool settings and verification tolerances — lives in
one frozen, JSON-round-trippable dataclass. The spec is embedded in the
:class:`~repro.offload.result.OffloadResult` artifact, so a saved
artifact is self-describing and ``python -m repro.offload resume`` needs
nothing but the artifact path.

Programs are named: a miniapp from :data:`repro.core.miniapps.MINIAPPS`
(``"himeno"``, ``"nasft"``, ``"hetero"``) or a model architecture as
``"arch:<name>"`` (the beyond-paper framework-level search, scored by the
analytic plan evaluator). Method configurations are the fig-5 columns,
centralized here so benchmarks stop re-declaring them.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional, Tuple

from repro.core import ga

# The fig-5 method configurations (paper §3.3): transfer mode, temp-area
# staging, and whether only `kernels`-class loops may be offloaded.
# Previously duplicated by benchmarks/fig5_speedup.py; now the single
# source of truth for every binary-mode search.
METHODS: Dict[str, Dict[str, Any]] = {
    # [33]: nest-level transfers, kernels directive only, no temp-area
    "previous": dict(transfer="nest", staged=False, kernels_only=True),
    # ablation: add the directive expansion, keep [33] transfers
    "dir-expansion-only": dict(transfer="nest", staged=False,
                               kernels_only=False),
    # ablation: add bulk/present/temp-area transfers, keep kernels-only
    "transfer-only": dict(transfer="bulk", staged=True, kernels_only=True),
    # this paper: both improvements
    "proposed": dict(transfer="bulk", staged=True, kernels_only=False),
    # extra reference: [32]-era naive per-kernel sync
    "naive-2018": dict(transfer="naive", staged=False, kernels_only=True),
}

MODES = ("binary", "mixed")

# How candidates are priced, end to end (docs/fidelity.md):
# - "modeled"    — the analytic HardwareModel/MixedEvaluator (default);
# - "measured"   — real wall clocks of the runnable miniapps through
#                  MeasuredEvaluator, one at a time, in the process that
#                  runs the Offloader (the one that holds the chip);
# - "calibrated" — a calibrate stage measures a designed probe set, fits
#                  per-destination constants by least squares, and the
#                  search runs the analytic model under the fitted machine.
FIDELITIES = ("modeled", "measured", "calibrated")

# programs with a runnable implementation the measured/calibrated levels
# can wall-clock; programs.RUNNABLE must stay in sync (asserted there)
MEASURED_PROGRAMS = ("himeno", "nasft")

# the scale at which runnable programs are wall-clocked and PCAST-checked
# (measured fidelity, calibrated fidelity sections, rank probes):
# "model" = the grid the searched LoopProgram models, "small" = a toy grid
# for tests on the CPU. programs.MEASURED_RUN_FNS holds the one table.
MEASURED_SCALES = ("model", "small")

# mixed-mode GA budgets (population, generations): the k=3 space needs
# ~24x24 to find the mixed optimum on every seed; the smoke budget is
# the CI-sized trim that still shows the win on the default seed. The
# CLI's --smoke and benchmarks/fig_mixed_destinations.py both consume
# these so the budgets can't drift apart.
MIXED_BUDGET = (24, 24)
MIXED_SMOKE_BUDGET = (10, 8)

_SPEC_VERSION = 1


@dataclasses.dataclass(frozen=True)
class GAControls:
    """Search-quality knobs (docs/observability.md), nested under
    ``OffloadSpec.ga``. No default changes the search: ``diversity=0.0``
    never enters the fitness-sharing block, and the stability/rank
    metrics run *after* the search, in the report stage, against the same
    fitness cache.
    """

    # fitness-sharing strength (GAParams.diversity): an individual's
    # roulette fitness is divided by (copies of its genome in the
    # generation) ** diversity. 0.0 = off, the historical selection.
    diversity: float = 0.0
    # pass@k winner stability in the report stage: the modeled search is
    # re-run at GA seeds seed+1 .. seed+k-1 (the recorded search covers
    # the spec's own seed), sharing the persistent fitness cache.
    # <= 1 disables the re-searches.
    stability_seeds: int = 3
    # a seed "passes" when its best time lands within this relative
    # window of the best seed's best
    stability_window: float = 0.02
    # when set, the report stage FAILS if the relative spread
    # (worst/best - 1) across seeds exceeds this gate
    stability_gate: Optional[float] = None
    # wall-clock the (at most two) realizable projections of the final
    # population so modeled/calibrated searches get a modeled-vs-measured
    # rank correlation too; measured fidelity computes it for free from
    # the search's own clocks
    rank_probe: bool = False
    # asynchronous steady-state GA (GAParams.steady_state): offspring are
    # bred per free worker lane instead of waiting at the generation
    # barrier. False = the generational loop.
    steady_state: bool = False
    # vectorized population pricing (BatchMixedEvaluator): mixed-mode
    # searches price whole populations in one numpy pass; the scalar
    # evaluator stays the verify-stage oracle and shares the same
    # fingerprint/cache keys. False = scalar pricing.
    batch: bool = False

    def __post_init__(self):
        if self.diversity < 0:
            raise ValueError(f"ga.diversity must be >= 0: {self.diversity}")
        if self.stability_seeds < 0:
            raise ValueError(
                f"ga.stability_seeds must be >= 0: {self.stability_seeds}"
            )
        if self.stability_window < 0:
            raise ValueError(
                f"ga.stability_window must be >= 0: {self.stability_window}"
            )
        if self.stability_gate is not None and self.stability_gate < 0:
            raise ValueError(
                f"ga.stability_gate must be >= 0: {self.stability_gate}"
            )

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GAControls":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown GAControls fields {sorted(unknown)}")
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class OffloadSpec:
    """Declarative input of one end-to-end offload search.

    ``population`` / ``generations`` / ``timeout_s`` default to ``None``
    = the mode's own budget: the paper rule
    (:meth:`GAParams.for_gene_length`) for binary searches, 24x24 with a
    no-op timeout for mixed searches, and min(n, 10) for arch searches.

    ``to_dict`` serialises every field; ``from_dict`` fills a field an
    older artifact lacks with its default, so older artifacts load.
    """

    program: str  # miniapp name, or "arch:<name>"
    mode: str = "binary"  # "binary" | "mixed"
    method: str = "proposed"  # binary only: METHODS key
    destinations: Tuple[str, ...] = ("cpu", "gpu", "fpga")  # mixed only
    # the modeled machine. Binary/arch: a HardwareModel name (rate
    # constants). Mixed: a machine Registry name from
    # ``repro.destinations.REGISTRIES`` — profiles, links AND
    # per-destination memory capacities, so a capacity-constrained
    # machine (e.g. "p4000-constrained", "tpu-v5e-host") is frozen into
    # the spec and its artifact/cache identity.
    hw: str = "quadro-p4000"
    # -- fidelity: how candidates are priced (FIDELITIES) ------------------
    # "measured" requires a runnable program (MEASURED_PROGRAMS), binary
    # mode, and one in-process measurement lane (executor="thread",
    # workers=1: the chip belongs to the process running the Offloader);
    # "calibrated" requires ``hw`` to name a known base registry — both
    # validated here at spec time, never mid-search.
    fidelity: str = "modeled"
    # measurement repeats per individual/probe (measured + calibrated).
    # The minimum over repeats is kept, so with the default of 2 the
    # first repeat absorbs the one-time jit compile and the clock bills
    # the COMPILED kernel; set 1 only if you explicitly want cold-start
    # costs in the fitness.
    repeats: int = 2
    # MEASURED_SCALES: the grid every wall clock and measured PCAST check
    # of a runnable program uses (programs.MEASURED_RUN_FNS)
    measured_scale: str = "model"
    # -- GA budget ---------------------------------------------------------
    population: Optional[int] = None
    generations: Optional[int] = None
    seed: int = 0
    timeout_s: Optional[float] = None
    penalty_time_s: float = 1000.0
    # -- genome-aware seeding (mixed only): warm the k-ary initial
    # population with each single-destination best re-expressed in the
    # k-ary alphabet (ROADMAP follow-on)
    warm_start: bool = False
    # -- function-block substitution (mixed only, docs/blocks.md): match
    # loop chains against the kernel library (repro.blocks) and extend
    # the genome with one gene per matched block choosing between
    # loop-level placement and library substitution per destination.
    # Off = the loop-level search.
    blocks: bool = False
    # -- evaluation pool ---------------------------------------------------
    workers: int = 1
    executor: str = "thread"
    cache: Optional[str] = None  # persistent JSONL fitness-cache path
    # -- verify tolerances (None = repro.core.pcast dtype defaults) --------
    rel_tol: Optional[float] = None
    abs_tol: Optional[float] = None
    # -- search-quality knobs (docs/observability.md) ----------------------
    ga: GAControls = dataclasses.field(default_factory=GAControls)

    def __post_init__(self):
        if isinstance(self.ga, dict):  # from_dict round-trip
            object.__setattr__(self, "ga", GAControls.from_dict(self.ga))
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}: {self.mode!r}")
        if self.mode == "binary" and self.method not in METHODS:
            raise ValueError(
                f"unknown method {self.method!r}; have {sorted(METHODS)}"
            )
        if self.mode == "mixed":
            if self.is_arch:
                raise ValueError("mixed mode applies to loop programs, "
                                 "not arch:<name> searches")
            if len(self.destinations) < 2:
                raise ValueError("mixed mode needs >= 2 destinations "
                                 "(host first)")
        if self.executor not in ("thread", "process"):
            raise ValueError(f"executor must be thread|process: "
                             f"{self.executor!r}")
        if self.warm_start and self.mode != "mixed":
            raise ValueError("warm_start is a mixed-mode (k-ary) feature")
        if self.blocks and self.mode != "mixed":
            raise ValueError("blocks (function-block substitution) is a "
                             "mixed-mode feature")
        if self.fidelity not in FIDELITIES:
            raise ValueError(
                f"fidelity must be one of {FIDELITIES}: {self.fidelity!r}"
            )
        if self.repeats < 1:
            raise ValueError(f"repeats must be >= 1: {self.repeats}")
        if self.measured_scale not in MEASURED_SCALES:
            raise ValueError(
                f"measured_scale must be one of {MEASURED_SCALES}: "
                f"{self.measured_scale!r}"
            )
        if self.population is not None and self.population < 1:
            raise ValueError(f"population must be >= 1: {self.population}")
        if self.generations is not None and self.generations < 0:
            # 0 is allowed: an analyze-only run records an empty search
            # ("no generations"), which report/verify handle explicitly
            raise ValueError(f"generations must be >= 0: {self.generations}")
        if self.fidelity == "measured":
            if self.program not in MEASURED_PROGRAMS:
                raise ValueError(
                    f"fidelity='measured' needs a program with a runnable "
                    f"implementation {MEASURED_PROGRAMS}; {self.program!r} "
                    "has none to wall-clock"
                )
            if self.mode != "binary":
                raise ValueError(
                    "fidelity='measured' is a binary-mode feature (the "
                    "runnable implementations switch one CPU/accelerator "
                    "path); use mode='binary'"
                )
            if self.executor != "thread":
                raise ValueError(
                    "fidelity='measured' measures in the process that runs "
                    "the Offloader: a chip belongs to one process at a "
                    "time, so a child process would find it held and JAX "
                    "would time the CPU instead; use executor='thread'"
                )
            if self.workers != 1:
                raise ValueError(
                    "fidelity='measured' times one candidate at a time, in "
                    "the process that holds the chip (concurrent clocks "
                    f"would time each other); use workers=1, not "
                    f"{self.workers}"
                )
        if self.fidelity == "calibrated":
            if self.is_arch:
                raise ValueError(
                    "fidelity='calibrated' calibrates a machine registry; "
                    "arch:<name> searches use the analytic plan evaluator "
                    "and have no machine to calibrate"
                )
            # lazy import: destinations never imports repro.offload, so
            # this cannot cycle — and it keeps spec importable without
            # dragging the destinations subsystem in for modeled specs
            from repro.destinations import REGISTRIES

            if self.hw not in REGISTRIES:
                raise ValueError(
                    f"fidelity='calibrated' needs a known base registry "
                    f"to calibrate; unknown hw {self.hw!r} (have "
                    f"{sorted(REGISTRIES)})"
                )
        # normalize list -> tuple for from_dict round-trips
        object.__setattr__(self, "destinations", tuple(self.destinations))

    # -- program identity ---------------------------------------------------

    @property
    def is_arch(self) -> bool:
        return self.program.startswith("arch:")

    @property
    def arch_name(self) -> str:
        assert self.is_arch, self.program
        return self.program.split(":", 1)[1]

    # -- GA parameter resolution ---------------------------------------------

    def ga_params(self, gene_length: int, alleles: int = 2) -> ga.GAParams:
        """Concrete :class:`GAParams` for this spec at a gene length.

        Unset (``None``) fields resolve to the mode's own budget;
        explicit values — including ``generations=0`` — are taken as-is.
        """
        if self.mode == "mixed":
            return ga.GAParams(
                population=self.population
                if self.population is not None else MIXED_BUDGET[0],
                generations=self.generations
                if self.generations is not None else MIXED_BUDGET[1],
                seed=self.seed,
                timeout_s=self.timeout_s if self.timeout_s is not None
                else 1e6,
                penalty_time_s=self.penalty_time_s,
                alleles=alleles,
                diversity=self.ga.diversity,
                steady_state=self.ga.steady_state,
            )
        if self.is_arch:
            return ga.GAParams(
                population=self.population
                if self.population is not None else min(gene_length, 10),
                generations=self.generations
                if self.generations is not None else min(gene_length, 10),
                seed=self.seed,
                timeout_s=self.timeout_s if self.timeout_s is not None
                else 1e6,
                penalty_time_s=self.penalty_time_s,
                diversity=self.ga.diversity,
                steady_state=self.ga.steady_state,
            )
        # binary miniapp: the paper rule (fig4/fig5)
        kw: Dict[str, Any] = dict(seed=self.seed,
                                  penalty_time_s=self.penalty_time_s,
                                  diversity=self.ga.diversity,
                                  steady_state=self.ga.steady_state)
        if self.timeout_s is not None:
            kw["timeout_s"] = self.timeout_s
        params = ga.GAParams.for_gene_length(gene_length, **kw)
        if self.population is not None or self.generations is not None:
            params = dataclasses.replace(
                params,
                population=self.population
                if self.population is not None else params.population,
                generations=self.generations
                if self.generations is not None else params.generations,
            )
        return params

    # -- (de)serialization --------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["destinations"] = list(self.destinations)
        d["v"] = _SPEC_VERSION
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "OffloadSpec":
        d = dict(d)
        v = d.pop("v", _SPEC_VERSION)
        if v != _SPEC_VERSION:
            raise ValueError(f"unsupported OffloadSpec version {v}")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown OffloadSpec fields {sorted(unknown)}")
        return cls(**d)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "OffloadSpec":
        return cls.from_dict(json.loads(s))
