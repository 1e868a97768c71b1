"""Kernel library registry: tuned accelerator implementations as
substitution targets for whole loop groups (function blocks).

The source paper places individual loop statements; its lineage's next
step (PAPERS.md: arXiv:2004.09883, evaluated in arXiv:2005.04174) is to
recognize whole *function blocks* and substitute a tuned library
implementation instead. This module is the library side of that step:

- :class:`KernelEntry` names a real implementation in
  ``repro.kernels.ops``, its reference oracle in ``repro.kernels.ref``,
  the destination kinds it can run on, and a structural
  :class:`BlockSignature` a loop chain must match.
- :class:`KernelLibrary` is an ordered, fingerprinted collection of
  entries. The fingerprint covers every field an evaluator prices from
  (signatures, destination kinds, gains), so block-enabled fitness-cache
  entries are keyed on the exact library that produced them.
- :func:`oracle_check` runs an entry's implementation (the Pallas kernel,
  compiled for the chip on a TPU backend and interpreted elsewhere)
  against its ``ref.py`` oracle on a tiny seeded input — the verify
  stage calls this for every substitution the search placed in a
  winner, the same way PCAST validates loop placements.

Signatures are derived from the same per-loop fields that
``LoopProgram.fingerprint()`` digests: :func:`loop_atom` renders the
(klass, sequential_carry) pair of one loop exactly as the fingerprint
does, and an entry matches a maximal run of consecutive dataflow-chained
loops whose atoms all equal the entry's (see ``repro.blocks.match``).

Calibration hook: ``fidelity="calibrated"`` fits a per-kernel *gain*
(speedup of the library implementation over the fused-roofline estimate)
from kernel probes (``repro.offload.calibrate``); ``install()``
registers those constants here under the calibration's hardware name so
``default_library(hw=...)`` prices with them. The modeled fallback is
gain 1.0 — the kernel is priced as a perfectly fused TIGHT nest.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Dict, Optional, Tuple

from repro.core.loopir import Loop, LoopClass


def loop_atom(loop: Loop) -> str:
    """One loop's structural atom, rendered from the same fields (and in
    the same ``{klass.value}:{int(sequential_carry)}`` form) that
    ``LoopProgram.fingerprint()`` digests per loop."""
    return f"{loop.klass.value}:{int(loop.sequential_carry)}"


@dataclasses.dataclass(frozen=True)
class BlockSignature:
    """Structural shape a loop chain must have to match an entry: every
    loop in the chain carries ``atom``, and the chain spans at least
    ``min_len`` consecutive dataflow-linked loops."""

    atom: str
    min_len: int = 2

    def __post_init__(self):
        assert self.min_len >= 1, self.min_len


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One library kernel: implementation + oracle + match signature."""

    name: str
    impl: str  # callable name in repro.kernels.ops
    oracle: str  # reference callable name in repro.kernels.ref
    signature: BlockSignature
    dest_kinds: Tuple[str, ...]  # destination kinds that can host it
    # Speedup of the library implementation over the fused-roofline
    # estimate (sum of covered flops at the destination's TIGHT rate).
    # 1.0 = modeled fallback; calibration fits a per-hw constant.
    gain: float = 1.0
    description: str = ""

    def __post_init__(self):
        assert self.gain > 0, self.gain

    def eligible(self, dest) -> bool:
        """Can ``dest`` host this kernel? Kind must be listed and the
        destination must accept a TIGHT nest (the fused kernel's class)."""
        return dest.kind in self.dest_kinds and dest.accepts(LoopClass.TIGHT)


class KernelLibrary:
    """Ordered, fingerprinted kernel collection (order = match priority)."""

    def __init__(self, entries: Tuple[KernelEntry, ...]):
        names = [e.name for e in entries]
        assert len(set(names)) == len(names), "duplicate entry names"
        self.entries: Tuple[KernelEntry, ...] = tuple(entries)

    def get(self, name: str) -> KernelEntry:
        for e in self.entries:
            if e.name == name:
                return e
        raise KeyError(name)

    def fingerprint(self) -> str:
        """Digest of every field the evaluator prices from. Two libraries
        with different gains (e.g. modeled vs calibrated) never share
        block-enabled fitness-cache entries."""
        parts = [
            f"{e.name}:{e.impl}:{e.oracle}:{e.signature.atom}"
            f":{e.signature.min_len}:{','.join(e.dest_kinds)}:{e.gain:.6g}"
            for e in self.entries
        ]
        digest = hashlib.sha256("|".join(parts).encode()).hexdigest()[:12]
        return f"kernlib-{digest}"


# -- per-hardware calibrated gains ------------------------------------------

# hw name (e.g. a calibration's digest-named registry) -> {kernel: gain}.
# Populated by repro.offload.calibrate.install(); read by default_library.
_HW_GAINS: Dict[str, Dict[str, float]] = {}


def register_kernel_gains(hw: str, gains: Dict[str, float]) -> None:
    """Install calibrated per-kernel gains under a hardware name."""
    _HW_GAINS[hw] = {k: float(v) for k, v in gains.items()}


def kernel_gains(hw: Optional[str]) -> Dict[str, float]:
    return dict(_HW_GAINS.get(hw, {})) if hw else {}


# -- the default library ----------------------------------------------------

_ENTRIES = (
    KernelEntry(
        name="flash_attention",
        impl="flash_attention",
        oracle="attention_ref",
        # a chain of tightly-nested carry-free stencil/attention-shaped
        # nests: each stage reads the previous stage's output
        signature=BlockSignature(atom="tight:0", min_len=2),
        dest_kinds=("gpu", "tpu"),
        description="fused attention-style pipeline (Pallas flash kernel)",
    ),
    KernelEntry(
        name="ssd_scan",
        impl="ssd_scan",
        oracle="ssd_ref",
        # a chain of vectorizable-only loops with sequential carries:
        # the chunked SSD scan fuses the whole recurrence
        signature=BlockSignature(atom="vector_only:1", min_len=2),
        dest_kinds=("gpu", "tpu", "fpga"),
        description="fused sequential-scan chain (Pallas chunked SSD)",
    ),
)


def default_library(hw: Optional[str] = None) -> KernelLibrary:
    """The stock library, with any calibrated gains for ``hw`` applied."""
    gains = kernel_gains(hw)
    entries = tuple(
        dataclasses.replace(e, gain=gains[e.name]) if e.name in gains else e
        for e in _ENTRIES
    )
    return KernelLibrary(entries)


# -- oracle checks ----------------------------------------------------------

# Tiny seeded shapes: the verify stage runs these on every block-enabled
# run (CI smoke included), so they must stay interpret-mode-on-CPU cheap.
_ORACLE_TOL = {"rtol": 2e-5, "atol": 2e-5}


def kernel_interpret() -> bool:
    """Whether the library's Pallas kernels run interpreted: only off the
    TPU. On a TPU backend they are compiled for the chip."""
    import jax

    return jax.default_backend() != "tpu"


def _attention_case(seed: int):
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    B, S, H, D = 1, 64, 2, 32
    q = rng.standard_normal((B, S, H, D)).astype("float32")
    k = rng.standard_normal((B, S, H, D)).astype("float32")
    v = rng.standard_normal((B, S, H, D)).astype("float32")
    impl = lambda: ops.flash_attention(  # noqa: E731
        q, k, v, causal=True, impl="pallas", interpret=kernel_interpret()
    )
    oracle = lambda: ref.attention_ref(q, k, v, causal=True)  # noqa: E731
    return impl, oracle, f"q{q.shape}"


def _ssd_case(seed: int):
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    B, S, H, P, N, chunk = 1, 64, 2, 8, 8, 16
    x = rng.standard_normal((B, S, H, P)).astype("float32")
    dt = np.log1p(np.exp(rng.standard_normal((B, S, H)))).astype("float32")
    A = (-np.exp(rng.standard_normal(H))).astype("float32")
    Bm = rng.standard_normal((B, S, N)).astype("float32")
    Cm = rng.standard_normal((B, S, N)).astype("float32")
    impl = lambda: ops.ssd_scan(  # noqa: E731
        x, dt, A, Bm, Cm, chunk=chunk, impl="pallas",
        interpret=kernel_interpret(),
    )
    oracle = lambda: ref.ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)  # noqa: E731
    return impl, oracle, f"x{x.shape}"


# entry name -> seeded case builder: (run_impl, run_oracle, shape label)
_ORACLE_HARNESSES: Dict[str, Callable] = {
    "flash_attention": _attention_case,
    "ssd_scan": _ssd_case,
}


def oracle_check(entry: KernelEntry, seed: int = 0) -> Dict[str, object]:
    """Run ``entry``'s implementation (the real kernel, compiled on a TPU
    and interpreted elsewhere) against its reference oracle on a tiny
    seeded input. Returns a JSON-able verdict row for the verify stage's
    ``block_oracles``. Both sides multiply at full f32 precision: the
    tolerance is an f32 one, and a TPU's default f32 matmul is a single
    bf16 pass."""
    import jax
    import numpy as np

    impl, oracle, shape = _ORACLE_HARNESSES[entry.name](seed)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(impl())
        want = np.asarray(oracle())
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    tol = _ORACLE_TOL["atol"] + _ORACLE_TOL["rtol"] * float(
        np.max(np.abs(want))
    )
    return {
        "kernel": entry.name,
        "impl": f"ops.{entry.impl}",
        "oracle": f"ref.{entry.oracle}",
        "shape": shape,
        "max_abs_err": err,
        "tol": tol,
        "interpret": kernel_interpret(),
        "ok": bool(err <= tol),
    }


def time_kernel(
    entry: KernelEntry, repeats: int = 1, seed: int = 0
) -> Tuple[float, float]:
    """(oracle seconds, implementation seconds) at the oracle-check
    shape: min over ``repeats`` timed runs after one warm-up each. The
    calibration's kernel probes fit per-kernel gains from the ratio."""
    import time

    import numpy as np

    impl, oracle, _ = _ORACLE_HARNESSES[entry.name](seed)

    def best(fn) -> float:
        np.asarray(fn())  # warm-up (traces/compiles)
        t = float("inf")
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            np.asarray(fn())  # block until the value is materialized
            t = min(t, time.perf_counter() - t0)
        return t

    return best(oracle), best(impl)
