"""Function-block offloading (PAPERS.md: arXiv:2004.09883 / 2005.04174):
match whole loop chains against a library of tuned kernels
(``repro.kernels``) and let the genome substitute the library
implementation instead of placing the loops individually.

- ``library``    — :class:`KernelLibrary` of :class:`KernelEntry` rows
  (implementation + ``ref.py`` oracle + structural signature +
  calibratable gain) and the oracle-check harness.
- ``match``      — deterministic, non-overlapping matching of maximal
  dataflow-chained loop runs against library signatures.
- ``substitute`` — :class:`BlockMixedEvaluator`: the per-block genome
  dimension, priced through a fused-nest variant program.

Enabled per run via ``OffloadSpec.blocks`` (mixed mode only; off =
the loop-level search). See docs/blocks.md.
"""
from repro.blocks.library import (  # noqa: F401
    BlockSignature,
    KernelEntry,
    KernelLibrary,
    default_library,
    kernel_gains,
    loop_atom,
    oracle_check,
    register_kernel_gains,
    time_kernel,
)
from repro.blocks.match import BlockMatch, match_blocks  # noqa: F401
from repro.blocks.substitute import (  # noqa: F401
    BatchBlockMixedEvaluator,
    BlockMixedEvaluator,
    fused_loop,
    internal_vars,
    substituted_program,
)
