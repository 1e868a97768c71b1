"""Operations and bytes of a kernel call, computed from its shapes, and
the least time a chip could take for them.

Counts are lower bounds of what the algorithm must do, so that a
roofline share computed from them cannot pass 100% through an
overcount: each array is read or written once, and only the cells the
kernel touches count.
"""
from __future__ import annotations

from typing import Dict, Tuple

F32 = 4


def himeno_sweep(grid: Tuple[int, int, int]) -> Dict[str, float]:
    """One Jacobi sweep of the Himeno stencil on an (i, j, k) float32
    grid: ``p`` is read whole (the interior's neighbours reach the
    faces), the 12 coefficient planes (a0..a3, b0..b2, c0..c2, bnd, wrk1)
    only over the interior, and the new pressure is written whole. 34
    floating-point operations per interior cell (the published count)."""
    i, j, k = grid
    cells = i * j * k
    interior = (i - 2) * (j - 2) * (k - 2)
    return {
        "bytes": float(F32 * (cells + 12 * interior + cells)),
        "flops": float(34 * interior),
    }


def least_time_s(work: Dict[str, float], peak: Dict[str, float]
                 ) -> Tuple[float, str]:
    """(seconds, bound) at the chip's published peaks: the larger of
    operations over peak FLOP/s and bytes over peak bandwidth."""
    t_flops = work["flops"] / peak["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")


def peak_for(peaks: Dict[str, Dict], device_kind: str) -> Dict[str, float]:
    """The peaks of a device kind; a kind not in the table is an error."""
    if device_kind not in peaks:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (have {sorted(peaks)})")
    return peaks[device_kind]
