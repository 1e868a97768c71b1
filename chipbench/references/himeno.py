"""Plain reference of the Himeno benchmark (RIKEN): Jacobi sweeps of the
19-point Poisson stencil, as ``himenobmtxpa.c`` writes them.

Independent of the program under test: it imports nothing from it and
follows the published C code line for line, in float32 (the benchmark's
precision), with the residual ``gosa`` accumulated in float64 so that
the reference's own rounding stays far below the program's.

``initial_state`` is the published ``initmt``: a0..a2 = 1, a3 = 1/6,
b = 0, c = 1, p[i] = i^2 / (imax - 1)^2, wrk1 = 0, bnd = 1.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def initial_state(grid: Tuple[int, int, int]) -> Dict[str, np.ndarray]:
    i, j, k = grid
    f32 = np.float32
    p = np.empty((i, j, k), f32)
    p[...] = (np.arange(i, dtype=np.float64) ** 2 / (i - 1) ** 2
              ).astype(f32)[:, None, None]
    a = np.ones((4, i, j, k), f32)
    a[3] = f32(1.0 / 6.0)
    return {
        "p": p,
        "a": a,
        "b": np.zeros((3, i, j, k), f32),
        "c": np.ones((3, i, j, k), f32),
        "bnd": np.ones((i, j, k), f32),
        "wrk1": np.zeros((i, j, k), f32),
    }


def jacobi(s: Dict[str, np.ndarray], omega: float) -> float:
    """One sweep over the interior; updates ``s["p"]`` and returns gosa."""
    p, a, b, c, bnd, wrk1 = (s[n] for n in ("p", "a", "b", "c", "bnd",
                                              "wrk1"))
    I, J, K = p.shape
    # interior, and its neighbours one step up (u) and down (d) each axis
    m = (slice(1, I - 1), slice(1, J - 1), slice(1, K - 1))
    iu, id_ = slice(2, I), slice(0, I - 2)
    ju, jd = slice(2, J), slice(0, J - 2)
    ku, kd = slice(2, K), slice(0, K - 2)
    mi, mj, mk = m
    s0 = (a[0][m] * p[iu, mj, mk]
          + a[1][m] * p[mi, ju, mk]
          + a[2][m] * p[mi, mj, ku]
          + b[0][m] * (p[iu, ju, mk] - p[iu, jd, mk]
                       - p[id_, ju, mk] + p[id_, jd, mk])
          + b[1][m] * (p[mi, ju, ku] - p[mi, jd, ku]
                       - p[mi, ju, kd] + p[mi, jd, kd])
          + b[2][m] * (p[iu, mj, ku] - p[id_, mj, ku]
                       - p[iu, mj, kd] + p[id_, mj, kd])
          + c[0][m] * p[id_, mj, mk]
          + c[1][m] * p[mi, jd, mk]
          + c[2][m] * p[mi, mj, kd]
          + wrk1[m])
    ss = (s0 * a[3][m] - p[m]) * bnd[m]
    gosa = float(np.sum(np.square(ss, dtype=np.float64)))
    p[m] = p[m] + np.float32(omega) * ss
    return gosa


def solve(grid: Tuple[int, int, int], sweeps: int, omega: float
          ) -> Dict[str, object]:
    """``sweeps`` Jacobi sweeps from the published initial state; returns
    the final pressure, the last sweep's gosa and the initial pressure."""
    s = initial_state(grid)
    p0 = s["p"].copy()
    gosa = 0.0
    for _ in range(sweeps):
        gosa = jacobi(s, omega)
    return {"p": s["p"], "gosa": gosa, "p0": p0}


def reference(cfg: Dict[str, object]) -> Dict[str, object]:
    """:func:`solve` at a configuration's sizes."""
    return solve(tuple(cfg["grid"]), int(cfg["nn"]), float(cfg["omega"]))


def compare(out: Dict[str, object], ref: Dict[str, object]
            ) -> Dict[str, float]:
    """The numbers ``correct`` is decided by, for one answer.

    - ``p_err``: the widest gap in the final pressure, as a share of the
      widest change the sweeps make to it (1.0 for a solver that leaves
      the pressure unchanged);
    - ``gosa_err``: the relative gap in the last sweep's residual.
    """
    p = np.asarray(out["p"], np.float64)
    p_ref = np.asarray(ref["p"], np.float64)
    change = float(np.max(np.abs(p_ref - np.asarray(ref["p0"], np.float64))))
    gap = float(np.max(np.abs(p - p_ref))) if p.shape == p_ref.shape \
        else float("inf")
    g, g_ref = float(out["gosa"]), float(ref["gosa"])
    return {
        "p_err": gap / change,
        "gosa_err": abs(g - g_ref) / abs(g_ref),
    }
