"""Plain reference of the NAS Parallel Benchmarks FT kernel (class A
grid): u1(t) = IFFT(exp(-4 pi^2 alpha t |k|^2) * FFT(u0)), one checksum of
1024 strided samples of u1 per iteration.

Independent of the program under test: it imports nothing from it and
computes in complex128 from the complex64 initial field, so its own
rounding stays far below the program's. Where the configuration departs
from NPB (initial field, alpha, sample stride), the configuration file
says so under ``assumed`` and the reference follows the configuration.

``bf16=True`` is the lower-precision control: the same reference with
every stored array (initial field, spectrum, evolution factors, each u1)
rounded to bfloat16 in both parts, as a bfloat16 port of the program
would hold them. Neither numpy nor XLA has a bfloat16 FFT, so the
transforms themselves run in float64 between the roundings.
"""
from __future__ import annotations

from typing import Dict, Tuple

import ml_dtypes
import numpy as np


def initial_field(grid: Tuple[int, int, int], seed: int) -> np.ndarray:
    """Complex standard normal field, (nz, ny, nx), from numpy's
    ``default_rng(seed)``: real parts first, then imaginary parts."""
    nx, ny, nz = grid
    rng = np.random.default_rng(seed)
    re = rng.standard_normal((nz, ny, nx))
    im = rng.standard_normal((nz, ny, nx))
    return (re + 1j * im).astype(np.complex64)


def wavenumbers_sq(grid: Tuple[int, int, int]) -> np.ndarray:
    nx, ny, nz = grid
    kz = np.fft.fftfreq(nz)[:, None, None]
    ky = np.fft.fftfreq(ny)[None, :, None]
    kx = np.fft.fftfreq(nx)[None, None, :]
    return (kx ** 2 + ky ** 2 + kz ** 2).astype(np.float32)


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round each part to bfloat16 (round to nearest even)."""
    def r(v):
        return v.astype(np.float32).astype(ml_dtypes.bfloat16).astype(
            np.float64)
    if np.iscomplexobj(x):
        return r(x.real) + 1j * r(x.imag)
    return r(x)


def checksum(u1: np.ndarray, samples: int, stride: int) -> complex:
    idx = (np.arange(samples) * stride) % u1.size
    return complex(u1.reshape(-1)[idx].sum() / u1.size)


def solve(grid: Tuple[int, int, int], niter: int, alpha: float,
          seed: int, samples: int, stride: int,
          bf16: bool = False) -> Dict[str, np.ndarray]:
    """The per-iteration checksums (complex128, shape (niter,))."""
    rnd = _bf16 if bf16 else (lambda v: v)
    u0 = rnd(initial_field(grid, seed).astype(np.complex128))
    k2 = rnd(wavenumbers_sq(grid).astype(np.float64))
    ut = rnd(np.fft.fftn(u0))
    sums = []
    for t in range(1, niter + 1):
        tw = rnd(np.exp(-4.0 * np.pi ** 2 * alpha * t * k2))
        u1 = rnd(np.fft.ifftn(rnd(ut * tw)))
        sums.append(checksum(u1, samples, stride))
    return {"checksums": np.asarray(sums, np.complex128)}


def reference(cfg: Dict[str, object], bf16: bool = False
              ) -> Dict[str, np.ndarray]:
    """:func:`solve` at a configuration's sizes and constants."""
    init = cfg["initial_field"]
    return solve(tuple(cfg["grid"]), int(cfg["niter"]), float(cfg["alpha"]),
                 int(init["seed"]), int(cfg["checksum"]["samples"]),
                 int(cfg["checksum"]["stride"]), bf16=bf16)


def compare(out: Dict[str, object], ref: Dict[str, np.ndarray]
            ) -> Dict[str, float]:
    """``chk_err``: the widest gap over the iterations' checksums, as a
    share of the largest reference checksum."""
    c = np.asarray(out["checksums"], np.complex128)
    c_ref = ref["checksums"]
    if c.shape != c_ref.shape:
        return {"chk_err": float("inf")}
    return {"chk_err": float(np.max(np.abs(c - c_ref))
                             / np.max(np.abs(c_ref)))}
