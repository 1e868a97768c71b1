"""Traffic ``placed``: the program runs under the placement the search
returns (its hot loop on the accelerator), back to back.

Each request is one call of the runnable program's entry, ``run(True)``
of the configuration's run fn: host set-up, transfers, the jitted hot
loop and the host-side reduction, exactly as the measured search times
it. The program takes no input data, so every request computes the same
answer; the seed draws which answers are kept for the comparison
(``sample`` of them, uniformly over the window).

A kept answer is copied into one of ``sample`` buffers mapped apart from
the host heap in set-up. Held in the heap, the program's own arrays (17
MB for Himeno) would keep it from shrinking for as long as the seed kept
them, and the requests after them would take time by that.
"""
from __future__ import annotations

import mmap

import numpy as np


def run_fn(cfg):
    """The configuration's run fn, built from its own sizes."""
    from repro.core import miniapps

    kw = {k: (tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
          for k in cfg["run_fn_args"]}
    return getattr(miniapps, cfg["run_fn"])(**kw)


def mapped_like(value) -> np.ndarray:
    """A zeroed array of ``value``'s shape and type, in memory mapped apart
    from the host heap."""
    a = np.asarray(value)
    buf = mmap.mmap(-1, max(a.nbytes, 1))
    return np.frombuffer(buf, a.dtype, a.size).reshape(a.shape)


def worse(a: float, b: float) -> float:
    """The larger reading; NaN, a reading that compares with nothing,
    wins."""
    return a if a != a or a >= b else b


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.fn = cell.program or run_fn(cell.config)
        self.sample = int(cell.traffic["sample"])
        self.rng = cell.rng(1)

    def setup(self) -> None:
        # compiles (or loads from the cache) every program a run uses,
        # and leaves the host allocator warm
        for _ in range(int(self.cell.traffic["warm_requests"])):
            out = self.fn.run(True)
        self.slots = [{k: mapped_like(v) for k, v in out.items()}
                      for _ in range(self.sample)]

    def request(self, i: int) -> None:
        out = self.fn.run(True)
        # reservoir sample: each request's answer is kept with equal odds
        kept = self.cell.outputs
        if len(kept) < self.sample:
            j = len(kept)
            kept.append(self.slots[j])
        else:
            j = int(self.rng.integers(0, i + 1))
        if j < self.sample:
            for k, v in out.items():
                np.copyto(kept[j][k], v)

    def check(self):
        ref_mod = self.cell.reference
        ref = ref_mod.reference(self.cell.config)
        worst = {}
        for out in self.cell.outputs:
            for name, v in ref_mod.compare(out, ref).items():
                worst[name] = worse(worst.get(name, v), v)
        limits = self.cell.config["limits"]
        return [(n, worst[n], float(limits[n])) for n in sorted(worst)]
