"""Lower-precision control of Himeno: the program's own bfloat16 path.

``himeno_run`` takes the state's dtype; with bfloat16 the jitted sweeps
hold and update the pressure in bfloat16, the step below the float32 the
configuration states. Put in the program's place, it has to come out as
not correct.
"""
from __future__ import annotations


class Control:
    def __init__(self, cfg):
        self.grid = tuple(cfg["grid"])
        self.nn = int(cfg["nn"])

    def run(self, offloaded: bool):
        import jax.numpy as jnp
        from repro.core import miniapps

        p, gosa = miniapps.himeno_run(self.grid, self.nn, jit_stencil=True,
                                      dtype=jnp.bfloat16)
        return {"p": p, "gosa": gosa}
