"""Lower-precision control of NAS FT: the plain reference computed with
every stored array rounded to bfloat16 (``references/nasft.py``,
``bf16=True``), put in the program's place.

``nasft_run`` has no precision argument, and neither numpy nor XLA has a
bfloat16 FFT, so the control is the reference itself one step below the
float32 parts of the configuration's complex64. It has to come out as
not correct.
"""
from __future__ import annotations

from references import nasft as reference


class Control:
    def __init__(self, cfg):
        self.cfg = cfg

    def run(self, offloaded: bool):
        return reference.reference(self.cfg, bf16=True)
