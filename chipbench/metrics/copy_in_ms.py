"""copy_in_ms: host milliseconds per traced request spent in the copies
of the program's input state to the chip (``himeno.copy_in``: six
``jnp.asarray`` calls; ``nasft.copy_in``: ``u0`` and ``k2``), from the
program's spans in the device trace. A copy that finishes on the chip
after its call returns counts only the host call."""
import spans


def read(cell):
    return spans.step_ms(cell, "copy_in")
