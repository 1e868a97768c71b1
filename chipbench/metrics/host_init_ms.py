"""host_init_ms: host milliseconds per traced request in which the
program builds its input state (``himeno.init``: ``himeno_init``;
``nasft.init``: the RNG draw of ``u0`` and the ``k2`` build), from the
program's spans in the device trace."""
import spans


def read(cell):
    return spans.step_ms(cell, "init")
