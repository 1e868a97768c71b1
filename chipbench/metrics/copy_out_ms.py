"""copy_out_ms: host milliseconds per traced request spent copying
results from the chip (``himeno.copy_out``: the final pressure;
``nasft.copy_out``: ``u1``, once per iteration), from the program's spans
in the device trace."""
import spans


def read(cell):
    return spans.step_ms(cell, "copy_out")
