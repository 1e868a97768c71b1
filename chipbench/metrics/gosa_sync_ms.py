"""gosa_sync_ms: host milliseconds per traced request spent in Himeno's
``float(gosa)`` after each sweep (``himeno.gosa_sync``): the wait for the
sweep to finish on the chip and the copy of its residual, from the
program's spans in the device trace."""
import spans


def read(cell):
    return spans.step_ms(cell, "gosa_sync")
