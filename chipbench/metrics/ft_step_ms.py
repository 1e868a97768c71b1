"""ft_step_ms: device milliseconds per call of the NAS FT evolve + IFFT
program (``jit_step``), from the trace."""


def read(cell):
    tr = cell.device_trace
    if tr is None:
        return None
    calls, seconds = tr.kernel("step")
    if not calls:
        return None
    return 1e3 * seconds / calls
