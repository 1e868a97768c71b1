"""device_idle.placed: the share of the traced placed runs in which no
operation ran on the chip, 1 - busy / window."""


def read(cell):
    tr = cell.device_trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.idle_share
