"""setup_s: seconds from the start of the process to the start of the
window: imports, device init, compiling (or loading from the persistent
cache) every program the cell uses, and the warm requests."""


def read(cell):
    return cell.setup_s
