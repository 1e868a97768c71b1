"""idle_unspanned_ms: milliseconds per traced request in which the first
chip ran no operation and no program span was open on the host: the idle
time still charged to the request alone (the program's return, and the
harness's own work inside the request). Listed for nasft-A.placed only:
in a traced Himeno request most of it is the harness copying a kept
answer into a fresh slot, which would hide the program's share."""
import spans


def read(cell):
    return spans.idle_unspanned_ms(cell)
