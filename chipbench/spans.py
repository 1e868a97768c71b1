"""The runnable programs' own host spans in a device trace, per traced
request.

``himeno_run`` and ``nasft_run`` wrap each host step in a
``TraceAnnotation`` named ``<program>.<step>`` (``himeno.copy_in``,
``nasft.checksum``, ...), where ``<program>`` is the ``program`` that the
cell's configuration names. The profiler writes them on the host plane,
on the clock of the chip's operations, so the time of a step is the
summed duration of its spans inside the traced window, and the idle time
no span explains is what is left of the requests once the chip's busy
time and every program span are taken out. A trace that holds none of
the spans asked for reads ``None``: the program under test writes none.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

from tracing import HOST_PLANE, clip, gaps, merge


def spans(trace, program: str, step: Optional[str] = None
          ) -> List[Tuple[float, float]]:
    """(start, end) in ns of ``program``'s spans inside the traced window;
    only those of ``step`` where one is given."""
    name = re.compile(re.escape(program) + r"\."
                      + (re.escape(step) if step else r"\w+") + "$")
    return [(e.start_ns, e.end_ns) for e in trace.events
            if e.plane == HOST_PLANE and name.match(e.name)
            and trace.lo <= e.start_ns and e.end_ns <= trace.hi]


def step_ms(cell, step: str) -> Optional[float]:
    """Milliseconds per traced request spent in ``<program>.<step>``."""
    trace = cell.device_trace
    found = spans(trace, cell.config["program"], step) \
        if trace is not None else []
    if not found:
        return None
    return sum(e - s for s, e in found) / 1e6 / len(trace.requests)


def idle_unspanned_ms(cell) -> Optional[float]:
    """Milliseconds per traced request in which the first chip ran no
    operation and no program span was open: the idle time that only the
    request's own annotation names."""
    trace = cell.device_trace
    found = spans(trace, cell.config["program"]) \
        if trace is not None else []
    if not found:
        return None
    busy = trace.busy[sorted(trace.busy)[0]] if trace.busy else []
    covered = merge(list(busy) + found)
    idle = sum(e - s
               for lo, hi in merge((r.start_ns, r.end_ns)
                                   for r in trace.requests)
               for s, e in gaps(clip(covered, lo, hi), lo, hi))
    return idle / 1e6 / len(trace.requests)
