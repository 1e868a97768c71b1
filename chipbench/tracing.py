"""Device traces: capture with JAX's profiler, and the reduction from a
trace to the numbers the per-layer metrics read.

The profiler writes an XSpace (``*.xplane.pb``): planes, their lines, and
events with a start and a duration in nanoseconds on one clock. A TPU
chip is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per operation the chip ran, and its ``XLA Modules`` line one event
per call of a compiled program, named after the jitted function
(``jit_<name>(<id>)``). The host is ``/host:CPU``; the harness's
``TraceAnnotation`` around each request and the runtime's own events
(transfers, dispatch, ``np.asarray(jax.Array)``) are events on its
threads' lines; the Python tracer is off.

- busy: the union of the operation intervals of a chip inside the traced
  window, averaged over the chips that ran anything;
- window: from the start of the first traced request to the end of the
  last;
- kernel time: the summed durations of one program's calls;
- idle gaps: the holes in a chip's busy union inside the window, cut
  where a request begins or ends, each named by the host event that
  overlaps most of the piece: what the host was doing while the chip
  waited.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the harness names each request's annotation "<cell> request <i>"
REQUEST = re.compile(r" request \d+$")
BREAKDOWN_ROWS = 10


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_xplane(path: str) -> List[Event]:
    """Every event of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [Event(pl.name, ln.name, e.name, float(e.start_ns),
                  float(e.duration_ns))
            for pl in pd.planes for ln in pl.lines for e in ln.events]


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The holes of a merged union inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class DeviceTrace:
    """The reduced trace of a run's traced requests."""

    def __init__(self, events: Sequence[Event]):
        self.events = list(events)
        reqs = [e for e in self.events
                if e.plane == HOST_PLANE and REQUEST.search(e.name)]
        if not reqs:
            raise ValueError("the trace holds no request annotation")
        self.requests = reqs
        self.lo = min(e.start_ns for e in reqs)
        self.hi = max(e.end_ns for e in reqs)
        self.ops: Dict[str, List[Event]] = {}
        for e in self.events:
            if DEVICE_PLANE.match(e.plane) and e.line == OPS_LINE \
                    and e.dur_ns > 0:
                self.ops.setdefault(e.plane, []).append(e)
        self.busy = {
            plane: merge(clip([(e.start_ns, e.end_ns) for e in evs],
                              self.lo, self.hi))
            for plane, evs in self.ops.items()
        }
        self.busy = {p: b for p, b in self.busy.items() if b}

    @classmethod
    def from_file(cls, path: str) -> "DeviceTrace":
        return cls(read_xplane(path))

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips
        that ran one (0 where none did)."""
        if not self.busy:
            return 0.0
        tot = sum(e - s for b in self.busy.values() for s, e in b)
        return tot / len(self.busy) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, name: str) -> Tuple[int, float]:
        """(calls, seconds) of the compiled program ``jit_<name>`` inside
        the window, summed over the chips."""
        pat = re.compile(rf"^jit_{re.escape(name)}(\(|$)")
        calls = [e for e in self.events
                 if DEVICE_PLANE.match(e.plane) and e.line == MODULES_LINE
                 and pat.match(e.name)
                 and self.lo <= e.start_ns and e.end_ns <= self.hi]
        return len(calls), sum(e.dur_ns for e in calls) / 1e9

    def top_ops(self, n: int = BREAKDOWN_ROWS) -> List[list]:
        """The operations that took most device time, per chip."""
        tot: Dict[str, float] = {}
        for evs in self.ops.values():
            for e in evs:
                if self.lo <= e.start_ns and e.end_ns <= self.hi:
                    tot[e.name] = tot.get(e.name, 0.0) + e.dur_ns
        chips = max(len(self.busy), 1)
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / chips / 1e9] for name, ns in rows]

    def host_cover(self, lo: float, hi: float) -> str:
        """What the host was doing over ``[lo, hi]``: the host event that
        overlaps most of it, where one overlaps half or more (the shorter
        on a tie); else the request around it."""
        best, key = None, None
        in_request = False
        for e in self.events:
            if e.plane != HOST_PLANE or e.dur_ns <= 0:
                continue
            over = min(hi, e.end_ns) - max(lo, e.start_ns)
            if over <= 0:
                continue
            if REQUEST.search(e.name):
                whole = e.start_ns <= lo and hi <= e.end_ns
                in_request = in_request or whole
            elif 2 * over >= hi - lo and (key is None
                                          or (over, -e.dur_ns) > key):
                best, key = e, (over, -e.dur_ns)
        if best is not None:
            return best.name
        return "request (no finer host span)" if in_request \
            else "(no host span)"

    def idle_gaps(self, n: int = BREAKDOWN_ROWS) -> List[list]:
        """The longest holes in the first chip's busy time, cut where a
        request begins or ends, each named by what the host was doing."""
        if not self.busy:
            return [["no device operation", self.window_s]]
        plane = sorted(self.busy)[0]
        cuts = sorted({t for e in self.requests
                       for t in (e.start_ns, e.end_ns)})
        pieces = []
        for s, e in gaps(self.busy[plane], self.lo, self.hi):
            edges = [s] + [c for c in cuts if s < c < e] + [e]
            pieces += zip(edges, edges[1:])
        holes = sorted(pieces, key=lambda g: g[0] - g[1])[:n]
        return [[self.host_cover(s, e), (e - s) / 1e9] for s, e in holes]

    def breakdown(self) -> Dict[str, List[list]]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


class Profiler:
    """Starts and stops JAX's profiler into a directory of its own.

    ``keep``, where set, is a path that the raw ``.xplane.pb`` is copied
    to before the directory goes (``record_trace.py`` sets it)."""

    keep: Optional[str] = None

    def __init__(self, scratch: str):
        self.dir = os.path.join(scratch, "profile")

    def start(self) -> None:
        import jax

        # the Python tracer (on by default) would slow the traced
        # requests' host work; the host keeps the annotations and the
        # runtime's own events
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> DeviceTrace:
        import jax

        jax.profiler.stop_trace()
        try:
            (path,) = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                             "*", "*.xplane.pb"))
            if self.keep:
                shutil.copyfile(path, self.keep)
            return DeviceTrace.from_file(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
