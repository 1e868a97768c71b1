#!/usr/bin/env python3
"""One benchmark cell, once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. ``BENCHMARK.json`` names the cell; the
cell names its configuration (``chipbench/configs/<config>.json``) and
its traffic (``chipbench/traffic/<traffic>.json``), whose ``kind``
selects the driver (``chipbench/drivers/<kind>.py``). Each metric is read
by ``chipbench/metrics/<metric>.py``, and each configuration names its
plain reference (``chipbench/references/<reference>.py``). A cell, a
traffic mix or a metric is added by adding files and entries; nothing
here names one.

A run: refuse anything but a TPU with as many chips as the cell asks
for; pin the host allocator's thresholds (``host_blocks``);
import the program and turn on its persistent compile cache; let
the driver set up and warm every shape it will use (``setup_s``); then a
closed loop of one caller sends requests back to back until ``--seconds``
have passed, and the window closes when the last request begun inside it
ends. With ``--trace 1`` the profiler records the window's first
``trace_requests`` requests. After the window: the device's peak memory,
then the plain reference and the comparison that decides ``correct``.
The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3  # glibc's mallopt parameters


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    modname = "chipbench_" + "_".join(
        "".join(c if c.isalnum() else "_" for c in part)
        for part in (kind, name))
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts: str):
    with open(os.path.join(*parts), encoding="utf-8") as fh:
        return json.load(fh)


def say(text: str) -> None:
    print(text, file=sys.stderr, flush=True)


class Cell:
    """Everything one run of one cell reads and records.

    Drivers fill ``outputs`` (the answers that ``check`` compares) and
    ``jobs`` (per-request records that the program's own trace readers
    use); the harness fills the clock readings and, with ``--trace 1``,
    the reduced device trace. ``config`` and ``traffic`` entries given
    here replace those read from the files (tests run a cell at a size
    that a CPU holds), and ``program`` replaces the run fn.
    """

    def __init__(self, workload: str, seed: int, seconds: float,
                 trace: bool, bench=None, config=None, traffic=None,
                 program=None):
        self.bench = bench if bench is not None else load_json(
            ROOT, "BENCHMARK.json")
        self.workload = next(w for w in self.bench["workloads"]
                             if w["name"] == workload)
        entry = next(c for c in self.bench["configs"]
                     if c["name"] == self.workload["config"])
        self.config = dict(load_json(ROOT, entry["file"]), **(config or {}))
        self.traffic = dict(load_json(HERE, "traffic",
                                      f"{self.workload['traffic']}.json"),
                            **(traffic or {}))
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.reference = load_module("references", self.config["reference"])
        self.peaks = load_json(HERE, "peaks.json")
        # the run fn drivers call; None is the configuration's own
        # (``chipbench/control.py`` puts the lower-precision control here)
        self.program = program
        self.scratch = ""
        self.device = None          # {"platform", "kind", "count"}
        self.times = []             # seconds of each request in the window
        self.window_s = None
        self.setup_s = None
        self.attempted = 0
        self.failed = 0
        self.errors = []
        self.outputs = []           # sampled answers, for the comparison
        self.jobs = []              # per-request records (job traffic)
        self.device_trace = None    # tracing.DeviceTrace of the traced part

    def rng(self, stream: int):
        """A numpy generator drawn from ``--seed``, one stream per use."""
        import numpy as np

        return np.random.default_rng(
            np.random.SeedSequence([self.seed & (2 ** 64 - 1), stream]))

    def metrics_for(self, group: str):
        """This cell's entries of ``BENCHMARK.json[group]``."""
        name = self.workload["name"]
        return [m for m in self.bench[group]
                if name in m.get("workloads", [name])]


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class Compiles:
    """Counts the programs JAX compiles (or loads from its persistent
    cache) while it is entered, and their seconds."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def _on(self, event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def measure(cell: Cell, driver) -> None:
    """The closed loop of one caller."""
    import jax
    from tracing import Profiler

    traced = int(cell.traffic["trace_requests"]) if cell.trace else 0
    prof = Profiler(cell.scratch) if traced else None
    name = cell.workload["name"]
    try:
        if prof is not None:
            prof.start()
        t0 = end = time.perf_counter()
        i = 0
        while True:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"{name} request {i}"):
                try:
                    driver.request(i)
                except Exception as e:  # noqa: BLE001 — counted, reported
                    cell.failed += 1
                    cell.errors.append(f"request {i}: {e!r}")
                    traceback.print_exc()
            end = time.perf_counter()
            cell.times.append(end - t)
            i += 1
            if prof is not None and i == traced:
                prof, tracing = None, prof
                cell.device_trace = tracing.stop()
            if end - t0 >= cell.seconds and prof is None:
                break
        cell.window_s = end - t0
        cell.attempted = i
    finally:
        if prof is not None:
            prof.stop()


def host_blocks() -> str:
    """Pin glibc's mmap threshold at 32 MiB and its trim threshold at
    64 MiB, the values it reaches by itself in a long-running process.

    Left alone, glibc raises both as the process frees large blocks, and
    then serves a block from memory the heap kept, or maps it fresh, by
    what the process held before: a Himeno M run took 0.11 s or 0.32 s on
    a TPU v5e host by what the harness held. Pinned, a block takes the
    same path in every run; blocks above 32 MiB are mapped fresh, as they
    always are."""
    import ctypes

    try:
        libc = ctypes.CDLL(None)
        ok = libc.mallopt(M_MMAP_THRESHOLD, 32 << 20) and libc.mallopt(
            M_TRIM_THRESHOLD, 64 << 20)
    except (OSError, AttributeError):
        ok = 0
    return "thresholds pinned" if ok else "allocator left as it is"


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             setup_parts=None, **overrides) -> dict:
    """Set up, measure and check one cell in this process; returns the
    result line. The caller has made sure of the chips."""
    for path in (HERE, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    parts = dict(setup_parts or {}, host_allocator=host_blocks())
    cell = Cell(workload, seed, seconds, trace, **overrides)
    cell.scratch = tempfile.mkdtemp(prefix="chipbench-")
    try:
        return _run(cell, parts)
    finally:
        shutil.rmtree(cell.scratch, ignore_errors=True)


def _run(cell: Cell, parts: dict) -> dict:
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    cell.device = device_info()
    cache = enable_compile_cache()
    # every program goes to the cache, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t = time.perf_counter()
    with Compiles() as setup_compiles:
        driver = load_module("drivers", cell.traffic["kind"]).Driver(cell)
        parts["program_import_s"] = time.perf_counter() - t
        driver.setup()
    parts["warm_s"] = time.perf_counter() - t - parts["program_import_s"]
    parts["compile_or_cache_load_s"] = setup_compiles.seconds
    parts["programs_compiled_or_loaded"] = setup_compiles.count
    cell.setup_s = time.perf_counter() - T_START
    say(f"setup_s {cell.setup_s!r} {json.dumps(parts)} "
        f"compile cache {cache}")

    with Compiles() as window_compiles:
        measure(cell, driver)
    say(f"window {cell.window_s!r} s, {cell.attempted} requests, "
        f"{cell.failed} failed; compilations in window: "
        f"{window_compiles.count}")

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())
    t = time.perf_counter()
    rows = driver.check() if cell.attempted > cell.failed else []
    say(f"reference and comparison {time.perf_counter() - t!r} s")
    correct = (cell.attempted > 0 and cell.failed == 0 and bool(rows)
               and all(v <= lim for _, v, lim in rows))  # NaN fails

    device = dict(cell.device, memory_peak_bytes=peak)
    tr = cell.device_trace
    if cell.trace:
        metrics = read_metrics(cell, "per_layer")
        if tr is not None:
            device.update(busy_s=tr.busy_s, window_s=tr.window_s)
    else:
        metrics = read_metrics(cell, "end_to_end")
    line = {"correct": bool(correct), "attempted": cell.attempted,
            "failed": cell.failed, "metrics": metrics, "device": device}
    if tr is not None:
        line["breakdown"] = tr.breakdown()
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for e in cell.errors[:5]:
        say(f"error {e}")
    for n, v, lim in rows:
        say(f"check {n} {v!r} limit {lim!r} {'ok' if v <= lim else 'FAIL'}")
    return line


def read_metrics(cell: Cell, group: str) -> dict:
    out = {}
    for m in cell.metrics_for(group):
        value = load_module("metrics", m["name"]).read(cell)
        if value is None:
            # the cell lists the metric, so a trace or window that holds
            # nothing for it is a fault to see, not a quiet gap
            say(f"metric {m['name']}: nothing to read in this run")
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        say(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    parts = {}
    t = time.perf_counter()
    import jax  # noqa: F401

    parts["jax_import_s"] = time.perf_counter() - t
    t = time.perf_counter()
    dev = device_info()
    parts["device_init_s"] = time.perf_counter() - t
    if dev["platform"] != "tpu" or dev["count"] < cell["chips"]:
        say(f"JAX found {dev['count']} {dev['platform']} device(s) "
            f"({dev['kind']}); the cell needs {cell['chips']} TPU chip(s). "
            "This benchmark measures the chip and never falls back.")
        return 2
    line = run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace), setup_parts=parts, bench=bench)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
