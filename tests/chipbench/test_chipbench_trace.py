"""The reduction from a device trace to the per-layer metrics, and the
roofline arithmetic, on traces built by hand and on two recorded on a
TPU v5 lite (``traces/``)."""
import gzip
import json
import os
import types

import pytest

import roofline
import run
from tracing import DeviceTrace, Event, gaps, merge

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
MS = 1e6  # nanoseconds


def ev(plane, line, name, start_ms, dur_ms):
    return Event(plane, line, name, start_ms * MS, dur_ms * MS)


def requests(*spans):
    return [ev(HOST, "python3", f"cell request {i}", s, d)
            for i, (s, d) in enumerate(spans)]


def test_busy_is_a_union_and_idle_its_complement():
    trace = DeviceTrace(requests((0, 10)) + [
        ev(DEV0, "XLA Ops", "fusion.1", 1, 2),
        ev(DEV0, "XLA Ops", "fusion.2", 2, 2),    # overlaps: 1..4
        ev(DEV0, "XLA Ops", "copy.3", 6, 1),
        ev(DEV0, "XLA Ops", "fusion.1", 9, 3),    # clipped at 10
        ev(DEV0, "XLA Ops", "fusion.1", 20, 5),   # outside the window
    ])
    assert trace.window_s == pytest.approx(0.010)
    assert trace.busy_s == pytest.approx(0.003 + 0.001 + 0.001)
    assert trace.idle_share == pytest.approx(0.5)


def test_busy_is_averaged_over_the_chips_that_ran():
    trace = DeviceTrace(requests((0, 10)) + [
        ev(DEV0, "XLA Ops", "a", 0, 4), ev(DEV1, "XLA Ops", "a", 0, 2)])
    assert trace.busy_s == pytest.approx(0.003)


def test_a_trace_with_no_device_operation_is_idle():
    trace = DeviceTrace(requests((0, 10)))
    assert trace.busy_s == 0.0 and trace.idle_share == 1.0
    assert trace.breakdown()["idle_gaps"][0][0] == "no device operation"


def test_a_trace_without_requests_is_refused():
    with pytest.raises(ValueError):
        DeviceTrace([ev(DEV0, "XLA Ops", "a", 0, 1)])


def test_merge_and_gaps():
    assert merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_kernel_time_by_program_name():
    trace = DeviceTrace(requests((0, 100)) + [
        ev(DEV0, "XLA Modules", "jit_sweep(12)", 1, 1.5),
        ev(DEV0, "XLA Modules", "jit_sweep(12)", 5, 2.5),
        ev(DEV0, "XLA Modules", "jit_sweep_other(3)", 9, 7),
        ev(DEV0, "XLA Modules", "jit_step(4)", 20, 4),
    ])
    calls, seconds = trace.kernel("sweep")
    assert calls == 2 and seconds == pytest.approx(0.004)
    assert trace.kernel("step") == (1, pytest.approx(0.004))
    assert trace.kernel("absent") == (0, 0.0)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    trace = DeviceTrace(requests((0, 10)) + [
        ev(HOST, "python3", "himeno_run", 0, 10),
        ev(HOST, "python3", "himeno_init", 0, 4),
        ev(HOST, "python3", "np.stack", 1, 1),    # covers part only
        ev(DEV0, "XLA Ops", "fusion", 4, 5),
    ])
    gaps_ = trace.idle_gaps()
    assert gaps_[0] == ["himeno_init", pytest.approx(0.004)]
    assert gaps_[1] == ["himeno_run", pytest.approx(0.001)]
    ops = trace.top_ops()
    assert ops == [["fusion", pytest.approx(0.005)]]


def test_idle_gaps_are_cut_where_a_request_begins_or_ends():
    trace = DeviceTrace(requests((0, 10), (10, 10)) + [
        ev(HOST, "python3", "np.asarray(jax.Array)", 7.5, 2.5),
        ev(DEV0, "XLA Ops", "fusion", 2, 5),
        ev(DEV0, "XLA Ops", "fusion", 14, 2),
    ])
    # the hole from 7 to 14 spans the end of one request and the start
    # of the next: each part is named within its own request, by a host
    # event that overlaps half of it or more
    assert trace.idle_gaps() == [
        ["request (no finer host span)", pytest.approx(0.004)],
        ["request (no finer host span)", pytest.approx(0.004)],
        ["np.asarray(jax.Array)", pytest.approx(0.003)],
        ["request (no finer host span)", pytest.approx(0.002)],
    ]


def test_the_sweep_counts_and_its_roofline():
    work = roofline.himeno_sweep((128, 128, 256))
    cells, interior = 128 * 128 * 256, 126 * 126 * 254
    assert work["bytes"] == 4 * (2 * cells + 12 * interior)
    assert work["flops"] == 34 * interior
    peaks = run.load_json(run.HERE, "peaks.json")
    peak = roofline.peak_for(peaks, "TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["bf16_flops_per_s"] == 197e12
    least, bound = roofline.least_time_s(work, peak)
    assert bound == "hbm"
    assert least == pytest.approx(work["bytes"] / 819e9)


def test_an_unknown_device_kind_is_an_error():
    peaks = run.load_json(run.HERE, "peaks.json")
    with pytest.raises(KeyError):
        roofline.peak_for(peaks, "TPU v9 imaginary")


def test_the_roofline_reader_on_a_made_up_trace():
    grid = (128, 128, 256)
    least, _ = roofline.least_time_s(
        roofline.himeno_sweep(grid),
        run.load_json(run.HERE, "peaks.json")["TPU v5 lite"])
    per_call_ms = 4 * least * 1e3  # a quarter of the roofline
    trace = DeviceTrace(requests((0, 100)) + [
        ev(DEV0, "XLA Modules", "jit_sweep(1)", 10 * k, per_call_ms)
        for k in range(3)])
    cell = types.SimpleNamespace(
        device_trace=trace, device={"kind": "TPU v5 lite"},
        config={"grid": list(grid)},
        peaks=run.load_json(run.HERE, "peaks.json"))
    share = run.load_module("metrics", "stencil_roofline").read(cell)
    assert share == pytest.approx(25.0)
    cell.device = {"kind": "cpu"}
    with pytest.raises(KeyError):
        run.load_module("metrics", "stencil_roofline").read(cell)
    cell.device_trace = None
    assert run.load_module("metrics", "stencil_roofline").read(cell) is None


def test_every_metric_has_a_reader_and_every_cell_reports_setup():
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.load_module("metrics", m["name"]).read)
    for w in bench["workloads"]:
        e2e = [m["name"] for m in bench["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2, (w["name"], e2e)
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in bench["per_layer"])
    json.dumps(bench)


def recorded(workload):
    """Two requests of a cell on a TPU v5 lite, as ``record_trace.py
    --requests 2`` wrote them; NAS FT's kept to the chip's lines and the
    Python thread's."""
    path = os.path.join(os.path.dirname(__file__), "traces",
                        f"{workload}.events.json.gz")
    with gzip.open(path, "rt", encoding="utf-8") as fh:
        return DeviceTrace([Event(*e) for e in json.load(fh)])


def test_a_recorded_himeno_trace():
    trace = recorded("himeno-M.placed")
    assert list(trace.busy) == ["/device:TPU:0"]
    assert trace.kernel("sweep") == (40, pytest.approx(0.0606467, rel=1e-5))
    assert trace.kernel("step") == (0, 0.0)
    assert trace.idle_share == pytest.approx(0.905870, rel=1e-5)
    cell = types.SimpleNamespace(
        device_trace=trace, device={"kind": "TPU v5 lite"},
        config=run.load_json(run.HERE, "configs", "himeno-M.json"),
        peaks=run.load_json(run.HERE, "peaks.json"))
    share = run.load_module("metrics", "stencil_roofline").read(cell)
    assert share == pytest.approx(18.6539, rel=1e-4)
    # each request opens with ~0.23 s of host set-up and copies in
    gaps_ = trace.idle_gaps()
    assert [round(s, 2) for _, s in gaps_[:2]] == [0.24, 0.23]


def test_a_recorded_nasft_trace():
    trace = recorded("nasft-A.placed")
    cell = types.SimpleNamespace(device_trace=trace)
    ms = run.load_module("metrics", "ft_step_ms").read(cell)
    assert trace.kernel("step")[0] == 12 and ms == pytest.approx(2.5154, 1e-4)
    assert trace.idle_share > 0.99
    # each of a request's six iterations waits ~0.36 s on the copy of u1
    # to the host
    names = [name for name, _ in trace.idle_gaps()]
    assert names[2:] == ["np.asarray(jax.Array)"] * 8
