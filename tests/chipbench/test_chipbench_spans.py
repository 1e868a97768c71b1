"""The per-layer metrics read from the runnable programs' own host spans
(``chipbench/spans.py`` and its readers), on traces built by hand and on
two recorded with the spans on a TPU v5 lite
(``traces/<cell>.spans.events.json.gz``)."""
import dataclasses
import gzip
import json
import os
import types

import pytest

import run
from test_chipbench_trace import DEV0, HOST, ev, requests
from tracing import DeviceTrace, Event

METRICS = ("host_init_ms", "copy_in_ms", "copy_out_ms", "gosa_sync_ms",
           "idle_unspanned_ms")


def read(name, trace, program="himeno"):
    """What metric ``name`` reads from ``trace`` in a cell whose
    configuration runs ``program``."""
    cell = types.SimpleNamespace(device_trace=trace,
                                 config={"program": program})
    return run.load_module("metrics", name).read(cell)


def himeno_request(t0, init_ms):
    """One Himeno request from ``t0``: init, copy in, two sweeps each with
    its op on the chip and its sync, copy out; then nothing spanned to
    the request's end."""
    t = t0 + init_ms
    out = [ev(HOST, "python3", "himeno.init", t0, init_ms),
           ev(HOST, "python3", "himeno.copy_in", t, 10)]
    t += 10
    for _ in range(2):
        out += [ev(HOST, "python3", "himeno.sweep", t, 1),
                ev(DEV0, "XLA Ops", "fusion", t + 1, 2),
                ev(HOST, "python3", "himeno.gosa_sync", t + 1, 3)]
        t += 4
    return out + [ev(HOST, "python3", "himeno.copy_out", t, 10)]


def himeno_trace():
    # request 0: spans end at 48 ms, then ops at 45..50 (2 ms past the
    # copy out) and 60..70: 52 - 2 - 10 = 40 ms unspanned and idle.
    # request 1: spans end at 158 ms, nothing after: 42 ms.
    return DeviceTrace(
        requests((0, 100), (100, 100))
        + himeno_request(0, 20) + himeno_request(100, 30)
        + [ev(DEV0, "XLA Ops", "copy", 45, 5),
           ev(DEV0, "XLA Ops", "copy", 60, 10),
           # outside the traced window: left out of every metric
           ev(HOST, "python3", "himeno.init", 250, 10),
           ev(HOST, "python3", "himeno.copy_out", 199, 5)])


def test_each_metric_reads_ms_per_request():
    trace = himeno_trace()
    assert read("host_init_ms", trace) == pytest.approx((20 + 30) / 2)
    assert read("copy_in_ms", trace) == pytest.approx(10)
    assert read("gosa_sync_ms", trace) == pytest.approx(2 * 3)
    assert read("copy_out_ms", trace) == pytest.approx(10)
    assert read("idle_unspanned_ms", trace) == pytest.approx((40 + 42) / 2)


def test_spans_outside_the_window_are_left_out():
    trace = himeno_trace()
    inside = read("host_init_ms", trace)
    more = DeviceTrace(trace.events + [
        ev(HOST, "python3", "himeno.init", -30, 20),
        ev(HOST, "python3", "himeno.init", 210, 20)])
    assert read("host_init_ms", more) == pytest.approx(inside)


def test_unspanned_idle_leaves_out_busy_and_spanned_time():
    base = [ev(HOST, "python3", "nasft.init", 0, 10)]
    # 10 ms of the request spanned, 90 ms idle and unspanned
    assert read("idle_unspanned_ms", DeviceTrace(requests((0, 100)) + base),
                "nasft") == pytest.approx(90)
    # an op inside the span takes nothing more away; one outside does,
    # and a second chip is not read
    trace = DeviceTrace(requests((0, 100)) + base + [
        ev(DEV0, "XLA Ops", "fusion", 5, 10),
        ev(DEV0, "XLA Ops", "fusion", 50, 20),
        ev("/device:TPU:1", "XLA Ops", "fusion", 80, 20)])
    assert read("idle_unspanned_ms", trace, "nasft") \
        == pytest.approx(100 - 15 - 20)
    # a span and an op that overlap are counted once
    trace = DeviceTrace(requests((0, 100)) + base + [
        ev(HOST, "python3", "nasft.copy_out", 40, 20),
        ev(DEV0, "XLA Ops", "fusion", 50, 20)])
    assert read("idle_unspanned_ms", trace, "nasft") \
        == pytest.approx(100 - 10 - 30)


def test_the_readers_take_nasft_spans_too():
    trace = DeviceTrace(requests((0, 50)) + [
        ev(HOST, "python3", "nasft.init", 0, 10),
        ev(HOST, "python3", "nasft.copy_in", 10, 5),
        ev(HOST, "python3", "nasft.copy_out", 20, 10),
        ev(HOST, "python3", "nasft.checksum", 30, 1),
        ev(HOST, "python3", "nasft.copy_out", 31, 10)])
    assert read("host_init_ms", trace, "nasft") == pytest.approx(10)
    assert read("copy_in_ms", trace, "nasft") == pytest.approx(5)
    assert read("copy_out_ms", trace, "nasft") == pytest.approx(20)
    assert read("gosa_sync_ms", trace, "nasft") is None  # Himeno's alone
    assert read("idle_unspanned_ms", trace, "nasft") \
        == pytest.approx(50 - 15 - 21)


def test_a_cell_reads_the_spans_of_its_own_program_alone():
    """The prefix comes from the cell's configuration: another program's
    spans are not read, and a program the helper was never told of is."""
    trace = himeno_trace()
    for name in METRICS:
        assert read(name, trace, "nasft") is None, name
    renamed = DeviceTrace([
        dataclasses.replace(e, name=e.name.replace("himeno.", "hetero."))
        for e in trace.events])
    for name in METRICS:
        assert read(name, renamed, "hetero") == pytest.approx(
            read(name, trace, "himeno")), name
        assert read(name, renamed, "himeno") is None, name


def test_a_trace_with_no_program_span_reads_none():
    trace = DeviceTrace(requests((0, 10)) + [
        ev(HOST, "python3", "himeno_init", 0, 4),      # not a program span
        ev(HOST, "python3", "np.asarray(jax.Array)", 5, 2),
        ev(DEV0, "XLA Ops", "himeno.init", 1, 1),      # not on the host
        ev(DEV0, "XLA Ops", "fusion", 4, 5)])
    for name in METRICS:
        for program in ("himeno", "nasft"):
            assert read(name, trace, program) is None, name
            assert read(name, None, program) is None, name


def recorded(workload):
    """Two requests of a cell with the program's spans, recorded on a TPU
    v5 lite by ``record_trace.py --requests 2``, kept to the chip's lines
    and the Python thread's."""
    path = os.path.join(os.path.dirname(__file__), "traces",
                        f"{workload}.spans.events.json.gz")
    with gzip.open(path, "rt", encoding="utf-8") as fh:
        return DeviceTrace([Event(*e) for e in json.load(fh)])


def span_counts(trace, program):
    out = {}
    for e in trace.events:
        if e.name.startswith(program + "."):
            out[e.name] = out.get(e.name, 0) + 1
    return out


def test_a_recorded_himeno_trace_with_spans():
    trace = recorded("himeno-M.placed")
    assert span_counts(trace, "himeno") == {
        "himeno.init": 2, "himeno.copy_in": 2, "himeno.sweep": 40,
        "himeno.gosa_sync": 40, "himeno.copy_out": 2}
    assert read("host_init_ms", trace) == pytest.approx(66.285844, rel=1e-6)
    assert read("copy_in_ms", trace) == pytest.approx(6.3185395, rel=1e-6)
    assert read("copy_out_ms", trace) == pytest.approx(3.0638145, rel=1e-6)
    assert read("gosa_sync_ms", trace) == pytest.approx(68.255498, rel=1e-6)
    assert read("idle_unspanned_ms", trace) == pytest.approx(174.128461,
                                                             rel=1e-6)
    # each sync waits out its sweep on the chip
    _, seconds = trace.kernel("sweep")
    assert read("gosa_sync_ms", trace) > 1e3 * seconds / len(trace.requests)
    # the start of each request is named by the program's init; the
    # longest gaps, at each request's end, lie after its last span
    names = [name for name, _ in trace.idle_gaps()]
    assert names[:4] == ["request (no finer host span)"] * 2 \
        + ["himeno.init"] * 2


def test_a_recorded_nasft_trace_with_spans():
    trace = recorded("nasft-A.placed")
    assert span_counts(trace, "nasft") == {
        "nasft.init": 2, "nasft.copy_in": 2, "nasft.fft": 2,
        "nasft.step": 12, "nasft.copy_out": 12, "nasft.checksum": 12}
    assert read("host_init_ms", trace, "nasft") == pytest.approx(
        839.166909, rel=1e-6)
    assert read("copy_in_ms", trace, "nasft") == pytest.approx(
        3.23484, rel=1e-6)
    # one copy of the twelve met a 3.3 s stall of the host
    assert read("copy_out_ms", trace, "nasft") == pytest.approx(
        3963.321425, rel=1e-6)
    assert read("gosa_sync_ms", trace, "nasft") is None
    assert read("idle_unspanned_ms", trace, "nasft") == pytest.approx(
        7.714565, rel=1e-6)
    # every long gap is named by a program span: the copies of u1, and
    # the init at the start of each request
    names = [name for name, _ in trace.idle_gaps()]
    assert sorted(set(names)) == ["nasft.copy_out", "nasft.init"]
    assert names.count("nasft.init") == 2
