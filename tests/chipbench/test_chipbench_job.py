"""The job cell (``drivers/job.py``) driven at a toy size on the CPU: cold
measured offload jobs whose own answers are compared, which come out not
correct when the sweep is broken or the control takes the program's
place; and the offload pipeline's readers on job traces, one of them
recorded on a TPU v5 lite.

The host path is slowed in these runs, so that the search places the hot
loop on the jitted path as it does on the chip, where that path is about
fifteen times faster: the answer compared is the jitted path's."""
import dataclasses
import gzip
import json
import os
import time
import types

import pytest

import run
from repro.core import miniapps
from repro.offload import result as result_mod
from repro.offload.result import StageFailure
from test_chipbench_cells import SEED, altered_sweep, stuck_sweep
from test_chipbench_trace import DEV0, HOST, ev, requests
from tracing import DeviceTrace, Event

CELL = "himeno-M.job"
# the search's configuration runs Himeno M, so it takes Himeno M's toy size
TOY = "himeno-M"
READERS = ("job_analyze_ms", "job_search_ms", "job_verify_ms",
           "job_measure_ms", "job_measurements")
SMALL = {"measured_scale": "small"}


@pytest.fixture
def slow_host(monkeypatch):
    real = miniapps._himeno_stencil_np

    def stencil(s):
        time.sleep(0.02)
        return real(s)
    monkeypatch.setattr(miniapps, "_himeno_stencil_np", stencil)


def job_run(small, seconds=0.0, trace=False, **kw):
    return run.run_cell(CELL, SEED, seconds, trace,
                        config=small[TOY], traffic=SMALL, **kw)


def cell_config(workload=CELL):
    """The configuration file that ``BENCHMARK.json`` gives a cell."""
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    name = next(w["config"] for w in bench["workloads"]
                if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == name)
    return run.load_json(run.ROOT, entry["file"])


def jobs_of(err):
    """The job records a run printed to standard error."""
    return [json.loads(line[len("job "):]) for line in err.splitlines()
            if line.startswith("job {")]


def test_a_job_run_is_correct(small, slow_host, capsys):
    line = job_run(small, seconds=0.3)
    assert line["correct"], line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) == {"p_err", "gosa_err"}
    assert {"run_s", "setup_s"} <= set(line["metrics"])
    jobs = jobs_of(capsys.readouterr().err)
    assert len(jobs) == line["attempted"]
    for job in jobs:
        assert job["hot_loop"] == "gpu"
        assert job["measurements"] == 2  # the two placements the fn runs
        assert job["pcast_max_rel"] < 3e-5
        assert set(job["stages_s"]) == {"calibrate", "analyze", "seed",
                                        "search", "verify", "report"}


def test_a_traced_job_run_reads_the_offload_pipeline(small, slow_host,
                                                     capsys):
    line = job_run(small, trace=True)
    assert line["correct"], line
    assert set(line["metrics"]) == set(READERS)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    (job,) = jobs_of(capsys.readouterr().err)[:1]
    # analyze's baseline, each search measurement and verify's re-measure
    # at two repeats, and the PCAST pair
    assert m["job_measurements"] == 2 * (1 + job["measurements"] + 1) + 2
    assert 0 < m["job_measure_ms"] < m["job_analyze_ms"] \
        + m["job_search_ms"] + m["job_verify_ms"]
    for stage in ("analyze", "search", "verify"):
        assert m[f"job_{stage}_ms"] <= 1e3 * job["stages_s"][stage] * 1.01


@pytest.mark.parametrize("fault", [stuck_sweep, altered_sweep])
def test_a_broken_sweep_fails_in_set_up(fault, small, slow_host,
                                        monkeypatch):
    """The warm job's own PCAST check fails, and the run ends there with
    no result line."""
    monkeypatch.setitem(miniapps._JITTED, "himeno_sweep", fault())
    with pytest.raises(StageFailure, match="PCAST"):
        job_run(small)


@pytest.mark.parametrize("fault", [stuck_sweep, altered_sweep])
def test_a_sweep_broken_in_the_window_fails_each_job(fault, small,
                                                     slow_host, monkeypatch,
                                                     tmp_path):
    cell, driver = set_up(small, tmp_path)
    monkeypatch.setitem(miniapps._JITTED, "himeno_sweep", fault())
    run.measure(cell, driver)
    assert cell.failed == cell.attempted > 0  # so the run is not correct
    assert "PCAST" in cell.errors[0] and cell.jobs == []


def test_the_control_in_the_programs_place_is_not_correct(small, slow_host):
    cfg = dict(cell_config(), **small[TOY])
    control = run.load_module("controls", cfg["reference"]).Control(cfg)
    line = job_run(small, program=control)
    assert line["failed"] == 0  # its own PCAST pair agrees with itself
    assert not line["correct"], line


@pytest.mark.parametrize("key", ["program", "run_fn", "run_fn_args",
                                 "grid", "nn", "omega", "dtype",
                                 "reference", "limits"])
def test_the_search_runs_the_placed_cells_program(key):
    """The job cell searches the program that himeno-M.placed runs, at
    its size, and compares with the same reference and limits: a change
    to the placed program shows in both cells."""
    assert cell_config()[key] == cell_config("himeno-M.placed")[key]


def set_up(small, scratch):
    """A job cell and its driver, set up (one warm job) in ``scratch``."""
    cell = run.Cell(CELL, SEED, 0.0, False, config=small[TOY],
                    traffic=SMALL)
    cell.scratch = str(scratch)
    driver = run.load_module("drivers", "job").Driver(cell)
    driver.setup()
    return cell, driver


def test_jobs_are_cold_and_seeded_apart(small, slow_host, tmp_path):
    cell, driver = set_up(small, tmp_path)
    assert cell.jobs == [] and list(tmp_path.iterdir()) == []
    driver.request(0)
    driver.request(1)
    assert list(tmp_path.iterdir()) == []  # no artifact, trace or cache
    seeds = [job["seed"] for job in cell.jobs]
    assert len(seeds) == 2 and seeds[0] != seeds[1]
    assert len(cell.outputs) == 2


def test_setup_refuses_a_search_of_another_size(small):
    with pytest.raises(RuntimeError, match="another size"):
        run.run_cell(CELL, SEED, 0.0, False, config=small[TOY])


def test_setup_refuses_a_result_without_an_answer(small, monkeypatch):
    @dataclasses.dataclass
    class Answerless:
        spec: object

    monkeypatch.setattr(result_mod, "OffloadResult", Answerless)
    with pytest.raises(RuntimeError, match="no answer"):
        job_run(small)


def read(name, trace):
    cell = types.SimpleNamespace(device_trace=trace,
                                 config={"program": "himeno"})
    return run.load_module("metrics", name).read(cell)


def job_request(t0):
    """One job of 100 ms from ``t0``: each stage, the program's runs
    inside them, and a sweep on the chip."""
    out = [ev(HOST, "python3", "offload.calibrate", t0, 1),
           ev(HOST, "python3", "offload.analyze", t0 + 1, 20),
           ev(HOST, "python3", "offload.seed", t0 + 21, 1),
           ev(HOST, "python3", "offload.search", t0 + 22, 40),
           ev(HOST, "python3", "offload.verify", t0 + 62, 30),
           ev(HOST, "python3", "offload.report", t0 + 92, 5),
           ev(DEV0, "XLA Ops", "fusion", t0 + 30, 2)]
    runs = [(2, 9), (12, 8), (25, 15), (42, 3), (46, 3), (64, 3), (68, 3),
            (72, 15), (88, 3)]
    return out + [ev(HOST, "python3", "offload.measure", t0 + s, d)
                  for s, d in runs]


def test_the_readers_read_ms_and_runs_per_job():
    trace = DeviceTrace(requests((0, 100), (100, 100))
                        + job_request(0) + job_request(100)
                        # outside the window: left out
                        + [ev(HOST, "python3", "offload.measure", 250, 5)])
    assert read("job_analyze_ms", trace) == pytest.approx(20)
    assert read("job_search_ms", trace) == pytest.approx(40)
    assert read("job_verify_ms", trace) == pytest.approx(30)
    assert read("job_measure_ms", trace) == pytest.approx(
        9 + 8 + 15 + 3 + 3 + 3 + 3 + 15 + 3)
    assert read("job_measurements", trace) == 9


def test_a_trace_without_offload_spans_reads_none():
    """The placed cells' traces, and a job trace of a program that writes
    no offload span, read nothing."""
    trace = DeviceTrace(requests((0, 10)) + [
        ev(HOST, "python3", "himeno.init", 0, 4),
        ev(HOST, "python3", "offload_measure", 4, 2),   # not the span
        ev(DEV0, "XLA Ops", "offload.measure", 6, 1),   # not on the host
    ])
    for name in READERS:
        assert read(name, trace) is None, name
        assert read(name, None) is None, name


def test_a_recorded_job_trace():
    """One himeno-M.job job recorded on a TPU v5 lite by ``record_trace.py
    --requests 1``, kept to the chip's lines and the Python thread's."""
    path = os.path.join(os.path.dirname(__file__), "traces",
                        "himeno-M.job.spans.events.json.gz")
    with gzip.open(path, "rt", encoding="utf-8") as fh:
        trace = DeviceTrace([Event(*e) for e in json.load(fh)])
    assert read("job_analyze_ms", trace) == pytest.approx(5648.725644,
                                                          rel=1e-6)
    assert read("job_search_ms", trace) == pytest.approx(5883.188437,
                                                         rel=1e-6)
    assert read("job_verify_ms", trace) == pytest.approx(3574.269304,
                                                         rel=1e-6)
    assert read("job_measure_ms", trace) == pytest.approx(14794.984333,
                                                          rel=1e-6)
    assert read("job_measurements", trace) == 10
    # five placed runs on the chip in a 15.2 s job: 20 sweeps each
    calls, _ = trace.kernel("sweep")
    assert calls == 100 and trace.idle_share > 0.99
    # every long idle gap is named by a stage of the pipeline
    names = {name for name, seconds in trace.idle_gaps() if seconds > 0.1}
    assert names == {"offload.analyze", "offload.search", "offload.verify"}
