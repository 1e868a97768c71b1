"""Each cell driven end to end at a toy size on the CPU, past the
harness's look for a chip: sound runs come out correct, and runs with
the timed path broken underneath, or with the control in the program's
place, come out not correct."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from repro.core import miniapps

CELLS = {"himeno-M.placed": "himeno-M", "nasft-A.placed": "nasft-A"}
SEED = 2 ** 31 + 12345  # larger than 32 signed bits hold


def cell_run(workload, small, seconds=0.3, trace=False, **kw):
    return run.run_cell(workload, SEED, seconds, trace,
                        config=small[CELLS[workload]], **kw)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_sound_run_is_correct(workload, small):
    line = cell_run(workload, small)
    assert line["correct"], line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert "setup_s" in line["metrics"]
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_traced_run_reports_its_layers(workload, small):
    line = cell_run(workload, small, trace=True)
    assert line["correct"], line
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    names = {m["name"] for m in bench["per_layer"]
             if workload in m["workloads"]}
    assert set(line["metrics"]) <= names
    assert any(n.startswith("device_idle") for n in line["metrics"])
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def stuck_sweep():
    """The sweep returns the pressure it was given."""
    real = miniapps._himeno_sweep_jit()

    def sweep(p, *rest):
        return p, real(p, *rest)[1]
    return sweep


def altered_sweep():
    """The sweep's residual is altered where it is produced."""
    real = miniapps._himeno_sweep_jit()

    def sweep(*args):
        p, gosa = real(*args)
        return p, gosa * 1.05
    return sweep


def stuck_step():
    """The FFT step returns the spectrum it was given."""
    return lambda ut, k2, t: ut


def altered_step():
    real = miniapps._nasft_step_jit()
    return lambda ut, k2, t: real(ut, k2, t) * 1.001


@pytest.mark.parametrize("workload,key,fault", [
    ("himeno-M.placed", "himeno_sweep", stuck_sweep),
    ("himeno-M.placed", "himeno_sweep", altered_sweep),
    ("nasft-A.placed", "nasft_step", stuck_step),
    ("nasft-A.placed", "nasft_step", altered_step),
])
def test_a_broken_step_is_not_correct(workload, key, fault, small,
                                      monkeypatch):
    monkeypatch.setitem(miniapps._JITTED, key, fault())
    line = cell_run(workload, small)
    assert not line["correct"], line


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_the_control_in_the_programs_place_is_not_correct(workload, small):
    cfg = dict(run.load_json(run.HERE, "configs",
                             f"{CELLS[workload]}.json"),
               **small[CELLS[workload]])
    control = run.load_module("controls", cfg["reference"]).Control(cfg)
    line = cell_run(workload, small, program=control)
    assert not line["correct"], line


def _cli(cwd, script, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, script, "--workload", "himeno-M.placed",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_the_command_refuses_a_machine_without_a_tpu():
    p = _cli(run.ROOT, os.path.join("chipbench", "run.py"))
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "TPU" in p.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no program to measure."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    for path in bench["paths"]:
        shutil.copytree(os.path.join(run.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path), os.path.join("chipbench", "run.py"))
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]
    json.dumps(bench)
