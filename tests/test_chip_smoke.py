"""chip_smoke.py: refuses anything but a TPU, and its phases pass at toy
size on the CPU (Pallas kernels interpreted), so a chip call only has the
chip left to find wrong."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from repro.offload import OffloadResult

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_script(path, cwd, out):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, path, "--out", str(out)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _result_lines(stdout):
    return [l for l in stdout.splitlines() if l.startswith("{")]


def test_chip_smoke_refuses_the_cpu(tmp_path):
    p = _run_script(SCRIPT, ROOT, tmp_path / "out")
    assert p.returncode != 0
    assert _result_lines(p.stdout) == []
    assert "[device] FAIL" in p.stdout and "'cpu'" in p.stdout
    assert not (tmp_path / "out").exists()  # no phase ran


def test_chip_smoke_alone_fails(tmp_path):
    """Without the rest of the checkout there is no program to drive."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SCRIPT, alone / "chip_smoke.py")
    p = _run_script(str(alone / "chip_smoke.py"), str(alone),
                    tmp_path / "out")
    assert p.returncode != 0
    assert _result_lines(p.stdout) == []


@pytest.mark.parametrize("program", ["himeno", "nasft"])
def test_chip_smoke_measured_phase_at_toy_size(tmp_path, program, capsys):
    smoke = _load()
    row = smoke.phase_measured(program, str(tmp_path), scale="small")
    assert row["penalized"] == 0 and row["pcast_ok"]
    assert row["best_s"] > 0 and row["baseline_s"] > 0
    printed = capsys.readouterr().out
    assert "PCAST PASS" in printed and "on ['cpu']" in printed
    art = OffloadResult.load(str(tmp_path / f"{program}.offload.json"))
    assert art.stage("analyze").payload["device"]["platform"] == "cpu"


def test_chip_smoke_kernel_phase_at_toy_size(capsys):
    rows = _load().phase_kernels(scale="small")
    assert [r["kernel"] for r in rows] == \
        ["flash_attention", "ssd_scan", "gather_rows"]
    assert all(r["ok"] and r["interpret"] for r in rows)  # CPU: interpreted
    assert capsys.readouterr().out.count("interpret=true") == 3


def test_chip_smoke_service_phase_at_toy_size(tmp_path, capsys):
    jobs = _load().phase_service(str(tmp_path), scale="small")
    assert len(jobs) == 2 and all(j.state == "done" for j in jobs)
    assert "duplicate coalesced onto" in capsys.readouterr().out
