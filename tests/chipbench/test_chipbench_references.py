"""The plain references agree with the program's host path at toy grids,
and the lower-precision controls fail the configured limits there."""
import math

import numpy as np
import pytest

import run
from references import himeno as href
from references import nasft as nref
from repro.core import miniapps


@pytest.mark.parametrize("grid,nn", [((9, 9, 17), 2), ((17, 17, 33), 4)])
def test_himeno_reference_agrees_with_the_host_path(grid, nn):
    ref = href.solve(grid, nn, 0.8)
    p, gosa = miniapps.himeno_run(grid, nn, jit_stencil=False)
    got = href.compare({"p": p, "gosa": gosa}, ref)
    assert got["p_err"] <= 1e-6 and got["gosa_err"] <= 1e-6, got


@pytest.mark.parametrize("grid,niter", [((8, 8, 8), 2), ((16, 16, 16), 3)])
def test_nasft_reference_agrees_with_the_host_path(grid, niter):
    ref = nref.solve(grid, niter, 1e-2, 314159, 1024, 17)
    got = nref.compare(
        {"checksums": miniapps.nasft_run(grid, niter, jit_fft=False)}, ref)
    assert got["chk_err"] <= 1e-6, got


def test_himeno_initial_state_is_the_published_initmt():
    s = href.initial_state((5, 4, 3))
    assert np.allclose(s["p"][:, 0, 0], (np.arange(5) / 4.0) ** 2)
    assert (s["a"][:3] == 1).all() and np.allclose(s["a"][3], 1 / 6)
    assert (s["b"] == 0).all() and (s["c"] == 1).all()
    assert (s["bnd"] == 1).all() and (s["wrk1"] == 0).all()


def test_an_unchanged_pressure_reads_one():
    ref = href.solve((9, 9, 17), 2, 0.8)
    got = href.compare({"p": ref["p0"], "gosa": ref["gosa"]}, ref)
    assert got["p_err"] == pytest.approx(1.0) and got["gosa_err"] == 0.0


def test_a_nan_answer_never_passes():
    ref = href.solve((9, 9, 17), 2, 0.8)
    p = ref["p"].copy()
    p[4, 4, 8] = np.nan
    got = href.compare({"p": p, "gosa": ref["gosa"]}, ref)
    assert math.isnan(got["p_err"]) and not got["p_err"] <= 1.0


@pytest.mark.parametrize("config", ["himeno-M", "nasft-A"])
def test_the_control_fails_the_configured_limits(config, small):
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config)
    cfg = dict(run.load_json(run.ROOT, entry["file"]), **small[config])
    ref_mod = run.load_module("references", cfg["reference"])
    control = run.load_module("controls", cfg["reference"]).Control(cfg)
    got = ref_mod.compare(control.run(True), ref_mod.reference(cfg))
    assert any(not v <= cfg["limits"][k] for k, v in got.items()), got
