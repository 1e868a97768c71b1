"""Miniapp structure, analytic evaluator, PCAST, and fig.5 endpoint bands."""
import numpy as np
import pytest

from repro.core import evaluator as ev
from repro.core import ga, miniapps, pcast
from repro.core import transfer as tr
from repro.core.loopir import LoopClass


# ---------------------------------------------------------------------------
# structure (paper counts)
# ---------------------------------------------------------------------------


def test_himeno_gene_length_is_13():
    prog = miniapps.himeno_program()
    assert prog.gene_length == 13


def test_nasft_has_82_loops_65_offloadable():
    prog = miniapps.nasft_program()
    assert len(prog.loops) == 82
    assert prog.gene_length == 65


def test_himeno_driver_excluded_from_genes():
    prog = miniapps.himeno_program()
    names = [l.name for l in prog.offloadable_loops]
    assert "jacobi_driver" not in names
    assert "jacobi_stencil" in names


def test_programs_validate_wellformed():
    for make in (miniapps.himeno_program, miniapps.nasft_program):
        prog = make()
        assert prog.total_flops() > 0
        # every region name resolves
        for l in prog.loops:
            prog.region_trip(l.parent_seq)


def test_genes_to_offloads_mapping():
    prog = miniapps.himeno_program()
    genes = [0] * prog.gene_length
    genes[prog.gene_length - 1] = 1
    off = prog.genes_to_offloads(genes)
    assert sum(off.values()) == 1


# ---------------------------------------------------------------------------
# analytic evaluator
# ---------------------------------------------------------------------------


def test_cpu_only_time_has_no_transfer_or_accel():
    prog = miniapps.himeno_program()
    bd = ev.predict_time(prog, (0,) * prog.gene_length)
    assert bd.accel_s == 0.0
    assert bd.transfer_s == 0.0
    assert bd.cpu_s > 0.0


def test_kernels_only_masks_non_tight_genes():
    prog = miniapps.nasft_program()
    e = ev.MiniappEvaluator(prog, kernels_only=True)
    genes = (1,) * prog.gene_length
    masked = e.admissible(genes)
    for g, l in zip(masked, prog.offloadable_loops):
        if l.klass != LoopClass.TIGHT:
            assert g == 0
        else:
            assert g == 1


def test_vector_only_loops_run_at_vector_rate():
    prog = miniapps.himeno_program()
    loop = next(l for l in prog.loops if l.klass == LoopClass.VECTOR_ONLY)
    hw = ev.QUADRO_P4000
    t = ev.loop_time(prog, loop, offloaded=True, hw=hw)
    # vector rate bound at least: cannot be faster than kernels-rate time
    t_flops_kernels = loop.total_flops / hw.accel_flops_kernels
    assert t >= t_flops_kernels


def test_offloading_stencil_beats_cpu_only():
    prog = miniapps.himeno_program()
    e = ev.MiniappEvaluator(prog)
    cpu = e((0,) * prog.gene_length)
    all_on = e((1,) * prog.gene_length)
    assert all_on < cpu / 5


# ---------------------------------------------------------------------------
# fig. 5 endpoints (the paper's result bands, via the real GA)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "app,prev_band,prop_band",
    [
        ("himeno", (4.0, 6.5), (12.0, 19.0)),  # paper: 4.8 / 15.4
        ("nasft", (3.5, 6.5), (7.5, 12.5)),  # paper: 5.4 / 10.0
    ],
)
def test_fig5_speedup_bands(app, prev_band, prop_band):
    prog = miniapps.MINIAPPS[app]()
    n = prog.gene_length
    cpu = ev.predict_time(prog, (0,) * n).total_s
    params = ga.GAParams.for_gene_length(n, seed=0)

    prev = ev.MiniappEvaluator(
        prog, tr.TransferMode.NEST, staged=False, kernels_only=True
    )
    r_prev = ga.run_ga(prev, n, params)
    s_prev = cpu / r_prev.best_time_s
    assert prev_band[0] <= s_prev <= prev_band[1], s_prev

    prop = ev.MiniappEvaluator(prog, tr.TransferMode.BULK, staged=True)
    r_prop = ga.run_ga(prop, n, params)
    s_prop = cpu / r_prop.best_time_s
    assert prop_band[0] <= s_prop <= prop_band[1], s_prop
    # the paper's core claim: proposed strictly beats previous
    assert s_prop > s_prev


# ---------------------------------------------------------------------------
# runnable implementations + PCAST
# ---------------------------------------------------------------------------


def test_himeno_pcast_jit_vs_numpy():
    p_j, g_j = miniapps.himeno_run(grid=(9, 9, 17), nn=3, jit_stencil=True)
    p_n, g_n = miniapps.himeno_run(grid=(9, 9, 17), nn=3, jit_stencil=False)
    rep = pcast.compare(
        {"p": p_n, "gosa": np.float32(g_n)},
        {"p": p_j, "gosa": np.float32(g_j)},
    )
    assert rep.ok, rep.describe()


def test_himeno_gosa_decreases():
    _, g3 = miniapps.himeno_run(grid=(9, 9, 17), nn=3)
    _, g12 = miniapps.himeno_run(grid=(9, 9, 17), nn=12)
    assert g12 < g3  # Jacobi converges on this SPD problem


def test_nasft_pcast_jit_vs_numpy():
    s_j = miniapps.nasft_run(grid=(8, 8, 8), niter=2, jit_fft=True)
    s_n = miniapps.nasft_run(grid=(8, 8, 8), niter=2, jit_fft=False)
    rep = pcast.compare({"chk": s_n}, {"chk": s_j})
    assert rep.ok, rep.describe()


@pytest.mark.parametrize("shape", [(8, 8, 8), (16, 16, 16), (2, 64, 256)])
def test_nasft_device_checksum_matches_the_host_checksum(shape):
    """The jitted path's checksum, reduced on the device, sums the same
    1024 samples as the host's; 8^3 and 16^3 wrap the stride-17 indices,
    (2, 64, 256) does not."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    u1 = (rng.standard_normal(shape)
          + 1j * rng.standard_normal(shape)).astype(np.complex64)
    idx = (np.arange(1024) * 17) % u1.size
    host = u1.ravel()[idx].sum() / u1.size
    dev = miniapps._nasft_checksum_jit()(jnp.asarray(u1))
    assert dev.dtype == jnp.complex64 and dev.shape == ()
    np.testing.assert_allclose(complex(dev), complex(host), rtol=1e-6)


# ---------------------------------------------------------------------------
# PCAST itself
# ---------------------------------------------------------------------------


def test_pcast_detects_differences():
    a = {"x": np.ones((4, 4), np.float32)}
    b = {"x": np.ones((4, 4), np.float32) * 1.5}
    rep = pcast.compare(a, b)
    assert not rep.ok
    assert rep.leaves[0].n_mismatch == 16


def test_pcast_dtype_aware_tolerance():
    import jax.numpy as jnp

    a = {"x": np.ones((8,), np.float32)}
    # bf16-level noise passes under bf16 tolerances, fails under f32
    noisy = (np.ones((8,)) * (1 + 5e-3)).astype(np.float32)
    assert not pcast.compare(a, {"x": noisy}).ok
    a16 = {"x": jnp.asarray(np.ones(8), jnp.bfloat16)}
    b16 = {"x": jnp.asarray(np.ones(8) * (1 + 5e-3), jnp.bfloat16)}
    assert pcast.compare(a16, b16).ok


def test_pcast_report_format():
    rep = pcast.compare({"x": np.zeros(3)}, {"x": np.zeros(3)})
    text = rep.describe()
    assert "PASS" in text and "max_rel" in text
