"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The TPU compiler ships with libtpu, so each test lowers a jitted step or
Pallas kernel of the main path at its real size and compiles it for one
chip of a ``v5e:2x2`` topology. A compile that passes here is not a chip
run — nothing executes — but it refuses what interpret mode cannot: block
shapes that cut the (8, 128) tiling, loads from HBM that need a DMA, and
programs that do not fit the chip's memory.

The topology is described inside a module fixture (never at import):
only one process may load libtpu, and that process keeps it until it
exits. The persistent compilation cache is off around these compiles —
an entry written for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import miniapps
from repro.kernels import ops
from repro.offload import programs

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any reason it can't be built
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)``: an abstract argument placed on one chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
    return compiled


def test_himeno_sweep_compiles_for_v5e(shape):
    """The measured path's jitted Jacobi sweep at the grid it times."""
    grid = programs.measured_run_fn("himeno", "model").grid
    assert grid == (128, 128, 256)
    f32 = jnp.float32
    compiled = _compile(
        miniapps._himeno_sweep_jit(),
        shape(grid, f32), shape((4,) + grid, f32), shape((3,) + grid, f32),
        shape((3,) + grid, f32), shape(grid, f32), shape(grid, f32),
    )
    # p, a, b, c, bnd, wrk1: 13 float32 planes of 128x128x256
    assert compiled.memory_analysis().argument_size_in_bytes == \
        13 * 4 * 128 * 128 * 256


def test_nasft_step_compiles_for_v5e(shape):
    """The measured path's jitted evolve + inverse FFT at class A."""
    nx, ny, nz = programs.measured_run_fn("nasft", "model").grid
    assert (nx, ny, nz) == (256, 256, 128)
    compiled = _compile(
        miniapps._nasft_step_jit(),
        shape((nz, ny, nx), jnp.complex64), shape((nz, ny, nx), jnp.float32),
        shape((), jnp.float32),
    )
    assert "fft" in compiled.as_text().lower()


def test_nasft_checksum_compiles_for_v5e(shape):
    """The device checksum gathers its 1024 samples from the class A
    field in place: no copy of the field."""
    nx, ny, nz = programs.measured_run_fn("nasft", "model").grid
    compiled = _compile(miniapps._nasft_checksum_jit(),
                        shape((nz, ny, nx), jnp.complex64))
    assert "gather" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_flash_attention_compiles_for_v5e(shape):
    """B=1, S=4096, 32 heads of 128 in bf16: a long-context layer."""
    q = shape((1, 4096, 32, 128), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            impl="pallas"),
        q, q, q,
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_for_v5e(shape):
    """mamba2-1.3b widths: 64 heads of P=64, state N=128, chunk 256."""
    B, S, H, P, N = 1, 4096, 64, 64, 128
    compiled = _compile(
        lambda x, dt, A, b, c: ops.ssd_scan(x, dt, A, b, c, chunk=256,
                                            impl="pallas"),
        shape((B, S, H, P), jnp.bfloat16), shape((B, S, H), jnp.float32),
        shape((H,), jnp.float32), shape((B, S, N), jnp.bfloat16),
        shape((B, S, N), jnp.bfloat16),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_gather_rows_compiles_for_v5e(shape):
    """moonshot-v1-16b-a3b dispatch: 4096 tokens of d_model 2048, top-6,
    forward and backward, in both the 32-bit and the packed bf16 path."""
    G, N, d, k = 1, 4096, 2048, 6
    idx = shape((G, N * k), jnp.int32)
    for dtype in (jnp.float32, jnp.bfloat16):
        def loss(src, out_idx, inv_idx):
            y = ops.moe_permute(src, out_idx, inv_idx, k, False, "pallas")
            return y.astype(jnp.float32).sum()

        fwd = _compile(
            lambda s, o, i: ops.moe_permute(s, o, i, k, False, "pallas"),
            shape((G, N, d), dtype), idx, idx,
        )
        bwd = _compile(jax.grad(loss), shape((G, N, d), dtype), idx, idx)
        assert "tpu_custom_call" in fwd.as_text()
        assert "tpu_custom_call" in bwd.as_text()
