"""NAS FT's inputs as the jitted path makes them: ``u0`` drawn as two
float32 planes and assembled on the device, ``k2`` built on the device.
On power-of-two grids each is bit for bit what the former host
construction made, so the checksums do not move."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import miniapps, pcast


def old_u0(shape):
    """The initial field as it was built on the host, complex temporaries
    and all."""
    rng = np.random.default_rng(314159)
    return (rng.standard_normal(shape) +
            1j * rng.standard_normal(shape)).astype(np.complex64)


def old_k2(shape):
    nz, ny, nx = shape
    kz = np.fft.fftfreq(nz)[:, None, None]
    ky = np.fft.fftfreq(ny)[None, :, None]
    kx = np.fft.fftfreq(nx)[None, None, :]
    return (kx**2 + ky**2 + kz**2).astype(np.float32)


def bits(a):
    a = np.ascontiguousarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


@pytest.mark.parametrize("shape", [(8, 8, 8), (32, 32, 32)])
def test_planes_are_the_old_fields_parts(shape):
    re, im = miniapps._nasft_planes(shape)
    u0 = old_u0(shape)
    assert re.dtype == im.dtype == np.float32
    assert re.flags.c_contiguous and im.flags.c_contiguous
    np.testing.assert_array_equal(bits(re), bits(u0.real))
    np.testing.assert_array_equal(bits(im), bits(u0.imag))


@pytest.mark.parametrize("shape", [(8, 8, 8), (16, 16, 16), (32, 32, 32),
                                   (128, 256, 256), (4096, 2, 4)])
def test_device_k2_is_the_host_k2(shape):
    """Power-of-two sides up to 4096, the longest float32 holds exactly."""
    k2 = np.asarray(miniapps._nasft_k2_jit()(shape))
    assert k2.dtype == np.float32
    np.testing.assert_array_equal(bits(k2), bits(old_k2(shape)))


@pytest.mark.parametrize("grid", [(8, 8, 8), (32, 16, 8), (12, 10, 6),
                                  (16, 16, 12)])
def test_k2_is_built_on_the_device_for_every_grid(monkeypatch, grid):
    """Off power-of-two sides the device build is within one float32
    spacing of the largest values (2**-24) of the host's, and the run
    stays within PCAST's tolerance of the host path."""
    nx, ny, nz = grid
    shape = (nz, ny, nx)
    np.testing.assert_allclose(np.asarray(miniapps._nasft_k2_jit()(shape)),
                               old_k2(shape), rtol=0, atol=2**-24)
    real, built = miniapps._nasft_k2_jit(), []

    def recorded(shape):
        built.append(shape)
        return real(shape)

    monkeypatch.setitem(miniapps._JITTED, "nasft_k2", recorded)
    sums = miniapps.nasft_run(grid, 2)
    assert built == [shape]
    rep = pcast.compare({"chk": miniapps.nasft_run(grid, 2, jit_fft=False)},
                        {"chk": sums})
    assert rep.ok, rep.describe()


def test_tag_names_the_input_placement():
    """The fitness cache is keyed on the tag: a change of where the
    inputs are made changes the times measured, so it changes the tag."""
    fn = miniapps.NasftRunFn(grid=(256, 256, 128), niter=6)
    assert fn.tag == "nasft:256x256x128:it6:chk-dev:u0-dev"


def old_jitted_sums(grid, niter):
    """The jitted path as it was: host ``u0`` and ``k2`` put on the device,
    ``jnp.fft.fftn``, then the same step and device checksum."""
    nx, ny, nz = grid
    shape = (nz, ny, nx)
    ut = jnp.fft.fftn(jnp.asarray(old_u0(shape)))
    k2 = jnp.asarray(old_k2(shape))
    step = miniapps._nasft_step_jit()
    checksum = miniapps._nasft_checksum_jit()
    sums = [checksum(step(ut, k2, jnp.float32(it)))
            for it in range(1, niter + 1)]
    return np.asarray(jnp.stack(sums), np.complex64)


def old_host_sums(grid, niter):
    nx, ny, nz = grid
    shape = (nz, ny, nx)
    ut = np.fft.fftn(old_u0(shape))
    k2 = old_k2(shape)
    sums = []
    for it in range(1, niter + 1):
        u1 = np.fft.ifftn(ut * np.exp(-4.0 * np.pi**2 * 1e-2 * it * k2))
        idx = (np.arange(1024) * 17) % u1.size
        sums.append(complex(u1.ravel()[idx].sum() / u1.size))
    return np.asarray(sums, np.complex64)


@pytest.mark.parametrize("jit_fft, old", [(True, old_jitted_sums),
                                          (False, old_host_sums)])
def test_checksums_are_the_old_constructions(jit_fft, old):
    sums = miniapps.nasft_run((16, 16, 16), 6, jit_fft=jit_fft)
    assert sums.shape == (6,)
    np.testing.assert_array_equal(bits(sums), bits(old((16, 16, 16), 6)))
