"""Pallas stencil kernel vs. pure-jnp oracle: shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.stencils import STENCILS
from repro.kernels.stencil import ops
from repro.kernels.stencil.ref import stencil_ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("name", ["j2d5pt", "j2d9pt", "j2d9pt-gol"])
@pytest.mark.parametrize("shape", [(16, 128), (24, 136), (64, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stencil_2d(name, shape, dtype):
    spec = STENCILS[name]
    r = spec.radius
    grid = jnp.asarray(RNG.standard_normal((shape[0] + 2 * r, shape[1] + 2 * r)),
                       dtype=dtype)
    got = ops.apply(grid, spec, tile=(8, 128), interpret=True)
    want = stencil_ref(grid, spec)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("name", ["j3d7pt", "j3d27pt"])
@pytest.mark.parametrize("shape", [(8, 8, 128), (10, 20, 130)])
def test_stencil_3d(name, shape):
    spec = STENCILS[name]
    r = spec.radius
    grid = jnp.asarray(
        RNG.standard_normal(tuple(s + 2 * r for s in shape)), dtype=jnp.float32)
    got = ops.apply(grid, spec, tile=(4, 8, 128), interpret=True)
    want = stencil_ref(grid, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_stencil_flops_accounting():
    spec = STENCILS["j3d27pt"]
    assert spec.points == 27
    assert ops.flops(spec, (10, 10, 10)) == 2 * 27 * 1000


@pytest.mark.parametrize("tile,interior,window,extent", [
    # one block along the last two dims: the window is the whole padded dim
    ((8, 32, 256), (256, 32, 256), (10, 34, 258), (258, 34, 258)),
    # several: rounded up to the (8, 128) tiling, far end padded to match
    ((8, 32, 256), (256, 256, 256), (10, 40, 258), (258, 264, 258)),
    ((256, 256), (4096, 4096), (264, 384), (4104, 4224)),
])
def test_halo_window_and_extent(tile, interior, window, extent):
    from repro.kernels.stencil.kernel import halo_window, input_extent
    blocks = [n // t for n, t in zip(interior, tile)]
    assert halo_window(tile, 1, blocks) == window
    assert input_extent(interior, tile, 1) == extent
