"""Jitted public API for the Pallas stencil kernels (padding + dispatch)."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.stencils import StencilSpec
from repro.kernels import tuning
from repro.kernels.stencil.kernel import input_extent, stencil


def _padded_tiles(interior: Tuple[int, ...], tile: Tuple[int, ...]):
    return tuple(-(-n // t) * t for n, t in zip(interior, tile))


@functools.partial(jax.jit, static_argnames=("spec", "tile", "interpret"))
def apply(grid_in: jax.Array, spec: StencilSpec, *, tile: Tuple[int, ...] | None = None,
          interpret: bool = False) -> jax.Array:
    """Apply ``spec`` to a halo-carrying grid; handles non-tile-aligned shapes.

    ``grid_in`` has shape interior + 2*radius per dim; returns the interior.
    """
    r = spec.radius
    ndim = spec.ndim
    assert grid_in.ndim == ndim
    interior = tuple(s - 2 * r for s in grid_in.shape)
    # Tile selection: explicit arg > autotune table (per dtype / platform).
    tile = tile or tuning.stencil_tile(interior, grid_in.dtype)
    # Shrink tiles that exceed the (already halo-less) interior.
    tile = tuple(min(t, -(-n // 8) * 8 if i < ndim - 1 else -(-n // 128) * 128)
                 for i, (t, n) in enumerate(zip(tile, interior)))
    padded = _padded_tiles(interior, tile)
    # One zero pad, at the far end: up to tile multiples and out to the last
    # halo window the kernel reads.
    extent = input_extent(padded, tile, r)
    x = jnp.pad(grid_in, [(0, e - s) for e, s in zip(extent, grid_in.shape)])
    out = stencil(x, spec, tile=tile, interior=padded, interpret=interpret)
    return out[tuple(slice(0, n) for n in interior)]


def flops(spec: StencilSpec, interior: Tuple[int, ...]) -> int:
    """FLOPs of one application (2 per tap per point, the paper's convention)."""
    n = 1
    for s in interior:
        n *= s
    return n * spec.flops_per_point()
