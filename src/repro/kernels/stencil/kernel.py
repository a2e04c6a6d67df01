"""Pallas stencil kernels: halo-overlapped BlockSpec streaming (SU analogue).

The SU mechanism being reproduced: Occamy programs two affine streams (grid
reads, result writes) so the FPU executes one FMA per tap per cycle with zero
address arithmetic. Here the Pallas grid pipeline streams overlapping
(tile + 2*halo) VMEM blocks (element-offset ``pl.Element`` indexing) while the unrolled
shifted-slice FMA chain inside the kernel is the exact analogue of Fig. 5's
"continuous FMA execution". Double-buffering of HBM->VMEM tiles is Pallas'
automatic pipelining -- Occamy's DMA-core double buffering.

Tiling: last dim is lanes (128-aligned), second-to-last sublanes (8-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.stencils import StencilSpec


LANE, SUBLANE = 128, 8


def halo_window(tile, r, blocks):
    """The VMEM window one grid step reads: ``tile + 2r`` per dim.

    Mosaic takes a block whose last two dims are multiples of the (8, 128)
    tiling or equal to the array's.  Where a grid has one block along such
    a dim the window is the whole padded dim; where it has more, the window
    is rounded up to the tiling, an over-read past the halo that the taps
    never touch."""
    window = [t + 2 * r for t in tile]
    for d, q in ((-1, LANE), (-2, SUBLANE)):
        if blocks[d] > 1:
            window[d] = -(-window[d] // q) * q
    return tuple(window)


def input_extent(interior, tile, r):
    """The shape :func:`stencil` reads for ``interior`` (a multiple of
    ``tile`` per dim): the halo, plus the far-end round-up of the last
    window."""
    blocks = [n // t for n, t in zip(interior, tile)]
    window = halo_window(tile, r, blocks)
    return tuple((b - 1) * t + w for b, t, w in zip(blocks, tile, window))


def _stencil_kernel(x_ref, o_ref, *, spec: StencilSpec, tile):
    r = spec.radius
    acc = jnp.zeros(tile, jnp.float32)
    # Unrolled FMA chain: one shifted VMEM read per tap, no address arithmetic.
    for off, c in zip(spec.offsets, spec.coeffs):
        tap = x_ref[tuple(slice(r + o, r + o + t) for o, t in zip(off, tile))]
        acc += c * tap.astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def stencil(x: jax.Array, spec: StencilSpec, *, tile, interior,
            interpret: bool = False) -> jax.Array:
    """Apply ``spec`` to ``x``; returns the ``interior``-shaped result.

    2-D or 3-D (j3d27pt is the paper's 83%-utilization kernel).  Each
    ``interior`` dim is a multiple of its ``tile`` entry and ``x`` has the
    shape :func:`input_extent` gives (ops.apply does all the padding).
    Each grid step reads an overlapping halo window at element offsets
    (``pl.Element``).
    """
    r = spec.radius
    assert len(tile) == len(interior) == x.ndim == spec.ndim, (x.shape, tile)
    assert all(n % t == 0 for n, t in zip(interior, tile)), (interior, tile)
    assert x.shape == input_extent(interior, tile, r), (x.shape, interior,
                                                        tile)
    blocks = tuple(n // t for n, t in zip(interior, tile))
    window = halo_window(tile, r, blocks)
    kern = functools.partial(_stencil_kernel, spec=spec, tile=tuple(tile))
    return pl.pallas_call(
        kern,
        grid=blocks,
        in_specs=[pl.BlockSpec(
            tuple(pl.Element(w) for w in window),
            lambda *ij: tuple(i * t for i, t in zip(ij, tile)))],
        out_specs=pl.BlockSpec(tuple(tile), lambda *ij: ij),
        out_shape=jax.ShapeDtypeStruct(tuple(interior), x.dtype),
        interpret=interpret,
        name=f"stencil_{spec.name}",
    )(x)
