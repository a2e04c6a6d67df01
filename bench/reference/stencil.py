"""Plain Jacobi sweep of a box or star stencil with a fixed (Dirichlet) halo,
independent of the program under test: the sum of the shifted grids, each
times its coefficient, written into the interior; the halo is kept as it is.
``dtype`` bfloat16 makes the control: the grid, the coefficients and the
sum all in bfloat16, the precision below the configuration's float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("offsets", "coeffs", "dtype"))
def sweep(x, *, offsets, coeffs, dtype=jnp.float32):
    r = max(abs(o) for off in offsets for o in off)
    inner = tuple(slice(r, n - r) for n in x.shape)
    y = x.astype(dtype)
    acc = jnp.zeros(tuple(n - 2 * r for n in x.shape), dtype)
    for off, c in zip(offsets, coeffs):
        sl = tuple(slice(r + o, n - r + o) for o, n in zip(off, x.shape))
        acc = acc + jnp.asarray(c, dtype) * y[sl]
    return x.at[inner].set(acc.astype(x.dtype))
