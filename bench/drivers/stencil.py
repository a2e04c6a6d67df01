"""Stencil cells: Jacobi sweeps through ``kernels.stencil.ops.apply``.

The configuration states the taps; the program and the reference are both
given them, so neither reads the other's table.  The grid carries a fixed
halo (Dirichlet boundary).  One sweep is the
program's stencil over the haloed grid, written back into the interior:
``x.at[interior].set(ops.apply(x, spec))``, one jitted program that takes
the grid's buffer (donated), as an iterating user would.  Set-up makes the
grid on the device from the seed and runs one sweep, which compiles; the
window dispatches sweeps back to back, with one device sync per group.

The check compares the window's last sweep with the plain reference
(``reference/stencil.py``), boundary included: its input, the state every
earlier sweep of the window made, is copied just before it, so that the
copy is the one buffer a Jacobi user would not hold, and only for that
sweep.  The number compared is the largest absolute difference over the
largest absolute value of the reference's grid.
"""
from __future__ import annotations

import functools
import itertools
import time
from typing import List

import jax
import jax.numpy as jnp

from bench import traffic as traffic_mod
from bench.harness import Check
from bench.reference import stencil as ref

CLOCK = time.monotonic


def taps(config: dict):
    """(offsets, coefficients) of the configuration's stencil: a box of
    ``radius``, its offsets in lexicographic order, one coefficient each."""
    r, nd = config["radius"], config["ndim"]
    if config["shape"] != "box":
        raise ValueError(f"stencil shape {config['shape']!r}: only 'box'")
    offsets = tuple(itertools.product(range(-r, r + 1), repeat=nd))
    coeffs = tuple(float(c) for c in config["coefficients"])
    if len(offsets) != config["points"] or len(coeffs) != len(offsets):
        raise ValueError(f"{config['name']}: {len(offsets)} offsets, "
                         f"{len(coeffs)} coefficients, {config['points']} "
                         f"points")
    return offsets, coeffs


def program_spec(config: dict):
    """The program's ``StencilSpec`` for the configuration's taps."""
    from repro.core.stencils import StencilSpec
    offsets, coeffs = taps(config)
    return StencilSpec(name=config["stencil"], ndim=config["ndim"],
                       offsets=offsets, coeffs=coeffs)


def sweep_program(spec, interpret: bool = False):
    """One Jacobi sweep as one jitted program that takes the grid's buffer:
    the program's stencil over the haloed grid, written into the interior."""
    from repro.kernels.stencil import ops
    inner = tuple(slice(spec.radius, -spec.radius) for _ in range(spec.ndim))
    return jax.jit(
        lambda x: x.at[inner].set(ops.apply(x, spec, interpret=interpret)),
        donate_argnums=0)


@functools.partial(jax.jit, static_argnames=("offsets", "coeffs", "dtype"))
def rel_error(x, y, *, offsets, coeffs, dtype=None):
    """max |y - sweep(x)| / max |sweep(x)| for the float32 reference sweep,
    in one program (no whole-grid difference is kept); with ``dtype`` the
    reference's own sweep in that type stands in for ``y``."""
    want = ref.sweep(x, offsets=offsets, coeffs=coeffs)
    if dtype is not None:
        y = ref.sweep(x, offsets=offsets, coeffs=coeffs,
                      dtype=jnp.dtype(dtype)).astype(want.dtype)
    return jnp.max(jnp.abs(y - want)) / jnp.maximum(
        jnp.max(jnp.abs(want)), 1e-30)


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 seconds: float):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.seconds = devices, seconds
        self.t_open = self.t_close = None

    def setup(self) -> None:
        c = self.config
        self.spec = spec = program_spec(c)
        self.sweep = sweep_program(
            spec, interpret=self.devices[0].platform != "tpu")
        key = jax.random.PRNGKey(traffic_mod.rng_for(self.seed).integers(
            2 ** 31))
        shape = (c["interior"] + 2 * spec.radius,) * spec.ndim
        x = jax.jit(lambda k: jax.random.uniform(
            k, shape, jnp.dtype(c["dtype"])))(key)
        self.x = jax.block_until_ready(self.sweep(x))   # compiles the sweep
        jax.block_until_ready(jnp.copy(self.x))       # and the check's copy

    def run(self, seconds: float, tracer) -> None:
        group = self.traffic["sweeps_per_sync"]
        x, n = self.x, 0
        self.x = None
        self.t_open = t0 = CLOCK()
        self.untraced = None
        if tracer is not None:
            tracer.arm(t0 + seconds)
        while True:
            now = CLOCK()
            if now - t0 >= seconds:
                break
            if tracer is not None:
                tracer.tick(now)
                if tracer.active and self.untraced is None:
                    self.untraced = (now - t0, n)
            with jax.profiler.TraceAnnotation("bench.sweeps"):
                for _ in range(group):
                    x = self.sweep(x)
                    n += 1
            with jax.profiler.TraceAnnotation("bench.sync"):
                x = jax.block_until_ready(x)
        with jax.profiler.TraceAnnotation("bench.sweeps"):
            self.check_in = jnp.copy(x)
            x = self.sweep(x)
            n += 1
        with jax.profiler.TraceAnnotation("bench.sync"):
            self.check_out = jax.block_until_ready(x)
        if tracer is not None:
            tracer.finish()
        self.t_close = CLOCK()
        self.sweeps = n

    def end_to_end(self) -> dict:
        return {"call_ms": 1e3 * (self.t_close - self.t_open) / self.sweeps}

    def untraced_call_ms(self):
        """call_ms over the part of a traced run's window before the trace
        began (None if it began at once)."""
        if self.untraced is None or self.untraced[1] == 0:
            return None
        return 1e3 * self.untraced[0] / self.untraced[1]

    def counts(self):
        return self.sweeps, 0

    def release(self) -> None:
        pass

    def errors(self, dtype=None) -> float:
        """Normalised max error of the checked sweep against the float32
        reference, max |got - want| / max |want|; with ``dtype`` the
        control's own sweep is compared in the program's place."""
        offsets, coeffs = taps(self.config)
        return float(rel_error(self.check_in, self.check_out,
                               offsets=offsets, coeffs=coeffs, dtype=dtype))

    def checks(self) -> List[Check]:
        return [Check("max_rel_error", self.errors(),
                      self.config["check"]["max_rel_error"])]
