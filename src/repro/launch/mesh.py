"""The production mesh layout, built by :func:`repro.parallel.mesh.make_mesh`.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count locks on first backend init).
"""
from __future__ import annotations

from repro.parallel.mesh import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 ("data","model") per pod; 2x16x16 ("pod","data","model") for the
    dual-pod system (the dual-chiplet analogue)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
