"""Operations and bytes of one stencil sweep, from the algorithm's shapes.

The same work whatever implements it: the haloed grid read once and the
interior written once, and 2 operations (a multiply and an add) per tap per
interior point, the paper's convention (as ``StencilSpec.flops_per_point``).
"""
from __future__ import annotations


def sweep_bytes(cfg: dict) -> int:
    n, r = cfg["interior"], cfg["radius"]
    item = {"float32": 4, "float64": 8, "bfloat16": 2}[cfg["dtype"]]
    dims = cfg["ndim"]
    return item * ((n + 2 * r) ** dims + n ** dims)


def sweep_flops(cfg: dict) -> int:
    return 2 * cfg["points"] * cfg["interior"] ** cfg["ndim"]


def roofline_s(cfg: dict, peaks: dict) -> float:
    """The least time a sweep can take on a chip with these peaks: its bytes
    over the HBM peak.  The operations do not bound it, for no peak of a
    vector unit in the grid's type is published (a TPU publishes matrix
    peaks only)."""
    return sweep_bytes(cfg) / peaks["hbm_bytes_per_s"]
