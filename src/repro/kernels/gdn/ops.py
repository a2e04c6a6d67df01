"""Public API of the Gated DeltaNet decode kernel: packing and dispatch."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.gdn.kernel import PACK, gdn_decode_pallas


def gdn_decode(q, k, v, g, beta, state, *, interpret: Optional[bool] = None):
    """One decode step of every (row, value head).

    q, k: (B, H, D) -- L2-normalised, ``q`` already scaled by D**-0.5, and
    repeated to the H value heads; v: (B, H, D); g (log decay, <= 0) and
    beta: (B, H); state: (B, H, D, D) float32, key dim first.  Returns
    (o (B, H, D) float32, new state).  ``interpret`` None runs the Pallas
    interpreter off the TPU."""
    if interpret is None:
        interpret = not tuning.on_tpu()
    B, H, D = v.shape
    f32 = jnp.float32
    lanes = lambda s: jnp.broadcast_to(s.astype(f32)[..., None], (B, H, D))
    rows = [q.astype(f32), k.astype(f32), v.astype(f32), lanes(g),
            lanes(beta)]
    rows += [jnp.zeros((B, H, D), f32)] * (PACK - len(rows))
    o, new_state = gdn_decode_pallas(jnp.stack(rows, axis=2),
                                     state.astype(f32), interpret=interpret)
    return o[:, :, 0], new_state
