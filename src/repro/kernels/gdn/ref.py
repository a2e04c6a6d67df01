"""Oracle for the Gated DeltaNet decode kernel: the same step in plain jnp."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gdn_decode_ref(q, k, v, g, beta, state):
    """Shapes as ``ops.gdn_decode``; float32 at HIGHEST precision."""
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    S = state.astype(f32) * jnp.exp(g)[..., None, None]
    kv = jnp.einsum("bhkv,bhk->bhv", S, k, precision=hi)
    delta = (v - kv) * beta[..., None]
    S = S + k[..., :, None] * delta[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", S, q, precision=hi), S
