"""Pallas kernel of the Gated DeltaNet one-token decode update.

One grid step is one (batch row, value head).  Its (K, V) float32 state
comes in from HBM once and goes back once (aliased in place); the step's
query, key, value, log-decay ``g`` and write strength ``beta`` ride in one
packed (8, K) block:

    S <- exp(g) * S
    S <- S + k (x) (beta * (v - S^T k))
    o  = S^T q

Everything is float32 on the vector unit: the two contractions over the key
dim are a broadcast multiply and a sublane sum, and the key and query
columns they need are read off the packed rows with an identity mask (one
nonzero per lane, so the sum is exact), so the kernel needs no transpose
and no matrix unit pass.  The device trace names it ``gdn_decode``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# rows of the packed per-(row, head) block
Q, K_, V_, G, BETA = range(5)
PACK = 8


def _column(row, eye):
    """(1, D) row -> (D, 1) column, exactly."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _gdn_decode_kernel(x_ref, s_ref, o_ref, s_out_ref):
    x = x_ref[0, 0]                                  # (PACK, D)
    D = x.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (D, D), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (D, D), 1))
    q_col = _column(x[Q:Q + 1], eye)                 # (D, 1)
    k_col = _column(x[K_:K_ + 1], eye)
    S = s_ref[0, 0] * jnp.exp(x[G:G + 1])            # g is the same per lane
    kv = jnp.sum(S * k_col, axis=0, keepdims=True)   # S^T k, (1, D)
    delta = (x[V_:V_ + 1] - kv) * x[BETA:BETA + 1]
    S = S + k_col * delta
    o_ref[0, 0] = jnp.sum(S * q_col, axis=0, keepdims=True)
    s_out_ref[0, 0] = S


def gdn_decode_pallas(packed, state, *, interpret: bool = False):
    """packed: (B, H, 8, D) f32 rows [q, k, v, g, beta, 0, 0, 0] (g and beta
    repeated along the lanes); state: (B, H, D, D) f32, key dim first.
    Returns (o (B, H, 1, D) f32, new state (B, H, D, D) f32); the state's
    buffer is aliased to the new state's."""
    B, H, P, D = packed.shape
    assert P == PACK and state.shape == (B, H, D, D), (packed.shape,
                                                       state.shape)
    return pl.pallas_call(
        _gdn_decode_kernel,
        grid=(B, H),
        in_specs=[
            pl.BlockSpec((1, 1, PACK, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, D, D), jnp.float32)],
        input_output_aliases={1: 1},
        interpret=interpret,
        name="gdn_decode",
    )(packed, state)
