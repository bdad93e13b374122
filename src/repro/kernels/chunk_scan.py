"""SSD chunk-scan Pallas kernel (Mamba-2 / mLSTM style linear-attention).

Computes, per group g (= batch x head) with a scalar-per-position log-decay:

    y[t] = sum_{s<=t} exp(cum[t]-cum[s]) * (C[t].B[s]) * x[s]  (+ carried state)

Grid is (G, S/Q) with the chunk dimension innermost/sequential carrying the
(P, N) state in VMEM scratch.  The chunk size Q is the tunable factor for
recurrent blocks (the IF analogue — DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_kernel(x_ref, b_ref, c_ref, la_ref, o_ref, state_ref, *, Q: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)             # (Q, P)
    Bm = b_ref[0].astype(jnp.float32)            # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)            # (Q, N)
    la = la_ref[0].astype(jnp.float32)           # (Q, 1)

    # Mosaic has no cumsum: the inclusive prefix sum is a product with the
    # lower-triangular ones matrix, taken once as a column and once as a
    # row so the pairwise decays need no transpose
    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    tril = causal.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(tril, la, (((1,), (0,)), ((), ())),
                              precision=hi,
                              preferred_element_type=jnp.float32)   # (Q, 1)
    cum_row = jax.lax.dot_general(la, tril, (((0,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)  # (1, Q)
    total = jnp.sum(la, axis=0, keepdims=True)   # (1, 1)

    # intra-chunk
    L = jnp.where(causal, jnp.exp(cum - cum_row), 0.0)   # decay j..i
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(cb * L, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk from carried state (P, N)
    y += jnp.exp(cum) * jax.lax.dot_general(
        Cm, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update
    seg = jnp.exp(total - cum)                   # (Q, 1)
    state_ref[...] = (state_ref[...] * jnp.exp(total)
                      + jax.lax.dot_general(
                          x, Bm * seg, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))
    o_ref[0] = y.astype(o_ref.dtype)


def chunk_scan_pallas(x: jax.Array, Bm: jax.Array, Cm: jax.Array,
                      la: jax.Array, *, chunk: int,
                      interpret: bool = False) -> jax.Array:
    """x: (G, S, P); Bm/Cm: (G, S, N); la: (G, S) log-decay.  -> y (G, S, P).

    ``la`` travels as ``(G, S, 1)`` blocked ``(1, Q, 1)``: a ``(1, Q)`` block
    of ``(G, S)`` breaks the TPU's (8, 128) block rule for any Q < S."""
    G, S, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    grid = (G, S // Q)
    return pl.pallas_call(
        functools.partial(_chunk_kernel, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, Q, N), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, Q, N), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, Q, 1), lambda g, c: (g, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, P), lambda g, c: (g, c, 0)),
        out_shape=jax.ShapeDtypeStruct((G, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, Bm, Cm, la[..., None])
