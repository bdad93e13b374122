"""Tiled matmul Pallas kernel — the primary tunable site (VF/IF analogue).

Grid is (M/bm, N/bn, K/bk), each rounded up; the K dimension is innermost
(sequential on TPU), accumulating into a VMEM f32 scratch tile.
``(bm, bn, bk)`` are the factors the NeuroVectorizer agent picks; they
directly set the VMEM working set (bm*bk + bk*bn + bm*bn tiles,
double-buffered by the pipeline) and the MXU utilization (alignment to
128x128).

The weight is ``(K, N)``, or a stack ``(L, K, N)`` read at a
scalar-prefetched layer index where it lies: the layer scan's stacked
parameters reach the kernel whole, with no copy of the layer.  No operand
is padded.  A tile larger than its dimension shrinks to the whole
dimension; where a tile does not divide one, the edge blocks run past the
operands: rows past M and columns past N are not written back, and the
last K block of x and of w is masked to zero past K (what lies there is
undefined, and a zero weight does not clear a NaN).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def block(dim: int, tile: int) -> int:
    """The block the kernel runs for a ``tile`` over ``dim``: the tile, or
    the whole dimension where the tile holds it."""
    return dim if dim <= tile else tile


def _matmul_kernel(layer_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int,
                   k_rem: int):
    del layer_ref
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(x, w):
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    if k_rem:
        @pl.when(k < n_k - 1)
        def _inner():
            accumulate(x_ref[...], w_ref[...])

        @pl.when(k == n_k - 1)
        def _edge():
            x, w = x_ref[...], w_ref[...]
            col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            row = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
            accumulate(jnp.where(col < k_rem, x, 0),
                       jnp.where(row < k_rem, w, 0))
    else:
        accumulate(x_ref[...], w_ref[...])

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(x: jax.Array, w: jax.Array, *, block_m: int, block_n: int,
                  block_k: int, layer: Optional[jax.Array] = None,
                  interpret: bool = False) -> jax.Array:
    """x: (M, K), w: (K, N) -> (M, N); or w: (L, K, N) and ``layer`` an
    int32 scalar -> ``x @ w[layer]``, read in place."""
    M, K = x.shape
    if w.ndim == 2:
        w, layer = w[None], 0
    _, K2, N = w.shape
    assert K == K2, (x.shape, w.shape)

    bm, bn, bk = block(M, block_m), block(N, block_n), block(K, block_k)
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk))
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=grid[2], k_rem=K % bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, at: (i, k)),
                pl.BlockSpec((None, bk, bn),
                             lambda i, j, k, at: (at[0], k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, at: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
        name="matmul",
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, w)
