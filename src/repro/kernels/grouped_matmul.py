"""Grouped (ragged) matmul Pallas kernel: rows sorted by group, each group
multiplied by its own weight matrix — the held experts of an MoE layer.

``x`` (M, K) holds the rows of group 0, then of group 1, ..., then rows that
belong to no group; ``group_sizes`` (G,) counts each group's rows and may
hold zeros.  The wrapper moves each group's rows to a start that is a
multiple of ``bm``, so that every row tile of ``bm`` rows belongs to one
group, and runs a grid of (N / bn, active row tiles, K / bk): the row-tile
count is read from the group sizes on the device, so empty groups and the
unrouted rows cost no grid steps.  A group's weights are read once per row
tile it fills (once where its rows fit one tile).  ``(bm, bn, bk)`` are the
NeuroVectorizer-tunable factors; ``bn`` and ``bk`` shrink to a multiple of
128 that divides N and K (``fit_tile``), so no block runs past the weights.

The weights are ``(G, K, N)``, or a stack ``(L, G, K, N)`` read at a
scalar-prefetched layer index where it lies: the layer scan's stacked
experts reach the kernel whole, with no copy of the layer.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.costmodel import fit_tile


def _gmm_kernel(tile_group_ref, layer_ref, x_ref, w_ref, o_ref, acc_ref, *,
                n_k: int):
    del tile_group_ref, layer_ref
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul_pallas(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
                          *, block_m: int, block_n: int, block_k: int,
                          layer: Optional[jax.Array] = None,
                          interpret: bool = False) -> jax.Array:
    """x (M, K) sorted by group, w (G, K, N), group_sizes (G,) int32 ->
    (M, N); rows past ``sum(group_sizes)`` are zero.  Or w (L, G, K, N)
    and ``layer`` an int32 scalar: the groups' weights are ``w[layer]``,
    read in place."""
    M, K = x.shape
    if w.ndim == 3:
        w, layer = w[None], 0
    _, G, K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm = min(block_m, -(-M // 8) * 8)
    bn, bk = fit_tile(N, block_n), fit_tile(K, block_k)
    gs = group_sizes.astype(jnp.int32)
    tiles = (gs + bm - 1) // bm                     # row tiles per group
    ends = jnp.cumsum(gs)
    starts = ends - gs
    pstarts = (jnp.cumsum(tiles) - tiles) * bm      # tile-aligned starts
    n_tiles = -(-M // bm) + G                       # bound on sum(tiles)
    rows = jnp.arange(M, dtype=jnp.int32)
    g = jnp.searchsorted(ends, rows, side="right")  # G past the last group
    gc = jnp.minimum(g, G - 1)
    dest = jnp.where(g < G, pstarts[gc] + rows - starts[gc], n_tiles * bm)
    xp = jnp.zeros((n_tiles * bm, K), x.dtype).at[dest].set(x, mode="drop")
    tile_group = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(tiles), jnp.arange(n_tiles, dtype=jnp.int32),
        side="right"), G - 1).astype(jnp.int32)
    n_active = jnp.sum(tiles)
    n_k = K // bk

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // bn, n_active, n_k),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda n, t, k, tg, at: (t, k)),
                pl.BlockSpec((None, None, bk, bn),
                             lambda n, t, k, tg, at: (at[0], tg[t], k, n)),
            ],
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda n, t, k, tg, at: (t, n)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles * bm, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="grouped_matmul",
    )(tile_group, jnp.asarray(layer, jnp.int32).reshape(1), xp, w)
    y = out[jnp.minimum(dest, n_tiles * bm - 1)]
    return jnp.where((g < G)[:, None], y, jnp.zeros_like(y))
