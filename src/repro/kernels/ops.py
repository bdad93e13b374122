"""jit'd wrappers around the Pallas kernels.

``tiles`` is the injected factor tuple from the NeuroVectorizer agent
(``repro.core.vectorizer``); ``None`` falls back to the heuristic baseline
(``repro.core.costmodel.baseline_tiles``) — exactly as un-pragma'd loops
fall back to LLVM's default cost model.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.chunk_scan import chunk_scan_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.matmul import matmul_pallas


def _default_matmul_tiles(M: int, N: int, K: int) -> Tuple[int, int, int]:
    from repro.core.costmodel import baseline_matmul_tiles
    return baseline_matmul_tiles(M, N, K)


def _default_attn_tiles(Sq: int, Skv: int) -> Tuple[int, int]:
    from repro.core.costmodel import baseline_attn_tiles
    return baseline_attn_tiles(Sq, Skv)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def matmul(x: jax.Array, w: jax.Array,
           tiles: Optional[Tuple[int, int, int]] = None,
           interpret: bool = False, layer=None) -> jax.Array:
    """x (M, K) times w (K, N) -> (M, N); or w (L, K, N) and ``layer`` an
    int32 scalar: times ``w[layer]``, read where it lies in the stack."""
    M, K = x.shape
    N = w.shape[-1]
    bm, bn, bk = tiles if tiles is not None else _default_matmul_tiles(M, N, K)
    return matmul_pallas(x, w, block_m=bm, block_n=bn, block_k=bk,
                         layer=layer, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                   tiles: Tuple[int, int, int], interpret: bool = False,
                   layer=None) -> jax.Array:
    """x (M, K) sorted by group, w (G, K, N), group_sizes (G,) -> (M, N)
    on tiles (bm, bn, bk); or w (L, G, K, N) and ``layer`` an int32
    scalar: the groups' weights are ``w[layer]``, read in place.  The
    caller gives the baseline where the plan has none
    (``costmodel.baseline_tiles`` of the site)."""
    bm, bn, bk = tiles[:3]
    return grouped_matmul_pallas(x, w, group_sizes, block_m=bm, block_n=bn,
                                 block_k=bk, layer=layer, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "tiles", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, scale: float,
                    tiles: Optional[Tuple[int, int]] = None,
                    interpret: bool = False) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    # TileProgram entries carry the unified 3-head action; attention uses
    # the first two factors
    bq, bkv = tiles[:2] if tiles is not None \
        else _default_attn_tiles(Sq, Skv)
    if Hq != Hkv:   # expand GQA groups for the kernel
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  block_q=bq, block_kv=bkv,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array, layer, pos, *,
                     scale: float, interpret: bool = False) -> tuple:
    """q (B, Hq, hd) against layer ``layer`` of the stacked caches
    (L, B, Hkv, hd, ctx), positions ``0 .. pos``, after writing this
    position's k, v (B, Hkv, hd) there; no tunable factors.  Returns
    (o, k_cache, v_cache)."""
    return decode_attention_pallas(q, k, v, k_cache, v_cache, layer, pos,
                                   scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def chunk_scan(x: jax.Array, Bm: jax.Array, Cm: jax.Array, la: jax.Array,
               chunk: int = 256, interpret: bool = False) -> jax.Array:
    return chunk_scan_pallas(x, Bm, Cm, la, chunk=chunk, interpret=interpret)
