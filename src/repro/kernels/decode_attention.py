"""Decode-attention Pallas kernel over the stacked K/V cache, read in place.

One query position per batch row attends over one layer of the layer
scan's stacked cache ``(L, B, Hkv, head_dim, ctx)``: ctx in the lanes and
head_dim in the sublanes, the order the TPU compiler keeps the cache in,
so the call needs no relayout of it and head_dim 80 is not padded.  The
layer and the position are scalar-prefetched.  The grid is
``(B / bb, cdiv(ctx, BK))``: each step DMAs the block ``(bb, Hkv,
head_dim, BK)`` of K and of V, every head of ``bb`` batch rows at once.
The block index stops at the block holding ``pos``, so nothing past it is
read; steps past it do nothing, and positions past ``pos`` in its block
(the previous batch's keys, or past ``ctx`` where ``BK`` does not divide
it) are masked, in K and in V.  The same call writes this position's
key and value into that block and hands the block back to the caches,
which the outputs alias: the stack is updated in place, one column at a
time.  Online softmax in f32 scratch; the ``Hq // Hkv`` query heads of a
key-value head share its block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: positions a block holds: one lane tile, so at most 127 are read past pos
BK = 128
#: K bytes one grid step reads at most; V reads as many.  On a TPU v5e,
#: StableLM-3B at batch 16, blocks of 2 to 8 batch rows (1.3 to 5.2 MB)
#: timed within 4% of each other at positions 639 and 1023, one row up to
#: 9% slower; 4 MiB (4 rows) was fastest on the mean of the two positions.
BLOCK_BYTES = 4 << 20
#: scoped VMEM the TPU compiler grants a kernel unless told otherwise
DEFAULT_VMEM_BYTES = 16 << 20


def batch_rows(batch: int, kv_heads: int, head_dim: int,
               itemsize: int) -> int:
    """The most batch rows a block holds: a divisor of ``batch`` whose K
    block stays within ``BLOCK_BYTES`` (at least 1)."""
    row = kv_heads * head_dim * BK * itemsize
    return max([d for d in range(1, batch + 1)
                if batch % d == 0 and d * row <= BLOCK_BYTES] or [1])


def _lane_column(x, width: int):
    """x (n, 1, d) -> (n, d, width): each row's d values down the sublanes,
    the same in every lane."""
    col = jnp.swapaxes(x.astype(jnp.float32), 1, 2)
    return jnp.broadcast_to(col, (x.shape[0], x.shape[2], width))


def _decode_kernel(at_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref, o_ref,
                   ko_ref, vo_ref, m_ref, l_ref, acc_ref, *, scale: float):
    bk = BK
    j = pl.program_id(1)
    pos = at_ref[1]
    last = pos // bk
    bb, hkv, group, hd = q_ref.shape
    n = bb * hkv

    def attend(k, v):
        q = q_ref[...].reshape(n, group, hd)
        # (n, group, hd) x (n, hd, bk) -> (n, group, bk)
        s = jax.lax.dot_general(
            q, k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(k_pos <= pos, s, NEG_INF)
        # the first block (always read: pos >= 0) starts the softmax; the
        # scratch holds nothing before it
        first = j == 0
        m_prev = jnp.where(first, NEG_INF, m_ref[...])
        l_prev = jnp.where(first, 0.0, l_ref[...])
        acc_prev = jnp.where(first, 0.0, acc_ref[...])
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        # (n, group, bk) x (n, hd, bk) -> (n, group, hd)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_prev * corr + pv

    @pl.when(j < last)
    def _block():
        attend(k_ref[...].reshape(n, hd, bk), v_ref[...].reshape(n, hd, bk))

    @pl.when(j == last)
    def _last_block():
        # write this position's key and value into the block, attend over
        # it, and hand it back to the stack (the outputs alias the caches)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
        at_pos = lane == pos - j * bk

        def written(new_ref, blk_ref):
            col = _lane_column(new_ref[...].reshape(n, 1, hd), bk)
            return jnp.where(at_pos, col.astype(blk_ref.dtype),
                             blk_ref[...].reshape(n, hd, bk))

        k, v = written(kn_ref, k_ref), written(vn_ref, v_ref)
        # a zero weight does not clear a NaN: positions past ctx in a
        # partial block hold whatever the buffer held
        attend(k, jnp.where(lane <= pos - j * bk, v, 0))
        ko_ref[...] = k.reshape(ko_ref.shape)
        vo_ref[...] = v.reshape(vo_ref.shape)
        o_ref[...] = (acc_ref[...] / l_ref[...]).reshape(
            o_ref.shape).astype(o_ref.dtype)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            k_cache: jax.Array, v_cache: jax.Array, layer,
                            pos, *, scale: float,
                            interpret: bool = False) -> tuple:
    """q: (B, Hq, hd); k, v: (B, Hkv, hd), this position's key and value;
    k_cache, v_cache: (L, B, Hkv, hd, ctx); ``layer`` and ``pos`` int32
    scalars.  Writes k, v into the caches at ``(layer, ..., pos)`` and
    returns (o (B, Hq, hd), k_cache, v_cache): query row b attends to
    positions ``0 .. pos`` of layer ``layer``.  The caches are updated in
    place where the caller donates them (the layer scan's carry)."""
    B, Hq, hd = q.shape
    _, _, Hkv, _, ctx = k_cache.shape
    group = Hq // Hkv
    itemsize = k_cache.dtype.itemsize
    bb, bk = batch_rows(B, Hkv, hd, itemsize), BK
    at = jnp.stack([jnp.asarray(layer, jnp.int32),
                    jnp.asarray(pos, jnp.int32)])

    def kv_block(b, j, at):
        return at[0], b, 0, 0, jnp.minimum(j, at[1] // bk)

    def last_block(b, j, at):
        return at[0], b, 0, 0, at[1] // bk

    def rows(b, j, at):
        return b, 0, 0, 0

    kv_spec = pl.BlockSpec((None, bb, Hkv, hd, bk), kv_block)
    out_spec = pl.BlockSpec((None, bb, Hkv, hd, bk), last_block)
    new_spec = pl.BlockSpec((bb, Hkv, 1, hd), rows)
    # K and V blocks in and out, double-buffered, and the f32 scores
    n = bb * Hkv
    need = 8 * n * hd * bk * itemsize + 2 * n * max(group, 8) * bk * 4
    o, k_cache, v_cache = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // bb, pl.cdiv(ctx, bk)),
            in_specs=[pl.BlockSpec((bb, Hkv, group, hd), rows), new_spec,
                      new_spec, kv_spec, kv_spec],
            out_specs=[pl.BlockSpec((bb, Hkv, group, hd), rows), out_spec,
                       out_spec],
            scratch_shapes=[
                pltpu.VMEM((n, group, 1), jnp.float32),
                pltpu.VMEM((n, group, 1), jnp.float32),
                pltpu.VMEM((n, group, hd), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, group, hd), q.dtype),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        # operands count the scalar prefetch: 4, 5 are the caches
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=(2 * need if 2 * need > DEFAULT_VMEM_BYTES
                              else None)),
        interpret=interpret,
    )(at, q.reshape(B, Hkv, group, hd),
      k.reshape(B, Hkv, 1, hd).astype(k_cache.dtype),
      v.reshape(B, Hkv, 1, hd).astype(v_cache.dtype), k_cache, v_cache)
    return o.reshape(B, Hq, hd), k_cache, v_cache
