"""Flash-attention Pallas kernel with tunable (block_q, block_kv).

Grid is (B*H, Sq/bq, Skv/bkv); the kv dimension is innermost/sequential and
carries the online-softmax state (m, l, acc) in VMEM scratch.  ``(bq, bkv)``
are the NeuroVectorizer-tunable factors for attention sites.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, n_kv: int, bq: int, bkv: int,
                  q_off: int):
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                 # (bq, d)
    k = k_ref[0]                                 # (bkv, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bkv)

    if causal:
        # bottom-right aligned (matches ``ref.attention_ref``): query row i
        # attends to keys 0..i + (Skv - Sq), so for Sq != Skv the final query
        # still sees the full key sequence.
        q_pos = (q_i * bq + q_off
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0))
        k_pos = kv_i * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * corr
                    + jax.lax.dot_general(
                        p.astype(v_ref.dtype), v_ref[0],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))
    m_ref[...], l_ref[...] = m_new, l_new

    @pl.when(kv_i == n_kv - 1)
    def _flush():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool, scale: float, block_q: int,
                           block_kv: int,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, D); k: (B, Hkv, Skv, D); v: (B, Hkv, Skv, Dv), where
    V's head dim may differ from Q's and K's (MLA: 192 and 128).  GQA
    groups are expanded by the wrapper in ``ops.py``; here H == Hkv."""
    B, H, Sq, D = q.shape
    _, _, Skv, _ = k.shape
    Dv = v.shape[-1]
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0, (Sq, bq, Skv, bkv)

    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Skv, D)
    vf = v.reshape(B * H, Skv, Dv)
    grid = (B * H, Sq // bq, Skv // bkv)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          n_kv=grid[2], bq=bq, bkv=bkv, q_off=Skv - Sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, Dv)
