"""GQA attention block: RoPE (1d/2d), qk-norm, KV-cache decode, cross-attn."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import compute
from repro.models.common import (apply_norm, apply_rope, dense_init,
                                 norm_init, rms_head_norm, split_keys)


def attn_init(cfg: ModelConfig, key, dtype, cross: bool = False):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = split_keys(key, 5)
    p = {
        "wq": dense_init(ks[0], (d, hq * hd), dtype),
        "wk": dense_init(ks[1], (d, hkv * hd), dtype),
        "wv": dense_init(ks[2], (d, hkv * hd), dtype),
        "wo": dense_init(ks[3], (hq * hd, d), dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _split_heads(x, n_heads, hd):
    B, S, _ = x.shape
    return x.reshape(B, S, n_heads, hd).transpose(0, 2, 1, 3)  # (B,H,S,hd)


def _merge_heads(x):
    B, H, S, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * hd)


def apply_attn(cfg: ModelConfig, p, x, *, positions, causal: bool,
               cache: Optional[dict] = None, decode_pos=None, layer=None,
               site_prefix: str = "attn"):
    """Self-attention.

    Train/prefill: ``cache is None`` or a zeroed (B, Hkv, hd, S_ctx) cache
    to fill (prefill), ctx last.
    Decode: ``cache`` holds the layer scan's stacked (L, B, Hkv, hd, S_ctx)
    k/v and ``layer`` the scan index; ``decode_pos`` is the scalar write
    position.  Returns (y, new_cache_or_None).
    """
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(compute.matmul(x, p["wq"], site=f"{site_prefix}.q"), hq, hd)
    k = _split_heads(compute.matmul(x, p["wk"], site=f"{site_prefix}.k"), hkv, hd)
    v = _split_heads(compute.matmul(x, p["wv"], site=f"{site_prefix}.v"), hkv, hd)

    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"])
        k = rms_head_norm(k, p["k_norm"])

    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope)

    new_cache = None
    if cache is not None and decode_pos is not None:
        # decode: write this step's k/v at decode_pos, attend up to it
        o, new_cache = compute.decode_attention(
            q, k, v, cache, pos=decode_pos, site=f"{site_prefix}.core",
            layer=layer)
    else:
        if cache is not None:
            # prefill: fill the cache with the computed k/v, ctx last
            new_cache = {"k": jnp.swapaxes(k, -1, -2),
                         "v": jnp.swapaxes(v, -1, -2)}
        o = compute.flash_attention(q, k, v, site=f"{site_prefix}.core",
                                    causal=causal)
    y = compute.matmul(_merge_heads(o), p["wo"], site=f"{site_prefix}.o")
    return y, new_cache


def apply_cross_attn(cfg: ModelConfig, p, x, *, memory=None,
                     mem_cache: Optional[dict] = None,
                     site_prefix: str = "xattn"):
    """Cross-attention: q from x, k/v from encoder memory.

    ``memory`` (B, S_src, d) on prefill (k/v computed, returned as cache);
    ``mem_cache`` holds precomputed k/v on decode.
    """
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(compute.matmul(x, p["wq"], site=f"{site_prefix}.q"), hq, hd)
    if mem_cache is None:
        k = _split_heads(compute.matmul(memory, p["wk"], site=f"{site_prefix}.k"), hkv, hd)
        v = _split_heads(compute.matmul(memory, p["wv"], site=f"{site_prefix}.v"), hkv, hd)
        mem_cache = {"k": k, "v": v}
    else:
        k, v = mem_cache["k"], mem_cache["v"]
    o = compute.flash_attention(q, k, v, site=f"{site_prefix}.core", causal=False)
    y = compute.matmul(_merge_heads(o), p["wo"], site=f"{site_prefix}.o")
    return y, mem_cache


#: Axes of one layer's self-attention K/V cache, ctx last: the order the
#: TPU compiler keeps it in, which the decode kernel reads in place.
ATTN_CACHE_AXES = ("batch", "heads", "head_dim", "ctx")
#: Axes of a cross-attention memory's K/V, as the encoder computes them.
CROSS_CACHE_AXES = ("batch", "heads", "ctx", "head_dim")


def _kv_zeros(cfg: ModelConfig, axes, batch: int, ctx: int, dtype):
    size = {"batch": batch, "heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "ctx": ctx}
    shape = tuple(size[a] for a in axes)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def make_attn_cache(cfg: ModelConfig, batch: int, ctx: int, dtype):
    """Self-attention K/V: (B, Hkv, hd, ctx), ``ATTN_CACHE_AXES``."""
    return _kv_zeros(cfg, ATTN_CACHE_AXES, batch, ctx, dtype)


def make_cross_cache(cfg: ModelConfig, batch: int, ctx: int, dtype):
    """Cross-attention memory K/V: (B, Hkv, S_src, hd),
    ``CROSS_CACHE_AXES``."""
    return _kv_zeros(cfg, CROSS_CACHE_AXES, batch, ctx, dtype)
