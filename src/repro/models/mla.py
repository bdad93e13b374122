"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Prefill/train uses the expanded form; decode uses the *absorbed* form: the
cache stores only the compressed latent c_kv (kv_lora_rank) + the shared
rope key (qk_rope_dim) per position — 576 floats/token for the 236B config —
and scores are computed against the latent directly (W_UK absorbed into q,
W_UV applied after the attention-weighted latent sum).

With YaRN (``cfg.yarn_factor``) the rope slice turns at YaRN's inverse
frequencies and the softmax scale is (qk_nope + qk_rope)^-0.5 times
mscale(factor, mscale_all_dim)^2 (``cfg.yarn_mscale``).  The cos/sin
factor, mscale(factor, mscale) / mscale(factor, mscale_all_dim), is 1:
the published config gives both mscales 0.707.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import compute
from repro.models.common import (apply_rope, dense_init, split_keys,
                                 yarn_freqs, yarn_mscale)


def mla_init(cfg: ModelConfig, key, dtype):
    d, h = cfg.d_model, cfg.n_heads
    r_kv, r_q = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = split_keys(key, 8)
    p = {
        "wkv_a": dense_init(ks[0], (d, r_kv + dr), dtype),
        "kv_norm": jnp.ones((r_kv,), dtype),
        "w_uk": dense_init(ks[1], (r_kv, h, dn), dtype),
        "w_uv": dense_init(ks[2], (r_kv, h, dv), dtype),
        "wo": dense_init(ks[3], (h * dv, d), dtype),
    }
    if r_q:
        p["wq_a"] = dense_init(ks[4], (d, r_q), dtype)
        p["q_norm"] = jnp.ones((r_q,), dtype)
        p["wq_b"] = dense_init(ks[5], (r_q, h * (dn + dr)), dtype)
    else:
        p["wq"] = dense_init(ks[4], (d, h * (dn + dr)), dtype)
    return p


def _rmsn(x, scale):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf ** 2).mean(-1, keepdims=True) + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(cfg: ModelConfig, x, positions):
    """Rotate adjacent pairs of the rope slice, at YaRN's frequencies where
    the config scales them."""
    if not cfg.yarn_factor:
        return apply_rope(x, positions, cfg.rope_theta, "1d")
    freqs = yarn_freqs(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn_factor,
                       cfg.yarn_original_ctx)
    return apply_rope(x, positions, cfg.rope_theta, "1d", freqs=freqs)


def softmax_scale(cfg: ModelConfig) -> float:
    s = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale:
        s *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) ** 2
    return s


def _q_heads(cfg: ModelConfig, p, x, positions):
    B, S, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        cq = _rmsn(compute.matmul(x, p["wq_a"], site="mla.q_down"), p["q_norm"])
        q = compute.matmul(cq, p["wq_b"], site="mla.q_up")
    else:
        q = compute.matmul(x, p["wq"], site="mla.q")
    q = q.reshape(B, S, h, dn + dr).transpose(0, 2, 1, 3)     # (B,h,S,dn+dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = _rope(cfg, q_rope, positions)
    return q_nope, q_rope


def _latent(cfg: ModelConfig, p, x, positions):
    r_kv, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    kv = compute.matmul(x, p["wkv_a"], site="mla.kv_down")     # (B,S,r_kv+dr)
    c_kv = _rmsn(kv[..., :r_kv], p["kv_norm"])
    k_rope = kv[..., None, r_kv:].transpose(0, 2, 1, 3)        # (B,1,S,dr)
    k_rope = _rope(cfg, k_rope, positions)
    return c_kv, k_rope


def apply_mla(cfg: ModelConfig, p, x, *, positions, causal: bool,
              cache: Optional[dict] = None, decode_pos=None):
    """Returns (y, new_cache_or_None).  Cache: {"c_kv": (B,S,r), "k_rope":
    (B,1,S,dr)}."""
    B, S, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv, r_kv = (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
                        cfg.kv_lora_rank)
    q_nope, q_rope = _q_heads(cfg, p, x, positions)

    if cache is not None and decode_pos is not None:
        # ----- absorbed decode -----
        c_new, kr_new = _latent(cfg, p, x, positions)
        c_kv = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_new, decode_pos, axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], kr_new, decode_pos, axis=2)
        new_cache = {"c_kv": c_kv, "k_rope": k_rope}
        with jax.named_scope("site=mla.core"):
            # absorb W_UK into q: (B,h,1,dn) x (r,h,dn) -> (B,h,1,r)
            q_lat = jnp.einsum("bhsd,rhd->bhsr", q_nope, p["w_uk"])
            s = (jnp.einsum("bhsr,bTr->bhsT", q_lat, c_kv)
                 + jnp.einsum("bhsd,bxTd->bhsT", q_rope, k_rope))
            s = s.astype(jnp.float32) * softmax_scale(cfg)
            ctx = cache["c_kv"].shape[1]
            mask = jnp.arange(ctx)[None, None, None, :] <= decode_pos
            s = jnp.where(mask, s, -jnp.inf)
            pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            ctx_lat = jnp.einsum("bhsT,bTr->bhsr", pr, c_kv)
            o = jnp.einsum("bhsr,rhd->bhsd", ctx_lat, p["w_uv"])  # (B,h,1,dv)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, h * dv)
        y = compute.matmul(o, p["wo"], site="mla.o")
        return y, new_cache

    # ----- expanded train / prefill -----
    c_kv, k_rope = _latent(cfg, p, x, positions)
    with jax.named_scope("site=mla.core"):
        k_nope = jnp.einsum("bsr,rhd->bhsd", c_kv, p["w_uk"])  # (B,h,S,dn)
        v = jnp.einsum("bsr,rhd->bhsd", c_kv, p["w_uv"])       # (B,h,S,dv)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope, (B, h, S, dr))], axis=-1)
    o = compute.flash_attention(q, k, v, site="mla.core", causal=causal,
                                scale=softmax_scale(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(B, S, h * dv)
    y = compute.matmul(o, p["wo"], site="mla.o")
    new_cache = None
    if cache is not None:
        new_cache = {"c_kv": c_kv, "k_rope": k_rope}
    return y, new_cache


def make_mla_cache(cfg: ModelConfig, batch: int, ctx: int, dtype):
    return {"c_kv": jnp.zeros((batch, ctx, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, 1, ctx, cfg.qk_rope_dim), dtype)}
