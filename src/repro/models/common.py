"""Shared layers: norms, RoPE (1d / 2d-partial), MLPs, init helpers."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import compute
from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, d: int, dtype):
    p = {"scale": jnp.ones((d,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(cfg: ModelConfig, p, x):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        var = (xf ** 2).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + 1e-6) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(x, scale):
    """qk-norm: rmsnorm over the head dim. x: (..., D_head)."""
    xf = x.astype(jnp.float32)
    var = (xf ** 2).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + 1e-6)
            * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


YARN_BETA_FAST, YARN_BETA_SLOW = 32.0, 1.0    # YaRN's (and DeepSeek-V2's)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(dim: int, theta: float, factor: float, original_ctx: int,
               beta_fast: float = YARN_BETA_FAST,
               beta_slow: float = YARN_BETA_SLOW):
    """YaRN inverse frequencies (DeepSeek-V2's modelling code): pair i of
    ``dim`` keeps its frequency below the ramp, is divided by ``factor``
    above it, and blends linearly over [floor(c(beta_fast)),
    ceil(c(beta_slow))], where c(r) = dim ln(original_ctx / (2 pi r)) /
    (2 ln theta) is the pair that turns r times over ``original_ctx``."""
    def corr(r):
        return dim * math.log(original_ctx / (r * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(corr(beta_fast)), 0)
    hi = min(math.ceil(corr(beta_slow)), dim - 1)
    extra = rope_freqs(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mode: str = "1d", freqs=None) -> jax.Array:
    """x: (B, H, S, D); positions: (S,) or (B, S) absolute positions.

    mode "1d": rotate all D dims (pairing [0::2], [1::2]).
    mode "2d": GLM-style — rotate only the first half of D, pass the rest.
    ``freqs``: the pairs' inverse frequencies, if not ``theta``'s own.
    """
    if mode == "none":
        return x
    B, H, S, D = x.shape
    rot_dim = D // 2 if mode == "2d" else D
    if freqs is None:
        freqs = rope_freqs(rot_dim, theta)                   # (rot_dim/2,)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
        ang = ang[None, None]                                # (1,1,S,rd/2)
    else:
        ang = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
        ang = ang[:, None]                                   # (B,1,S,rd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr = x[..., :rot_dim].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rot = jnp.stack([r1, r2], axis=-1).reshape(*x.shape[:-1], rot_dim)
    rot = rot.astype(x.dtype)
    if rot_dim == D:
        return rot
    return jnp.concatenate([rot, x[..., rot_dim:]], axis=-1)


# ---------------------------------------------------------------------------
# MLP (dense)
# ---------------------------------------------------------------------------

def mlp_init(cfg: ModelConfig, key, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = split_keys(key, 3)
    if cfg.act == "silu":   # gated
        return {"wi": dense_init(ks[0], (d, f), dtype),
                "wg": dense_init(ks[1], (d, f), dtype),
                "wo": dense_init(ks[2], (f, d), dtype)}
    return {"wi": dense_init(ks[0], (d, f), dtype),
            "wo": dense_init(ks[2], (f, d), dtype)}


def apply_mlp(cfg: ModelConfig, p, x):
    if cfg.act == "silu":
        h = (jax.nn.silu(compute.matmul(x, p["wg"], site="mlp.gate", fused_ops=1))
             * compute.matmul(x, p["wi"], site="mlp.up"))
    else:
        h = jax.nn.gelu(compute.matmul(x, p["wi"], site="mlp.up", fused_ops=1))
    return compute.matmul(h, p["wo"], site="mlp.down")
