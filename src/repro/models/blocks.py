"""Per-BlockDesc init/apply dispatch: one period slot = mixer + optional MLP."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import BlockDesc, ModelConfig
from repro.models import attention, mla, moe, ssm, xlstm
from repro.models.common import apply_mlp, apply_norm, mlp_init, norm_init, split_keys


def block_init(cfg: ModelConfig, b: BlockDesc, key, dtype):
    ks = split_keys(key, 4)
    p = {"norm1": norm_init(cfg, cfg.d_model, dtype)}
    if b.kind == "attn":
        p["mixer"] = (mla.mla_init(cfg, ks[0], dtype) if cfg.mla
                      else attention.attn_init(cfg, ks[0], dtype))
    elif b.kind == "mamba":
        p["mixer"] = ssm.ssm_init(cfg, ks[0], dtype)
    elif b.kind == "mlstm":
        p["mixer"] = xlstm.mlstm_init(cfg, ks[0], dtype)
    elif b.kind == "slstm":
        p["mixer"] = xlstm.slstm_init(cfg, ks[0], dtype)
    if b.mlp != "none":
        p["norm2"] = norm_init(cfg, cfg.d_model, dtype)
        p["mlp"] = (moe.moe_init(cfg, ks[1], dtype) if b.mlp == "moe"
                    else mlp_init(cfg, ks[1], dtype))
    return p


def block_cache(cfg: ModelConfig, b: BlockDesc, batch: int, ctx: int, dtype):
    if b.kind == "attn":
        if cfg.mla:
            return mla.make_mla_cache(cfg, batch, ctx, dtype)
        return attention.make_attn_cache(cfg, batch, ctx, dtype)
    if b.kind == "mamba":
        return ssm.make_ssm_cache(cfg, batch, dtype)
    if b.kind == "mlstm":
        return xlstm.make_mlstm_cache(cfg, batch)
    if b.kind == "slstm":
        return xlstm.make_slstm_cache(cfg, batch)
    raise ValueError(b.kind)


def block_apply(cfg: ModelConfig, b: BlockDesc, p, x, *, positions,
                causal: bool = True, cache: Optional[dict] = None,
                decode_pos=None, layer=None):
    """Returns (x, new_cache, aux).  ``layer``: the layer scan's index when
    ``cache`` is the stacked self-attention cache (decode)."""
    h = apply_norm(cfg, p["norm1"], x)
    if b.kind == "attn":
        if cfg.mla:
            y, nc = mla.apply_mla(cfg, p["mixer"], h, positions=positions,
                                  causal=causal, cache=cache,
                                  decode_pos=decode_pos)
        else:
            y, nc = attention.apply_attn(cfg, p["mixer"], h,
                                         positions=positions, causal=causal,
                                         cache=cache, decode_pos=decode_pos,
                                         layer=layer)
    elif b.kind == "mamba":
        y, nc = ssm.apply_ssm(cfg, p["mixer"], h, cache=cache,
                              decode_pos=decode_pos)
    elif b.kind == "mlstm":
        y, nc = xlstm.apply_mlstm(cfg, p["mixer"], h, cache=cache,
                                  decode_pos=decode_pos, chunk=cfg.ssm_chunk)
    elif b.kind == "slstm":
        y, nc = xlstm.apply_slstm(cfg, p["mixer"], h, cache=cache,
                                  decode_pos=decode_pos)
    else:
        raise ValueError(b.kind)
    x = x + y

    aux = {"lb_loss": jnp.zeros((), jnp.float32),
           "router_z": jnp.zeros((), jnp.float32)}
    if b.mlp != "none":
        h = apply_norm(cfg, p["norm2"], x)
        if b.mlp == "moe":
            y, aux = moe.apply_moe(cfg, p["mlp"], h)
        else:
            y = apply_mlp(cfg, p["mlp"], h)
        x = x + y
    return x, nc, aux
