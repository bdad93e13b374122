"""Unified model builder.

``build_model(cfg)`` returns a :class:`Model` of pure functions:

* ``init(key)``                                        -> params
* ``train_loss(params, batch)``                        -> (loss, metrics)
* ``prefill(params, batch)``                           -> (last_logits, cache)
* ``decode_step(params, token, pos, cache)``           -> (logits, new_cache)
* ``make_cache(batch, ctx, dtype)``                    -> zeroed cache pytree

The layer stack is a single ``lax.scan`` over ``cfg.n_periods`` with each
period's parameters stacked on the leading axis (small HLO, fast compiles,
remat via ``jax.checkpoint`` around the period body).  Leading dense layers
(``cfg.n_dense_layers``, DeepSeek-V2's ``first_k_dense_replace``) are a
stack and a scan of their own before it, with their own parameters
(``params["dense_blocks"]``) and cache (``cache["dense"]``); a config with
none has neither.  Encoder-decoder configs scan two stacks and add
cross-attention.
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import BlockDesc, ModelConfig
from repro.models import attention, blocks, compute
from repro.models.common import apply_norm, dense_init, norm_init, split_keys


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    train_loss: Callable
    prefill: Callable
    decode_step: Callable
    make_cache: Callable


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _stack_init(cfg: ModelConfig, key, dtype, n_units: int,
                cross: bool = False, period: Optional[tuple] = None):
    """Stacked per-period params: tuple over period slots, leaves
    (n_periods, ...)."""
    period = period or cfg.period
    n_periods = n_units // len(period)
    out = []
    for slot, b in enumerate(period):
        keys = jax.random.split(jax.random.fold_in(key, slot), n_periods)
        per = [blocks.block_init(cfg, b, k, dtype) for k in keys]
        if cross:
            for i, k in enumerate(keys):
                per[i]["cross"] = attention.attn_init(
                    cfg, jax.random.fold_in(k, 99), dtype, cross=True)
                per[i]["norm_x"] = norm_init(cfg, cfg.d_model, dtype)
        out.append(jax.tree.map(lambda *a: jnp.stack(a), *per))
    return tuple(out)


def model_init(cfg: ModelConfig, key):
    dtype = jnp.dtype(cfg.dtype)
    ks = split_keys(key, 8)
    p = {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype,
                            scale=cfg.d_model ** -0.5),
        "final_norm": norm_init(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.vocab_size, cfg.d_model), dtype)
    if cfg.frontend != "none" and not cfg.enc_dec:
        p["frontend_proj"] = dense_init(ks[2], (cfg.d_model, cfg.d_model),
                                        dtype)
    if cfg.enc_dec:
        p["enc_blocks"] = _stack_init(cfg, ks[3], dtype, cfg.n_enc_layers)
        p["dec_blocks"] = _stack_init(cfg, ks[4], dtype, cfg.n_dec_layers,
                                      cross=True)
        p["enc_norm"] = norm_init(cfg, cfg.d_model, dtype)
    else:
        p["blocks"] = _stack_init(cfg, ks[3], dtype,
                                  cfg.n_layers - cfg.n_dense_layers)
        if cfg.n_dense_layers:
            p["dense_blocks"] = _stack_init(cfg, ks[5], dtype,
                                            cfg.n_dense_layers,
                                            period=(cfg.dense_block,))
    return p


# ---------------------------------------------------------------------------
# stack application (the scan)
# ---------------------------------------------------------------------------

def _run_stack(cfg: ModelConfig, stack_params, x, *, positions, causal,
               caches=None, decode_pos=None, memory=None, mem_caches=None,
               mem_init=None, remat: bool = True,
               period: Optional[tuple] = None):
    """Scan the period stack (``period``: its slots, ``cfg.period`` by
    default).  Returns (x, new_caches, new_mem, aux_sums).

    Caches travel in the scan CARRY and are updated in place with
    dynamic_update_index (XLA aliases while-loop carry buffers), never as
    xs->ys — emitting updated caches as scan outputs allocates a full fresh
    copy of every cache per step (measured +2x cache bytes of pure temp on
    the 32k-decode cells).

    Decoding, a self-attention slot gets its whole stacked cache and the
    scan index instead of a slice, and returns the stack written: in
    ``pallas`` mode the decode kernel writes and reads the layer where it
    lies.  Sliced out and written back around a Pallas call, each layer's
    K/V would be relayouted and copied whole every step.

    The same holds for the weights in ``pallas`` mode: the scan runs over
    the index alone, and the period body gets the stacked parameters and
    the index (``compute.layer_params``).  A weight that a matmul or
    grouped-matmul kernel reads reaches it whole with the index, and the
    kernel reads the layer where it lies; every other leaf is sliced.  In
    ``xla`` mode (training, gradients) the scan slices the parameters as
    its ``xs``."""
    has_cache = caches is not None
    has_mem = memory is not None or mem_caches is not None
    # cross-attn k/v is written only on prefill (cache fill); train
    # recomputes it under remat and decode reuses the cache passed in.
    write_mem = has_mem and mem_caches is None and has_cache
    decode_attn = has_cache and decode_pos is not None and not cfg.mla
    in_place = compute.current_mode() == "pallas"

    from jax.sharding import PartitionSpec as _P
    period = period or cfg.period

    def _slice(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            tree)

    def _update(tree, upd, i):
        return jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, i, 0),
            tree, upd)

    def period_body(x, caches_c, mem_c, slot_params, idx):
        with (compute.layer_params(slot_params, idx) if in_place
              else contextlib.nullcontext(slot_params)) as slot_params:
            return _period(x, caches_c, mem_c, slot_params, idx)

    def _period(x, caches_c, mem_c, slot_params, idx):
        # pin the carry: batch over DP, d over TP.  The carry is what scan
        # saves for backward (n_periods, B, S, d) — sharding d cuts the
        # dominant saved-activation term by the TP degree (ZeRO-R style);
        # layer internals all-gather it back (overlappable collectives).
        x = compute.constrain(x, lambda dp, tp: _P(
            dp if x.shape[0] > 1 else None, None,
            tp if compute._HINTS.get("carry_tp", True) else None))
        aux_tot = {"lb_loss": jnp.zeros((), jnp.float32),
                   "router_z": jnp.zeros((), jnp.float32)}
        new_caches = list(caches_c) if has_cache else None
        new_mem = list(mem_c) if mem_c is not None else None
        for slot, b in enumerate(period):
            pp = slot_params[slot]
            stack = decode_attn and b.kind == "attn"
            cs = None
            if has_cache:
                cs = caches_c[slot] if stack else _slice(caches_c[slot], idx)
            x, nc, aux = blocks.block_apply(
                cfg, b, pp, x, positions=positions, causal=causal,
                cache=cs, decode_pos=decode_pos, layer=idx if stack else None)
            if has_cache:
                new_caches[slot] = nc if stack else _update(
                    new_caches[slot], nc, idx)
            if has_mem:
                hx = apply_norm(cfg, pp["norm_x"], x)
                mc = _slice(mem_c[slot], idx) if mem_caches is not None \
                    else None
                y, mkv = attention.apply_cross_attn(
                    cfg, pp["cross"], hx, memory=memory, mem_cache=mc)
                x = x + y
                if write_mem:
                    new_mem[slot] = _update(new_mem[slot], mkv, idx)
            aux_tot = jax.tree.map(lambda a, b: a + b, aux_tot, aux)
        return (x, tuple(new_caches) if has_cache else None,
                tuple(new_mem) if new_mem is not None else None, aux_tot)

    if remat:
        period_body = jax.checkpoint(
            period_body, policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=())

    # stacked mem caches to fill on prefill (donated zeros from make_cache)
    if write_mem:
        assert mem_init is not None, "prefill requires cache['mem']"
        mem0 = mem_init
    else:
        mem0 = mem_caches

    def scan_body(carry, slot_inputs):
        x, caches_c, mem_c = carry
        slot_params, idx = ((stack_params, slot_inputs) if in_place
                            else slot_inputs)
        x, caches_c, mem_c, aux = period_body(x, caches_c, mem_c,
                                              slot_params, idx)
        return (x, caches_c, mem_c), aux

    n_periods = jax.tree.leaves(stack_params)[0].shape[0]
    index = jnp.arange(n_periods)
    (x, new_caches, new_mem), auxes = jax.lax.scan(
        scan_body, (x, caches, mem0),
        index if in_place else (stack_params, index))
    aux = jax.tree.map(lambda a: a.sum(), auxes)
    return x, new_caches, new_mem, aux


# ---------------------------------------------------------------------------
# forward paths
# ---------------------------------------------------------------------------

def _embed(cfg, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)


def _logits(cfg, params, x):
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    return compute.matmul(x, head.T, site="lm_head").astype(jnp.float32)


def _prep_inputs(cfg, params, batch):
    """tokens (+ frontend prefix embeds) -> (x, positions, loss_mask)."""
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens)
    B, S_text = tokens.shape
    n_pre = 0
    if cfg.frontend != "none" and not cfg.enc_dec:
        fe = batch["frontend_embeds"]                   # (B, P, d)
        fe = compute.matmul(fe.astype(x.dtype), params["frontend_proj"],
                            site="frontend.proj")
        x = jnp.concatenate([fe, x], axis=1)
        n_pre = fe.shape[1]
    S = x.shape[1]
    positions = jnp.arange(S)
    mask = jnp.concatenate([jnp.zeros((n_pre,)), jnp.ones((S_text,))])
    return x, positions, mask, n_pre


def decoder_forward(cfg, params, batch, caches=None, decode_pos=None):
    if decode_pos is None:
        x, positions, mask, n_pre = _prep_inputs(cfg, params, batch)
    else:
        x = _embed(cfg, params, batch["tokens"])
        positions = decode_pos + jnp.arange(x.shape[1])
        mask, n_pre = None, 0
    remat = decode_pos is None and caches is None
    dense_caches = None
    if cfg.n_dense_layers:
        x, dense_caches, _, _ = _run_stack(
            cfg, params["dense_blocks"], x, positions=positions, causal=True,
            caches=None if caches is None else caches["dense"],
            decode_pos=decode_pos, remat=remat, period=(cfg.dense_block,))
    x, new_caches, _, aux = _run_stack(
        cfg, params["blocks"], x, positions=positions, causal=True,
        caches=None if caches is None else caches["caches"],
        decode_pos=decode_pos, remat=remat)
    x = apply_norm(cfg, params["final_norm"], x)
    out_caches = None if caches is None else {"caches": new_caches}
    if dense_caches is not None:
        out_caches["dense"] = dense_caches
    return x, out_caches, aux, mask, n_pre


def encdec_forward(cfg, params, batch, caches=None, decode_pos=None,
                   mem_caches=None, memory=None, mem_init=None):
    """Encoder runs only when memory/mem_caches are absent (train/prefill)."""
    if memory is None and mem_caches is None:
        src = batch["src_embeds"].astype(jnp.dtype(cfg.dtype))   # (B,Ss,d)
        pos_e = jnp.arange(src.shape[1])
        memory, _, _, _ = _run_stack(cfg, params["enc_blocks"], src,
                                     positions=pos_e, causal=False,
                                     remat=(decode_pos is None))
        memory = apply_norm(cfg, params["enc_norm"], memory)
    x = _embed(cfg, params, batch["tokens"])
    if decode_pos is None:
        positions = jnp.arange(x.shape[1])
    else:
        positions = decode_pos + jnp.arange(x.shape[1])
    x, new_caches, new_mem, aux = _run_stack(
        cfg, params["dec_blocks"], x, positions=positions, causal=True,
        caches=caches, decode_pos=decode_pos, memory=memory,
        mem_caches=mem_caches, mem_init=mem_init,
        remat=(decode_pos is None and caches is None))
    x = apply_norm(cfg, params["final_norm"], x)
    return x, new_caches, new_mem, aux


# ---------------------------------------------------------------------------
# public step functions
# ---------------------------------------------------------------------------

def _xent(logits, targets, mask):
    """Cross-entropy in f32.  logits (B,S,V), targets (B,S), mask (S,) or
    (B,S).  The gold logit is picked with a one-hot contraction rather than
    take_along_axis: a gather along the TP-sharded vocab axis would force
    GSPMD to replicate the logits (checked: 700+ GiB/device on 256k vocabs);
    the one-hot einsum partitions cleanly and reduces over the shard."""
    from jax.sharding import PartitionSpec as _P
    spec = lambda dp, tp: _P(dp if logits.shape[0] > 1 else None, None, tp)
    logits = compute.constrain(logits, spec)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    onehot = compute.constrain(onehot, spec)
    gold = jnp.einsum("bsv,bsv->bs", logits, onehot)
    nll = lse - gold
    if mask is not None:
        mask = jnp.broadcast_to(mask, nll.shape)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def train_loss(cfg: ModelConfig, params, batch):
    if cfg.enc_dec:
        x, _, _, aux = encdec_forward(cfg, params, batch)
        mask = None
        n_pre = 0
    else:
        x, _, aux, mask, n_pre = decoder_forward(cfg, params, batch)
    logits = _logits(cfg, params, x)
    tgt = batch["targets"]
    if n_pre:
        logits = logits[:, n_pre:]
        mask = None
    loss = _xent(logits, tgt, mask if not n_pre else None)
    total = loss + 1e-2 * aux["lb_loss"] + 1e-3 * aux["router_z"]
    return total, {"xent": loss, **aux}


def make_cache(cfg: ModelConfig, batch: int, ctx: int, dtype):
    n_periods = (cfg.n_dec_layers // len(cfg.period) if cfg.enc_dec
                 else cfg.n_periods)

    def stacked(mk, n=n_periods):
        one = mk()
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), one)

    caches = tuple(
        stacked(lambda b=b: blocks.block_cache(cfg, b, batch, ctx, dtype))
        for b in cfg.period)
    out = {"caches": caches}
    if cfg.n_dense_layers:
        out["dense"] = (stacked(lambda: blocks.block_cache(
            cfg, cfg.dense_block, batch, ctx, dtype), cfg.n_dense_layers),)
    if cfg.enc_dec:
        out["mem"] = tuple(
            stacked(lambda: attention.make_cross_cache(cfg, batch, ctx, dtype))
            for _ in cfg.period)
    return out


def prefill(cfg: ModelConfig, params, batch, cache):
    """Fill the cache from a full-sequence forward; return last logits."""
    if cfg.enc_dec:
        x, new_caches, new_mem, _ = encdec_forward(
            cfg, params, batch, caches=cache["caches"],
            mem_init=cache["mem"])
        out_cache = {"caches": new_caches, "mem": new_mem}
    else:
        x, out_cache, _, _, _ = decoder_forward(cfg, params, batch,
                                                caches=cache)
    logits = _logits(cfg, params, x[:, -1:])[:, 0]
    return logits, out_cache


def decode_step(cfg: ModelConfig, params, token, pos, cache):
    """token (B,1) int32; pos scalar int32 — absolute position of the new
    token; cache holds ctx positions.  Returns (logits (B,V), new_cache)."""
    batch = {"tokens": token}
    if cfg.enc_dec:
        x, new_caches, new_mem, _ = encdec_forward(
            cfg, params, batch, caches=cache["caches"],
            mem_caches=cache["mem"], decode_pos=pos)
        out_cache = {"caches": new_caches, "mem": cache["mem"]}
    else:
        x, out_cache, _, _, _ = decoder_forward(cfg, params, batch,
                                                caches=cache, decode_pos=pos)
    logits = _logits(cfg, params, x)[:, 0]
    return logits, out_cache


def build_model(cfg: ModelConfig) -> Model:
    return Model(
        cfg=cfg,
        init=functools.partial(model_init, cfg),
        train_loss=functools.partial(train_loss, cfg),
        prefill=functools.partial(prefill, cfg),
        decode_step=functools.partial(decode_step, cfg),
        make_cache=functools.partial(make_cache, cfg),
    )
