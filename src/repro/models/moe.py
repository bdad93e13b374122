"""Dropless token-choice top-k MoE over the experts held here.

The router has the full ``n_experts`` width and runs in float32.  A token's
experts are its top ``moe_top_k`` softmax scores; with ``n_group`` > 1
(DeepSeek-V2's group-limited greedy) only experts of the ``topk_group``
groups with the highest best score compete.  Gates are the softmax
probabilities, renormalised over the chosen experts where
``norm_topk_prob``, else times ``routed_scaling_factor``.

Expert parallelism: this layer holds routed experts ``expert_offset`` ..
``expert_offset + held_experts - 1`` (all of them unless the config says
otherwise) and computes their part of the result for every route that
lands on them.  Routes to experts held elsewhere add nothing here; there
is no exchange and nothing stands in for it.  Held routes are sorted by
expert and run as ragged groups (``compute.grouped_matmul``, sites
``moe.experts.gate`` / ``.up`` / ``.down``), so no route is ever dropped
and a group may be empty.  The shared experts, whole on every chip that
holds routed experts, add their part for every token.
Long inputs run in chunks of at most ``ROUTE_CHUNK`` routes, which bounds
the sorted buffers and the float32 sums.

Each expert's weights are drawn from its own key (``fold_in`` of the
expert's global index), so a layer that holds a slice of the experts holds
exactly those experts of the whole layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import compute
from repro.models.common import dense_init, split_keys

ROUTE_CHUNK = 32768     # routes (tokens x top-k) sorted at once


def _experts_init(key, cfg: ModelConfig, shape, dtype):
    """(held, *shape): held expert e is drawn from fold_in(key, e)."""
    ids = cfg.expert_offset + jnp.arange(cfg.held_experts)
    return jax.vmap(lambda e: dense_init(jax.random.fold_in(key, e), shape,
                                         dtype))(ids)


def moe_init(cfg: ModelConfig, key, dtype):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = split_keys(key, 7)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "ewi": _experts_init(ks[1], cfg, (d, f), dtype),
        "ewg": _experts_init(ks[2], cfg, (d, f), dtype),
        "ewo": _experts_init(ks[3], cfg, (f, d), dtype),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * cfg.moe_d_ff
        p["shared_wi"] = dense_init(ks[4], (d, fs), dtype)
        p["shared_wg"] = dense_init(ks[5], (d, fs), dtype)
        p["shared_wo"] = dense_init(ks[6], (fs, d), dtype)
    return p


def route(cfg: ModelConfig, probs):
    """(T, E) router probabilities -> (gate (T, K), expert ids (T, K))."""
    T, E = probs.shape
    if cfg.n_group > 1:
        grouped = probs.reshape(T, cfg.n_group, E // cfg.n_group)
        _, top_groups = jax.lax.top_k(grouped.max(-1), cfg.topk_group)
        keep = jnp.zeros((T, cfg.n_group), bool).at[
            jnp.arange(T)[:, None], top_groups].set(True)
        probs = jnp.where(jnp.repeat(keep, E // cfg.n_group, axis=1),
                          probs, 0.0)
    gate, eidx = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    else:
        gate = gate * cfg.routed_scaling_factor
    return gate, eidx


def _held_experts(cfg: ModelConfig, p, xt, gate, eidx):
    """The held experts' part of the result for tokens ``xt`` (T, d) routed
    to ``eidx`` (T, K) with ``gate``: (T, d) float32."""
    T, d = xt.shape
    K, H = cfg.moe_top_k, cfg.held_experts
    local = eidx.reshape(-1) - cfg.expert_offset                # (T*K,)
    held = (local >= 0) & (local < H)
    order = jnp.argsort(jnp.where(held, local, H), stable=True)
    group_sizes = jnp.zeros((H,), jnp.int32).at[
        jnp.where(held, local, H)].add(1, mode="drop")
    tok = order // K
    x_sorted = xt[tok]                                          # (T*K, d)
    rows = -(-T * K * H // cfg.n_experts)      # the rows the groups expect
    gmm = lambda a, w, site: compute.grouped_matmul(
        a, w, group_sizes, site=site, rows=rows)
    h = (jax.nn.silu(gmm(x_sorted, p["ewg"], "moe.experts.gate"))
         * gmm(x_sorted, p["ewi"], "moe.experts.up"))
    y_sorted = gmm(h, p["ewo"], "moe.experts.down")
    w = jnp.where(held[order], gate.reshape(-1)[order], 0.0)
    return jnp.zeros((T, d), jnp.float32).at[tok].add(
        y_sorted.astype(jnp.float32) * w[:, None])


def _moe_tokens(cfg: ModelConfig, p, xt, gate, eidx):
    """The layer's output for tokens ``xt`` (T, d): the held experts' part
    plus the shared experts', in ``xt``'s dtype."""
    y = _held_experts(cfg, p, xt, gate, eidx)
    if cfg.n_shared_experts:
        hs = (jax.nn.silu(compute.matmul(xt, p["shared_wg"],
                                         site="moe.shared_gate", fused_ops=1))
              * compute.matmul(xt, p["shared_wi"], site="moe.shared_up"))
        y = y + compute.matmul(hs, p["shared_wo"],
                               site="moe.shared_down").astype(jnp.float32)
    return y.astype(xt.dtype)


def apply_moe(cfg: ModelConfig, p, x):
    """x: (B, S, d) -> (y, aux) where aux = {"lb_loss", "router_z"}."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = compute.matmul(xt.astype(jnp.float32), p["router"],
                            site="moe.router")                  # (T,E) f32
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = route(cfg, probs)

    # ---- aux losses (Switch-style load balance + router z-loss) ----
    me = probs.mean(0)                                          # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(
        1.0 / (T * K))
    lb_loss = E * jnp.sum(me * ce)
    router_z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # ---- experts, dropless, in chunks of at most ROUTE_CHUNK routes ----
    n_chunks = next(n for n in range(1, T + 1)
                    if T % n == 0 and (T // n) * K <= ROUTE_CHUNK
                    or n == T)
    split = lambda a: a.reshape(n_chunks, T // n_chunks, *a.shape[1:])
    y = jax.lax.map(lambda c: _moe_tokens(cfg, p, *c),
                    (split(xt), split(gate), split(eidx)))

    aux = {"lb_loss": lb_loss, "router_z": router_z}
    return y.reshape(B, S, d), aux
