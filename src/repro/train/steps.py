"""Training / serving step functions (the things the launcher pjit-compiles).

``make_train_step`` builds a microbatched (gradient-accumulation) step:
the global batch is split into ``accum`` microbatches scanned sequentially —
the standard memory/throughput lever at scale.  Optional int8 error-feedback
gradient compression hooks in before the optimizer (see
``distributed/compression.py``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import compute
from repro.models.lm import Model
from repro.optim import adamw


def make_train_state(model: Model, key, opt_cfg: adamw.AdamWConfig):
    params = model.init(key)
    return {"params": params, "opt": adamw.init(params),
            "step": jnp.zeros((), jnp.int32)}


def _split_microbatches(batch, accum: int, mb_specs=None):
    """Reshape (B, ...) -> (accum, B/accum, ...).

    GSPMD is free to re-shard a reshaped tensor and (observed) may shard the
    *accumulation* axis, collapsing the data-parallel batch sharding inside
    the scan and replicating every activation.  When ``mb_specs`` (the batch
    PartitionSpecs) is given, each microbatched leaf is pinned to
    P(None, <original batch spec>)."""
    from jax.sharding import PartitionSpec as P

    def sp(x):
        b = x.shape[0]
        assert b % accum == 0, (b, accum)
        return x.reshape(accum, b // accum, *x.shape[1:])

    out = jax.tree.map(sp, batch)
    if mb_specs is not None:
        def pin(x, spec):
            return jax.lax.with_sharding_constraint(x, P(None, *spec))
        out = jax.tree.map(pin, out, mb_specs,
                           is_leaf=lambda v: isinstance(v, P))
    return out


def make_train_step(model: Model, opt_cfg: adamw.AdamWConfig,
                    accum: int = 1, compression=None, mb_specs=None,
                    accum_dtype=jnp.float32):
    """Returns train_step(state, batch) -> (state, metrics).

    ``accum_dtype``: dtype of the gradient-accumulation buffers.  f32 is the
    safe default; bf16 halves a full parameter-sized buffer set, which is
    the difference between fitting and not fitting the 200B+ MoE configs on
    a single 256-chip pod (see EXPERIMENTS.md §Perf).
    """

    def loss_fn(params, mb):
        loss, metrics = model.train_loss(params, mb)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        params = state["params"]
        if accum == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            mbs = _split_microbatches(batch, accum, mb_specs)

            def body(carry, mb):
                gsum, lsum = carry
                (l, m), g = grad_fn(params, mb)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(accum_dtype), gsum, g)
                return (gsum, lsum + l), m

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)
            (gsum, lsum), ms = jax.lax.scan(body, (zero, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
            metrics = jax.tree.map(lambda a: a[-1], ms)

        if compression is not None:
            grads, comp_metrics = compression(grads)
            metrics = {**metrics, **comp_metrics}

        new_params, new_opt, opt_metrics = adamw.update(
            opt_cfg, grads, state["opt"], params)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, **metrics, **opt_metrics}

    return train_step


def make_prefill_step(model: Model):
    """The prefill step; tracing it in ``pallas`` mode prints how each
    Pallas kernel got its weight (``compute.weight_reads``)."""
    def prefill_step(params, batch, cache):
        with compute.weight_reads("prefill"):
            return model.prefill(params, batch, cache)
    return prefill_step


def make_serve_step(model: Model):
    """One decode step: sample greedy next token for a batch of requests.
    Tracing it in ``pallas`` mode prints how each Pallas kernel got its
    weight (``compute.weight_reads``)."""

    def serve_step(params, token, pos, cache):
        with compute.weight_reads("decode"):
            logits, cache = model.decode_step(params, token, pos, cache)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return next_tok, logits, cache

    return serve_step
