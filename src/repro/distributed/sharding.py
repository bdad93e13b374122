"""Sharding rules: DP / TP (Megatron-style) / EP / FSDP / SP on a
("pod",)"data","model" mesh.

Parameters get a PartitionSpec from path-keyword rules; every 2-D+ weight is
TP-sharded on its role axis over "model" and FSDP-sharded over "data" on the
other large axis (ZeRO-3 style — weights are all-gathered per layer inside
the scan, gradients reduce-scattered by GSPMD).  Optimizer state inherits
the parameter sharding.  GSPMD (pjit) propagates activation shardings and
inserts the collectives.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import attention


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


DP = "__dp__"      # placeholder replaced with the mesh's dp axes


# ---------------------------------------------------------------------------
# parameter rules (first match on the joined parameter path wins)
# ---------------------------------------------------------------------------
# fmt: off
_PARAM_RULES = [
    # MoE expert tensors: EP over model, FSDP over d_model
    ("ewi",         {3: P("model", "data", None), 4: P(None, "model", "data", None)}),
    ("ewg",         {3: P("model", "data", None), 4: P(None, "model", "data", None)}),
    ("ewo",         {3: P("model", None, "data"), 4: P(None, "model", None, "data")}),
    ("router",      {2: P("data", "model"), 3: P(None, "data", "model")}),
    ("shared_wi",   {2: P("data", "model"), 3: P(None, "data", "model")}),
    ("shared_wg",   {2: P("data", "model"), 3: P(None, "data", "model")}),
    ("shared_wo",   {2: P("model", "data"), 3: P(None, "model", "data")}),
    # embeddings / lm head: vocab over model, d over data
    ("embed",       {2: P("model", "data")}),
    ("head",        {2: P("model", "data")}),
    ("frontend_proj", {2: P("data", "model")}),
    # dense MLP (gated): D x F over (data, model)
    ("wi",          {2: P("data", "model"), 3: P(None, "data", "model")}),
    ("wg",          {2: P("data", "model"), 3: P(None, "data", "model")}),
    # attention / MLA
    ("wq",          {2: P("data", "model"), 3: P(None, "data", "model"), 4: P(None, None, None, "model")}),
    ("wk",          {2: P("data", "model"), 3: P(None, "data", "model"), 4: P(None, None, None, "model")}),
    ("wv",          {2: P("data", "model"), 3: P(None, "data", "model"), 4: P(None, None, None, "model")}),
    ("wo",          {2: P("model", "data"), 3: P(None, "model", "data")}),
    ("wq_a",        {3: P(None, "data", "model")}),
    ("wq_b",        {3: P(None, "data", "model")}),
    ("wkv_a",       {3: P(None, "data", "model")}),
    ("w_uk",        {4: P(None, None, "model", None)}),
    ("w_uv",        {4: P(None, None, "model", None)}),
    # dense / ssm / xlstm projections
    ("in_proj",     {3: P(None, "data", "model")}),
    ("out_proj",    {3: P(None, "model", "data")}),
    ("up",          {3: P(None, "data", "model")}),
    ("down",        {3: P(None, "model", "data")}),
    ("wx",          {3: P(None, "data", "model")}),
    ("conv",        {3: P(None, None, "model")}),
    # sLSTM recurrent weights stay TP-sharded: replicating them was tried
    # and REFUTED — the per-step dL/dr accumulation then all-reduces a
    # full 16 MiB replica every timestep (16x more traffic; EXPERIMENTS.md
    # Cell C it2)
    ("r",           {5: P(None, None, None, None, "model")}),
]
# fmt: on


def _spec_for(path: str, ndim: int) -> P:
    for key, by_rank in _PARAM_RULES:
        if f"/{key}" in path or path.endswith(key) or f"{key}/" in path:
            if ndim in by_rank:
                return by_rank[ndim]
    if ndim >= 2:
        # fallback: FSDP-shard the biggest trailing dim over data
        spec = [None] * ndim
        spec[-1] = "data"
        return P(*spec)
    return P()


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


def _fit_spec(spec: P, shape, mesh: Optional[Mesh]) -> P:
    """pjit requires argument dims to divide evenly by their mesh axes;
    drop (replicate) any assignment that doesn't."""
    if mesh is None:
        return spec
    out = []
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape)
                                                         - len(spec))):
        if axes is None:
            out.append(None)
            continue
        ax_tuple = axes if isinstance(axes, tuple) else (axes,)
        n = int(np.prod([mesh.shape[a] for a in ax_tuple]))
        out.append(axes if dim % n == 0 else None)
    return P(*out)


def _drop_axis(spec: P, axis: str) -> P:
    out = []
    for e in spec:
        if e == axis:
            out.append(None)
        elif isinstance(e, tuple):
            keep = tuple(a for a in e if a != axis)
            out.append(keep if keep else None)
        else:
            out.append(e)
    return P(*out)


def param_specs(params_tree, mesh: Optional[Mesh] = None,
                fsdp: bool = True) -> "pytree[P]":
    """PartitionSpec tree for a parameter (or optimizer-state) pytree.

    ``fsdp=False`` drops the "data" axis from every weight spec (pure TP).
    For models whose optimizer state fits without ZeRO-3 this removes the
    per-layer weight all-gathers entirely — a §Perf hillclimb lever.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_tree)
    specs = []
    for path, leaf in flat:
        sp = _spec_for(_path_str(path), len(leaf.shape))
        if not fsdp:
            sp = _drop_axis(sp, "data")
        specs.append(_fit_spec(sp, leaf.shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# batch / cache rules
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """PartitionSpecs for the input batch pytree."""
    dp = dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    bdim = dp if shape.global_batch % max(n_dp, 1) == 0 \
        and shape.global_batch >= n_dp else None
    tok = P(bdim, None)
    out = {"tokens": tok, "targets": tok}
    if cfg.frontend == "vision":
        out["frontend_embeds"] = P(bdim, None, "model")
    if cfg.enc_dec:
        out["src_embeds"] = P(bdim, None, "model")
    return out


def cache_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, cache_tree):
    """Cache shardings.  batch over DP; heads/features over TP.  For the
    batch=1 long-context shape, sequence axes are sharded over "data"
    (sequence parallelism) instead."""
    dp = dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    seq_par = shape.global_batch < n_dp
    b = None if seq_par else dp

    def spec_for(path, leaf):
        p = _path_str(path)
        seg = p.split("/")[-1]       # exact last key ("conv" must not match "v")
        nd = len(leaf.shape)
        # leading axis is the stacked period axis (scan) — unsharded
        if seg in ("k", "v"):
            # the layouts attention.py gives a layer's self-attention cache
            # and a cross-attention memory (lm.make_cache's "mem")
            heads = cfg.n_kv_heads >= mesh.shape["model"]
            axis = {"batch": b, "heads": "model" if heads else None,
                    "ctx": "data" if seq_par else (None if heads else "model"),
                    "head_dim": None}
            axes = (attention.CROSS_CACHE_AXES if p.startswith("mem/")
                    else attention.ATTN_CACHE_AXES)
            return P(None, *(axis[a] for a in axes))
        if seg == "c_kv":                                # (L,B,S,r)
            return P(None, b, "data" if seq_par else None, "model")
        if seg == "k_rope":                              # (L,B,1,S,dr)
            return P(None, b, None, "data" if seq_par else None, None)
        if seg == "ssd":                                 # (L,B,h,P,N)
            return P(None, b, "model", None, None)
        if seg == "conv":                                # (L,B,W,C)
            return P(None, b, None, "model")
        if seg == "C":                                   # (L,B,h,hd,hd)
            return P(None, b, None, "model", None)
        if seg == "n" and nd == 4:                       # mlstm n (L,B,h,hd)
            return P(None, b, None, "model")
        if nd == 3 and leaf.shape[-1] == cfg.d_model:    # slstm states (L,B,d)
            return P(None, b, "model")
        if nd >= 3:
            return P(None, b, *([None] * (nd - 2)))
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_tree)
    specs = [spec_for(path, leaf) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def scalar_spec():
    return P()
