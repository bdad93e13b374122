"""Site-aware compute wrappers — the injection point for the paper's technique.

NeuroVectorizer injects ``#pragma clang loop vectorize_width(VF)
interleave_count(IF)`` above each loop.  Here, every tunable hot op in the
model zoo goes through :func:`matmul` / :func:`flash_attention` /
:func:`decode_attention` with a *site* label.  Three modes:

* ``xla``     — plain jnp ops (the default; what the dry-run lowers);
  grouped matmuls run ``jax.lax.ragged_dot``.
* ``pallas``  — route through the Pallas kernels in ``repro.kernels`` using
  tile factors from the active :class:`TileProgram` (the "pragma" — see
  ``repro.core.vectorizer``).  Missing sites fall back to the heuristic
  baseline tiles, exactly as un-pragma'd loops fall back to LLVM's cost model.
* recording   — a :class:`SiteRecorder` is installed; tracing a step function
  (``jax.eval_shape``) registers every site with its concrete shapes/dtypes.
  This is the paper's *loop extractor* (DESIGN.md §2).

In ``pallas`` mode a matmul or grouped-matmul weight that is one layer of
the layer scan's stacked parameters (:func:`layer_params`) reaches its
kernel as the whole stack and the scan index, and the kernel reads the
layer where it lies: no copy of it is made.  Each such operand is counted,
at trace time, by how it arrived (:func:`weight_reads`).

Every call runs its ops, in every mode, under ``jax.named_scope("site=<site>")``:
the GQA repeat, the decode cache's column write, the grouped rows' scatter
and gather, the ``pallas_call`` in ``pallas`` mode, the jnp ops in ``xla``
mode.  XLA keeps the scope in each
instruction's ``op_name`` metadata, so the device ops an operator sees in a
profile (TensorBoard, Perfetto) name their site, and the benchmark reads
per-site device time from the same scope.  It is trace-time metadata and
costs nothing at run time.  A fusion takes its root's ``op_name``: an op of
the site fused into a consumer outside it is billed to the consumer.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import math
import sys
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Global mode (single-threaded tracing; a context stack is sufficient)
# ---------------------------------------------------------------------------


@dataclass
class _ComputeState:
    mode: str = "xla"                  # "xla" | "pallas"
    tiles: Optional[dict] = None       # site -> tile tuple (the TileProgram)
    recorder: Optional["SiteRecorder"] = None
    interpret: bool = False            # Pallas interpret mode (CPU validation)


_STATE = _ComputeState()


@contextlib.contextmanager
def compute_mode(mode: str = "xla", tiles: Optional[dict] = None,
                 recorder: Optional["SiteRecorder"] = None,
                 interpret: bool = False):
    global _STATE
    prev = _STATE
    _STATE = _ComputeState(mode=mode, tiles=tiles, recorder=recorder,
                           interpret=interpret)
    try:
        yield _STATE
    finally:
        _STATE = prev


# ---------------------------------------------------------------------------
# Activation-sharding hints.  Model code is mesh-agnostic; the launcher
# installs logical axis names (dp tuple, tp name) and hot activations get
# pinned with with_sharding_constraint.  Without hints (unit tests, single
# device) every constraint is a no-op.  GSPMD otherwise occasionally drops
# the batch sharding of scan carries / one-hots and replicates multi-GiB
# tensors (observed on the 256-chip dry-run — see DESIGN.md §6).
# ---------------------------------------------------------------------------

_HINTS: dict = {"active": False, "dp": None, "tp": None,
                "carry_tp": True}


@contextlib.contextmanager
def sharding_hints(dp, tp, carry_tp: bool = True):
    prev = dict(_HINTS)
    _HINTS.update(active=True, dp=dp, tp=tp, carry_tp=carry_tp)
    try:
        yield
    finally:
        _HINTS.update(prev)


def constrain(x: jax.Array, builder):
    """builder(dp, tp) -> PartitionSpec; applied only when hints active."""
    if not _HINTS["active"]:
        return x
    spec = builder(_HINTS["dp"], _HINTS["tp"])
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Site recording (the "loop extractor" output format)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KernelSite:
    """A tunable kernel instance — the analogue of one extracted loop."""

    site: str            # stable label, e.g. "attn.qkv_proj"
    kind: str            # "matmul" | "grouped_matmul" | "attention" |
                         # "chunk_scan"
    m: int               # rows (tokens) — matmul M / attention q_len;
                         # grouped: the rows all groups expect together
    n: int               # cols — matmul N / attention head_dim
    k: int               # contraction — matmul K / attention kv_len
    batch: int = 1       # leading batch (attention B*heads; matmul 1;
                         # grouped matmul: the number of groups)
    dtype: str = "bfloat16"
    transpose: str = "nn"    # operand layouts
    causal: bool = False
    fused_ops: int = 0       # elementwise ops fused at the site (bias/act)

    def key(self) -> str:
        # memoized: key() sits on the batched-oracle hot path (baseline
        # cache, TileProgram lookups) and the dataclass is frozen
        k = self.__dict__.get("_key")
        if k is None:
            k = (f"{self.kind}:{self.site}:m{self.m}n{self.n}k{self.k}"
                 f"b{self.batch}:{self.dtype}:{self.transpose}"
                 f"{':c' if self.causal else ''}:f{self.fused_ops}")
            object.__setattr__(self, "_key", k)
        return k


class SiteRecorder:
    def __init__(self):
        self.sites: dict[str, KernelSite] = {}

    def record(self, s: KernelSite):
        self.sites[s.key()] = s

    def unique_sites(self) -> list[KernelSite]:
        return list(self.sites.values())


# ---------------------------------------------------------------------------
# weights read where they lie in the stacked parameters
# ---------------------------------------------------------------------------

#: id(slice) -> (slice, stack, index) for each slice ``layer_params`` made
_LAYERS: dict = {}
#: the open ``weight_reads`` tallies
_TALLIES: list = []


def current_mode() -> str:
    return _STATE.mode


@contextlib.contextmanager
def layer_params(stack, index):
    """Layer ``index`` of each leaf of the scanned parameters ``stack``
    (leaves (L, ...)), sliced.  While the context is open, a Pallas matmul
    or grouped matmul given one of these slices as its weight gets the
    whole stack and ``index`` instead, and reads the layer where it lies;
    the slice is then left unused and XLA drops it.  Every other use
    (norms, biases, einsums) reads the slice."""
    leaves, tree = jax.tree.flatten(stack)
    sliced = [jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False)
              for a in leaves]
    for s, a in zip(sliced, leaves):
        _LAYERS[id(s)] = (s, a, index)
    try:
        yield jax.tree.unflatten(tree, sliced)
    finally:
        for s in sliced:
            _LAYERS.pop(id(s), None)


def _pallas_weight(w: jax.Array, kind: str, site: str):
    """The weight operand of a Pallas kernel and the layer it reads: the
    whole stack and the index where ``w`` is a slice ``layer_params``
    made (``in_place``), else ``w`` itself and None (``materialized``: an
    array of its own, such as ``lm_head``'s transposed embedding).
    Counted in the ``compute_pallas_weights_total`` counter and the open
    ``weight_reads`` tallies."""
    from repro.obs.metrics import get_registry
    hit = _LAYERS.get(id(w))
    in_place = hit is not None and hit[0] is w
    how = "in_place" if in_place else "materialized"
    get_registry().counter(
        "compute_pallas_weights_total",
        "Pallas kernel weight operands traced, by how each arrived: "
        "in_place (the whole stack and the layer index) or materialized",
        labelnames=("kind", "site", "how")).labels(
            kind=kind, site=site, how=how).inc()
    for tally in _TALLIES:
        tally[kind, site, how] += 1
    return hit[1:] if in_place else (w, None)


@contextlib.contextmanager
def weight_reads(step: str):
    """Tally, while ``step`` traces, how each Pallas kernel got its weight,
    and print the tally to stderr once the trace ends: one line for each
    traced, so each compiled, step.  Yields the tally, a Counter of
    (kind, site, how) with ``how`` ``in_place`` or ``materialized``."""
    tally = collections.Counter()
    _TALLIES.append(tally)
    try:
        yield tally
    finally:
        _TALLIES.remove(tally)
    if tally:
        parts = []
        for how in ("in_place", "materialized"):
            sites = sorted({s for _, s, h in tally if h == how})
            n = sum(c for (_, _, h), c in tally.items() if h == how)
            parts.append(f"{how} {n}" + (f" ({' '.join(sites)})"
                                         if sites else ""))
        print(f"[serve] {step} step, Pallas weight operands: "
              f"{', '.join(parts)}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# matmul wrapper
# ---------------------------------------------------------------------------


def matmul(x: jax.Array, w: jax.Array, *, site: str,
           fused_ops: int = 0) -> jax.Array:
    """``x @ w`` where x is (..., K) and w is (K, N).  In ``pallas`` mode a
    ``w`` that :func:`layer_params` sliced reaches the kernel as the whole
    stack and the layer index."""
    *lead, K = x.shape
    K2, N = w.shape
    assert K == K2, (site, x.shape, w.shape)
    M = int(math.prod(lead)) if lead else 1
    st = _STATE
    if st.recorder is not None:
        st.recorder.record(KernelSite(
            site=site, kind="matmul", m=M, n=int(N), k=int(K),
            dtype=str(x.dtype), fused_ops=fused_ops))
    with jax.named_scope(f"site={site}"):
        if st.mode == "pallas":
            from repro.kernels import ops as kops
            ksite = KernelSite(site=site, kind="matmul", m=M, n=int(N),
                               k=int(K), dtype=str(x.dtype),
                               fused_ops=fused_ops)
            tiles = None if st.tiles is None else st.tiles.get(ksite.key())
            w, layer = _pallas_weight(w, "matmul", site)
            y = kops.matmul(x.reshape(M, K), w, tiles=tiles,
                            interpret=st.interpret, layer=layer)
            return y.reshape(*lead, N)
        return jnp.matmul(x, w)


def grouped_matmul(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
                   site: str, rows: Optional[int] = None) -> jax.Array:
    """Ragged product: ``x`` (M, K) holds the rows of group 0, then of
    group 1, ..., then rows of no group; group g's rows times ``w[g]``
    (K, N).  ``group_sizes`` (G,) int32 may hold zeros.  Returns (M, N),
    rows past ``sum(group_sizes)`` zero.

    Recorded as a ``grouped_matmul`` site (m = ``rows``, the rows the
    groups expect together, M where not given; n, k; batch = G).  In
    ``pallas`` mode the grouped-matmul kernel runs it on the plan's
    (bm, bn, bk), and a ``w`` that :func:`layer_params` sliced reaches it
    as the whole stack (L, G, K, N) and the layer index; in ``xla`` mode
    ``jax.lax.ragged_dot``, which has a gradient."""
    M, K = x.shape
    G, K2, N = w.shape
    assert K == K2, (site, x.shape, w.shape)
    st = _STATE
    ksite = KernelSite(site=site, kind="grouped_matmul",
                       m=int(M if rows is None else rows), n=int(N),
                       k=int(K), batch=int(G), dtype=str(x.dtype))
    if st.recorder is not None:
        st.recorder.record(ksite)
    with jax.named_scope(f"site={site}"):
        if st.mode == "pallas":
            from repro.core.costmodel import baseline_tiles
            from repro.kernels import ops as kops
            tiles = None if st.tiles is None else st.tiles.get(ksite.key())
            w, layer = _pallas_weight(w, "grouped_matmul", site)
            return kops.grouped_matmul(
                x, w, group_sizes, interpret=st.interpret, layer=layer,
                tiles=tuple(tiles or baseline_tiles(ksite)))
        return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32))


def einsum(spec: str, *args, site: str) -> jax.Array:
    """Non-canonical contractions (per-head block-diagonal projections etc.).

    Recorded as a matmul site with flattened dims; always executed by XLA —
    the Pallas path only specializes the canonical (M,K)x(K,N) shape.
    """
    st = _STATE
    if st.recorder is not None:
        out = jax.eval_shape(lambda *a: jnp.einsum(spec, *a), *args)
        n = int(out.shape[-1])
        m = int(math.prod(out.shape[:-1])) if out.ndim > 1 else 1
        # contraction length from the (last) weight operand
        k = int(args[-1].shape[-2]) if args[-1].ndim >= 2 else 1
        st.recorder.record(KernelSite(
            site=site, kind="matmul", m=m, n=n, k=k,
            dtype=str(args[0].dtype)))
    with jax.named_scope(f"site={site}"):
        return jnp.einsum(spec, *args)


# ---------------------------------------------------------------------------
# attention wrapper (chunked online-softmax "flash" reference in XLA)
# ---------------------------------------------------------------------------


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    site: str, causal: bool,
                    q_chunk: int = 1024, kv_chunk: int = 2048,
                    scale: Optional[float] = None) -> jax.Array:
    """Memory-chunked attention.

    q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D) with Hq % Hkv == 0 (GQA).
    Causal masks are bottom-right aligned: the last query sees every key.
    Self-attention decode goes through :func:`decode_attention`.

    In ``pallas`` mode routes to the flash-attention kernel with tuned
    (block_q, block_kv); in ``xla`` mode runs the same algorithm with
    lax.scan over chunks so 32k-prefill never materializes (Sq, Skv) scores.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    st = _STATE
    if st.recorder is not None:
        st.recorder.record(KernelSite(
            site=site, kind="attention", m=Sq, n=D, k=Skv, batch=B * Hq,
            dtype=str(q.dtype), causal=causal))
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    with jax.named_scope(f"site={site}"):
        return _attention(q, k, v, site=site, causal=causal, q_chunk=q_chunk,
                          kv_chunk=kv_chunk, scale=scale)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, cache: dict,
                     *, pos, layer, site: str) -> tuple:
    """One decode position of causal self-attention against layer
    ``layer`` of the layer scan's stacked K/V cache, in its stored layout,
    ctx last: ``cache["k"]``, ``cache["v"]`` are ``(L, B, Hkv, hd, ctx)``.

    q: (B, Hq, 1, hd); k, v: (B, Hkv, 1, hd), this position's keys and
    values, written into the stack at ``(layer, ..., pos)`` before it is
    read.  Returns (o (B, Hq, 1, hd), the written stack).

    Recorded as the same attention site as :func:`flash_attention` with
    ``Sq == 1``.  In ``pallas`` mode one decode-kernel call writes the
    column into the stack in place and reads the layer where it lies; in
    ``xla`` mode a plain einsum reads the layer, sliced out and written
    back.
    """
    B, Hq, _, D = q.shape
    Hkv, Skv = k.shape[1], cache["k"].shape[-1]
    st = _STATE
    if st.recorder is not None:
        st.recorder.record(KernelSite(
            site=site, kind="attention", m=1, n=D, k=Skv, batch=B * Hq,
            dtype=str(q.dtype), causal=True))
    scale = 1.0 / math.sqrt(D)
    with jax.named_scope(f"site={site}"):
        if st.mode == "pallas":
            from repro.kernels import ops as kops
            o, kc, vc = kops.decode_attention(
                q[:, :, 0], k[:, :, 0], v[:, :, 0], cache["k"], cache["v"],
                layer, pos, scale=scale, interpret=st.interpret)
            return o[:, :, None], {"k": kc, "v": vc}
        # the column goes in by a select over the layer: XLA lays out a
        # cache written by a one-column dynamic-update-slice with ctx major,
        # and relayouts the whole stacked carry to do so
        hit = jnp.arange(Skv) == pos
        kv = {n: jnp.where(hit, jnp.swapaxes(x, -1, -2).astype(
            cache[n].dtype), jax.lax.dynamic_index_in_dim(
                cache[n], layer, 0, keepdims=False))
            for n, x in (("k", k), ("v", v))}
        qg = q.reshape(B, Hkv, Hq // Hkv, D)
        s = jnp.einsum("bhgd,bhdk->bhgk", qg, kv["k"]).astype(jnp.float32)
        s = jnp.where(jnp.arange(Skv) <= pos, s * scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bhgk,bhdk->bhgd", p, kv["v"])
        new = {n: jax.lax.dynamic_update_index_in_dim(cache[n], kv[n], layer,
                                                      0) for n in kv}
    return o.reshape(B, Hq, 1, D), new


def _attention(q, k, v, *, site, causal, q_chunk, kv_chunk, scale):
    """The body of :func:`flash_attention`, inside its site scope."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[-1]        # MLA: v head dim may differ from qk head dim
    st = _STATE
    if st.mode == "pallas" and Sq > 1:
        from repro.kernels import ops as kops
        ksite = KernelSite(site=site, kind="attention", m=Sq, n=D, k=Skv,
                           batch=B * Hq, dtype=str(q.dtype), causal=causal)
        tiles = None if st.tiles is None else st.tiles.get(ksite.key())
        return kops.flash_attention(q, k, v, causal=causal, scale=scale,
                                    tiles=tiles, interpret=st.interpret)

    if _HINTS["active"] and Sq > 1:
        # Megatron-style TP attention: expand GQA groups so heads shard
        # over "model" even when Hq % tp != 0 (GSPMD pads intermediates;
        # without the explicit constraint it falls back to full replication
        # of the (bq, bkv) score blocks — observed 2+ GiB/device).
        from jax.sharding import PartitionSpec as _P
        if Hq != Hkv:
            k = jnp.repeat(k, Hq // Hkv, axis=1)
            v = jnp.repeat(v, Hq // Hkv, axis=1)
            Hkv = Hq
        hspec = lambda dp, tp: _P(dp if B > 1 else None, tp, None, None)
        q = constrain(q, hspec)
        k = constrain(k, hspec)
        v = constrain(v, hspec)

    if Sq == 1:
        group = Hq // Hkv
        qf = q.reshape(B, Hkv, group, Sq, D)
        # one query (cross-attention decode): it sees every key, causal
        # or not, and needs no chunking
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, k).astype(jnp.float32) * scale
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v)
        return o.reshape(B, Hq, Sq, Dv)

    # prefill / train: memory-efficient attention with a flash-style custom
    # VJP.  A plain scan-based implementation saves its per-step (bq, bkv)
    # probability blocks for backward — at 32L x 32k that is tens of GiB per
    # device (measured).  The custom VJP saves only (q, k, v, o, lse) and
    # recomputes blocks in the backward scans.
    if Hq != Hkv:                       # expand GQA groups (grad sums back)
        k = jnp.repeat(k, Hq // Hkv, axis=1)
        v = jnp.repeat(v, Hq // Hkv, axis=1)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    assert Sq % q_chunk == 0 and Skv % kv_chunk == 0, (Sq, Skv)
    return _mem_efficient_attention(
        q, k, v, causal=causal, scale=scale, bq=q_chunk, bkv=kv_chunk)


# ---------------------------------------------------------------------------
# memory-efficient attention (custom VJP, flash algorithm in XLA)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _mem_efficient_attention(q, k, v, causal, scale, bq, bkv):
    o, _ = _mea_fwd_impl(q, k, v, causal, scale, bq, bkv)
    return o


def _mea_fwd_impl(q, k, v, causal, scale, bq, bkv):
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    Dv = v.shape[-1]
    n_q, n_kv = Sq // bq, Skv // bkv
    kc = jnp.moveaxis(k.reshape(B, H, n_kv, bkv, D), 2, 0)
    vc = jnp.moveaxis(v.reshape(B, H, n_kv, bkv, Dv), 2, 0)
    qc = jnp.moveaxis(q.reshape(B, H, n_q, bq, D), 2, 0)

    def q_body(_, qi_idx):
        qi, iq = qi_idx                            # (B,H,bq,D)
        # bottom-right aligned causal offset, matching ref/pallas kernels
        q_pos = iq * bq + jnp.arange(bq) + (Skv - Sq)

        def kv_body(carry, kv_idx):
            m, l, acc = carry
            kj, vj, ik = kv_idx
            s = jnp.einsum("bhqd,bhkd->bhqk", qi, kj).astype(jnp.float32)
            s = s * scale
            if causal:
                k_pos = ik * bkv + jnp.arange(bkv)
                mask = k_pos[None, :] <= q_pos[:, None]
                s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vj.dtype), vj
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        init = (jnp.full((B, H, bq), NEG_INF, jnp.float32),
                jnp.zeros((B, H, bq), jnp.float32),
                jnp.zeros((B, H, bq, Dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_body, init,
                                      (kc, vc, jnp.arange(n_kv)))
        l = jnp.maximum(l, 1e-30)
        o = (acc / l[..., None]).astype(q.dtype)
        lse = m + jnp.log(l)
        return None, (o, lse)

    _, (o, lse) = jax.lax.scan(q_body, None, (qc, jnp.arange(n_q)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, Sq, Dv)
    lse = jnp.moveaxis(lse, 0, 2).reshape(B, H, Sq)
    return o, lse


def _mea_fwd(q, k, v, causal, scale, bq, bkv):
    o, lse = _mea_fwd_impl(q, k, v, causal, scale, bq, bkv)
    return o, (q, k, v, o, lse)


def _mea_bwd(causal, scale, bq, bkv, res, do):
    q, k, v, o, lse = res
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    Dv = v.shape[-1]
    n_q, n_kv = Sq // bq, Skv // bkv
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)  # BHS

    qc = jnp.moveaxis(q.reshape(B, H, n_q, bq, D), 2, 0)
    doc = jnp.moveaxis(do.reshape(B, H, n_q, bq, Dv), 2, 0)
    lsec = jnp.moveaxis(lse.reshape(B, H, n_q, bq), 2, 0)
    dltc = jnp.moveaxis(delta.reshape(B, H, n_q, bq), 2, 0)
    kc = jnp.moveaxis(k.reshape(B, H, n_kv, bkv, D), 2, 0)
    vc = jnp.moveaxis(v.reshape(B, H, n_kv, bkv, Dv), 2, 0)

    def kv_body(dq, kv_idx):
        kj, vj, ik = kv_idx
        k_pos = ik * bkv + jnp.arange(bkv)

        def q_body(carry, q_idx):
            dkj, dvj = carry
            qi, doi, lsei, dlti, iq = q_idx
            q_pos = iq * bq + jnp.arange(bq) + (Skv - Sq)
            s = jnp.einsum("bhqd,bhkd->bhqk", qi, kj).astype(jnp.float32)
            s = s * scale
            if causal:
                mask = k_pos[None, :] <= q_pos[:, None]
                s = jnp.where(mask[None, None], s, NEG_INF)
            p = jnp.exp(s - lsei[..., None])               # (B,H,bq,bkv)
            dvj = dvj + jnp.einsum("bhqk,bhqd->bhkd", p,
                                   doi.astype(jnp.float32))
            dp = jnp.einsum("bhqd,bhkd->bhqk", doi.astype(jnp.float32),
                            vj.astype(jnp.float32))
            ds = p * (dp - dlti[..., None]) * scale
            dkj = dkj + jnp.einsum("bhqk,bhqd->bhkd", ds,
                                   qi.astype(jnp.float32))
            dqi = jnp.einsum("bhqk,bhkd->bhqd", ds, kj.astype(jnp.float32))
            return (dkj, dvj), dqi

        init = (jnp.zeros((B, H, bkv, D), jnp.float32),
                jnp.zeros((B, H, bkv, Dv), jnp.float32))
        (dkj, dvj), dq_blocks = jax.lax.scan(
            q_body, init, (qc, doc, lsec, dltc, jnp.arange(n_q)))
        dq = dq + jnp.moveaxis(dq_blocks, 0, 2).reshape(B, H, Sq, D)
        return dq, (dkj, dvj)

    dq0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(kv_body, dq0, (kc, vc, jnp.arange(n_kv)))
    dk = jnp.moveaxis(dk, 0, 2).reshape(B, H, Skv, D)
    dv = jnp.moveaxis(dv, 0, 2).reshape(B, H, Skv, Dv)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_mem_efficient_attention.defvjp(_mea_fwd, _mea_bwd)
