"""Fused on-device tune path — the cost grid, inf-masking, and greedy
argmin as ONE jitted dispatch.

``core/costmodel_vec.py`` evaluates ``(n_sites, n_actions)`` grids in
float64 NumPy; brute-force `act` is then a host-side argmin and a Python
decode loop.  For serving, that is several host round-trips per request.
This module re-expresses the same pipeline in JAX so a model-oracle
``tune`` is a single device dispatch:

* the three per-kind cost kernels translated op-for-op from
  ``costmodel_vec`` (float32 on device — argmin agreement with the
  float64 reference is asserted in ``tests/test_serving.py``);
* every kind's action-tile grid padded into one ``(3, a_max, 3)``
  constant baked into the trace, with per-kind action counts masking the
  padding columns to ``inf`` so a row argmin *is* the flat action;
* flat-action → head-index decode and tile lookup on device, so the only
  host transfer is the final result arrays.

The batch dimension is padded up to a power-of-two bucket (rows replicate
row 0) so concurrent serving batches of varying size reuse one jit
specialization; ``trace_count`` is incremented *inside* the jitted impl —
i.e. only when XLA (re)traces — and ``dispatch_count`` once per call, the
counters ``BENCH_serving.json`` and the tests use to assert the
one-dispatch/no-per-site-host-sync property.

``surrogate=`` swaps the analytic formulas for the learned cost model
(PR 7): the 19-dim featurizer, z-normalization, and the MLP-ensemble
forward all run inside the same jit, with analytic legality still
masking VMEM-illegal tiles to ``inf``.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import costmodel as cm
from repro.core import costmodel_vec
from repro.core.env import ActionSpace
from repro.core.vectorizer import TileProgram
from repro.models.compute import KernelSite

KINDS = ("matmul", "attention", "chunk_scan")
_KIND_IDX = {k: i for i, k in enumerate(KINDS)}

_LOG_CLAMP = 64.0           # surrogate prior stand-in for log2(inf)


def bucket_size(n: int, floor: int = 8) -> int:
    """Next power of two >= max(n, floor) — bounds distinct jit shapes."""
    b = floor
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# device cost kernels (op-for-op translations of costmodel_vec, float32)
# ---------------------------------------------------------------------------


def _ceil(a, b):
    return -(-a // b)


def _mxu_util(bm, bn, bk):
    u = (jnp.minimum(bm, cm.MXU) / cm.MXU
         * (jnp.minimum(bn, cm.LANE) / cm.LANE))
    u = jnp.where(bm % cm.SUBLANE != 0, u * 0.6, u)
    u = jnp.where(bn % cm.LANE != 0, u * 0.5, u)
    u = u * (bk / (bk + cm.MXU))
    return jnp.maximum(u, 1e-3)


def _matmul_cost(c, t0, t1, t2):
    M, N, K, s, peak = c["m"], c["n"], c["k"], c["s"], c["peak"]
    tm, tn, tk = _ceil(M, t0), _ceil(N, t1), _ceil(K, t2)
    vmem = 2 * (t0 * t2 + t2 * t1) * s + t0 * t1 * 4 + t0 * t1 * s
    legal = vmem <= cm.VMEM_BYTES
    pm = (tm * t0).astype(jnp.float32)
    pn = (tn * t1).astype(jnp.float32)
    pk = (tk * t2).astype(jnp.float32)
    grid = tm.astype(jnp.float32) * tn * tk
    flops = 2.0 * pm * pn * pk
    t_compute = flops / (peak * _mxu_util(t0, t1, t2))
    bytes_ = pm * pk * tn * s + pk * pn * tm * s + pm * pn * s
    t_mem = bytes_ / cm.HBM_BW
    cost = (jnp.maximum(t_compute, t_mem) + grid * cm.GRID_STEP_OVERHEAD
            + cm.FIXED_OVERHEAD)
    return jnp.where(legal, cost, jnp.inf)


def _attention_cost(c, t0, t1, t2):
    # site semantics: m=Sq, k=Skv, n=D, batch=B*H; tiles (bq, bkv, 1)
    Sq, Skv, D, BH = c["m"], c["k"], c["n"], c["batch"]
    causal, s, peak = c["causal"], c["s"], c["peak"]
    bq, bkv = t0, t1
    tq, tkv = _ceil(Sq, bq), _ceil(Skv, bkv)
    vmem = (2 * (bq * D + 2 * bkv * D) * s + bq * D * 4 + 2 * bq * 4
            + bq * bkv * 4)
    legal = vmem <= cm.VMEM_BYTES
    pq = (tq * bq).astype(jnp.float32)
    pkv = (tkv * bkv).astype(jnp.float32)
    grid = BH.astype(jnp.float32) * tq * tkv
    frac = jnp.where(causal, 0.5 * (1 + 1 / jnp.maximum(tq, 1)), 1.0)
    flops = 4.0 * BH * pq * pkv * D * frac
    vpu_ops = 6.0 * BH * pq * pkv * frac
    t_compute = (flops / (peak * _mxu_util(bq, bkv, D))
                 + vpu_ops / (cm.PEAK_FLOPS_BF16 / 16))
    bytes_ = BH * s * (pq * D + 2 * pkv * D * tq * frac + pq * D)
    t_mem = bytes_ / cm.HBM_BW
    cost = (jnp.maximum(t_compute, t_mem)
            + grid * frac * cm.GRID_STEP_OVERHEAD + cm.FIXED_OVERHEAD)
    return jnp.where(legal, cost, jnp.inf)


def _chunk_scan_cost(c, t0, t1, t2):
    # tiles (chunk, 1, 1); P=site.n, N=site.k
    m, P, N, batch, s, peak = c["m"], c["n"], c["k"], c["batch"], c["s"], \
        c["peak"]
    Q = t0
    tokens = batch * m
    vmem = 2 * Q * (P + 2 * N) * s + P * N * 4 + Q * Q * 4
    legal = vmem <= cm.VMEM_BYTES
    chunks_total = _ceil(tokens, Q)
    per_chunk = 2.0 * Q * Q * N + 2.0 * Q * Q * P + 4.0 * Q * P * N
    flops = per_chunk * chunks_total
    t_compute = flops / (peak * _mxu_util(Q, jnp.maximum(P, N), Q))
    bytes_ = tokens.astype(jnp.float32) * (P + 2 * N) * s * 2
    t_mem = bytes_ / cm.HBM_BW
    cost = (jnp.maximum(t_compute, t_mem)
            + chunks_total * cm.GRID_STEP_OVERHEAD + cm.FIXED_OVERHEAD)
    return jnp.where(legal, cost, jnp.inf)


_KIND_COST = (_matmul_cost, _attention_cost, _chunk_scan_cost)


# ---------------------------------------------------------------------------
# site packing (host, one O(n) pass — mirrors costmodel_vec._site_cols)
# ---------------------------------------------------------------------------


def _pack_sites(sites: Sequence[KernelSite], pad_to: int):
    rows = [(s.m, s.n, s.k, s.batch, s.causal,
             *costmodel_vec._dtype_meta(s.dtype)) for s in sites]
    if pad_to > len(rows):                  # replicate row 0 into padding
        rows = rows + [rows[0]] * (pad_to - len(rows))
    m, n, k, b, causal, sb, peak = zip(*rows)
    cols = {"m": np.array(m, np.int32), "n": np.array(n, np.int32),
            "k": np.array(k, np.int32), "batch": np.array(b, np.int32),
            "causal": np.array(causal, bool), "s": np.array(sb, np.int32),
            "peak": np.array(peak, np.float32)}
    unknown = sorted({s.kind for s in sites} - set(KINDS))
    if unknown:
        # the fused grid holds no pricing for these: refuse, do not guess
        raise ValueError(f"FusedTuner cannot price site kinds {unknown}; "
                         f"it prices {KINDS}")
    kind_idx = np.array([_KIND_IDX[s.kind] for s in sites]
                        + [0] * (pad_to - len(sites)), np.int32)
    return cols, kind_idx


class FusedTuner:
    """Model/surrogate-oracle tuning as one jitted device dispatch.

    ``actions(sites)`` returns the same ``(n, 3)`` head indices as the
    brute-force argmin over ``oracle.cost_grid`` (flat-action order and
    argmin tie-breaking preserved); ``tune(sites)`` wraps them into a
    :class:`TileProgram`.  Pass ``surrogate=`` (a trained
    :class:`~repro.surrogate.model.SurrogateModel`) to price the grid
    with the learned model instead of the analytic formulas.
    """

    def __init__(self, cfg, surrogate=None):
        self.space = ActionSpace(cfg)
        self.surrogate = surrogate
        grids = {k: costmodel_vec.action_tiles_grid(self.space, k)
                 for k in KINDS}
        self._a_max = max(len(g) for g in grids.values())
        # padded per-kind tile grids + action counts + head sizes: numpy
        # constants closed over by the impl, baked in at trace time
        G = np.ones((3, self._a_max, 3), np.int32)
        NA = np.zeros((3,), np.int32)
        VS = np.ones((3, 3), np.int32)
        for i, k in enumerate(KINDS):
            G[i, :len(grids[k])] = grids[k]
            NA[i] = len(grids[k])
            VS[i] = self.space.valid_sizes(k)
        self._G, self._NA, self._VS = G, NA, VS
        if surrogate is not None:
            self._sur_params = jax.tree.map(jnp.asarray, surrogate.params)
            self._sur_stats = (
                jnp.asarray(surrogate.x_mean, jnp.float32),
                jnp.asarray(np.asarray(surrogate.x_std, np.float64),
                            jnp.float32))
        self._jit = jax.jit(self._impl)
        self.trace_count = 0      # bumped inside the impl: only on (re)trace
        self.dispatch_count = 0   # bumped once per tune/actions call
        self.sites_tuned = 0
        self.last_padded_batch = 0

    # -- the fused pipeline (everything below runs inside one jit) ----------
    def _surrogate_pred(self, c, kidx, t, grid_steps, vmem, analytic):
        """(B, a_max) predicted seconds from the 19-dim featurizer + the
        MLP-ensemble forward, all on device (feature layout matches
        ``surrogate/features.py::featurize`` column-for-column)."""
        B, A = kidx.shape[0], self._a_max

        def col(x):                         # (B,) -> (B, a_max, 1)
            return jnp.broadcast_to(
                x.astype(jnp.float32)[:, None, None], (B, A, 1))

        lt = jnp.log2(jnp.maximum(t.astype(jnp.float32), 1e-30))
        ldims = jnp.log2(jnp.stack(
            [c["m"], c["n"], c["k"], c["batch"]], -1).astype(jnp.float32))
        prior = jnp.where(jnp.isfinite(analytic),
                          jnp.log2(jnp.maximum(analytic, 1e-30)),
                          _LOG_CLAMP)
        feats = ([col(kidx == i) for i in range(3)]            # 0-2 one-hot
                 + [col(ldims[:, i]) for i in range(4)]        # 3-6 dims
                 + [col(c["s"]),                               # 7 bytes
                    col(c["causal"]),                          # 8 causal
                    lt,                                        # 9-11 tiles
                    lt - ldims[:, None, :3],                   # 12-14 ratios
                    jnp.log2(jnp.maximum(vmem, 1e-30))[..., None],   # 15
                    (vmem / cm.VMEM_BYTES)[..., None],               # 16
                    jnp.log2(jnp.maximum(grid_steps, 1.0))[..., None],  # 17
                    prior[..., None]])                               # 18
        X = jnp.concatenate(feats, -1).reshape(-1, 19)    # (B*a_max, 19)
        x_mean, x_std = self._sur_stats
        Xn = (X - x_mean) / x_std
        preds = []
        for member in self._sur_params:
            h = Xn
            for layer in member[:-1]:
                h = jnp.tanh(h @ layer["w"] + layer["b"])
            preds.append((h @ member[-1]["w"] + member[-1]["b"])[:, 0])
        pred = jnp.mean(jnp.stack(preds), 0)
        pred = pred * self.surrogate.y_std + self.surrogate.y_mean
        return jnp.exp(pred).reshape(B, A)   # log-seconds -> seconds

    def _analytic(self, c, kidx, t):
        """(B, a_max) analytic costs with per-kind selection."""
        t0, t1, t2 = t[..., 0], t[..., 1], t[..., 2]
        cc = {k: (v[:, None] if v.ndim == 1 else v) for k, v in c.items()}
        costs = [fn(cc, t0, t1, t2) for fn in _KIND_COST]
        return jnp.select([kidx[:, None] == i for i in range(3)], costs)

    def _vmem_grid(self, c, kidx, t):
        """(B, a_max) VMEM footprint + grid steps per the featurizer's
        formulas (``surrogate/features.py::_vmem_and_grid``)."""
        t0 = t[..., 0].astype(jnp.float32)
        t1 = t[..., 1].astype(jnp.float32)
        t2 = t[..., 2].astype(jnp.float32)
        m = c["m"].astype(jnp.float32)[:, None]
        n = c["n"].astype(jnp.float32)[:, None]
        k = c["k"].astype(jnp.float32)[:, None]
        b = c["batch"].astype(jnp.float32)[:, None]
        s = c["s"].astype(jnp.float32)[:, None]
        vmems = jnp.stack([
            2 * (t0 * t2 + t2 * t1) * s + t0 * t1 * 4 + t0 * t1 * s,
            (2 * (t0 * n + 2 * t1 * n) * s + t0 * n * 4 + 2 * t0 * 4
             + t0 * t1 * 4),
            2 * t0 * (n + 2 * k) * s + n * k * 4 + t0 * t0 * 4])
        grids = jnp.stack([
            jnp.ceil(m / t0) * jnp.ceil(n / t1) * jnp.ceil(k / t2),
            b * jnp.ceil(m / t0) * jnp.ceil(k / t1),
            jnp.ceil(b * m / t0)])
        sel = [kidx[:, None] == i for i in range(3)]
        return jnp.select(sel, list(vmems)), \
            jnp.maximum(jnp.select(sel, list(grids)), 1.0)

    def _impl(self, cols, kind_idx):
        self.trace_count += 1
        t = jnp.asarray(self._G)[kind_idx]          # (B, a_max, 3)
        analytic = self._analytic(cols, kind_idx, t)
        if self.surrogate is not None:
            vmem, grid_steps = self._vmem_grid(cols, kind_idx, t)
            pred = self._surrogate_pred(cols, kind_idx, t, grid_steps, vmem,
                                        analytic)
            # a tile the analytic model rejects has no runtime to predict
            cost = jnp.where(jnp.isfinite(analytic), pred, jnp.inf)
        else:
            cost = analytic
        pad = (jnp.arange(self._a_max)[None, :]
               >= jnp.asarray(self._NA)[kind_idx][:, None])
        cost = jnp.where(pad, jnp.inf, cost)
        flat = jnp.argmin(cost, axis=1)             # first-min, like numpy
        tiles = jnp.take_along_axis(t, flat[:, None, None], 1)[:, 0]
        vs = jnp.asarray(self._VS)[kind_idx]        # (B, 3) head sizes
        heads = jnp.stack([flat // (vs[:, 1] * vs[:, 2]),
                           (flat // vs[:, 2]) % vs[:, 1],
                           flat % vs[:, 2]], -1)
        best = jnp.take_along_axis(cost, flat[:, None], 1)[:, 0]
        return heads, tiles, best

    # -- host entry points ---------------------------------------------------
    def _run(self, sites: Sequence[KernelSite]):
        n = len(sites)
        b = bucket_size(n)
        cols, kind_idx = _pack_sites(sites, b)
        heads, tiles, best = self._jit(cols, kind_idx)
        self.dispatch_count += 1
        self.sites_tuned += n
        self.last_padded_batch = b
        return (np.asarray(heads)[:n], np.asarray(tiles)[:n],
                np.asarray(best)[:n])

    def actions(self, sites: Sequence[KernelSite]) -> np.ndarray:
        """(n, 3) greedy head indices — the device-side brute argmin."""
        if not len(sites):
            return np.zeros((0, 3), np.int64)
        return self._run(sites)[0].astype(np.int64)

    def tune(self, sites: Sequence[KernelSite]) -> TileProgram:
        """Greedy tiles for ``sites`` as one device dispatch."""
        if not len(sites):
            return TileProgram()
        _, tiles, _ = self._run(sites)
        return TileProgram({s.key(): tuple(int(x) for x in t)
                            for s, t in zip(sites, tiles)})

    def tune_many(self, site_lists) -> "list[TileProgram]":
        """One program per request from ONE dispatch over the
        concatenation (the fused route of the serving micro-batcher) —
        the per-site costs are row-independent, so each slice is bitwise
        equal to tuning that request alone."""
        flat = [s for sl in site_lists for s in sl]
        if not flat:
            return [TileProgram() for _ in site_lists]
        _, tiles, _ = self._run(flat)
        out, off = [], 0
        for sl in site_lists:
            out.append(TileProgram(
                {s.key(): tuple(int(x) for x in t)
                 for s, t in zip(sl, tiles[off:off + len(sl)])}))
            off += len(sl)
        return out

    def stats(self) -> Dict[str, float]:
        return {"serving_fused_dispatches_total": self.dispatch_count,
                "serving_fused_traces_total": self.trace_count,
                "serving_fused_sites_total": self.sites_tuned}
