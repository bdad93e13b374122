"""Vectorized (NumPy) cost-model engine — the batched reward oracle.

The scalar functions in :mod:`repro.core.costmodel` price one (site, tile)
pair per interpreted Python call; that made the reward oracle the slowest
thing in the repo (every RL step, every brute-force label, every benchmark
figure walks it point-by-point).  This module evaluates whole
``(n_sites, n_actions)`` grids at once with float64 NumPy, keeping every
expression in the *same evaluation order* as the scalar model so the two
agree to ~1e-9 relative on all legal tiles (property-tested in
``tests/test_costmodel_vec.py``).

Illegal tiles (VMEM overflow — the paper's compile-timeout analogue) are
``np.inf`` entries instead of ``None``, so downstream consumers can mask,
argmin, and broadcast without branching:

* :func:`cost_grid` — the full per-site action-grid cost tensor (brute
  force becomes a single argmin; see ``agents/brute.py``).
* :func:`costs_for_actions` — one chosen action per site (the
  ``CostModelEnv.rewards_batch`` fast path).
* :func:`baseline_costs` — vectorized heuristic-baseline pricing (feeds
  the environment's per-site baseline cache).

All site-metadata packing is O(n_sites) Python; the pricing itself is pure
array math.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.core import costmodel as cm
from repro.models.compute import KernelSite

ILLEGAL = np.inf


# ---------------------------------------------------------------------------
# vectorized primitives (exact array translations of the scalar model)
# ---------------------------------------------------------------------------


def _ceil(a, b):
    return -(-a // b)


def _mxu_util_vec(bm, bn, bk):
    """Array version of ``costmodel._mxu_util`` (same op order)."""
    u = np.minimum(bm, cm.MXU) / cm.MXU * (np.minimum(bn, cm.LANE) / cm.LANE)
    u = np.where(bm % cm.SUBLANE != 0, u * 0.6, u)
    u = np.where(bn % cm.LANE != 0, u * 0.5, u)
    u = u * (bk / (bk + cm.MXU))
    return np.maximum(u, 1e-3)


def matmul_cost_vec(M, N, K, s, peak, bm, bn, bk) -> np.ndarray:
    """Broadcasted ``matmul_cost``: site params (n, 1) x tiles (1, a)."""
    tm, tn, tk = _ceil(M, bm), _ceil(N, bn), _ceil(K, bk)
    vmem = 2 * (bm * bk + bk * bn) * s + bm * bn * 4 + bm * bn * s
    legal = vmem <= cm.VMEM_BYTES
    # padded extents promoted to float64 up front: the byte/flop/grid
    # products overflow int64 for dims ~2^22+, while float64 stays exact
    # below 2^53 and within ~1e-16 relative beyond (scalar parity holds)
    pm = (tm * bm).astype(np.float64)
    pn = (tn * bn).astype(np.float64)
    pk = (tk * bk).astype(np.float64)
    grid = tm.astype(np.float64) * tn * tk
    flops = 2.0 * pm * pn * pk
    t_compute = flops / (peak * _mxu_util_vec(bm, bn, bk))
    bytes_ = pm * pk * tn * s + pk * pn * tm * s + pm * pn * s
    t_mem = bytes_ / cm.HBM_BW
    cost = (np.maximum(t_compute, t_mem) + grid * cm.GRID_STEP_OVERHEAD
            + cm.FIXED_OVERHEAD)
    return np.where(legal, cost, ILLEGAL)


def fit_tile_vec(dim, t) -> np.ndarray:
    """Broadcasted ``costmodel.fit_tile``."""
    dim, t = np.broadcast_arrays(np.asarray(dim, np.int64),
                                 np.asarray(t, np.int64))
    top = max(int(t.max(initial=0)) // cm.LANE, 1)
    b = np.arange(top, 0, -1, dtype=np.int64) * cm.LANE     # descending
    ok = (b <= t[..., None]) & (dim[..., None] % b == 0)
    best = np.where(ok.any(-1), b[np.argmax(ok, axis=-1)], dim)
    return np.where(dim <= t, dim, best)


def grouped_matmul_cost_vec(M, N, K, G, s, peak, bm, bn, bk) -> np.ndarray:
    """Broadcasted ``grouped_matmul_cost`` (same op order)."""
    bn, bk = fit_tile_vec(N, bn), fit_tile_vec(K, bk)
    vmem = 2 * (bm * bk + bk * bn) * s + bm * bn * 4 + bm * bn * s
    legal = vmem <= cm.VMEM_BYTES
    tiles_m = G * _ceil(_ceil(M, G), bm)
    tn, tk = N // bn, K // bk
    grid = tiles_m.astype(np.float64) * tn * tk
    pm = (tiles_m * bm).astype(np.float64)
    flops = 2.0 * pm * N * K
    t_compute = flops / (peak * _mxu_util_vec(bm, bn, bk))
    bytes_ = pm * K * tn * s + tiles_m.astype(np.float64) * K * N * s \
        + pm * N * s
    t_mem = bytes_ / cm.HBM_BW
    cost = (np.maximum(t_compute, t_mem) + grid * cm.GRID_STEP_OVERHEAD
            + cm.FIXED_OVERHEAD)
    return np.where(legal, cost, ILLEGAL)


def attention_cost_vec(Sq, Skv, D, BH, causal, s, peak, bq, bkv) -> np.ndarray:
    tq, tkv = _ceil(Sq, bq), _ceil(Skv, bkv)
    vmem = 2 * (bq * D + 2 * bkv * D) * s + bq * D * 4 + 2 * bq * 4 \
        + bq * bkv * 4
    legal = vmem <= cm.VMEM_BYTES
    pq = (tq * bq).astype(np.float64)       # float64 early: see matmul note
    pkv = (tkv * bkv).astype(np.float64)
    grid = BH.astype(np.float64) * tq * tkv
    frac = np.where(causal, 0.5 * (1 + 1 / np.maximum(tq, 1)), 1.0)
    flops = 4.0 * BH * pq * pkv * D * frac
    vpu_ops = 6.0 * BH * pq * pkv * frac
    t_compute = (flops / (peak * _mxu_util_vec(bq, bkv, D))
                 + vpu_ops / (cm.PEAK_FLOPS_BF16 / 16))
    bytes_ = BH * s * (pq * D + 2 * pkv * D * tq * frac + pq * D)
    t_mem = bytes_ / cm.HBM_BW
    cost = (np.maximum(t_compute, t_mem) + grid * frac * cm.GRID_STEP_OVERHEAD
            + cm.FIXED_OVERHEAD)
    return np.where(legal, cost, ILLEGAL)


def chunk_scan_cost_vec(m, P, N, batch, s, peak, Q) -> np.ndarray:
    tokens = batch * m
    vmem = 2 * Q * (P + 2 * N) * s + P * N * 4 + Q * Q * 4
    legal = vmem <= cm.VMEM_BYTES
    chunks_total = _ceil(tokens, Q)
    per_chunk = 2.0 * Q * Q * N + 2.0 * Q * Q * P + 4.0 * Q * P * N
    flops = per_chunk * chunks_total
    t_compute = flops / (peak * _mxu_util_vec(Q, np.maximum(P, N), Q))
    bytes_ = tokens.astype(np.float64) * (P + 2 * N) * s * 2
    t_mem = bytes_ / cm.HBM_BW
    cost = (np.maximum(t_compute, t_mem)
            + chunks_total * cm.GRID_STEP_OVERHEAD + cm.FIXED_OVERHEAD)
    return np.where(legal, cost, ILLEGAL)


# ---------------------------------------------------------------------------
# site packing
# ---------------------------------------------------------------------------


_DTYPE_META: Dict[str, Tuple[int, float]] = {}


def _dtype_meta(dtype: str) -> Tuple[int, float]:
    m = _DTYPE_META.get(dtype)
    if m is None:
        m = (cm._dtype_bytes(dtype), cm._peak(dtype))
        _DTYPE_META[dtype] = m
    return m


def _site_cols(sites: Sequence[KernelSite], grid: bool = True):
    """Pack site fields into int64/float64 arrays — column vectors (n, 1)
    when broadcasting against an action grid, flat (n,) when evaluating one
    aligned tile per site.  Single Python pass over the sites."""
    rows = [(s.m, s.n, s.k, s.batch, s.causal, *_dtype_meta(s.dtype))
            for s in sites]
    m, n, k, b, causal, sb, peak = zip(*rows) if rows else ((),) * 7
    def col(vals, dt):
        a = np.array(vals, dt)
        return a[:, None] if grid else a
    return {
        "m": col(m, np.int64), "n": col(n, np.int64), "k": col(k, np.int64),
        "batch": col(b, np.int64), "causal": col(causal, bool),
        "s": col(sb, np.int64), "peak": col(peak, np.float64),
    }


def _cost_kind(kind: str, c: Dict[str, np.ndarray],
               tiles: np.ndarray, grid: bool = True) -> np.ndarray:
    """Cost of sites (packed in ``c``) under tile rows of ``tiles``.

    ``tiles``: (a, 3) int64 — columns beyond the kind's arity are ignored.
    With ``grid=True`` every site is priced under every tile row (``c``
    holds (n, 1) columns; result (n_sites, a)).  With ``grid=False`` tile
    row i belongs to site i (``c`` holds flat (n,) columns; result (n,)).
    ``inf`` marks VMEM-illegal entries.
    """
    t = np.asarray(tiles, np.int64)
    if grid:
        t0, t1, t2 = t[None, :, 0], t[None, :, 1], t[None, :, 2]
    else:
        t0, t1, t2 = t[:, 0], t[:, 1], t[:, 2]
    if kind == "matmul":
        return matmul_cost_vec(c["m"], c["n"], c["k"], c["s"], c["peak"],
                               t0, t1, t2)
    if kind == "grouped_matmul":
        return grouped_matmul_cost_vec(c["m"], c["n"], c["k"], c["batch"],
                                       c["s"], c["peak"], t0, t1, t2)
    if kind == "attention":
        # site semantics: m=Sq, k=Skv, n=D, batch=B*H
        return attention_cost_vec(c["m"], c["k"], c["n"], c["batch"],
                                  c["causal"], c["s"], c["peak"], t0, t1)
    if kind == "chunk_scan":
        return chunk_scan_cost_vec(c["m"], c["n"], c["k"], c["batch"],
                                   c["s"], c["peak"], t0)
    raise ValueError(kind)


def group_by_kind(sites: Sequence[KernelSite]) -> Dict[str, np.ndarray]:
    """kind -> int index array into ``sites`` (order-preserving)."""
    out: Dict[str, List[int]] = {}
    for i, s in enumerate(sites):
        out.setdefault(s.kind, []).append(i)
    return {k: np.asarray(v, np.int64) for k, v in out.items()}


# ---------------------------------------------------------------------------
# action grids (full factor product, itertools.product / row-major order —
# matching the scalar brute-force enumeration so argmin ties break the same)
# ---------------------------------------------------------------------------

_GRID_CACHE: Dict[Tuple, np.ndarray] = {}


def action_tiles_grid(space, kind: str) -> np.ndarray:
    """(n_actions, 3) tile values in flat-action order for ``kind``."""
    choices = space.choices(kind)
    key = (choices, kind)
    g = _GRID_CACHE.get(key)
    if g is None:
        g = np.array(list(itertools.product(*choices)), np.int64)
        _GRID_CACHE[key] = g
    return g


def cost_grid_kind(space, sites: Sequence[KernelSite],
                   kind: str) -> np.ndarray:
    """(n_sites, n_actions(kind)) cost tensor for same-kind ``sites``."""
    return _cost_kind(kind, _site_cols(sites), action_tiles_grid(space, kind))


def cost_grid(space, sites: Sequence[KernelSite]) -> np.ndarray:
    """(n_sites, max_n_actions) cost tensor over the full action grid.

    Rows are per-site; columns follow the flat-action order of that site's
    kind.  Columns past ``space.n_actions(kind)`` are padded with ``inf``
    (never win an argmin), so a row-wise argmin directly yields the
    brute-force flat action.
    """
    groups = group_by_kind(sites)
    if len(groups) == 1:                   # single kind: no padding needed
        (kind, _), = groups.items()
        return cost_grid_kind(space, sites, kind)
    a_max = max((space.n_actions(k) for k in groups), default=0)
    # empty + per-row padding writes (not np.full): every cell is written
    # exactly once, which matters on the memory-bound assembly path
    out = np.empty((len(sites), a_max), np.float64)
    for kind, idx in groups.items():
        na = space.n_actions(kind)
        out[idx, :na] = cost_grid_kind(space, [sites[i] for i in idx], kind)
        if na < a_max:
            out[idx, na:] = ILLEGAL
    return out


# ---------------------------------------------------------------------------
# chosen-action costs (the rewards_batch fast path)
# ---------------------------------------------------------------------------


def _tiles_for_actions_kind(space, kind: str, acts: np.ndarray,
                            idx: np.ndarray) -> np.ndarray:
    """(g, 3) tile values for same-kind action rows (clamped like
    ``ActionSpace.tiles``; validated when strict mode is active)."""
    ch = space.choices(kind)
    if acts.shape[1] < 3:
        # the scalar ActionSpace.tiles indexes action[0..2] for every kind
        # and raises on short actions; mirror that instead of silently
        # pricing missing heads at the tile=1 placeholder
        raise IndexError(
            f"actions need 3 head indices, got shape {acts.shape}")
    out = np.ones((len(acts), 3), np.int64)
    strict = space.strict_enabled(None)
    for d in range(3):
        arr = np.asarray(ch[d], np.int64)
        if strict:
            bad = (acts[:, d] < 0) | (acts[:, d] >= len(arr))
            if bad.any():
                j = int(np.flatnonzero(bad)[0])
                raise IndexError(
                    f"action index {int(acts[j, d])} out of range "
                    f"[0, {len(arr)}) for head {d} of kind {kind!r} "
                    f"(site index {int(idx[j])})")
        out[:, d] = arr[np.minimum(acts[:, d], len(arr) - 1)]
    return out


def costs_for_actions(space, sites: Sequence[KernelSite],
                      actions) -> np.ndarray:
    """(n,) cost of each site under its chosen action (``inf`` = illegal).

    One grouping pass: per kind, action indices are decoded to tile values
    and priced in the same vectorized evaluation."""
    acts = np.asarray(actions, np.int64).reshape(len(sites), -1)
    out = np.empty((len(sites),), np.float64)
    for kind, idx in group_by_kind(sites).items():
        tiles = _tiles_for_actions_kind(space, kind, acts[idx], idx)
        c = _site_cols([sites[i] for i in idx], grid=False)
        out[idx] = _cost_kind(kind, c, tiles, grid=False)
    return out


def tiles_for_actions(space, sites: Sequence[KernelSite],
                      actions) -> np.ndarray:
    """(n, 3) tile values for per-site action indices (unused dims = 1).

    The batched ``ActionSpace.tiles``: clamps by default, raises in strict
    mode.  Used by oracles that price tiles rather than action indices
    (``MeasuredEnv``)."""
    acts = np.asarray(actions, np.int64).reshape(len(sites), -1)
    out = np.ones((len(sites), 3), np.int64)
    for kind, idx in group_by_kind(sites).items():
        out[idx] = _tiles_for_actions_kind(space, kind, acts[idx], idx)
    return out


def costs_for_tiles(sites: Sequence[KernelSite], tiles) -> np.ndarray:
    """(n,) model cost of each site under explicit tile values (``inf`` =
    illegal).  Unlike :func:`costs_for_actions` the tiles need not lie on
    the action grid — this prices arbitrary ``TileProgram`` entries and is
    the legality pre-filter for hardware measurement."""
    t = np.asarray(tiles, np.int64)
    if t.ndim != 2 or t.shape[0] != len(sites):
        raise ValueError(f"tiles must be (n_sites, k), got {t.shape}")
    if t.shape[1] < 3:
        t = np.concatenate(
            [t, np.ones((len(t), 3 - t.shape[1]), np.int64)], 1)
    out = np.empty((len(sites),), np.float64)
    for kind, idx in group_by_kind(sites).items():
        c = _site_cols([sites[i] for i in idx], grid=False)
        out[idx] = _cost_kind(kind, c, t[idx], grid=False)
    return out


# ---------------------------------------------------------------------------
# baselines (the heuristic "LLVM cost model" tiles, vectorized)
# ---------------------------------------------------------------------------


def baseline_tiles_batch(sites: Sequence[KernelSite]) -> np.ndarray:
    """(n, 3) heuristic-baseline tile values (unused dims = 1)."""
    out = np.ones((len(sites), 3), np.int64)
    for kind, idx in group_by_kind(sites).items():
        M = np.array([sites[i].m for i in idx], np.int64)
        N = np.array([sites[i].n for i in idx], np.int64)
        K = np.array([sites[i].k for i in idx], np.int64)
        if kind == "grouped_matmul":
            G = np.array([sites[i].batch for i in idx], np.int64)
            M = _ceil(M, G)                         # one group's rows
        if kind in ("matmul", "grouped_matmul"):
            out[idx, 0] = np.minimum(128, _ceil(M, cm.SUBLANE) * cm.SUBLANE)
            out[idx, 1] = np.minimum(128, _ceil(N, cm.LANE) * cm.LANE)
            out[idx, 2] = np.minimum(512, _ceil(K, cm.LANE) * cm.LANE)
        elif kind == "attention":
            out[idx, 0] = np.minimum(128, _ceil(M, cm.SUBLANE) * cm.SUBLANE)
            out[idx, 1] = np.minimum(512, _ceil(K, cm.LANE) * cm.LANE)
        elif kind == "chunk_scan":
            out[idx, 0] = np.minimum(256, M)
    return out


def baseline_costs(sites: Sequence[KernelSite]) -> np.ndarray:
    """(n,) baseline cost per site — vectorized ``costmodel.baseline_cost``."""
    tiles = baseline_tiles_batch(sites)
    out = np.empty((len(sites),), np.float64)
    for kind, idx in group_by_kind(sites).items():
        c = _site_cols([sites[i] for i in idx], grid=False)
        out[idx] = _cost_kind(kind, c, tiles[idx], grid=False)
    assert np.isfinite(out).all(), "baseline illegal for some site"
    return out
