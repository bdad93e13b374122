"""TPU-v5e analytic kernel cost model — the reward source (DESIGN.md §5).

Plays the role of the paper's wall-clock measurement on the i7-8559U: for a
kernel site and a tile choice it returns estimated seconds, or ``None`` when
the tile is illegal (VMEM overflow — the TPU analogue of the paper's
compile-timeout, penalized with −9 by the environment).

Also provides the *heuristic baseline* tile pickers — the stand-in for
LLVM's fixed cost model that the agent is rewarded against.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

from repro.models.compute import KernelSite

# ---- TPU v5e hardware constants (also used by the roofline analysis) ----
PEAK_FLOPS_BF16 = 197e12          # per chip
PEAK_FLOPS_F32 = 49.25e12         # MXU f32 ~ 1/4 of bf16
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link
VMEM_BYTES = 16 * 2 ** 20         # usable vector memory per core
MXU = 128                         # systolic array dim
SUBLANE = 8
LANE = 128
GRID_STEP_OVERHEAD = 3e-7         # s per grid step (pipeline bubble, DMA setup)
FIXED_OVERHEAD = 2e-6             # s per kernel launch


def _dtype_bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float32": 4, "float16": 2, "int8": 1}.get(
        str(dtype), 2)


def _peak(dtype: str) -> float:
    return PEAK_FLOPS_F32 if "32" in str(dtype) else PEAK_FLOPS_BF16


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def _mxu_util(bm: int, bn: int, bk: int) -> float:
    """Fraction of MXU throughput achieved by a (bm,bn,bk) tile.

    Tiles smaller than the 128x128 systolic array waste rows/columns;
    sublane-misaligned bm wastes loads; small bk pays pipeline fill.
    """
    u = min(bm, MXU) / MXU * (min(bn, LANE) / LANE)
    if bm % SUBLANE:
        u *= 0.6
    if bn % LANE:
        u *= 0.5
    # systolic fill: K-dim pipeline latency ~128 cycles amortized over bk
    u *= bk / (bk + MXU)
    return max(u, 1e-3)


# ===========================================================================
# matmul
# ===========================================================================

def matmul_cost(site: KernelSite,
                tiles: Tuple[int, int, int]) -> Optional[float]:
    M, N, K = site.m, site.n, site.k
    bm, bn, bk = tiles
    s = _dtype_bytes(site.dtype)
    if bm <= 0 or bn <= 0 or bk <= 0:
        return None
    tm, tn, tk = _ceil(M, bm), _ceil(N, bn), _ceil(K, bk)
    # VMEM: in/out tiles double-buffered + f32 accumulator
    vmem = 2 * (bm * bk + bk * bn) * s + bm * bn * 4 + bm * bn * s
    if vmem > VMEM_BYTES:
        return None                                   # "compile failure"
    grid = tm * tn * tk
    # compute (over padded extents — padding waste is real work)
    flops = 2.0 * (tm * bm) * (tn * bn) * (tk * bk)
    t_compute = flops / (_peak(site.dtype) * _mxu_util(bm, bn, bk))
    # memory: A re-streamed tn times, B re-streamed tm times, C written once
    bytes_ = (tm * bm) * (tk * bk) * tn * s \
        + (tk * bk) * (tn * bn) * tm * s \
        + (tm * bm) * (tn * bn) * s
    t_mem = bytes_ / HBM_BW
    return (max(t_compute, t_mem) + grid * GRID_STEP_OVERHEAD
            + FIXED_OVERHEAD)


def baseline_matmul_tiles(M: int, N: int, K: int) -> Tuple[int, int, int]:
    """The heuristic "LLVM cost model": fixed square-ish MXU-aligned tiles.

    Decent defaults, but shape-oblivious — it never adapts bm to skinny
    matmuls, never grows bn for bandwidth-bound wide outputs, and caps bk at
    512 regardless of reuse, which is exactly the gap the agent learns to
    exploit (paper Fig. 1 phenomenology).
    """
    bm = min(128, _ceil(M, SUBLANE) * SUBLANE)
    bn = min(128, _ceil(N, LANE) * LANE)
    bk = min(512, _ceil(K, LANE) * LANE)
    return bm, bn, bk


# ===========================================================================
# grouped matmul (ragged groups: the held experts of an MoE layer)
# ===========================================================================

def fit_tile(dim: int, t: int) -> int:
    """The grouped-matmul kernel's ``bn`` / ``bk`` for a tile of ``t``: the
    largest multiple of 128 that is at most ``t`` and divides ``dim``; the
    whole ``dim`` where it is at most ``t`` or no multiple of 128 divides
    it."""
    if dim <= t:
        return dim
    b = t // LANE * LANE
    while b >= LANE:
        if dim % b == 0:
            return b
        b -= LANE
    return dim


def grouped_matmul_cost(site: KernelSite,
                        tiles: Tuple[int, int, int]) -> Optional[float]:
    """Site semantics: m = rows all groups expect together, batch = G
    groups of ceil(m / G) rows each.  Each group's rows are padded to
    ``bm``; each row tile reads its group's (K, N) weights once, so a
    group whose rows fit one tile reads its weights once."""
    M, N, K, G = site.m, site.n, site.k, site.batch
    bm, bn, bk = tiles
    s = _dtype_bytes(site.dtype)
    if bm <= 0 or bn <= 0 or bk <= 0:
        return None
    bn, bk = fit_tile(N, bn), fit_tile(K, bk)
    vmem = 2 * (bm * bk + bk * bn) * s + bm * bn * 4 + bm * bn * s
    if vmem > VMEM_BYTES:
        return None
    tiles_m = G * _ceil(_ceil(M, G), bm)
    tn, tk = N // bn, K // bk
    grid = tiles_m * tn * tk
    pm = tiles_m * bm
    flops = 2.0 * pm * N * K
    t_compute = flops / (_peak(site.dtype) * _mxu_util(bm, bn, bk))
    bytes_ = pm * K * tn * s + tiles_m * K * N * s + pm * N * s
    t_mem = bytes_ / HBM_BW
    return (max(t_compute, t_mem) + grid * GRID_STEP_OVERHEAD
            + FIXED_OVERHEAD)


def baseline_grouped_tiles(M: int, N: int, K: int,
                           G: int) -> Tuple[int, int, int]:
    """The matmul heuristic at one group's rows."""
    return baseline_matmul_tiles(_ceil(M, G), N, K)


# ===========================================================================
# attention (flash)
# ===========================================================================

def attention_cost(site: KernelSite,
                   tiles: Tuple[int, int]) -> Optional[float]:
    Sq, Skv, D, BH = site.m, site.k, site.n, site.batch
    bq, bkv = tiles
    s = _dtype_bytes(site.dtype)
    if bq <= 0 or bkv <= 0:
        return None
    tq, tkv = _ceil(Sq, bq), _ceil(Skv, bkv)
    vmem = 2 * (bq * D + 2 * bkv * D) * s + bq * D * 4 + 2 * bq * 4 \
        + bq * bkv * 4
    if vmem > VMEM_BYTES:
        return None
    grid = BH * tq * tkv
    frac = 0.5 * (1 + 1 / max(tq, 1)) if site.causal else 1.0
    flops = 4.0 * BH * (tq * bq) * (tkv * bkv) * D * frac
    # softmax runs on the VPU at ~1/16 MXU rate: exp + max + sum ~ 6 ops/elt
    vpu_ops = 6.0 * BH * (tq * bq) * (tkv * bkv) * frac
    t_compute = (flops / (_peak(site.dtype) * _mxu_util(bq, bkv, D))
                 + vpu_ops / (PEAK_FLOPS_BF16 / 16))
    bytes_ = BH * s * ((tq * bq) * D            # q once
                       + 2 * (tkv * bkv) * D * tq * frac   # k,v per q block
                       + (tq * bq) * D)         # out
    t_mem = bytes_ / HBM_BW
    return (max(t_compute, t_mem) + grid * frac * GRID_STEP_OVERHEAD
            + FIXED_OVERHEAD)


def baseline_attn_tiles(Sq: int, Skv: int) -> Tuple[int, int]:
    """Heuristic: fixed 128/512 blocks (shape-oblivious)."""
    bq = min(128, _ceil(Sq, SUBLANE) * SUBLANE)
    bkv = min(512, _ceil(Skv, LANE) * LANE)
    return bq, bkv


# ===========================================================================
# chunk scan (SSD / mLSTM)
# ===========================================================================

def chunk_scan_cost(site: KernelSite, tiles: Tuple[int]) -> Optional[float]:
    """Site semantics: m = model-configured chunk, n = P (head dim),
    k = N (state dim), batch = #(group x configured-chunk) instances, so
    total scanned tokens = batch * m.  The action re-tiles the scan with
    chunk Q — bigger Q amortizes state I/O but grows the O(Q^2) intra term.
    """
    Q = tiles[0]
    P, N = site.n, site.k
    tokens = site.batch * site.m
    s = _dtype_bytes(site.dtype)
    if Q <= 0:
        return None
    vmem = 2 * Q * (P + 2 * N) * s + P * N * 4 + Q * Q * 4
    if vmem > VMEM_BYTES:
        return None
    chunks_total = _ceil(tokens, Q)
    # FLOPs/chunk: CB^T (2QQN) + (cb*L)X (2QQP) + inter (2QPN) + state (2QPN)
    per_chunk = 2.0 * Q * Q * N + 2.0 * Q * Q * P + 4.0 * Q * P * N
    flops = per_chunk * chunks_total
    t_compute = flops / (_peak(site.dtype) * _mxu_util(Q, max(P, N), Q))
    bytes_ = tokens * (P + 2 * N) * s * 2
    t_mem = bytes_ / HBM_BW
    return (max(t_compute, t_mem) + chunks_total * GRID_STEP_OVERHEAD
            + FIXED_OVERHEAD)


def baseline_chunk(S: int) -> Tuple[int]:
    return (min(256, S),)


# ===========================================================================
# dispatch
# ===========================================================================

def site_cost(site: KernelSite, tiles: Tuple[int, ...]) -> Optional[float]:
    if site.kind == "matmul":
        return matmul_cost(site, tiles[:3])
    if site.kind == "grouped_matmul":
        return grouped_matmul_cost(site, tiles[:3])
    if site.kind == "attention":
        return attention_cost(site, tiles[:2])
    if site.kind == "chunk_scan":
        return chunk_scan_cost(site, tiles[:1])
    raise ValueError(site.kind)


def baseline_tiles(site: KernelSite) -> Tuple[int, ...]:
    if site.kind == "matmul":
        return baseline_matmul_tiles(site.m, site.n, site.k)
    if site.kind == "grouped_matmul":
        return baseline_grouped_tiles(site.m, site.n, site.k, site.batch)
    if site.kind == "attention":
        return baseline_attn_tiles(site.m, site.k)
    if site.kind == "chunk_scan":
        return baseline_chunk(site.m)
    raise ValueError(site.kind)


def baseline_cost(site: KernelSite) -> float:
    c = site_cost(site, baseline_tiles(site))
    assert c is not None, f"baseline illegal for {site}"
    return c
