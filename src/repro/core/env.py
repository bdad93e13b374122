"""The contextual-bandit environment (paper §3.3–3.4).

State  = kernel site (embedded by the agent's code-embedding generator).
Action = joint discrete factor indices — (i_bm, i_bn, i_bk) for matmul,
         (i_bq, i_bkv, ·) for attention, (i_chunk, ·, ·) for chunk scans —
         the VF/IF analogue, powers of two only (eq. 3).
Reward = (t_baseline − t_action) / t_baseline                       (eq. 2)
         with the −9 penalty for VMEM-overflow tiles (§3.4's compile
         timeout).  On TPU hardware the cost model is swapped for wall-clock
         measurement of the compiled kernel (``MeasuredEnv`` hook).

Perf architecture: baselines are pure functions of the site, so the
environment keeps a per-site baseline-cost cache (keyed by ``site.key()``)
and every batched entry point — :meth:`CostModelEnv.rewards_batch`,
:meth:`costs_batch`, :meth:`cost_grid` — routes through the vectorized
engine in :mod:`repro.core.costmodel_vec` instead of the scalar per-call
model.  Construct with ``vectorized=False`` to get the original scalar
loops (kept as the reference path for parity tests and benchmarks).
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.configs.neurovec import NeuroVecConfig
from repro.core import costmodel
from repro.core import costmodel_vec
from repro.models.compute import KernelSite

# Global strict-action toggle: when on, out-of-range action indices raise
# instead of being clamped, so head-masking bugs can't hide behind the
# clamp.  Enable per-call (``tiles(..., strict=True)``), per-config
# (``NeuroVecConfig.strict_actions``), process-wide via this switch, or
# with ``REPRO_STRICT_ACTIONS=1`` in the environment.
_STRICT_ACTIONS = os.environ.get("REPRO_STRICT_ACTIONS", "0") == "1"


def set_strict_actions(on: bool) -> None:
    global _STRICT_ACTIONS
    _STRICT_ACTIONS = bool(on)


@dataclass(frozen=True)
class ActionSpace:
    """Per-kind factor arrays + unified 3-head indexing with masking."""

    cfg: NeuroVecConfig

    def choices(self, kind: str) -> Tuple[Tuple[int, ...], ...]:
        c = self.cfg
        if kind in ("matmul", "grouped_matmul"):
            return (c.bm_choices, c.bn_choices, c.bk_choices)
        if kind == "attention":
            return (c.bq_choices, c.bkv_choices, (1,))
        if kind == "chunk_scan":
            return (c.chunk_choices, (1,), (1,))
        raise ValueError(kind)

    @property
    def head_sizes(self) -> Tuple[int, int, int]:
        c = self.cfg
        return (max(len(c.bm_choices), len(c.bq_choices), len(c.chunk_choices)),
                max(len(c.bn_choices), len(c.bkv_choices)),
                len(c.bk_choices))

    def valid_sizes(self, kind: str) -> Tuple[int, int, int]:
        return tuple(len(x) for x in self.choices(kind))

    def strict_enabled(self, strict: Optional[bool]) -> bool:
        if strict is not None:
            return strict
        return _STRICT_ACTIONS or getattr(self.cfg, "strict_actions", False)

    def tiles(self, kind: str, action: Sequence[int],
              strict: Optional[bool] = None) -> Tuple[int, ...]:
        ch = self.choices(kind)
        if self.strict_enabled(strict):
            for d in range(3):
                if not 0 <= int(action[d]) < len(ch[d]):
                    raise IndexError(
                        f"action index {int(action[d])} out of range "
                        f"[0, {len(ch[d])}) for head {d} of kind {kind!r}")
        return tuple(ch[d][min(int(action[d]), len(ch[d]) - 1)]
                     for d in range(3))

    def n_actions(self, kind: str) -> int:
        return int(np.prod(self.valid_sizes(kind)))

    def unflatten(self, kind: str, flat: int) -> Tuple[int, int, int]:
        s = self.valid_sizes(kind)
        return (flat // (s[1] * s[2]), (flat // s[2]) % s[1], flat % s[2])

    def unflatten_batch(self, kind: str, flat: np.ndarray) -> np.ndarray:
        """(n,) flat actions -> (n, 3) head indices (vectorized)."""
        s = self.valid_sizes(kind)
        flat = np.asarray(flat, np.int64)
        return np.stack([flat // (s[1] * s[2]),
                         (flat // s[2]) % s[1],
                         flat % s[2]], -1)


class CostModelEnv:
    """Reward oracle backed by the analytic TPU cost model.

    ``vectorized=True`` (default) uses the batched engine with a per-site
    baseline cache; ``vectorized=False`` reproduces the original scalar
    per-call loops (the reference path for parity tests and benchmarks).
    """

    def __init__(self, nv_cfg: NeuroVecConfig, seed: int = 0,
                 vectorized: bool = True):
        self.cfg = nv_cfg
        self.space = ActionSpace(nv_cfg)
        self.vectorized = vectorized
        self._rng = np.random.default_rng(seed)
        self._baseline_cache: Dict[str, float] = {}

    # -- baseline cache ----------------------------------------------------
    def baseline_cost(self, site: KernelSite) -> float:
        """Cached ``costmodel.baseline_cost`` (pure function of the site)."""
        key = site.key()
        c = self._baseline_cache.get(key)
        if c is None:
            c = costmodel.baseline_cost(site)
            self._baseline_cache[key] = c
        return c

    def baseline_costs(self, sites: Sequence[KernelSite]) -> np.ndarray:
        """(n,) baseline costs; fills the cache for unseen sites in one
        vectorized evaluation."""
        keys = [s.key() for s in sites]
        missing = [i for i, k in enumerate(keys)
                   if k not in self._baseline_cache]
        if missing:
            fresh = costmodel_vec.baseline_costs([sites[i] for i in missing])
            for i, c in zip(missing, fresh):
                self._baseline_cache[keys[i]] = float(c)
        return np.array([self._baseline_cache[k] for k in keys], np.float64)

    def clear_baseline_cache(self) -> None:
        self._baseline_cache.clear()

    # -- the paper's eq. 2 --
    def reward(self, site: KernelSite, action: Sequence[int]) -> float:
        t = self.cost(site, action)
        if t is None:
            return float(self.cfg.fail_penalty)
        # the scalar reference path recomputes the baseline per call,
        # faithful to the original implementation (what bench_env measures)
        t_base = (self.baseline_cost(site) if self.vectorized
                  else costmodel.baseline_cost(site))
        if not math.isfinite(t_base):       # failed baseline measurement
            return float(self.cfg.fail_penalty)
        if self.cfg.reward_noise > 0:
            t *= float(np.exp(self._rng.normal(0, self.cfg.reward_noise)))
        return float((t_base - t) / t_base)

    def cost(self, site: KernelSite, action: Sequence[int]) -> Optional[float]:
        return costmodel.site_cost(site, self.space.tiles(site.kind, action))

    def speedup(self, site: KernelSite, action: Sequence[int]) -> float:
        """t_baseline / t_action (clamped to the penalty semantics)."""
        t = self.cost(site, action)
        t_base = (self.baseline_cost(site) if self.vectorized
                  else costmodel.baseline_cost(site))
        if t is None or not math.isfinite(t_base):
            # illegal tile (or failed baseline measurement):
            # cfg.illegal_slowdown-times slower than baseline — the same
            # constant vectorizer.program_speedup charges
            return 1.0 / float(self.cfg.illegal_slowdown)
        return float(t_base / t)

    # -- batched fast paths -------------------------------------------------
    def costs_batch(self, sites, actions) -> np.ndarray:
        """(n,) per-site cost of the chosen actions; ``inf`` = illegal."""
        if not len(sites):
            return np.zeros((0,), np.float64)
        if not self.vectorized:
            return np.array([c if (c := self.cost(s, a)) is not None
                             else np.inf for s, a in zip(sites, actions)],
                            np.float64)
        return costmodel_vec.costs_for_actions(self.space, sites, actions)

    def rewards_batch(self, sites, actions) -> np.ndarray:
        if not self.vectorized:
            return np.array([self.reward(s, a)
                             for s, a in zip(sites, actions)], np.float32)
        if not len(sites):
            return np.zeros((0,), np.float32)
        # routed through the overridable batched surface so subclasses
        # (MeasuredEnv) swap the cost source without reimplementing eq. 2
        t = self.costs_batch(sites, actions)
        t_base = self.baseline_costs(sites)
        if self.cfg.reward_noise > 0:
            # draw only for legal entries, in site order — the same RNG
            # stream as the scalar path (which returns the penalty before
            # drawing), so seeded runs agree across both paths
            legal = np.isfinite(t)
            t = t.copy()
            t[legal] *= np.exp(self._rng.normal(
                0, self.cfg.reward_noise, size=int(legal.sum())))
        # a failed baseline measurement (inf t_base under MeasuredEnv)
        # fails closed to the penalty — never a silent nan into training.
        # errstate: the np.where arms evaluate eagerly and the discarded
        # arm divides by inf
        with np.errstate(invalid="ignore", divide="ignore"):
            r = np.where(np.isfinite(t) & np.isfinite(t_base),
                         (t_base - t) / t_base,
                         float(self.cfg.fail_penalty))
        return r.astype(np.float32)

    def speedups_batch(self, sites, actions) -> np.ndarray:
        """(n,) t_baseline / t_action with the illegal-tile clamp
        (``1 / cfg.illegal_slowdown`` — the env/vectorizer-shared
        constant)."""
        t = self.costs_batch(sites, actions)
        if self.vectorized:
            t_base = self.baseline_costs(sites)
        else:                     # faithful scalar reference: recompute
            t_base = np.array([costmodel.baseline_cost(s) for s in sites])
        return np.where(np.isfinite(t) & np.isfinite(t_base),
                        t_base / np.maximum(t, 1e-300),
                        1.0 / float(self.cfg.illegal_slowdown))

    def cost_grid(self, sites) -> np.ndarray:
        """(n_sites, max_n_actions) full action-grid cost tensor (``inf``
        for illegal tiles and for padding past a kind's action count)."""
        return costmodel_vec.cost_grid(self.space, sites)

    def tiles_costs(self, sites, tiles) -> np.ndarray:
        """(n,) cost of explicit tile values — need not lie on the action
        grid (``inf`` = illegal).  Prices arbitrary ``TileProgram``
        entries with the same source as the rest of this oracle, so
        ``program_speedup`` never mixes cost sources."""
        if not len(sites):
            return np.zeros((0,), np.float64)
        return costmodel_vec.costs_for_tiles(sites, tiles)


class MeasuredEnv(CostModelEnv):
    """Hardware-measurement oracle — eq. 2 priced by wall-clock timings.

    On TPU the analytic cost model is swapped for measurement of the
    compiled kernel; this class is that swap, behind the *same* batched
    Oracle surface as :class:`CostModelEnv` (``costs_batch`` /
    ``rewards_batch`` / ``speedups_batch`` / ``cost_grid`` /
    ``baseline_costs``), so agents and the facade never branch on it.

    ``measure_fn(sites, tiles) -> (n,) seconds`` is the batched measure
    hook: called at most once per oracle entry point with every
    cache-missing, model-legal ``(site, tile)`` pair of that batch
    (``tiles`` is an ``(n, 3)`` int array; unused dims are 1).  Non-finite
    or non-positive returns mark failed runs and are treated as illegal
    (a failed *baseline* measurement fails the whole site closed to the
    penalty — never a nan reward).  Results, including failures, are
    cached per ``(site.key(), tiles)`` and deduplicated within a batch, so
    repeated tuning sweeps re-measure nothing; ``clear_result_cache()``
    forces a re-measure after flaky runs.

    Tiles the cost model rejects (VMEM overflow — the compile-failure
    analogue) are never sent to the hook: a kernel that cannot compile
    cannot be timed.  With ``measure_fn=None`` (off-TPU) every query falls
    back to the analytic model, making this a drop-in
    :class:`CostModelEnv`.

    **Grid pruning** (``prune_topk`` + ``surrogate``): with a trained
    surrogate cost model attached, each site's legal tile grid is ranked
    by predicted runtime once and only the top-k candidates (plus the
    heuristic baseline tile, so eq. 2 stays measured-vs-measured) are
    ever submitted to the measurement hook — everything else is priced
    by the surrogate directly.  ``surrogate`` is duck-typed
    (``predict_seconds(sites, tiles) -> (n,) seconds``; see
    ``repro.surrogate``), keeping this module free of any model
    dependency.  ``pruned_pairs`` counts pairs priced by the surrogate
    instead of hardware.

    **Circuit breaker** (graceful degradation): when the measurement path
    collapses — the hook raises (dead transport), or
    ``breaker_threshold`` consecutive batches come back with *every* pair
    failed — the breaker opens and the oracle degrades to the analytic
    cost model instead of feeding all-``inf`` costs (= all-penalty
    rewards, a corrupted training signal) into tuning.  ``health()``
    reports ``"degraded"`` while open; cached failure verdicts from the
    collapse are purged so degraded queries re-price with the model.
    The breaker is one-way by design: call :meth:`reset_breaker` once
    the backend recovers.
    """

    #: a down transport degrades this oracle (resolve_health) rather
    #: than taking tuning down with it
    can_degrade = True

    def __init__(self, nv_cfg: NeuroVecConfig, measure_fn=None,
                 seed: int = 0, breaker_threshold: int = 2,
                 prune_topk: Optional[int] = None, surrogate=None):
        super().__init__(nv_cfg, seed=seed, vectorized=True)
        if breaker_threshold < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1, got {breaker_threshold}")
        if prune_topk is not None and prune_topk < 1:
            raise ValueError(
                f"prune_topk must be >= 1, got {prune_topk}")
        self.measure_fn = measure_fn
        self.prune_topk = prune_topk
        self.surrogate = surrogate
        self._allowed_cache: Dict[str, frozenset] = {}
        self.breaker_threshold = breaker_threshold
        self.breaker_open = False
        self.degraded_reason: Optional[str] = None
        self._consec_failed_batches = 0
        self._result_cache: Dict[Tuple[str, Tuple[int, int, int]],
                                 float] = {}
        self.measure_calls = 0          # hook invocations (for tests/ops)
        self.measured_pairs = 0         # (site, tile) pairs sent to hw
        self.pruned_pairs = 0           # pairs priced by the surrogate

    def clear_result_cache(self) -> None:
        self._result_cache.clear()

    def health(self) -> str:
        """``"degraded"`` once the breaker opened (analytic fallback in
        effect), ``"ok"`` otherwise."""
        return "degraded" if self.breaker_open else "ok"

    def _trip_breaker(self, reason: str) -> None:
        self.breaker_open = True
        self.degraded_reason = reason
        # failure verdicts cached during the collapse are artifacts of
        # the dead measurement path, not of the kernels: purge them so
        # degraded-mode queries re-price with the analytic model
        for k in [k for k, v in self._result_cache.items()
                  if not math.isfinite(v)]:
            del self._result_cache[k]

    def reset_breaker(self) -> None:
        """Re-arm measurement after the backend recovers."""
        self.breaker_open = False
        self.degraded_reason = None
        self._consec_failed_batches = 0

    # -- surrogate grid pruning ---------------------------------------------
    @property
    def prune_active(self) -> bool:
        """Pruning needs all three legs: a budget, a trained surrogate,
        and an actual measurement path to save work on."""
        return (self.prune_topk is not None and self.surrogate is not None
                and self.measure_fn is not None)

    def _allowed_tiles(self, site) -> frozenset:
        """The measurable tile set for ``site``: the surrogate's top-k of
        the legal action grid plus the heuristic baseline tile (eq. 2
        must stay measured-vs-measured).  Ranked once per site."""
        key = site.key()
        allowed = self._allowed_cache.get(key)
        if allowed is None:
            grid = costmodel_vec.action_tiles_grid(self.space, site.kind)
            legal = np.flatnonzero(np.isfinite(
                costmodel_vec.costs_for_tiles([site] * len(grid), grid)))
            pred = np.asarray(self.surrogate.predict_seconds(
                [site] * len(legal), grid[legal]), np.float64)
            top = legal[np.argsort(pred, kind="stable")[:self.prune_topk]]
            base = costmodel_vec.baseline_tiles_batch([site])[0]
            allowed = frozenset(
                [tuple(int(x) for x in grid[i]) for i in top]
                + [tuple(int(x) for x in base)])
            self._allowed_cache[key] = allowed
        return allowed

    # -- the measured cost of explicit tiles --------------------------------
    def _measured_costs(self, sites, tiles) -> np.ndarray:
        """(n,) seconds per (site, tile) pair; ``inf`` = illegal/failed.
        One batched hook call covering all cache misses."""
        tiles = np.asarray(tiles, np.int64)
        keys = [(s.key(), (int(t[0]), int(t[1]), int(t[2])))
                for s, t in zip(sites, tiles)]
        # first occurrence of each uncached key: duplicates inside one
        # batch (training samples sites with replacement) are measured once
        first = {}
        for i, k in enumerate(keys):
            if k not in self._result_cache and k not in first:
                first[k] = i
        miss = list(first.values())
        if miss:
            m_sites = [sites[i] for i in miss]
            m_tiles = tiles[miss]
            vals = costmodel_vec.costs_for_tiles(m_sites, m_tiles)
            if self.measure_fn is not None and not self.breaker_open:
                legal = np.flatnonzero(np.isfinite(vals))
                if len(legal) and self.prune_active:
                    # surrogate grid pruning: only each site's top-k
                    # candidates (plus its baseline tile) reach the
                    # hardware; the rest are priced by the surrogate
                    keep = np.array(
                        [tuple(int(x) for x in m_tiles[j])
                         in self._allowed_tiles(m_sites[j])
                         for j in legal], bool)
                    pruned = legal[~keep]
                    if len(pruned):
                        vals[pruned] = self.surrogate.predict_seconds(
                            [m_sites[j] for j in pruned], m_tiles[pruned])
                        self.pruned_pairs += len(pruned)
                    legal = legal[keep]
                if len(legal):
                    try:
                        raw = self.measure_fn(
                            [m_sites[j] for j in legal], m_tiles[legal])
                    except Exception as e:
                        # a raising hook is a collapsed measurement path
                        # (closed/dead transport): open the breaker and
                        # keep the analytic prices for this batch
                        self._trip_breaker(
                            f"measure_fn raised {type(e).__name__}: {e}")
                        raw = None
                    if raw is not None:
                        t = np.asarray(raw, np.float64).reshape(-1)
                        if t.shape != (len(legal),):
                            raise ValueError(
                                f"measure_fn returned shape {t.shape}, "
                                f"expected ({len(legal)},)")
                        measured = np.where(np.isfinite(t) & (t > 0),
                                            t, np.inf)
                        self.measure_calls += 1
                        self.measured_pairs += len(legal)
                        if np.isfinite(measured).any():
                            self._consec_failed_batches = 0
                            vals[legal] = measured
                        else:
                            # every pair failed: one flaky batch is
                            # honest data (fail-closed inf), a streak is
                            # a dead backend — degrade instead of
                            # poisoning rewards with all-penalty
                            self._consec_failed_batches += 1
                            if self._consec_failed_batches \
                                    >= self.breaker_threshold:
                                self._trip_breaker(
                                    f"{self._consec_failed_batches} "
                                    f"consecutive all-failed "
                                    f"measurement batches")
                            else:
                                vals[legal] = measured
            for i, v in zip(miss, vals):
                self._result_cache[keys[i]] = float(v)
        gone = [i for i, k in enumerate(keys)
                if k not in self._result_cache]
        if gone:
            # a mid-batch breaker trip purged these keys' cached failure
            # verdicts (they were cached before this batch, so they are
            # not in ``miss``): re-price them with the analytic model
            fresh = costmodel_vec.costs_for_tiles(
                [sites[i] for i in gone], tiles[gone])
            for i, v in zip(gone, fresh):
                self._result_cache[keys[i]] = float(v)
        return np.array([self._result_cache[k] for k in keys], np.float64)

    # -- Oracle surface (measured) ------------------------------------------
    def costs_batch(self, sites, actions) -> np.ndarray:
        if not len(sites):
            return np.zeros((0,), np.float64)
        tiles = costmodel_vec.tiles_for_actions(self.space, sites, actions)
        return self._measured_costs(sites, tiles)

    def baseline_costs(self, sites) -> np.ndarray:
        if not len(sites):
            return np.zeros((0,), np.float64)
        return self._measured_costs(
            sites, costmodel_vec.baseline_tiles_batch(sites))

    def baseline_cost(self, site: KernelSite) -> float:
        return float(self.baseline_costs([site])[0])

    def cost(self, site: KernelSite, action: Sequence[int]) -> Optional[float]:
        c = float(self.costs_batch([site], np.asarray([action]))[0])
        return None if math.isinf(c) else c

    def tiles_costs(self, sites, tiles) -> np.ndarray:
        if not len(sites):
            return np.zeros((0,), np.float64)
        t = np.asarray(tiles, np.int64)
        if t.ndim != 2 or t.shape[0] != len(sites):  # same error as model
            raise ValueError(f"tiles must be (n_sites, k), got {t.shape}")
        if t.shape[1] < 3:                   # pad unused dims like the model
            t = np.concatenate(
                [t, np.ones((len(t), 3 - t.shape[1]), np.int64)], 1)
        return self._measured_costs(sites, t)

    def cost_grid(self, sites) -> np.ndarray:
        groups = costmodel_vec.group_by_kind(sites)
        a_max = max((self.space.n_actions(k) for k in groups), default=0)
        out = np.full((len(sites), a_max), np.inf, np.float64)
        for kind, idx in groups.items():
            tg = costmodel_vec.action_tiles_grid(self.space, kind)
            rep_sites = [sites[i] for i in idx for _ in range(len(tg))]
            rep_tiles = np.tile(tg, (len(idx), 1))
            out[idx, :len(tg)] = self._measured_costs(
                rep_sites, rep_tiles).reshape(len(idx), len(tg))
        return out
