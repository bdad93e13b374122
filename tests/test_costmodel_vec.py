"""Parity + regression suite for the vectorized cost-model engine.

The batched oracle (``repro.core.costmodel_vec``) must agree with the
scalar reference model to ~1e-9 relative on every legal tile, mark every
VMEM-illegal tile as ``inf``, and the consumers built on top of it
(baseline cache, batched rewards, brute-force argmin, jit-cached PPO
paths) must match their scalar ancestors exactly.
"""
import itertools

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.configs.neurovec import NeuroVecConfig
from repro.core import costmodel, costmodel_vec, dataset
from repro.core import env as env_mod
from repro.core.agents import PPOAgent, brute_force_action, brute_force_labels
from repro.core.agents.brute import brute_force_costs
from repro.core.env import ActionSpace, CostModelEnv
from repro.models.compute import KernelSite

NV = NeuroVecConfig(train_batch=256, sgd_minibatch=64, ppo_epochs=4)
ENV = CostModelEnv(NV)
SPACE = ENV.space


def _scalar_brute(env, site):
    """The original interpreted brute force (reference implementation)."""
    best_a, best_c = (0, 0, 0), float("inf")
    for a in itertools.product(*(range(s)
                                 for s in env.space.valid_sizes(site.kind))):
        c = env.cost(site, a)
        if c is not None and c < best_c:
            best_a, best_c = a, c
    return best_a, best_c


# ---------------------------------------------------------------------------
# grid parity: vectorized vs scalar cost over the full action space
# ---------------------------------------------------------------------------

def test_cost_grid_matches_scalar_on_random_corpus():
    sites = dataset.generate(120, seed=42)
    grid = ENV.cost_grid(sites)
    for i, s in enumerate(sites):
        n_a = SPACE.n_actions(s.kind)
        for j, a in enumerate(itertools.product(
                *(range(n) for n in SPACE.valid_sizes(s.kind)))):
            c = costmodel.site_cost(s, SPACE.tiles(s.kind, a))
            if c is None:
                assert np.isinf(grid[i, j]), (s, a)
            else:
                assert abs(grid[i, j] - c) <= 1e-9 * c, (s, a, c, grid[i, j])
        assert np.isinf(grid[i, n_a:]).all()     # padding never legal


@settings(max_examples=40, deadline=None)
@given(m=st.integers(3, 20), n=st.integers(5, 15), k=st.integers(5, 15),
       dt=st.integers(0, 1), kind=st.integers(0, 2), b=st.integers(0, 8))
def test_cost_vec_property_parity(m, n, k, dt, kind, b):
    dtype = ("bfloat16", "float32")[dt]
    kindname = ("matmul", "attention", "chunk_scan")[kind]
    site = KernelSite(site="p", kind=kindname, m=2 ** m, n=2 ** n, k=2 ** k,
                      batch=2 ** b, dtype=dtype, causal=bool(m % 2))
    grid = costmodel_vec.cost_grid_kind(SPACE, [site], kindname)[0]
    for j, a in enumerate(itertools.product(
            *(range(x) for x in SPACE.valid_sizes(kindname)))):
        c = costmodel.site_cost(site, SPACE.tiles(kindname, a))
        if c is None:
            assert np.isinf(grid[j])
        else:
            assert abs(grid[j] - c) <= 1e-9 * c


@pytest.mark.parametrize("m,n,k,g", [
    (96, 1536, 5120, 20), (96, 5120, 1536, 20), (6144, 1536, 5120, 20),
    (3, 640, 300, 7), (1000, 128, 4096, 1), (40, 64, 64, 4)])
def test_grouped_matmul_cost_vec_parity(m, n, k, g):
    """The grouped kind on the matmul action row: scalar and vector prices
    agree on every action (each group's rows padded to bm, weights read
    once per row tile, bn and bk fitted to divisors of N and K)."""
    site = KernelSite(site="moe.experts.up", kind="grouped_matmul", m=m,
                      n=n, k=k, batch=g)
    assert SPACE.choices("grouped_matmul") == SPACE.choices("matmul")
    grid = costmodel_vec.cost_grid_kind(SPACE, [site], "grouped_matmul")[0]
    tiles = costmodel_vec.action_tiles_grid(SPACE, "grouped_matmul")
    for j, t in enumerate(tiles):
        c = costmodel.site_cost(site, tuple(int(x) for x in t))
        if c is None:
            assert np.isinf(grid[j]), t
        else:
            assert abs(grid[j] - c) <= 1e-9 * c, (t, c, grid[j])
    fit = costmodel_vec.fit_tile_vec(np.array([n, k])[:, None],
                                     tiles[None, :, 1])
    assert [[costmodel.fit_tile(d, int(t)) for t in tiles[:, 1]]
            for d in (n, k)] == fit.tolist()
    base = costmodel_vec.baseline_costs([site])[0]
    assert abs(base - costmodel.baseline_cost(site)) <= 1e-9 * base
    assert tuple(costmodel_vec.baseline_tiles_batch([site])[0]) == \
        costmodel.baseline_tiles(site)


def test_grouped_matmul_cost_pads_each_group_to_bm():
    """20 groups of about 5 rows: a 512-row tile pays 100x the padded
    compute of an 8-row tile, and the weights are read once either way."""
    site = KernelSite(site="g", kind="grouped_matmul", m=96, n=1536,
                      k=5120, batch=20)
    assert costmodel.fit_tile(5120, 4096) == 2560
    assert costmodel.fit_tile(1536, 1024) == 768
    assert costmodel.fit_tile(300, 128) == 300
    small = costmodel.site_cost(site, (8, 512, 4096))
    big = costmodel.site_cost(site, (512, 512, 4096))
    weights = 20 * 5120 * 1536 * 2 / costmodel.HBM_BW
    assert weights < small < 2 * weights
    assert big > 2 * small


def test_cost_vec_no_int64_overflow_at_huge_dims():
    # byte/grid products exceed int64 for dims ~2^22+; the engine must
    # promote to float64 and keep parity with the arbitrary-precision
    # scalar model (regression: values wrapped negative and flipped labels)
    for kind, big in (("matmul", dict(m=2 ** 22, n=2 ** 22, k=2 ** 22)),
                      ("attention", dict(m=2 ** 22, n=128, k=2 ** 22,
                                         batch=2 ** 18)),
                      ("chunk_scan", dict(m=2 ** 20, n=512, k=512,
                                          batch=2 ** 22))):
        site = KernelSite(site="huge", kind=kind, causal=True, **big)
        grid = costmodel_vec.cost_grid_kind(SPACE, [site], kind)[0]
        assert (grid[np.isfinite(grid)] > 0).all()
        for j, a in enumerate(itertools.product(
                *(range(x) for x in SPACE.valid_sizes(kind)))):
            c = costmodel.site_cost(site, SPACE.tiles(kind, a))
            if c is None:
                assert np.isinf(grid[j])
            else:
                assert abs(grid[j] - c) <= 1e-9 * c, (kind, a, c, grid[j])


def test_baseline_costs_vectorized_parity():
    sites = dataset.generate(200, seed=43)
    vec = costmodel_vec.baseline_costs(sites)
    ref = np.array([costmodel.baseline_cost(s) for s in sites])
    np.testing.assert_allclose(vec, ref, rtol=1e-9)


def test_rewards_and_costs_batch_match_scalar_env():
    sites = dataset.generate(150, seed=44)
    rng = np.random.default_rng(0)
    actions = np.stack([[rng.integers(0, n)
                         for n in SPACE.valid_sizes(s.kind)] for s in sites])
    env_v = CostModelEnv(NV, vectorized=True)
    env_s = CostModelEnv(NV, vectorized=False)
    np.testing.assert_allclose(env_v.rewards_batch(sites, actions),
                               env_s.rewards_batch(sites, actions),
                               rtol=1e-6, atol=1e-7)
    cv = env_v.costs_batch(sites, actions)
    cs = env_s.costs_batch(sites, actions)
    np.testing.assert_array_equal(np.isinf(cv), np.isinf(cs))
    legal = np.isfinite(cv)
    np.testing.assert_allclose(cv[legal], cs[legal], rtol=1e-9)


def test_rewards_batch_noise_matches_scalar_rng_stream():
    nv = NeuroVecConfig(reward_noise=0.05)
    sites = dataset.generate(40, seed=60)
    # include an illegal action so the streams would diverge if the
    # vectorized path drew noise for penalty entries (regression)
    actions = [[0, 0, 0] for _ in sites]
    actions[3] = [len(nv.bm_choices) - 1, len(nv.bn_choices) - 1,
                  len(nv.bk_choices) - 1]
    big = KernelSite(site="t", kind="matmul", m=65536, n=16384, k=16384)
    sites[3] = big
    env_v = CostModelEnv(nv, seed=7, vectorized=True)
    env_s = CostModelEnv(nv, seed=7, vectorized=False)
    np.testing.assert_allclose(env_v.rewards_batch(sites, actions),
                               env_s.rewards_batch(sites, actions),
                               rtol=1e-6, atol=1e-7)


def test_short_action_rows_raise_like_scalar():
    s = KernelSite(site="t", kind="matmul", m=512, n=512, k=512)
    with pytest.raises(IndexError):
        ENV.costs_batch([s], np.zeros((1, 2), np.int64))
    with pytest.raises(IndexError):
        ENV.rewards_batch([s], np.zeros((1,), np.int64))


def test_speedups_batch_matches_scalar_speedup_on_both_paths():
    sites = dataset.generate(30, seed=61)
    rng = np.random.default_rng(2)
    actions = np.stack([[rng.integers(0, n)
                         for n in SPACE.valid_sizes(s.kind)] for s in sites])
    for vec in (True, False):
        env = CostModelEnv(NV, vectorized=vec)
        ref = np.array([env.speedup(s, a) for s, a in zip(sites, actions)])
        np.testing.assert_allclose(env.speedups_batch(sites, actions), ref,
                                   rtol=1e-9)


def test_rewards_batch_empty_and_penalty():
    assert ENV.rewards_batch([], np.zeros((0, 3))).shape == (0,)
    s = KernelSite(site="t", kind="matmul", m=65536, n=16384, k=16384)
    a = [[len(NV.bm_choices) - 1, len(NV.bn_choices) - 1,
          len(NV.bk_choices) - 1]]
    assert ENV.rewards_batch([s], a)[0] == NV.fail_penalty


# ---------------------------------------------------------------------------
# baseline cache
# ---------------------------------------------------------------------------

def test_baseline_cache_hit_and_invalidation():
    env = CostModelEnv(NV)
    s = KernelSite(site="c", kind="matmul", m=4096, n=4096, k=4096)
    ref = costmodel.baseline_cost(s)
    assert env.baseline_cost(s) == ref
    assert s.key() in env._baseline_cache
    # poison the cache entry: a hit must return it (proving no recompute)
    env._baseline_cache[s.key()] = 123.0
    assert env.baseline_cost(s) == 123.0
    assert env.baseline_costs([s])[0] == 123.0
    # invalidation restores the true value
    env.clear_baseline_cache()
    assert env.baseline_cost(s) == ref


def test_baseline_batch_fills_cache_vectorized():
    env = CostModelEnv(NV)
    sites = dataset.generate(60, seed=45)
    out = env.baseline_costs(sites)
    ref = np.array([costmodel.baseline_cost(s) for s in sites])
    np.testing.assert_allclose(out, ref, rtol=1e-9)
    assert len(env._baseline_cache) == len({s.key() for s in sites})


# ---------------------------------------------------------------------------
# brute force: argmin over the cost tensor == interpreted search
# ---------------------------------------------------------------------------

def test_brute_force_action_matches_scalar_search():
    for s in dataset.generate(40, seed=46):
        ref_a, ref_c = _scalar_brute(ENV, s)
        a, c = brute_force_action(ENV, s)
        assert tuple(a) == tuple(ref_a), (s, a, ref_a)
        assert c == pytest.approx(ref_c, rel=1e-9)


def test_brute_force_labels_batch_matches_per_site():
    sites = dataset.generate(50, seed=47)
    labels = brute_force_labels(ENV, sites)
    assert labels.shape == (len(sites), 3)
    for i, s in enumerate(sites):
        assert tuple(labels[i]) == tuple(brute_force_action(ENV, s)[0])
    costs = brute_force_costs(ENV, sites)
    for i, s in enumerate(sites):
        assert costs[i] == pytest.approx(brute_force_action(ENV, s)[1],
                                         rel=1e-9)


def test_brute_force_all_illegal_returns_inf():
    # chunk_scan holds the full (P, N) state in VMEM for every Q, so huge
    # state dims make every action illegal — the documented inf contract
    s = KernelSite(site="t", kind="chunk_scan", m=256, n=4096, k=4096,
                   batch=64)
    grid = ENV.cost_grid([s])[0]
    assert np.isinf(grid).all()
    a, c = brute_force_action(ENV, s)
    assert np.isinf(c) and tuple(a) == (0, 0, 0)
    assert tuple(a) == tuple(_scalar_brute(ENV, s)[0])
    # and a normal site still returns the finite grid minimum
    s2 = KernelSite(site="t", kind="matmul", m=64, n=64, k=64)
    a2, c2 = brute_force_action(ENV, s2)
    assert np.isfinite(c2) and c2 == ENV.cost_grid([s2])[0].min()


# ---------------------------------------------------------------------------
# strict action mode (the clamp-hides-masking-bugs fix)
# ---------------------------------------------------------------------------

def test_tiles_clamps_by_default_and_raises_in_strict_mode():
    assert SPACE.tiles("matmul", (99, 0, 0)) == \
        SPACE.tiles("matmul", (len(NV.bm_choices) - 1, 0, 0))
    with pytest.raises(IndexError):
        SPACE.tiles("matmul", (99, 0, 0), strict=True)
    with pytest.raises(IndexError):
        SPACE.tiles("attention", (0, 0, 1), strict=True)   # padded head
    # config-level strict
    strict_space = ActionSpace(NeuroVecConfig(strict_actions=True))
    with pytest.raises(IndexError):
        strict_space.tiles("matmul", (0, 99, 0))
    # process-level strict covers the batched path too
    env_mod.set_strict_actions(True)
    try:
        with pytest.raises(IndexError):
            ENV.costs_batch([KernelSite(site="t", kind="matmul",
                                        m=512, n=512, k=512)], [[99, 0, 0]])
    finally:
        env_mod.set_strict_actions(False)
    # valid actions are unaffected in strict mode
    assert SPACE.tiles("matmul", (0, 0, 0), strict=True) == \
        SPACE.tiles("matmul", (0, 0, 0))


# ---------------------------------------------------------------------------
# PPO: greedy act must not retrace; tail minibatch must not be dropped
# ---------------------------------------------------------------------------

def test_greedy_act_does_not_retrace_across_calls():
    agent = PPOAgent(NV, seed=0)
    sites = dataset.generate(16, seed=48)
    a1 = agent.act(sites, sample=False)
    assert agent.trace_counts["greedy"] == 1
    for _ in range(3):
        a2 = agent.act(sites, sample=False)
    assert agent.trace_counts["greedy"] == 1, "greedy path retraced"
    np.testing.assert_array_equal(a1, a2)      # deterministic
    # a different batch size may trace once more, but stays cached after
    agent.act(dataset.generate(8, seed=49), sample=False)
    agent.act(dataset.generate(8, seed=50), sample=False)
    assert agent.trace_counts["greedy"] == 2


def test_update_includes_tail_minibatch():
    agent = PPOAgent(NV, seed=1)
    env = CostModelEnv(NV)
    sites = dataset.generate(70, seed=51)      # 70 % 64 = 6-sample tail
    feats = agent.feats(sites)
    a, raw, logp, v = agent.sample_actions(sites, feats=feats)
    r = env.rewards_batch(sites, a)
    agent.update(sites, a, raw, logp, r, feats=feats)
    # 1 full minibatch + 1 tail minibatch per epoch
    assert agent.last_minibatch_count == NV.ppo_epochs * 2
    # divisible batch: all-full single-dispatch path
    sites = dataset.generate(128, seed=52)
    feats = agent.feats(sites)
    a, raw, logp, v = agent.sample_actions(sites, feats=feats)
    r = env.rewards_batch(sites, a)
    agent.update(sites, a, raw, logp, r, feats=feats)
    assert agent.last_minibatch_count == NV.ppo_epochs * 2


def test_fused_and_legacy_update_both_learn():
    sites = dataset.generate(120, seed=53)
    env = CostModelEnv(NV)
    for fused in (True, False):
        agent = PPOAgent(NV, lr=5e-4, seed=0, fused=fused)
        hist = agent.train(sites, env, total_steps=1500)
        first = np.mean([h["reward_mean"] for h in hist[:2]])
        last = np.mean([h["reward_mean"] for h in hist[-2:]])
        assert last > first, (fused, first, last)
