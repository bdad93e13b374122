"""The serving path (PR 10): fused one-dispatch tuning, cross-session
micro-batching with spy-asserted bitwise parity against the unbatched
path, the deadline-aware admission queue's typed errors and health
transitions, and per-session result isolation under concurrent load."""
import threading
import time

import numpy as np
import pytest

from repro.configs.neurovec import NeuroVecConfig
from repro.core.agents import AGENT_NAMES, make_agent
from repro.core.agents.brute import brute_force_labels
from repro.core.env import ActionSpace, CostModelEnv
from repro.models.compute import KernelSite
from repro.serving import (AgentBatch, DeadlineExceeded, FusedTuner,
                           QueueFull, Server, ServingConfig, ServingError,
                           bucket_size)
from repro.service import TuningService


def small_cfg() -> NeuroVecConfig:
    return NeuroVecConfig(
        bm_choices=(16, 32), bn_choices=(128,), bk_choices=(128,),
        bq_choices=(32, 64), bkv_choices=(128,), chunk_choices=(16, 32),
        train_batch=32, sgd_minibatch=16, ppo_epochs=2)


CFG = small_cfg()

SITES = [
    KernelSite(site="sv.mm0", kind="matmul", m=64, n=128, k=128),
    KernelSite(site="sv.mm1", kind="matmul", m=96, n=256, k=128),
    KernelSite(site="sv.attn", kind="attention", m=64, n=32, k=64,
               batch=2, causal=True),
    KernelSite(site="sv.scan", kind="chunk_scan", m=32, n=16, k=8,
               batch=2),
]


def _sites(tag: str, n: int = 3):
    """Distinct per-session site lists so cross-request mixing in the
    batcher would change results."""
    return [KernelSite(site=f"{tag}.mm{i}", kind="matmul",
                       m=32 * (i + 1), n=128, k=128) for i in range(n)]


# ---------------------------------------------------------------------------
# FusedTuner: one dispatch, argmin parity with the float64 reference
# ---------------------------------------------------------------------------

class TestFusedTuner:
    def test_actions_match_brute_force_float64_reference(self):
        """The float32 device grid must pick the same argmin as the
        float64 NumPy reference, per site and per kind."""
        env = CostModelEnv(CFG, seed=0)
        ref = brute_force_labels(env, SITES)
        fused = FusedTuner(CFG).actions(SITES)
        np.testing.assert_array_equal(fused, np.asarray(ref))

    def test_tune_matches_inline_vectorizer_assembly(self):
        env = CostModelEnv(CFG, seed=0)
        space = ActionSpace(CFG)
        ref = brute_force_labels(env, SITES)
        prog = FusedTuner(CFG).tune(SITES)
        assert set(prog.tiles) == {s.key() for s in SITES}
        for s, a in zip(SITES, ref):
            assert prog.tiles[s.key()] == space.tiles(s.kind, a)

    def test_refuses_a_kind_it_cannot_price(self):
        """A grouped matmul has no formula in the fused grid: the tuner
        refuses it rather than pricing it as another kind."""
        gmm = KernelSite(site="moe.experts.up", kind="grouped_matmul",
                         m=96, n=1536, k=5120, batch=20)
        with pytest.raises(ValueError, match="grouped_matmul"):
            FusedTuner(CFG).actions(SITES[:1] + [gmm])

    def test_one_dispatch_and_bucketed_trace_reuse(self):
        """tune() is ONE device dispatch; batch sizes inside one
        power-of-two bucket reuse the jit specialization (no retrace)."""
        t = FusedTuner(CFG)
        t.tune(SITES[:3])
        assert t.dispatch_count == 1 and t.trace_count == 1
        t.tune(SITES)                         # 4 sites: same bucket of 8
        assert t.dispatch_count == 2 and t.trace_count == 1
        t.actions(SITES[:2])
        assert t.dispatch_count == 3 and t.trace_count == 1
        assert t.last_padded_batch == bucket_size(2)
        st = t.stats()
        assert st["serving_fused_dispatches_total"] == 3
        assert st["serving_fused_traces_total"] == 1
        assert st["serving_fused_sites_total"] == 9

    def test_tune_many_slices_bitwise_equal_to_solo_tunes(self):
        t = FusedTuner(CFG)
        a, b = SITES[:2], SITES[2:]
        many = t.tune_many([a, b, []])
        assert many[0].tiles == FusedTuner(CFG).tune(a).tiles
        assert many[1].tiles == FusedTuner(CFG).tune(b).tiles
        assert many[2].tiles == {}
        assert t.dispatch_count == 1          # the pair was one dispatch

    def test_fused_surrogate_matches_surrogate_oracle_argmin(self, tmp_path):
        from repro.measure.db import MeasureDB, make_key
        from repro.surrogate import SurrogateOracle, train_from_db

        db = MeasureDB(str(tmp_path / "m.jsonl"))
        for s in SITES:
            if s.kind != "matmul":
                continue
            for t0 in (16, 32):
                db.put(make_key(s.key(), (t0, 128, 128), "fix"),
                       1e-3 * (1 + t0) * (1 + s.m / 64))
        db.put(make_key(SITES[2].key(), (64, 128, 1), "fix"), 2e-3)
        db.put(make_key(SITES[3].key(), (32, 1, 1), "fix"), 3e-3)
        db.close()
        model = train_from_db(str(tmp_path / "m.jsonl"), min_pairs=4,
                              hidden=(16,), ensemble=2, steps=40)
        assert model is not None
        oracle = SurrogateOracle(CFG, model, seed=0)
        ref = brute_force_labels(oracle, SITES)
        fused = FusedTuner(CFG, surrogate=model).actions(SITES)
        np.testing.assert_array_equal(fused, np.asarray(ref))


# ---------------------------------------------------------------------------
# AgentBatch: spy-asserted bitwise parity for every registry agent
# ---------------------------------------------------------------------------

def _fitted(name: str):
    agent = make_agent(name, CFG, seed=0)
    env = CostModelEnv(CFG, seed=0)
    kw = {"total_steps": 48} if name == "ppo" else {}
    agent.fit(SITES, env, **kw)
    return agent


@pytest.mark.parametrize("name", AGENT_NAMES)
def test_batched_act_bitwise_equals_sequential_act(name):
    """Concatenate two requests through one AgentBatch forward: each
    request's actions are bitwise what a solo act() returns, and a spy
    proves the batched path ran ONE forward (batch-unsafe agents run one
    per request by design)."""
    agent = _fitted(name)
    a, b = SITES[:2], SITES[2:]
    expect = [np.asarray(agent.act(a, sample=False)),
              np.asarray(agent.act(b, sample=False))]

    calls = []
    orig_act = agent.act
    agent.act = lambda *args, **kw: (calls.append("act"),
                                     orig_act(*args, **kw))[1]
    if hasattr(agent, "act_bucketed"):
        orig_bucketed = agent.act_bucketed
        agent.act_bucketed = lambda *args, **kw: (
            calls.append("bucketed"), orig_bucketed(*args, **kw))[1]
    batch = AgentBatch(agent)
    got = batch.act_many([a, b])

    np.testing.assert_array_equal(got[0], expect[0])
    np.testing.assert_array_equal(got[1], expect[1])
    if batch.coalesced:
        assert len(calls) == 1               # one forward for the batch
        if name == "ppo":
            assert calls == ["bucketed"]     # padded-bucket jit reuse
    else:
        assert calls == ["act", "act"]       # per-request by design
    assert batch.requests == 2 and batch.sites == len(SITES)


def test_ppo_act_bucketed_padding_is_bitwise_invisible():
    agent = _fitted("ppo")
    plain = np.asarray(agent.act(SITES, sample=False))
    padded = agent.act_bucketed(SITES, bucket=16)
    np.testing.assert_array_equal(plain, padded)


# ---------------------------------------------------------------------------
# Server: admission, batching, typed errors, health
# ---------------------------------------------------------------------------

def test_concurrent_sessions_one_fused_dispatch_and_isolation():
    """Concurrent model-oracle tunes coalesce into one batch = one fused
    device dispatch; each session gets exactly its own program."""
    lists = [_sites(f"c{i}", n=2 + i % 2) for i in range(4)]
    with TuningService(CFG, serving={"max_wait_ms": 50.0},
                       metrics=False) as svc:
        sessions = [svc.open_session(agent="brute", oracle="model")
                    for _ in lists]
        for s, ss in zip(sessions, lists):
            s.fit(ss)
        futs = [s.tune_async(ss) for s, ss in zip(sessions, lists)]
        progs = [f.result(timeout=120) for f in futs]
        st = svc.server.stats()
    env = CostModelEnv(CFG, seed=0)
    space = ActionSpace(CFG)
    for ss, prog in zip(lists, progs):
        assert set(prog.tiles) == {x.key() for x in ss}
        for x, a in zip(ss, brute_force_labels(env, ss)):
            assert prog.tiles[x.key()] == space.tiles(x.kind, a)
    assert st["serving_requests_total"] == 4
    assert st["serving_batches_total"] == 1
    assert st["serving_fused_dispatches_total"] == 1
    assert st["serving_fused_traces_total"] == 1


def test_fifo_resolution_within_an_slo_class():
    """Requests sharing one SLO class resolve strictly in admission
    order within the flushed batch."""
    order = []
    with TuningService(CFG, serving={"max_wait_ms": 30.0},
                       metrics=False) as svc:
        sessions = [svc.open_session(agent="brute", oracle="model")
                    for _ in range(4)]
        lists = [_sites(f"f{i}") for i in range(4)]
        for s, ss in zip(sessions, lists):
            s.fit(ss)
        futs = []
        for i, (s, ss) in enumerate(zip(sessions, lists)):
            f = s.tune_async(ss)
            f.add_done_callback(lambda _f, i=i: order.append(i))
            futs.append(f)
        for f in futs:
            f.result(timeout=120)
    assert order == [0, 1, 2, 3]


def test_queue_full_sheds_with_typed_error_and_degrades_health():
    with TuningService(CFG, serving={"max_queue": 1, "max_wait_ms": 150.0,
                                     "slo_ms": 10_000.0},
                       metrics=False) as svc:
        s = svc.open_session(agent="brute", oracle="model")
        s.fit(SITES[:1])
        assert svc.server.health() == "ok"
        f1 = s.tune_async(SITES[:1])
        with pytest.raises(QueueFull, match="max_queue"):
            s.tune_async(SITES[:1])
        assert svc.server.health() == "degraded"     # breach in window
        assert svc.health() == "degraded"            # service agrees
        assert f1.result(timeout=120) is not None    # queued one survives
        assert svc.server.stats()["serving_shed_total"] == 1
    assert svc.server.health() == "down"             # closed


def test_expired_budget_fails_future_with_deadline_exceeded():
    with TuningService(CFG, serving=True, metrics=False) as svc:
        s = svc.open_session(agent="brute", oracle="model")
        s.fit(SITES[:1])
        fut = s.tune_async(SITES[:1], slo_ms=1e-4)   # expired on arrival
        with pytest.raises(DeadlineExceeded, match="budget"):
            fut.result(timeout=120)
        st = svc.server.stats()
        assert st["serving_deadline_misses_total"] == 1
        assert svc.server.health() == "degraded"
        # the session survives its failed request — and close() drains
        # the dead future without re-raising
        assert s.tune(SITES[:1]).tiles


def test_health_recovers_after_breach_window():
    with TuningService(CFG, serving={"max_queue": 1, "max_wait_ms": 1.0,
                                     "health_window_s": 0.2},
                       metrics=False) as svc:
        s = svc.open_session(agent="brute", oracle="model")
        s.fit(SITES[:1])
        f1 = s.tune_async(SITES[:1])
        try:
            s.tune_async(SITES[:1])
            shed = False
        except QueueFull:
            shed = True
        if shed:                      # breach is fresh: inside the window
            assert svc.server.health() == "degraded"
        f1.result(timeout=120)
        time.sleep(0.25)              # ...and expired once it passes
        assert svc.server.health() == "ok"


def test_submit_after_close_raises_and_slo_needs_serving():
    svc = TuningService(CFG, serving=True, metrics=False)
    s = svc.open_session(agent="brute", oracle="model")
    svc.close()
    with pytest.raises(ServingError, match="closed"):
        svc.server.submit(s, SITES[:1])
    with TuningService(CFG, metrics=False) as plain:
        p = plain.open_session(agent="brute", oracle="model")
        with pytest.raises(ValueError, match="serving"):
            p.tune_async(SITES[:1], slo_ms=5.0)


def test_empty_sites_resolve_immediately():
    with TuningService(CFG, serving=True, metrics=False) as svc:
        s = svc.open_session(agent="brute", oracle="model")
        assert s.tune([]).tiles == {}
        assert svc.server.stats()["serving_batches_total"] == 0


def test_warm_store_tier_answers_at_admission(tmp_path):
    with TuningService(CFG, serving=True, metrics=False,
                       program_store=str(tmp_path / "p.jsonl")) as svc:
        s = svc.open_session(agent="brute", oracle="model")
        s.fit(SITES[:2])
        p1 = s.tune(SITES[:2])               # miss: through the batcher
        p2 = s.tune(SITES[:2])               # hit: resolved at admission
        assert p2.tiles == p1.tiles
        st = svc.server.stats()
        assert st["serving_store_hits_total"] == 1
        assert st["serving_batches_total"] == 1      # hit never queued
        sst = s.stats()
        assert sst["session_store_hits_total"] == 1
        assert sst["session_store_misses_total"] == 1


def test_mixed_agent_routes_interleaved_under_load():
    """Fused (brute/model) and coalesced-forward (ppo) sessions submit
    concurrently from threads: every result is isolated per session and
    bitwise equal to that session's own unbatched decision."""
    with TuningService(CFG, serving={"max_wait_ms": 30.0},
                       metrics=False) as svc:
        brutes = [(svc.open_session(agent="brute", oracle="model"),
                   _sites(f"mb{i}")) for i in range(2)]
        ppos = [(svc.open_session(agent="ppo", oracle="model"),
                 _sites(f"mp{i}")) for i in range(2)]
        for s, ss in brutes + ppos:
            kw = {"total_steps": 48} if s.agent.name == "ppo" else {}
            s.fit(ss, **kw)
        space = ActionSpace(CFG)
        expect = {}
        for s, ss in brutes + ppos:
            acts = np.asarray(s.agent.act(ss, sample=False))
            expect[s.name] = {x.key(): space.tiles(x.kind, a)
                              for x, a in zip(ss, acts)}

        results, errors = {}, []

        def worker(sess, ss):
            try:
                results[sess.name] = sess.tune(ss)
            except Exception as e:           # pragma: no cover - surfaced
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(s, ss))
                   for s, ss in brutes + ppos]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        st = svc.server.stats()
    assert not errors
    for s, _ in brutes + ppos:
        assert results[s.name].tiles == expect[s.name]
    assert st["serving_requests_total"] == 4
    assert st["serving_fused_dispatches_total"] >= 1
    assert st["serving_batched_requests_total"] >= 1


def test_serving_config_spellings_and_stats_keys():
    with TuningService(CFG, serving=ServingConfig(slo_ms=250.0),
                       metrics=False) as svc:
        assert isinstance(svc.server, Server)
        assert svc.server.cfg.slo_ms == 250.0
        s = svc.open_session(agent="brute", oracle="model")
        s.fit(SITES[:1]).tune(SITES[:1])
        st = svc.server.stats()
        for k in ("serving_requests_total", "serving_queue_depth",
                  "serving_shed_total", "serving_deadline_misses_total",
                  "serving_batches_total", "serving_store_hits_total",
                  "serving_queue_wait_seconds_total",
                  "serving_batch_requests_hist", "serving_tune_p50_ms",
                  "serving_tune_p99_ms", "serving_fused_dispatches_total",
                  "health"):
            assert k in st, k
        assert st["serving_tune_p99_ms"] >= st["serving_tune_p50_ms"] >= 0
        assert "serving" in svc.stats()
    assert svc.stats()["serving"]["health"] == "down"


def test_instrument_serving_lands_series_in_registry():
    from repro.obs import MetricsRegistry
    reg = MetricsRegistry()
    with TuningService(CFG, serving=True, metrics=reg) as svc:
        s = svc.open_session(agent="brute", oracle="model")
        s.fit(SITES[:2]).tune(SITES[:2])
        snap = reg.snapshot()
    assert snap["serving_requests_total"] == 1.0
    assert snap["serving_batches_total"] == 1.0
    assert snap["serving_fused_dispatches_total"] == 1.0
    assert snap["serving_tune_seconds"]["count"] == 1
    assert snap["serving_queue_wait_seconds"]["count"] == 1
    assert snap["serving_batch_requests"]["count"] == 1
