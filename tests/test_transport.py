"""The MeasureTransport contract — ONE conformance suite over every
implementation, plus the pool-specific failure modes (worker death,
requeue, fail-closed, persistent-DB exactly-once semantics).

The pool cases run *real* worker subprocesses speaking the real pipe
protocol; the runners inside them come from ``pool_helpers`` factories
(deterministic values derived from the DB key, so in-process and pool
results are bit-identical — the parity the service tests build on).
"""
import json
import os

import numpy as np
import pytest

from repro.core.protocols import AsyncOracle, MeasureTransport, Oracle
from repro.measure import (InProcessTransport, MeasureDB, TransportMeasureFn,
                           WorkerPoolTransport, make_key, make_measured_env,
                           make_transport)
from repro.models.compute import KernelSite

from pool_helpers import FailRunner, FakeRunner, fake_value

MM = KernelSite(site="t.mm", kind="matmul", m=32, n=128, k=128)
ATTN = KernelSite(site="t.attn", kind="attention", m=64, n=32, k=64,
                  batch=2, causal=True)
SCAN = KernelSite(site="t.scan", kind="chunk_scan", m=32, n=16, k=8,
                  batch=2)
SITES = [MM, ATTN, SCAN]
TILES = np.array([[16, 128, 128], [64, 128, 1], [32, 1, 1]])

TRANSPORTS = ("inproc", "pool")


def _make(kind: str, db_path=None, factory="pool_helpers:deterministic",
          **kw):
    if kind == "inproc":
        runner = kw.pop("runner", None) or FakeRunner()
        assert not kw
        return InProcessTransport(
            runner, MeasureDB(db_path) if db_path else None)
    return WorkerPoolTransport(workers=2, db=db_path, factory=factory, **kw)


# ---------------------------------------------------------------------------
# the shared conformance suite
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", TRANSPORTS)
def test_conformance_protocol_and_values(kind):
    with _make(kind) as t:
        assert isinstance(t, MeasureTransport)
        futs = t.submit(SITES, TILES)
        t.drain()
        assert len(futs) == 3
        for s, tile, f in zip(SITES, TILES, futs):
            assert f.done()
            assert f.result() == fake_value(s.key(), tile)
        st = t.stats()
        assert st["transport_misses_total"] == 3
        assert st["transport_timed_pairs_total"] == 3
        assert st["transport_inflight_pairs"] == 0
        for key in ("transport_hits_total", "transport_misses_total",
                    "transport_coalesced_total",
                    "transport_timed_pairs_total",
                    "transport_failed_pairs_total",
                    "transport_retries_total",
                    "transport_inflight_pairs", "transport_hit_ratio"):
            assert key in st
        for legacy in ("hits", "misses", "timed_pairs", "in_flight",
                       "hit_rate"):
            assert legacy not in st


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_conformance_duplicate_keys_coalesce(kind):
    """The same (site, tiles) key submitted many times in one batch is
    measured exactly once; every future resolves to that value."""
    with _make(kind) as t:
        sites = [MM] * 4 + [ATTN]
        tiles = np.array([[16, 128, 128]] * 4 + [[64, 128, 1]])
        futs = t.submit(sites, tiles)
        t.drain()
        vals = [f.result() for f in futs]
        assert vals[:4] == [fake_value(MM.key(), (16, 128, 128))] * 4
        st = t.stats()
        assert st["transport_misses_total"] == 2
        assert st["transport_coalesced_total"] == 3
        assert st["transport_timed_pairs_total"] == 2


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_conformance_db_hits_and_zero_retiming(kind, tmp_path):
    """Second transport against the same DB path re-times nothing."""
    p = str(tmp_path / "m.jsonl")
    with _make(kind, db_path=p) as t1:
        out1 = [f.result() for f in t1.submit(SITES, TILES)]
    with _make(kind, db_path=p) as t2:
        futs = t2.submit(SITES, TILES)
        out2 = [f.result() for f in futs]
        st = t2.stats()
    assert out2 == out1
    assert st["transport_hits_total"] == 3
    assert st["transport_misses_total"] == 0
    assert st["transport_timed_pairs_total"] == 0
    assert st["transport_hit_ratio"] == 1.0


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_conformance_db_written_exactly_once_per_key(kind, tmp_path):
    """Coalesced duplicates must not produce duplicate DB lines."""
    p = str(tmp_path / "m.jsonl")
    sites = [MM, MM, ATTN, MM]
    tiles = np.array([[16, 128, 128]] * 2 + [[64, 128, 1], [16, 128, 128]])
    with _make(kind, db_path=p) as t:
        t.submit(sites, tiles)
        t.drain()
        backend = t.backend_key
    keys = [json.loads(line)["k"] for line in open(p)]
    assert sorted(keys) == sorted({
        make_key(MM.key(), (16, 128, 128), backend),
        make_key(ATTN.key(), (64, 128, 1), backend)})


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_conformance_failure_fails_closed(kind):
    """A pair the runner cannot measure resolves to inf — never raises."""
    fail = KernelSite(site="fail", kind="matmul", m=32, n=128, k=128)
    t = _make(kind, factory="pool_helpers:failing") if kind == "pool" \
        else _make(kind, runner=FailRunner())
    with t:
        futs = t.submit([fail, MM], np.array([[16, 128, 128]] * 2))
        t.drain()
        assert futs[0].result() == float("inf")
        assert futs[1].result() == fake_value(MM.key(), (16, 128, 128))
        st = t.stats()
        assert st["transport_failed_pairs_total"] == 1
        assert st["transport_timed_pairs_total"] == 1


@pytest.mark.parametrize("kind", TRANSPORTS)
def test_conformance_submit_after_close_raises(kind):
    t = _make(kind)
    t.close()
    with pytest.raises(RuntimeError, match="closed"):
        t.submit([MM], np.array([[16, 128, 128]]))
    t.close()                                      # idempotent


# ---------------------------------------------------------------------------
# pool-specific failure modes
# ---------------------------------------------------------------------------

def test_pool_worker_death_requeues_and_recovers(tmp_path, monkeypatch):
    """A worker killed mid-batch loses one attempt; the requeued job
    succeeds on the respawned worker and the batch completes."""
    sentinel = str(tmp_path / "died_once")
    monkeypatch.setenv("REPRO_TEST_BOOM_FILE", sentinel)
    boom = KernelSite(site="boom", kind="matmul", m=64, n=128, k=128)
    with _make("pool", factory="pool_helpers:boom_once") as t:
        futs = t.submit([boom, MM], np.array([[16, 128, 128]] * 2))
        t.drain()
        assert futs[0].result() == fake_value(boom.key(), (16, 128, 128))
        assert futs[1].result() == fake_value(MM.key(), (16, 128, 128))
        st = t.stats()
        assert st["transport_retries_total"] >= 1
        assert st["pool_worker_restarts_total"] >= 1
        assert st["transport_failed_pairs_total"] == 0
    assert os.path.exists(sentinel)                # it really did die


def test_pool_worker_death_fails_closed_after_max_attempts(tmp_path):
    """A job that kills every worker it lands on burns its attempts and
    resolves inf (persisted, so it is never re-attempted) while
    unrelated jobs survive."""
    p = str(tmp_path / "m.jsonl")
    boom = KernelSite(site="boom", kind="matmul", m=64, n=128, k=128)
    with _make("pool", db_path=p, factory="pool_helpers:boom_always",
               max_attempts=2) as t:
        futs = t.submit([boom, MM], np.array([[16, 128, 128]] * 2))
        t.drain()
        assert futs[0].result() == float("inf")
        assert futs[1].result() == fake_value(MM.key(), (16, 128, 128))
        st = t.stats()
        assert st["transport_retries_total"] == 1  # attempt 1 requeued
        assert st["transport_failed_pairs_total"] == 1
        assert st["transport_timed_pairs_total"] == 1
        backend = t.backend_key
    # the fail-closed verdict is persisted as null -> inf: a later run
    # serves it from the DB instead of crashing more workers
    db = MeasureDB(p)
    assert db.get(make_key(boom.key(), (16, 128, 128),
                           backend)) == float("inf")


def test_pool_cross_submit_inflight_coalescing():
    """A second submit of a key already measuring joins the in-flight
    job instead of queueing a duplicate."""
    with _make("pool", factory="pool_helpers:slow") as t:
        f1 = t.submit([MM], np.array([[16, 128, 128]]))
        f2 = t.submit([MM], np.array([[16, 128, 128]]))   # while in flight
        t.drain()
        assert f1[0] is f2[0]
        assert f1[0].result() == fake_value(MM.key(), (16, 128, 128))
        st = t.stats()
        assert st["transport_misses_total"] == 1
        assert st["transport_coalesced_total"] == 1


def test_pool_raising_runner_fails_closed_without_killing_worker():
    """A runner that raises inside the worker answers the failure
    marker (inf) instead of dying — no respawn, no retry burn."""
    boom = KernelSite(site="boom", kind="matmul", m=64, n=128, k=128)
    with _make("pool", factory="pool_helpers:raising") as t:
        futs = t.submit([boom, MM], np.array([[16, 128, 128]] * 2))
        t.drain()
        assert futs[0].result() == float("inf")
        assert futs[1].result() == fake_value(MM.key(), (16, 128, 128))
        st = t.stats()
        assert st["transport_failed_pairs_total"] == 1
        assert st["transport_retries_total"] == 0
        assert st["pool_worker_restarts_total"] == 0


def test_pool_wedged_worker_hits_job_timeout_and_fails_closed():
    """A measurement that hangs costs one worker per attempt (killed at
    job_timeout, job requeued), then fails closed — drain() returns."""
    wedge = KernelSite(site="wedge", kind="matmul", m=64, n=128, k=128)
    with WorkerPoolTransport(workers=2, factory="pool_helpers:wedging",
                             max_attempts=2, job_timeout=1.5) as t:
        futs = t.submit([wedge, MM], np.array([[16, 128, 128]] * 2))
        t.drain()
        assert futs[0].result() == float("inf")
        assert futs[1].result() == fake_value(MM.key(), (16, 128, 128))
        st = t.stats()
        assert st["transport_failed_pairs_total"] == 1
        assert st["transport_retries_total"] == 1
        assert st["pool_worker_restarts_total"] >= 1


def test_inproc_raising_runner_resolves_futures_before_propagating():
    """A raising runner must not strand in-flight futures (a coalesced
    waiter would hang forever); they fail closed, then the error
    surfaces to the submitting caller."""

    class Boom(FakeRunner):
        def __call__(self, sites, tiles):
            raise RuntimeError("runner bug")

    t = InProcessTransport(Boom())
    with pytest.raises(RuntimeError, match="runner bug"):
        t.submit([MM], np.array([[16, 128, 128]]))
    t.drain()                                      # must not hang
    st = t.stats()
    assert st["transport_failed_pairs_total"] == 1
    assert st["transport_inflight_pairs"] == 0
    # the key is re-submittable (not stuck on a dead in-flight future)
    t.runner = FakeRunner()
    futs = t.submit([MM], np.array([[16, 128, 128]]))
    assert futs[0].result() == fake_value(MM.key(), (16, 128, 128))
    t.close()


def test_pool_rejects_bad_arguments():
    with pytest.raises(ValueError, match="workers"):
        WorkerPoolTransport(workers=0)
    with pytest.raises(ValueError, match="max_attempts"):
        WorkerPoolTransport(workers=1, max_attempts=0)
    with pytest.raises(RuntimeError, match="failed to start"):
        WorkerPoolTransport(workers=1,
                            factory="pool_helpers:no_such_factory")


def test_pool_refuses_tpu_backend(monkeypatch):
    """A chip belongs to one process: the pool refuses before it spawns."""
    import threading

    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = threading.active_count()
    with pytest.raises(RuntimeError, match="one process"):
        WorkerPoolTransport(workers=2)
    assert threading.active_count() == before


# ---------------------------------------------------------------------------
# the factories and adapters around transports
# ---------------------------------------------------------------------------

def test_make_transport_validation():
    with pytest.raises(ValueError, match="unknown transport"):
        make_transport("carrier-pigeon")
    with pytest.raises(ValueError, match="workers"):
        make_transport("inproc", workers=4)
    with pytest.raises(ValueError, match="workers"):
        make_transport("pool", workers=0)          # not coerced to default
    with pytest.raises(TypeError, match="db"):
        make_transport("inproc", db=MeasureDB("/tmp/x.jsonl"),
                       db_path="/tmp/y.jsonl")
    with pytest.raises(TypeError, match="runner"):
        make_transport("pool", runner=FakeRunner())
    t = make_transport("inproc", runner=FakeRunner())
    assert isinstance(t, InProcessTransport)
    t.close()


def test_make_measured_env_rejects_args_with_prebuilt_transport():
    t = InProcessTransport(FakeRunner())
    with pytest.raises(TypeError, match="pre-built transport"):
        make_measured_env(transport=t, db_path="/tmp/x.jsonl")
    with pytest.raises(TypeError, match="pre-built transport"):
        make_measured_env(transport=t, reps=3)
    t.close()


def test_transport_measure_fn_adapts_any_transport():
    with InProcessTransport(FakeRunner()) as t:
        fn = TransportMeasureFn(t)
        out = fn(SITES, TILES)
        assert out.shape == (3,)
        np.testing.assert_allclose(
            out, [fake_value(s.key(), tl) for s, tl in zip(SITES, TILES)])
        assert fn.misses == 3 and fn.hits == 0


def test_async_oracle_delegates_and_submits():
    from repro.configs.neurovec import NeuroVecConfig
    from repro.core.env import CostModelEnv, MeasuredEnv

    cfg = NeuroVecConfig(bm_choices=(8, 16), bn_choices=(128,),
                         bk_choices=(128,), bq_choices=(64,),
                         bkv_choices=(128,), chunk_choices=(32,))
    t = InProcessTransport(FakeRunner())
    env = MeasuredEnv(cfg, measure_fn=TransportMeasureFn(t))
    ao = AsyncOracle(env, t)
    assert isinstance(ao, Oracle)
    assert ao.cfg is cfg and ao.space is env.space

    tiles = np.array([[16, 128, 128]])
    futs = ao.submit_tiles([MM], tiles)
    ao.drain()
    # the async path and the synchronous Oracle path price identically
    np.testing.assert_allclose([f.result() for f in futs],
                               ao.tiles_costs([MM], tiles))

    # a purely synchronous oracle adapts too — but has no async path
    sync = AsyncOracle(CostModelEnv(cfg))
    assert isinstance(sync, Oracle)
    with pytest.raises(RuntimeError, match="no transport"):
        sync.submit_tiles([MM], tiles)
    sync.drain()                                   # no-op, must not raise
    ao.close()
