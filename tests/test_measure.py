"""The ``repro.measure`` subsystem: timing helper, measurement DB
(round-trip, key collisions, corruption recovery, zero re-timing),
runner fail-closed behaviour, and the assembled measured oracle."""
import json

import numpy as np
import pytest

from repro.configs.neurovec import NeuroVecConfig
from repro.measure import (CachedMeasureFn, MeasureDB, MeasureRunner,
                           make_key, make_measured_env, timing)
from repro.models.compute import KernelSite

SMALL = NeuroVecConfig(
    bm_choices=(8, 16), bn_choices=(128,), bk_choices=(128,),
    bq_choices=(64,), bkv_choices=(128,), chunk_choices=(32,))

MM = KernelSite(site="t.mm", kind="matmul", m=32, n=128, k=128)
ATTN = KernelSite(site="t.attn", kind="attention", m=64, n=32, k=64,
                  batch=2, causal=True)
SCAN = KernelSite(site="t.scan", kind="chunk_scan", m=32, n=16, k=8,
                  batch=2)


class SpyRunner:
    """Counting measure_fn with a stable backend fingerprint."""

    backend_key = "spy-backend"

    def __init__(self, value=1e-3):
        self.value = value
        self.calls = 0
        self.pairs = 0

    def __call__(self, sites, tiles):
        self.calls += 1
        self.pairs += len(sites)
        return np.full(len(sites), self.value, np.float64)


# ---------------------------------------------------------------------------
# timing helper
# ---------------------------------------------------------------------------

def test_median_time_basic():
    t = timing.median_time(lambda: sum(range(1000)), reps=3, warmup=1)
    assert t >= 0.0 and np.isfinite(t)
    with pytest.raises(ValueError):
        timing.median_time(lambda: None, reps=0)


def test_interleaved_medians_shapes():
    ta, tb = timing.interleaved_medians(lambda: 1, lambda: 2, reps=3)
    assert ta >= 0.0 and tb >= 0.0


# ---------------------------------------------------------------------------
# the persistent DB
# ---------------------------------------------------------------------------

def test_db_roundtrip(tmp_path):
    p = str(tmp_path / "m.jsonl")
    db = MeasureDB(p)
    k1 = make_key(MM.key(), (16, 128, 128), "b")
    k2 = make_key(ATTN.key(), (64, 128, 1), "b")
    db.put(k1, 1.5e-3)
    db.put(k2, float("inf"))            # failed measurement persists too
    db.close()

    db2 = MeasureDB(p)
    assert len(db2) == 2
    assert db2.get(k1) == pytest.approx(1.5e-3)
    assert db2.get(k2) == float("inf")  # null round-trips to inf
    assert db2.get("missing") is None
    assert db2.skipped_lines == 0


def test_db_key_collision_safety_dtype(tmp_path):
    # two sites differing ONLY in dtype must never share an entry
    a = KernelSite(site="x", kind="matmul", m=64, n=128, k=128,
                   dtype="bfloat16")
    b = KernelSite(site="x", kind="matmul", m=64, n=128, k=128,
                   dtype="float32")
    t = (16, 128, 128)
    ka, kb = make_key(a.key(), t, "be"), make_key(b.key(), t, "be")
    assert ka != kb
    db = MeasureDB(str(tmp_path / "m.jsonl"))
    db.put(ka, 1.0)
    db.put(kb, 2.0)
    assert db.get(ka) == 1.0 and db.get(kb) == 2.0
    # same site, different backend fingerprint: also distinct
    assert make_key(a.key(), t, "other") != ka


def test_db_corrupted_file_recovery(tmp_path):
    p = str(tmp_path / "m.jsonl")
    good1 = {"k": "a", "v": 1.0}
    good2 = {"k": "b", "v": None}
    with open(p, "w") as f:
        f.write(json.dumps(good1) + "\n")
        f.write("this is not json\n")
        f.write('{"k": "truncated", "v": 0.\n')      # torn write
        f.write('{"no_key_field": 1}\n')
        f.write('{"k": "c", "v": "not-a-number"}\n')
        f.write(json.dumps(good2) + "\n")
    db = MeasureDB(p)
    assert db.get("a") == 1.0
    assert db.get("b") == float("inf")
    assert db.skipped_lines == 4
    db.put("d", 3.0)                     # still writable after recovery
    db.close()
    assert MeasureDB(p).get("d") == 3.0


def test_db_torn_trailing_line_recovery(tmp_path):
    """A crash mid-append leaves a partial record with no newline; the
    next open must keep every intact line AND isolate the torn tail so
    the first new append cannot merge into it."""
    p = str(tmp_path / "m.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps({"k": "a", "v": 1.0}) + "\n")
        f.write(json.dumps({"k": "b", "v": 2.0}) + "\n")
        f.write('{"k": "c", "v": 3.')           # torn: no newline
    db = MeasureDB(p)
    assert db.get("a") == 1.0 and db.get("b") == 2.0
    assert db.get("c") is None
    assert db.skipped_lines == 1
    db.put("d", 3.0)                     # must land on a fresh line
    db.close()
    db2 = MeasureDB(p)
    assert db2.get("d") == 3.0
    assert db2.get("a") == 1.0 and db2.get("b") == 2.0
    assert db2.skipped_lines == 1        # torn tail still isolated, not
    assert len(db2) == 3                 # merged into the new record


def test_db_iter_records_skips_quarantine_and_corruption(tmp_path):
    """``iter_records`` is the surrogate training corpus: finite and
    failed measurements come through (last-wins), quarantined keys and
    corrupt lines never do, and the LRU bound does not hide disk rows."""
    p = str(tmp_path / "m.jsonl")
    kmm = make_key(MM.key(), (16, 128, 128), "spy-backend")
    kat = make_key(ATTN.key(), (64, 128, 1), "spy-backend")
    with open(p, "w") as f:
        f.write(json.dumps({"k": kmm, "v": 1.0}) + "\n")
        f.write("not json at all\n")                    # corrupt: skipped
        f.write(json.dumps({"k": "malformed-key", "v": 2.0}) + "\n")
        f.write(json.dumps({"k": kat, "v": None}) + "\n")
        f.write(json.dumps({"k": kmm, "v": 4.0}) + "\n")  # last-wins
    db = MeasureDB(p, max_entries=1)      # LRU must not limit iteration
    db.quarantine(make_key(SCAN.key(), (32, 1, 1), "spy-backend"),
                  attempts=2, reason="wedged")
    db.put(make_key(MM.key(), (8, 128, 128), "spy-backend"), 5.0)

    recs = {r.key: r for r in db.iter_records()}
    assert kmm in recs and recs[kmm].value == 4.0       # last-wins
    assert recs[kmm].kind == "matmul"
    assert recs[kmm].fingerprint == "spy-backend"
    assert recs[kat].value == float("inf")              # null -> inf
    assert recs[kat].kind == "attention"
    assert "malformed-key" not in recs                  # no 3-part shape
    assert not any("chunk_scan:t.scan" in k for k in recs)  # quarantined
    assert len(recs) == 3                # kmm, kat, and the post-open put
    db.close()
    assert {r.key for r in MeasureDB(p).iter_records()} == set(recs)


def test_db_quarantine_roundtrip_and_lru_survival(tmp_path):
    p = str(tmp_path / "m.jsonl")
    db = MeasureDB(p, max_entries=1)
    db.quarantine("poison", attempts=3, reason="killed workers")
    db.put("x", 1.0)                     # evicts "poison" from the LRU
    db.put("y", 2.0)
    assert db.get("poison") == float("inf")   # survives LRU eviction
    assert db.n_quarantined == 1
    db.close()
    db2 = MeasureDB(p)                   # fresh process analogue
    assert db2.get("poison") == float("inf")
    assert db2.quarantined("poison") == {"attempts": 3,
                                         "reason": "killed workers"}
    assert db2.quarantined("x") is None
    # backward compatible: an old reader sees a plain failed measurement
    rec = json.loads(open(p).readline())
    assert rec["v"] is None and rec["kind"] == "quarantine"


def test_db_duplicate_key_last_wins(tmp_path):
    p = str(tmp_path / "m.jsonl")
    db = MeasureDB(p)
    db.put("k", 1.0)
    db.put("k", 2.0)                     # re-measure appends; load last-wins
    db.close()
    assert MeasureDB(p).get("k") == 2.0


def test_db_lru_bounds_memory_not_disk(tmp_path):
    p = str(tmp_path / "m.jsonl")
    db = MeasureDB(p, max_entries=2)
    for i in range(4):
        db.put(f"k{i}", float(i))
    assert len(db) == 2 and db.get("k3") == 3.0 and db.get("k0") is None
    db.close()
    assert len(MeasureDB(p)) == 4        # disk kept everything


def test_second_run_performs_zero_timings(tmp_path):
    """THE persistence guarantee: same DB path => no runner calls."""
    p = str(tmp_path / "m.jsonl")
    sites = [MM, ATTN, SCAN, MM]                   # duplicate in batch
    tiles = np.array([[16, 128, 128], [64, 128, 1], [32, 1, 1],
                      [16, 128, 128]])

    spy1 = SpyRunner()
    fn1 = CachedMeasureFn(spy1, MeasureDB(p))
    out1 = fn1(sites, tiles)
    # cold DB: the 3 unique pairs are timed once each — the in-batch
    # duplicate coalesces onto the in-flight key (transport semantics)
    assert spy1.pairs == 3 and fn1.misses == 3
    assert fn1.transport.stats()["transport_coalesced_total"] == 1
    np.testing.assert_allclose(out1[3], out1[0])
    fn1.db.close()

    spy2 = SpyRunner(value=99.0)                   # would be visible if run
    fn2 = CachedMeasureFn(spy2, MeasureDB(p))
    out2 = fn2(sites, tiles)
    assert spy2.calls == 0 and spy2.pairs == 0     # zero timings
    assert fn2.hit_rate == 1.0
    np.testing.assert_allclose(out2, out1)


def test_cached_measure_fn_without_db_still_counts():
    spy = SpyRunner()
    fn = CachedMeasureFn(spy, db=None)
    fn([MM], np.array([[16, 128, 128]]))
    fn([MM], np.array([[16, 128, 128]]))
    assert spy.pairs == 2 and fn.misses == 2 and fn.hit_rate == 0.0


# ---------------------------------------------------------------------------
# the runner (interpret mode; tiny caps keep this fast)
# ---------------------------------------------------------------------------

def _tiny_runner(**kw):
    kw.setdefault("reps", 1)
    kw.setdefault("warmup", 1)
    kw.setdefault("interpret", True)
    kw.setdefault("max_dim", 64)
    return MeasureRunner(**kw)


def test_runner_times_every_kind():
    r = _tiny_runner()
    out = r([MM, ATTN, SCAN],
            np.array([[16, 128, 128], [64, 128, 1], [32, 1, 1]]))
    assert out.shape == (3,)
    assert np.isfinite(out).all() and (out > 0).all()
    assert r.timed_pairs == 3 and r.failed_pairs == 0


def test_runner_failure_fails_closed():
    r = _tiny_runner()
    bogus = KernelSite(site="b", kind="unknown_kind", m=8, n=8, k=8)
    out = r([bogus, MM], np.array([[16, 128, 128], [16, 128, 128]]))
    assert out[0] == float("inf")                  # isolated failure
    assert np.isfinite(out[1]) and out[1] > 0      # batch survives
    assert r.failed_pairs == 1 and r.timed_pairs == 1


def test_runner_backend_key_reflects_conditions():
    a = _tiny_runner().backend_key
    b = _tiny_runner(max_dim=32).backend_key
    assert a != b                       # different caps must not share cache
    assert "interpret" in a


@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("gpu", True),
                                               ("cpu", True)])
def test_runner_compiles_kernels_only_on_tpu(monkeypatch, backend,
                                             interpret):
    """The pltpu kernels compile only for a TPU; every other backend
    interprets them."""
    import jax

    from repro.measure.runner import default_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert default_interpret() is interpret


def test_device_kind_raises_without_a_device(monkeypatch):
    """No silent "unknown" backend: a timing must name its device."""
    import jax

    from repro.measure.runner import device_kind

    def no_devices():
        raise RuntimeError("no devices")
    monkeypatch.setattr(jax, "devices", no_devices)
    with pytest.raises(RuntimeError, match="no devices"):
        device_kind()


# ---------------------------------------------------------------------------
# the assembled measured oracle
# ---------------------------------------------------------------------------

def test_make_measured_env_persistent_stack(tmp_path):
    p = str(tmp_path / "m.jsonl")
    env = make_measured_env(SMALL, db_path=p, reps=1, warmup=1,
                            interpret=True, max_dim=64)
    sites = [MM, ATTN]
    acts = np.array([[1, 0, 0], [0, 0, 0]])
    r = env.rewards_batch(sites, acts)
    assert r.shape == (2,) and np.isfinite(r).all()
    first_timed = env.measure_fn.runner.timed_pairs
    assert first_timed > 0

    # fresh env + runner, same DB: rewards identical, zero timings
    env2 = make_measured_env(SMALL, db_path=p, reps=1, warmup=1,
                             interpret=True, max_dim=64)
    np.testing.assert_allclose(env2.rewards_batch(sites, acts), r)
    assert env2.measure_fn.runner.timed_pairs == 0
    assert env2.measure_fn.hit_rate == 1.0


def test_make_measured_env_rejects_conflicting_args():
    with pytest.raises(TypeError):
        make_measured_env(SMALL, runner=_tiny_runner(), reps=2)
