"""The ``repro.surrogate`` learned cost model: featurizer determinism,
corpus round-trip from a fixture DB, checkpoint fingerprint discipline,
and the payoff layer — ``MeasuredEnv(prune_topk=k)`` submitting exactly
the top-k candidates per site to the measurement transport."""
import numpy as np
import pytest

from repro.artifacts.agentio import ArtifactError
from repro.configs.neurovec import NeuroVecConfig
from repro.core import costmodel_vec
from repro.core.env import CostModelEnv, MeasuredEnv
from repro.measure import CachedMeasureFn, MeasureDB, make_key
from repro.models.compute import KernelSite
from repro.surrogate import (N_FEATURES, build_corpus, featurize,
                             load_surrogate, parse_key, save_surrogate,
                             train_from_db, train_surrogate)
from test_measure import ATTN, MM, SCAN, SpyRunner

# baseline matmul tile for MM (m=32) is (32, 128, 128) — deliberately NOT
# in bm_choices, so the pruned-grid pair count below is exactly top-k
# with no baseline-tile overlap.
CFG = NeuroVecConfig(
    bm_choices=(4, 8, 16), bn_choices=(128,), bk_choices=(128,),
    bq_choices=(64,), bkv_choices=(128,), chunk_choices=(32,))

FIXTURE_SITES = [
    KernelSite(site=f"f.mm{i}", kind="matmul", m=32 * (1 + i % 2),
               n=128, k=128)
    for i in range(4)
] + [ATTN, SCAN]


def _fixture_db(path, backend="fix"):
    """A warm MeasureDB: deterministic per-(site, tile) timings with real
    variance, plus one failed and one foreign-backend record."""
    db = MeasureDB(str(path))
    for s in FIXTURE_SITES:
        if s.kind != "matmul":
            continue
        for t0 in (4, 8, 16):
            db.put(make_key(s.key(), (t0, 128, 128), backend),
                   1e-3 * (1 + t0) * (1 + s.m / 64))
    db.put(make_key(ATTN.key(), (64, 128, 1), backend), 2e-3)
    db.put(make_key(SCAN.key(), (32, 1, 1), backend), 3e-3)
    db.put(make_key(MM.key(), (8, 128, 128), backend), float("inf"))
    db.put(make_key(MM.key(), (16, 128, 128), "other-backend"), 9e-3)
    db.close()
    return str(path)


# ---------------------------------------------------------------------------
# featurizer
# ---------------------------------------------------------------------------

def test_featurizer_shape_finite_deterministic():
    sites = [MM, ATTN, SCAN]
    tiles = np.array([[16, 128, 128], [64, 128, 1], [32, 1, 1]])
    X1 = featurize(sites, tiles)
    assert X1.shape == (3, N_FEATURES)
    assert np.isfinite(X1).all()
    # bitwise deterministic — the corpus and the oracle must agree
    np.testing.assert_array_equal(X1, featurize(sites, tiles))
    # sites differing only in shape get distinct rows
    assert not np.array_equal(X1[0], featurize(
        [KernelSite(site="t.mm", kind="matmul", m=64, n=128, k=128)],
        tiles[:1])[0])


def test_featurizer_refuses_a_kind_it_has_no_formula_for():
    gmm = KernelSite(site="moe.experts.up", kind="grouped_matmul", m=96,
                     n=1536, k=5120, batch=20)
    with pytest.raises(ValueError, match="grouped_matmul"):
        featurize([MM, gmm], np.array([[16, 128, 128], [8, 512, 512]]))


def test_featurizer_illegal_tile_still_finite():
    # the analytic-prior feature is clamped for illegal tiles; the row
    # must stay finite so training never sees inf/nan
    X = featurize([MM], np.array([[4096, 4096, 4096]]))
    assert X.shape == (1, N_FEATURES) and np.isfinite(X).all()


# ---------------------------------------------------------------------------
# corpus builder
# ---------------------------------------------------------------------------

def test_corpus_roundtrip_from_fixture_db(tmp_path):
    p = _fixture_db(tmp_path / "m.jsonl")
    corpus = build_corpus(p)
    # finite records only: the inf row never enters the corpus
    assert len(corpus.sites) == 3 * 4 + 2 + 1
    assert corpus.tiles.shape == (len(corpus.sites), 3)
    assert np.isfinite(corpus.y).all()
    # THE round-trip: every parsed (site, tiles, backend) regenerates its
    # own DB key exactly
    db = MeasureDB(p)
    vals = {r.key: r.value for r in db.iter_records()}
    for site, tiles, backend, y in zip(corpus.sites, corpus.tiles,
                                       corpus.backends, corpus.y):
        key = make_key(site.key(), tuple(int(t) for t in tiles), backend)
        assert key in vals
        assert y == pytest.approx(np.log(vals[key]))
    # backend filter drops the foreign fingerprint
    ours = build_corpus(p, backend="fix")
    assert len(ours.sites) == len(corpus.sites) - 1
    assert set(ours.backends) == {"fix"}


def test_parse_key_rejects_malformed():
    assert parse_key("malformed-key|1x2x3|b") is None
    assert parse_key("no pipes at all") is None
    ok = parse_key(make_key(ATTN.key(), (64, 128, 1), "be"))
    site, tiles, backend = ok
    assert site.kind == "attention" and site.causal and backend == "be"
    assert tiles == (64, 128, 1)
    assert site.key() == ATTN.key()


# ---------------------------------------------------------------------------
# model: training + checkpoint discipline
# ---------------------------------------------------------------------------

def test_train_predict_checkpoint_roundtrip(tmp_path):
    corpus = build_corpus(_fixture_db(tmp_path / "m.jsonl"), backend="fix")
    model = train_surrogate(corpus, hidden=(16,), ensemble=2, steps=60,
                            seed=0, backend="fix")
    pred = model.predict_seconds(list(corpus.sites), corpus.tiles)
    assert pred.shape == (len(corpus.sites),)
    assert np.isfinite(pred).all() and (pred > 0).all()
    # ranking should beat chance on its own (noiseless) training corpus
    mm = [i for i, s in enumerate(corpus.sites)
          if s.kind == "matmul" and s.m == 32]
    order = np.argsort(pred[mm])
    assert list(order) == list(np.argsort(corpus.y[mm]))

    art = str(tmp_path / "ck")
    save_surrogate(model, art)
    loaded = load_surrogate(art)
    assert loaded.backend == "fix"
    np.testing.assert_allclose(
        loaded.predict_seconds(list(corpus.sites), corpus.tiles), pred)


def test_checkpoint_fingerprint_rejection(tmp_path):
    corpus = build_corpus(_fixture_db(tmp_path / "m.jsonl"), backend="fix")
    model = train_surrogate(corpus, hidden=(16,), ensemble=2, steps=30)
    art = str(tmp_path / "ck")
    save_surrogate(model, art)
    # perturb one stored tensor (keeping the archive well-formed): the
    # recomputed fingerprint must disagree with the manifest — a silently
    # corrupted cost model is worse than none
    npz = tmp_path / "ck" / "state.npz"
    arrays = dict(np.load(str(npz)))
    key = sorted(arrays)[0]
    arrays[key] = arrays[key] + 1.0
    np.savez(str(npz), **arrays)
    with pytest.raises(ArtifactError, match="fingerprint"):
        load_surrogate(art)


def test_train_from_db_cold_returns_none(tmp_path):
    p = str(tmp_path / "cold.jsonl")
    db = MeasureDB(p)
    db.put(make_key(MM.key(), (8, 128, 128), "b"), 1e-3)
    db.close()
    assert train_from_db(p) is None               # < min_pairs
    assert train_from_db(None) is None            # no DB at all
    warm = train_from_db(_fixture_db(tmp_path / "warm.jsonl"),
                         hidden=(16,), ensemble=2, steps=30)
    assert warm is not None and warm.backend == "fix"


# ---------------------------------------------------------------------------
# the payoff: pruned measured grid
# ---------------------------------------------------------------------------

def test_pruned_env_submits_exactly_topk(tmp_path):
    surrogate = train_from_db(_fixture_db(tmp_path / "m.jsonl"),
                              hidden=(16,), ensemble=2, steps=60)
    grid = costmodel_vec.action_tiles_grid(CostModelEnv(CFG).space,
                                           "matmul")
    n_legal = int(np.isfinite(
        costmodel_vec.costs_for_tiles([MM] * len(grid), grid)).sum())
    assert n_legal == 3                   # the fixture grid, sanity

    for topk in (1, 2):
        spy = SpyRunner()
        env = MeasuredEnv(CFG, measure_fn=CachedMeasureFn(spy, db=None),
                          prune_topk=topk, surrogate=surrogate)
        assert env.prune_active
        costs = env.cost_grid([MM])[0]
        # exactly top-k pairs reach the transport (baseline tile is
        # off-grid by construction); the rest are surrogate-priced
        assert spy.pairs == topk
        assert env.pruned_pairs == n_legal - topk
        assert np.isfinite(costs[:n_legal]).all()

    # without a surrogate the same env measures the full legal grid
    spy = SpyRunner()
    env = MeasuredEnv(CFG, measure_fn=CachedMeasureFn(spy, db=None))
    assert not env.prune_active
    env.cost_grid([MM])
    assert spy.pairs == n_legal


def test_pruned_env_baseline_always_measured(tmp_path):
    """Eq. 2 stays measured-vs-measured: the heuristic-baseline tile is
    in every site's allowed set even when the surrogate ranks it last."""
    surrogate = train_from_db(_fixture_db(tmp_path / "m.jsonl"),
                              hidden=(16,), ensemble=2, steps=60)
    spy = SpyRunner()
    env = MeasuredEnv(CFG, measure_fn=CachedMeasureFn(spy, db=None),
                      prune_topk=1, surrogate=surrogate)
    r = env.rewards_batch([ATTN, SCAN], np.array([[0, 0, 0], [0, 0, 0]]))
    assert r.shape == (2,) and np.isfinite(r).all()
    allowed = env._allowed_tiles(ATTN)
    base = tuple(int(x) for x in
                 costmodel_vec.baseline_tiles_batch([ATTN])[0])
    assert base in allowed


def test_pruned_env_rejects_bad_topk():
    with pytest.raises(ValueError, match="prune_topk"):
        MeasuredEnv(CFG, prune_topk=0)
