"""``repro.service`` — the TuningService session API, its protocol
parity with the in-process facade path, and the new facade/serve wiring.

The acceptance seam: tuning through ``TuningService`` +
``WorkerPoolTransport(workers=2)`` must yield a ``TileProgram`` identical
to the in-process ``oracle="measured"`` path, and a second run against
the same ``MeasureDB`` must perform zero re-timings.
"""
import numpy as np
import pytest

from repro.api import (NeuroVectorizer, NeuroVecConfig, Oracle,
                       SessionHandle, TileProgram, TuningService,
                       WorkerPoolTransport)
from repro.models.compute import KernelSite
from repro.service import open_session

from pool_helpers import fake_value

SMALL = NeuroVecConfig(
    bm_choices=(16, 32), bn_choices=(128,), bk_choices=(128,),
    bq_choices=(64,), bkv_choices=(128,), chunk_choices=(32,))

MM = KernelSite(site="s.mm", kind="matmul", m=32, n=128, k=128)
ATTN = KernelSite(site="s.attn", kind="attention", m=64, n=32, k=64,
                  batch=2, causal=True)
SITES = [MM, ATTN]

RUNNER_KW = dict(reps=1, warmup=1, interpret=True, max_dim=64)


def _fake_pool(**kw):
    return WorkerPoolTransport(workers=2,
                               factory="pool_helpers:deterministic", **kw)


# ---------------------------------------------------------------------------
# THE acceptance criterion: pool-service parity with the in-process path
# ---------------------------------------------------------------------------

def test_service_pool_parity_with_inproc_measured(tmp_path):
    """Real runners: the in-process measured facade populates the DB;
    the pool-backed service must reproduce the identical TileProgram
    with ZERO re-timings (and vice versa on a shared DB)."""
    p = str(tmp_path / "m.jsonl")
    with NeuroVectorizer(SMALL, agent="brute", oracle="measured",
                         db_path=p, oracle_kwargs=RUNNER_KW) as nv:
        prog_inproc = nv.fit(SITES).tune_sites(SITES)
        t = nv.oracle.measure_fn.transport
        assert t.stats()["transport_timed_pairs_total"] > 0

    with TuningService(SMALL, transport="pool", workers=2, db_path=p,
                       **RUNNER_KW) as svc:
        session = svc.open_session(agent="brute", oracle="measured")
        prog_pool = session.fit(SITES).tune(SITES)
        st = svc.transport.stats()
    assert prog_pool.tiles == prog_inproc.tiles
    assert st["transport_timed_pairs_total"] == 0 \
        and st["transport_misses_total"] == 0   # zero re-timings
    assert st["transport_hits_total"] > 0


def test_service_pool_parity_cold_fake_runners():
    """Deterministic fake runners: pool service and in-process facade
    agree bit-for-bit even with *separate* cold DBs (values derive from
    the key, so this checks the whole decision path, not the cache)."""
    from repro.measure import InProcessTransport
    from pool_helpers import FakeRunner

    with NeuroVectorizer(SMALL, agent="brute", oracle="measured",
                         transport=InProcessTransport(FakeRunner())) as nv:
        prog_inproc = nv.fit(SITES).tune_sites(SITES)
    with TuningService(SMALL, transport=_fake_pool()) as svc:
        prog_pool = svc.open_session(
            agent="brute", oracle="measured").fit(SITES).tune(SITES)
    assert prog_pool.tiles == prog_inproc.tiles


# ---------------------------------------------------------------------------
# the session API
# ---------------------------------------------------------------------------

def test_tune_async_returns_program_future_and_tracks_stats():
    with TuningService(SMALL, transport=_fake_pool()) as svc:
        s = svc.open_session(agent="brute", oracle="measured")
        assert isinstance(s, SessionHandle)
        assert isinstance(s.oracle, Oracle)
        fut = s.fit(SITES).tune_async(SITES)
        prog = fut.result(timeout=120)
        assert isinstance(prog, TileProgram)
        assert set(prog.tiles) == {x.key() for x in SITES}
        st = s.stats()
        assert st["session_tunes_total"] == 1
        assert st["session_sites_tuned_total"] == 2
        assert st["session_inflight_tunes"] == 0
        assert st["transport"]["transport_timed_pairs_total"] > 0
        assert st["transport"]["transport_inflight_pairs"] == 0
        assert st["session_wall_seconds"] > 0 and st["agent"] == "brute"


def test_sessions_share_one_transport_and_its_cache(tmp_path):
    """Two sessions over one pool: the second session's identical sweep
    is served entirely from the shared transport's DB — its stats window
    shows hits, not timings."""
    with TuningService(SMALL,
                       transport=_fake_pool(
                           db=str(tmp_path / "m.jsonl"))) as svc:
        s1 = svc.open_session(agent="brute", oracle="measured")
        p1 = s1.fit(SITES).tune(SITES)
        s2 = svc.open_session(agent="brute", oracle="measured")
        p2 = s2.fit(SITES).tune(SITES)
        assert p1.tiles == p2.tiles
        st2 = s2.stats()["transport"]            # deltas since s2 opened
        assert st2["transport_timed_pairs_total"] == 0
        assert svc.stats()["service_sessions_total"] == 2
    # MeasuredEnv caches per oracle; session 2 has its own env, so its
    # sweep re-queries the transport and must land on the cache
    assert st2["transport_hits_total"] > 0


def test_session_model_oracle_needs_no_transport_traffic():
    with TuningService(SMALL, transport=_fake_pool()) as svc:
        s = svc.open_session(agent="brute", oracle="model")
        prog = s.fit(SITES).tune(SITES)
        assert len(prog.tiles) == 2
        st = svc.transport.stats()
        assert st["transport_misses_total"] == 0      # untouched
        assert s.stats()["transport"]["transport_timed_pairs_total"] == 0


def test_service_validation_and_lifecycle():
    svc = TuningService(SMALL)                        # default inproc
    with pytest.raises(ValueError, match="unknown oracle"):
        svc.open_session(oracle="wat")
    s = svc.open_session(agent="baseline", oracle="model")
    svc.close()
    svc.close()                                       # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        svc.open_session(agent="baseline")
    with pytest.raises(RuntimeError, match="closed"):
        s.tune(SITES)
    with pytest.raises(TypeError, match="pre-built transport"):
        TuningService(SMALL, transport=_fake_pool(), workers=4)


def test_service_borrows_prebuilt_transport_without_closing_it():
    t = _fake_pool()
    with TuningService(SMALL, transport=t) as svc:
        svc.open_session(agent="baseline", oracle="measured")
    # the service is closed; the borrowed transport must still work
    futs = t.submit([MM], np.array([[16, 128, 128]]))
    t.drain()
    assert futs[0].result() == fake_value(MM.key(), (16, 128, 128))
    t.close()


def test_open_session_convenience_wraps_private_service():
    h = open_session(SMALL, agent="baseline", oracle="model")
    prog = h.fit(SITES).tune(SITES)
    assert len(prog.tiles) == 2
    h.service.close()


# ---------------------------------------------------------------------------
# facade + serve wiring
# ---------------------------------------------------------------------------

def test_facade_transport_args_require_measured_oracle():
    with pytest.raises(ValueError, match="oracle='measured'"):
        NeuroVectorizer(SMALL, transport="pool")
    with pytest.raises(ValueError, match="oracle='measured'"):
        NeuroVectorizer(SMALL, oracle="model", workers=2)


def test_facade_close_is_safe_for_model_oracle():
    nv = NeuroVectorizer(SMALL, agent="baseline")
    nv.close()                                        # no-op, must not raise
    with NeuroVectorizer(SMALL, agent="baseline"):
        pass


def test_serve_rejects_bad_measure_flags():
    from repro.launch import serve

    base = ["--arch", "stablelm_3b", "--autotune", "brute", "--measured"]
    with pytest.raises(SystemExit):
        serve.main(base + ["--measure-reps", "0"])
    with pytest.raises(SystemExit):
        serve.main(base + ["--transport", "pool", "--workers", "0"])
    with pytest.raises(SystemExit):
        serve.main(base + ["--transport", "teleport"])
    # warm-start flags apply to the tuning pipeline, not loaded plans
    with pytest.raises(SystemExit):
        serve.main(["--arch", "stablelm_3b", "--agent-ckpt", "/tmp/x"])
    with pytest.raises(SystemExit):
        serve.main(["--arch", "stablelm_3b", "--tiles", "t.json",
                    "--program-store", "/tmp/x.jsonl"])


def test_serve_measured_tune_on_analytic_fallback_exits_nonzero(monkeypatch):
    """A measured tune whose every timing failed ends on the analytic
    fallback (health 'degraded'): serve must fail, not serve that plan."""
    from repro.launch import serve
    from repro.measure.runner import MeasureRunner

    monkeypatch.setattr(MeasureRunner, "measure_one",
                        lambda self, site, tiles: float("inf"))
    with pytest.raises(SystemExit) as exc:
        serve.main(["--arch", "stablelm_3b", "--batch", "2",
                    "--prompt-len", "8", "--gen", "2", "--autotune", "brute",
                    "--measured"])
    assert "health 'degraded'" in str(exc.value.code)


def test_serve_warns_on_uncovered_sites(capsys):
    from repro.launch import serve

    prog = TileProgram({MM.key(): (16, 128, 128)})
    missing = serve._warn_missing_tiles(prog, SITES)
    assert missing == [ATTN.site]
    err = capsys.readouterr().err
    assert "WARNING" in err and ATTN.site in err and "1/2" in err
    # full coverage: silent
    full = TileProgram({s.key(): (16, 128, 128) for s in SITES})
    assert serve._warn_missing_tiles(full, SITES) == []
    assert capsys.readouterr().err == ""
