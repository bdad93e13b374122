"""Substrate tests: checkpointing, data pipeline, optimizer, fault
tolerance, compression, sharding rules."""
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.distributed import sharding as shd
from repro.distributed.compression import make_compressor
from repro.ft.monitor import (PreemptionHandler, StepMonitor,
                              plan_elastic_mesh)
from repro.models.lm import build_model
from repro.optim import adamw
from repro.train.steps import make_train_state, make_train_step


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def _tiny_state():
    return {"params": {"w": jnp.arange(6.0).reshape(2, 3),
                       "blocks": ({"a": jnp.ones((2, 2))},)},
            "step": jnp.int32(7)}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = _tiny_state()
    mgr.save(state, 10)
    restored, step = mgr.restore(jax.tree.map(jnp.zeros_like, state))
    assert step == 10
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))


def test_checkpoint_resume_latest_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2)
    state = _tiny_state()
    for s in (10, 20, 30):
        mgr.save(state, s)
    assert mgr.complete_steps() == [20, 30]   # GC kept 2
    assert mgr.latest_step() == 30


def test_checkpoint_async_and_partial_ignored(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = _tiny_state()
    mgr.save_async(state, 5)
    mgr.wait()
    # a partial (manifest-less) step dir must not be restorable
    os.makedirs(tmp_path / "step_000000099", exist_ok=True)
    assert mgr.latest_step() == 5


def test_trainer_restart_reproduces_loss(tmp_path):
    """FT end-to-end: train 6 steps; kill; resume from ckpt at 4 and verify
    the loss trajectory matches an uninterrupted run."""
    from repro.launch import train as train_mod
    args = ["--arch", "stablelm_3b", "--steps", "6", "--batch", "4",
            "--seq", "32", "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"]
    losses_full = train_mod.main(args)
    # wipe later checkpoints so the resume starts at step 4
    mgr = CheckpointManager(str(tmp_path))
    for s in mgr.complete_steps():
        if s > 4:
            import shutil
            shutil.rmtree(mgr._step_dir(s))
    losses_resumed = train_mod.main(args)
    np.testing.assert_allclose(losses_resumed, losses_full[4:], rtol=1e-4)


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache sits at the fixed ``<checkout>/.jax_cache``."""
    from pathlib import Path

    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        got = enable_compile_cache()
        if env_dir:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir is None
        else:
            checkout = Path(__file__).resolve().parents[1]
            assert got == str(checkout / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert enable_compile_cache() == got       # fixed, not fresh
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_train_state_is_created_and_restored_sharded(tmp_path):
    """The trainer's state is born with its mesh shardings, and a resumed
    state lands on the same shardings with the saved values."""
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import init_state

    model = build_model(get_config("stablelm_3b").reduced())
    opt = adamw.AdamWConfig()
    mesh = make_local_mesh(1)
    mgr = CheckpointManager(str(tmp_path))
    state, sh, step = init_state(model, opt, mesh, mgr)
    assert step == 0
    assert all(a.sharding == s for a, s in zip(jax.tree.leaves(state),
                                               jax.tree.leaves(sh)))
    assert sh["params"]["embed"].spec == jax.sharding.PartitionSpec(
        "model", "data")
    mgr.save(state, 3)
    restored, sh2, step = init_state(model, opt, mesh, mgr)
    assert step == 3 and sh2 == sh
    for a, b, s in zip(jax.tree.leaves(restored), jax.tree.leaves(state),
                       jax.tree.leaves(sh)):
        assert a.sharding == s
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_determinism_and_restart():
    cfg = get_config("qwen3_8b").reduced()
    shape = ShapeConfig("t", 64, 8, "train")
    p1 = SyntheticPipeline(cfg, shape, DataConfig(seed=3))
    p2 = SyntheticPipeline(cfg, shape, DataConfig(seed=3))
    b1, b2 = p1.batch_at(17), p2.batch_at(17)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = p1.batch_at(18)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_data_host_sharding_differs():
    cfg = get_config("qwen3_8b").reduced()
    shape = ShapeConfig("t", 64, 8, "train")
    a = SyntheticPipeline(cfg, shape, DataConfig(seed=3, host_index=0,
                                                 host_count=2))
    b = SyntheticPipeline(cfg, shape, DataConfig(seed=3, host_index=1,
                                                 host_count=2))
    assert a.local_batch == 4
    assert not np.array_equal(np.asarray(a.batch_at(0)["tokens"]),
                              np.asarray(b.batch_at(0)["tokens"]))


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_reduces_quadratic_loss():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                            weight_decay=0.0)
    params = {"w": jnp.array([3.0, -2.0])}
    state = adamw.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    l0 = float(loss(params))
    for _ in range(50):
        grads = jax.grad(loss)(params)
        params, state, _ = adamw.update(cfg, grads, state, params)
    assert float(loss(params)) < 0.05 * l0


def test_adamw_clips_gradients():
    cfg = adamw.AdamWConfig(clip_norm=1.0)
    params = {"w": jnp.ones((3,))}
    state = adamw.init(params)
    _, _, metrics = adamw.update(cfg, {"w": jnp.full((3,), 100.0)}, state,
                                 params)
    assert float(metrics["grad_norm"]) > 100


def test_lr_schedule_shape():
    cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(adamw.lr_schedule(cfg, jnp.int32(s)))
           for s in (0, 5, 10, 50, 100)]
    assert lrs[0] < lrs[1] < lrs[2]
    assert lrs[2] == pytest.approx(1e-3, rel=1e-5)
    assert lrs[3] > lrs[4]


def test_grad_accum_matches_single_batch():
    cfg = get_config("stablelm_3b").reduced()
    model = build_model(cfg)
    opt_cfg = adamw.AdamWConfig()
    state = make_train_state(model, jax.random.PRNGKey(0), opt_cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                          cfg.vocab_size, jnp.int32),
             "targets": jax.random.randint(jax.random.PRNGKey(2), (4, 16),
                                           0, cfg.vocab_size, jnp.int32)}
    s1 = make_train_step(model, opt_cfg, accum=1)
    s2 = make_train_step(model, opt_cfg, accum=2)
    st1, m1 = jax.jit(s1)(state, batch)
    st2, m2 = jax.jit(s2)(state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    w1 = jax.tree.leaves(st1["params"])[0]
    w2 = jax.tree.leaves(st2["params"])[0]
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=2e-5)


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_step_monitor_flags_straggler():
    mon = StepMonitor(warmup=3, z_thresh=2.0)
    for i in range(10):
        mon.start()
        mon._t0 -= 0.01           # simulate 10ms steps without sleeping
        ev = mon.stop(i)
        assert ev is None
    mon.start()
    mon._t0 -= 1.0                # a 1s step: 100x the mean
    ev = mon.stop(99)
    assert ev is not None and ev["kind"] == "straggler"


def test_preemption_handler():
    h = PreemptionHandler(signals=(signal.SIGUSR1,))
    assert not h.should_stop
    os.kill(os.getpid(), signal.SIGUSR1)
    time.sleep(0.05)
    assert h.should_stop
    h.restore()


def test_elastic_plan():
    p = plan_elastic_mesh(healthy_chips=256, model_parallel=16,
                          global_batch=256)
    assert p.mesh_shape == (16, 16) and p.dropped_chips == 0
    p = plan_elastic_mesh(healthy_chips=250, model_parallel=16,
                          global_batch=256)      # lost 6 chips
    assert p.mesh_shape == (8, 16)               # largest pow2 DP that fits
    assert p.global_batch % p.mesh_shape[0] == 0
    with pytest.raises(AssertionError):
        plan_elastic_mesh(healthy_chips=8, model_parallel=16,
                          global_batch=256)


# ---------------------------------------------------------------------------
# gradient compression (int8 error feedback)
# ---------------------------------------------------------------------------

def test_compression_error_feedback_converges():
    params = {"w": jnp.zeros((32,))}
    comp = make_compressor(params)
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    total_q = jnp.zeros((32,))
    for _ in range(50):
        deq, _ = comp({"w": g_true})
        total_q = total_q + deq["w"]
    # over many steps the quantized stream must integrate to the true sum
    np.testing.assert_allclose(np.asarray(total_q / 50),
                               np.asarray(g_true), atol=1e-2)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_param_specs_cover_all_archs():
    from jax.sharding import PartitionSpec as P
    for arch in ("qwen3_8b", "deepseek_v2_236b", "xlstm_1_3b",
                 "jamba_v0_1_52b", "seamless_m4t_medium"):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = shd.param_specs(shapes)
        flat_shapes = jax.tree.leaves(shapes)
        flat_specs = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_shapes) == len(flat_specs)
        for sh, sp in zip(flat_shapes, flat_specs):
            assert len(sp) <= len(sh.shape), (sh.shape, sp)


@pytest.mark.parametrize("batch,model_par,heads_axis,ctx_axis", [
    (1, 2, "model", "data"),   # batch 1 over 2 data shards: sequence parallel
    (4, 8, None, "model"),     # 4 kv heads over 8 model shards: ctx over model
])
def test_cache_specs_shard_ctx_where_each_cache_keeps_it(
        batch, model_par, heads_axis, ctx_axis):
    # an enc-dec config holds both layouts: the decoder's self-attention
    # cache (L, B, Hkv, hd, ctx) and the cross-attention memory
    # (L, B, Hkv, S, hd)
    from jax.sharding import PartitionSpec as P

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": model_par}

    cfg = get_config("seamless_m4t_medium").reduced()
    ctx = 96
    cache = jax.eval_shape(
        lambda: build_model(cfg).make_cache(batch, ctx, jnp.bfloat16))
    specs = shd.cache_specs(cfg, ShapeConfig("t", ctx, batch, "decode"),
                            FakeMesh(), cache)
    for part, ctx_dim in (("caches", 4), ("mem", 3)):
        for name in ("k", "v"):
            leaf, spec = cache[part][0][name], specs[part][0][name]
            assert leaf.shape[ctx_dim] == ctx
            want = [None] * 5
            want[ctx_dim] = ctx_axis
            want[1] = None if ctx_axis == "data" else ("data",)
            want[2] = heads_axis
            assert spec == P(*want), (part, name, spec)


def test_leading_dense_layers_have_their_own_params_and_cache_specs():
    """DeepSeek-V2's leading dense layer is a stack of its own: its
    parameters and its latent cache get the same rules as the period
    stack's (expert tensors over "model", the latent's rank over "model")."""
    from jax.sharding import PartitionSpec as P

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 2}

    cfg = get_config("deepseek_v2_236b").reduced()
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = shd.param_specs(shapes)
    assert specs["dense_blocks"][0]["mlp"]["wi"] == P(None, "data", "model")
    assert specs["blocks"][0]["mlp"]["ewi"] == P(None, "model", "data", None)
    cache = jax.eval_shape(lambda: model.make_cache(4, 32, jnp.bfloat16))
    assert set(cache) == {"caches", "dense"}
    cspecs = shd.cache_specs(cfg, ShapeConfig("t", 32, 4, "decode"),
                             FakeMesh(), cache)
    for part in ("caches", "dense"):
        assert cspecs[part][0]["c_kv"] == P(None, ("data",), None, "model")


def test_fit_spec_drops_indivisible_axes():
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("model",))

    class FakeMesh:
        shape = {"model": 16, "data": 16}
    spec = shd._fit_spec(P(None, "model"), (4, 85), FakeMesh())
    assert spec == P(None, None)
    spec = shd._fit_spec(P("data", "model"), (32, 512), FakeMesh())
    assert spec == P("data", "model")
