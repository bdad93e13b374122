"""Every kernel site's ops carry its ``site=<label>`` scope in the compiled
step's ``op_name`` metadata, in ``xla`` and ``pallas`` modes, in prefill and
decode: the scope a profile shows, and the one the benchmark reads."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.configs import get_config
from repro.launch import serve
from repro.models.lm import build_model
from repro.train.steps import make_prefill_step, make_serve_step

B, S = 2, 16
OP_NAME = re.compile(r'op_name="([^"]*)"')
SITE = re.compile(r"site=([\w.]+)")


@pytest.fixture(scope="module")
def model_and_sites():
    model = build_model(get_config("stablelm_3b").reduced())
    params = serve.init_params(model, 0)
    cache = jax.eval_shape(lambda: model.make_cache(B, 2 * S, jnp.float32))
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    sites = serve.serving_sites(model, params, batch, cache)
    return model, params, cache, sites


def _compiled(model, params, cache, phase, program):
    """The compiled HLO text of one prefill or decode step; ``program``
    None compiles the plain XLA path, else the Pallas path under it."""
    if phase == "prefill":
        fn = make_prefill_step(model)
        args = (params, {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)},
                cache)
    else:
        fn = make_serve_step(model)
        args = (params, jax.ShapeDtypeStruct((B, 1), jnp.int32),
                jnp.int32(S), cache)
    if program is None:
        return jax.jit(fn).lower(*args).compile().as_text()
    with api.inject(program, interpret=True):
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def hlo(model_and_sites):
    model, params, cache, sites = model_and_sites
    base = api.baseline_program(sites)
    return {(phase, mode): _compiled(model, params, cache, phase,
                                     base if mode == "pallas" else None)
            for phase in ("prefill", "decode") for mode in ("xla", "pallas")}


def test_serving_sites_are_the_nine_of_dense_gated(model_and_sites):
    assert {s.site for s in model_and_sites[3]} == {
        "attn.q", "attn.k", "attn.v", "attn.o", "attn.core", "mlp.gate",
        "mlp.up", "mlp.down", "lm_head"}


@pytest.mark.parametrize("mode", ["xla", "pallas"])
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_every_site_names_its_ops(model_and_sites, hlo, phase, mode):
    labels = {s.site for s in model_and_sites[3]}
    scoped = {m for n in OP_NAME.findall(hlo[phase, mode])
              for m in SITE.findall(n)}
    assert labels <= scoped, sorted(labels - scoped)
    assert scoped <= labels, sorted(scoped - labels)


@pytest.mark.parametrize("mode", ["xla", "pallas"])
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_no_op_is_under_two_sites(hlo, phase, mode):
    names = OP_NAME.findall(hlo[phase, mode])
    assert names
    assert [n for n in names if n.count("site=") > 1] == []


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_matmul_operand_pad_carries_its_site(model_and_sites, hlo, phase):
    # d_model 64 is not a multiple of the kernel's 128-wide K tile.  The
    # kernel takes the whole dimension as its block (and masks its edge
    # blocks where a tile does not divide one), so the Pallas path pads no
    # projection's operand: no pad is left under a matmul site
    model = model_and_sites[0]
    assert model.cfg.d_model % 128
    pads = [OP_NAME.search(line).group(1)
            for line in hlo[phase, "pallas"].splitlines()
            if " pad(" in line and OP_NAME.search(line)]
    for label in ("attn.q", "mlp.gate", "mlp.up", "lm_head"):
        assert not any(f"site={label}/" in n for n in pads), (label, pads)


def test_cached_executable_keeps_its_own_site_scopes(tmp_path, monkeypatch):
    # two programs that differ only in their site scope share a cache key
    # unless the key includes the metadata; enable_compile_cache makes it
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch.compile_cache import enable_compile_cache

    def scoped(label):
        def f(x):
            with jax.named_scope(f"site={label}"):
                return jnp.tanh(x) * 2.0
        return f

    keys = ("jax_compilation_cache_dir",
            "jax_compilation_cache_include_metadata_in_key",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert enable_compile_cache() == str(tmp_path)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        x = jnp.ones((8, 128))
        texts = [jax.jit(scoped(label)).lower(x).compile().as_text()
                 for label in ("mlp.up", "mlp.down")]
        assert any(tmp_path.iterdir())             # the first was cached
        assert "site=mlp.up" in texts[0]
        assert "site=mlp.down" in texts[1] and "site=mlp.up" not in texts[1]
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


@pytest.fixture(scope="module")
def deepseek_hlo():
    """DeepSeek-V2 at a small size: a leading dense layer, then MLA with
    group-routed held experts (2 of 4) and a shared expert."""
    cfg = get_config("deepseek_v2_236b").reduced(n_held_experts=2,
                                                 expert_offset=1)
    model = build_model(cfg)
    params = serve.init_params(model, 0)
    cache = jax.eval_shape(lambda: model.make_cache(B, 2 * S, jnp.float32))
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    sites = serve.serving_sites(model, params, batch, cache)
    base = api.baseline_program(sites)
    hlo = {(phase, mode): _compiled(model, params, cache, phase,
                                    base if mode == "pallas" else None)
           for phase in ("prefill", "decode") for mode in ("xla", "pallas")}
    return sites, hlo


@pytest.mark.parametrize("mode", ["xla", "pallas"])
@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_moe_and_mla_sites_name_their_ops(deepseek_hlo, phase, mode):
    """The held experts' grouped products, the router, the shared experts,
    the MLA projections and the attention core (the absorbed decode core
    included) each name their ops, and the three grouped products are the
    extracted ``grouped_matmul`` sites."""
    sites, hlo = deepseek_hlo
    text = hlo[phase, mode]
    scoped = {m for n in OP_NAME.findall(text) for m in SITE.findall(n)}
    want = {"moe.experts.gate", "moe.experts.up", "moe.experts.down",
            "moe.router", "moe.shared_gate", "moe.shared_up",
            "moe.shared_down", "mla.q_down", "mla.q_up", "mla.kv_down",
            "mla.o", "mla.core", "mlp.gate", "mlp.up", "mlp.down", "lm_head"}
    assert want <= scoped, sorted(want - scoped)
    assert {s.site for s in sites if s.kind == "grouped_matmul"} == {
        "moe.experts.gate", "moe.experts.up", "moe.experts.down"}
    assert [n for n in OP_NAME.findall(text) if n.count("site=") > 1] == []
