"""Per-arch smoke tests (reduced configs) + cache-path consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, supported_shapes
from repro.models.lm import build_model


def _smoke_batch(cfg, B=2, S=16, key=0):
    k = jax.random.PRNGKey(key)
    batch = {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size,
                                          jnp.int32),
             "targets": jax.random.randint(jax.random.fold_in(k, 1), (B, S),
                                           0, cfg.vocab_size, jnp.int32)}
    if cfg.frontend == "vision":
        n = cfg.n_frontend_tokens
        batch["tokens"] = batch["tokens"][:, :S - n]
        batch["targets"] = batch["targets"][:, :S - n]
        batch["frontend_embeds"] = jax.random.normal(
            jax.random.fold_in(k, 2), (B, n, cfg.d_model)) * 0.1
    if cfg.enc_dec:
        batch["src_embeds"] = jax.random.normal(
            jax.random.fold_in(k, 3), (B, S, cfg.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    """One forward/backward on the reduced config: shapes + finite values."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _smoke_batch(cfg)
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(model.train_loss, has_aux=True))(params, batch)
    assert jnp.isfinite(loss), (arch, loss)
    assert 1.0 < float(loss) < 20.0, (arch, loss)
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves), arch
    gnorm = sum(float(jnp.sum(g.astype(jnp.float32) ** 2)) for g in leaves)
    assert gnorm > 0, arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_decode_matches_prefill(arch):
    """Cache-path correctness: prefill(t[:n]) + decode(t[n]) must equal
    prefill(t[:n+1]) logits (MoE routing is dropless, so a token's experts
    do not depend on the batch around it)."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    batch = _smoke_batch(cfg, B, S)
    n_pre = cfg.n_frontend_tokens if cfg.frontend == "vision" else 0
    toks = batch["tokens"]
    S_text = toks.shape[1]
    ctx = n_pre + S_text

    full = dict(batch)
    logits_full, _ = jax.jit(model.prefill)(
        params, full, model.make_cache(B, ctx, jnp.dtype(cfg.dtype)))

    part = dict(batch)
    part["tokens"] = toks[:, :-1]
    logits_part, cache = jax.jit(model.prefill)(
        params, part, model.make_cache(B, ctx, jnp.dtype(cfg.dtype)))
    logits_dec, _ = jax.jit(model.decode_step)(
        params, toks[:, -1:], jnp.int32(ctx - 1), cache)

    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_full),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch,overrides", [
    ("stablelm_3b", dict(head_dim=80)),
    ("chatglm3_6b", dict(n_heads=32, n_kv_heads=2, head_dim=128)),
], ids=["mha_hd80", "gqa16_hd128"])
def test_pallas_decode_matches_xla(arch, overrides):
    """Decode steps through the decode kernel on the stacked cache (Pallas,
    interpret mode) give the plain XLA path's logits and cache, across a
    kernel block boundary (positions 126-128 of a 384-position cache)."""
    from repro.models import compute
    cfg = get_config(arch).reduced(n_layers=2, **overrides)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, P, ctx = 2, 126, 384
    toks = _smoke_batch(cfg, B, P)["tokens"]

    def run(mode):
        with compute.compute_mode(mode, interpret=True):
            # fresh functions: jit's trace cache does not key on the mode
            prefill = jax.jit(lambda *a: model.prefill(*a))
            decode = jax.jit(lambda *a: model.decode_step(*a))
            logits, cache = prefill(params, {"tokens": toks},
                                    model.make_cache(B, ctx, jnp.float32))
            out = []
            for pos in range(P, P + 3):
                tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
                logits, cache = decode(params, tok, jnp.int32(pos), cache)
                out.append(logits)
        return out, cache

    (want, want_cache), (got, got_cache) = run("xla"), run("pallas")
    for w, g in zip(want, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
    for w, g in zip(jax.tree.leaves(want_cache), jax.tree.leaves(got_cache)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


#: primitives that change only an array's shape: a weight seen through one
#: is still the weight
_SHAPE_ONLY = ("reshape", "broadcast_in_dim", "squeeze", "expand_dims")


def _kernel_weights(jaxpr, origin=None, scope=""):
    """(site, source) of the weight operand of every matmul and
    grouped-matmul ``pallas_call`` in ``jaxpr``: ``source`` is the
    primitive that made the operand, looking through calls, scans and
    shape-only ops; ``input`` for an argument of the step, ``scan_xs``
    for a layer the scan sliced as its ``xs``."""
    import re
    origin = dict(origin or {})
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        where = scope + "/" + str(eqn.source_info.name_stack)
        src = [origin.get(v, "input") if hasattr(v, "count") or
               not hasattr(v, "val") else "literal" for v in eqn.invars]
        if name == "pallas_call":
            if eqn.params.get("name") in ("matmul", "grouped_matmul"):
                out.append((re.findall(r"site=([\w.]+)", where)[-1],
                             src[-1]))
        elif name == "scan":
            n_in = eqn.params["num_consts"] + eqn.params["num_carry"]
            inner = eqn.params["jaxpr"].jaxpr
            out += _kernel_weights(inner, dict(zip(
                inner.invars, src[:n_in] + ["scan_xs"] * (len(src) - n_in))),
                where)
        else:
            for p in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                inner = eqn.params.get(p)
                if inner is not None:
                    inner = getattr(inner, "jaxpr", inner)
                    out += _kernel_weights(
                        inner, dict(zip(inner.invars, src)), where)
        for v in eqn.outvars:
            origin[v] = src[0] if name in _SHAPE_ONLY else name
    return out


@pytest.mark.parametrize("arch,overrides", [
    ("stablelm_3b", dict(n_layers=2)),
    ("deepseek_v2_236b", dict(n_layers=3, n_held_experts=2,
                              expert_offset=1)),
], ids=["dense_gated", "mla_moe"])
def test_pallas_kernels_read_stacked_weights_in_place(arch, overrides):
    """In ``pallas`` mode every matmul and grouped-matmul kernel of the
    layer stacks (the leading dense layer's included) gets its weight as
    the stacked parameter itself, never a slice or a pad of it, and the
    step's tally counts each such site ``in_place`` (``lm_head`` reads the
    transposed embedding: ``materialized``); the logits are the ``xla``
    path's."""
    from repro.models import compute
    from repro.train.steps import make_prefill_step, make_serve_step
    cfg = get_config(arch).reduced(**overrides)
    assert cfg.n_layers - cfg.n_dense_layers >= 2
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, P, ctx = 2, 8, 12
    toks = _smoke_batch(cfg, B, P)["tokens"]

    def run(mode):
        # fresh step functions: jit's trace cache does not key on the mode
        prefill, decode = make_prefill_step(model), make_serve_step(model)
        with compute.compute_mode(mode, interpret=True):
            cache = model.make_cache(B, ctx, jnp.float32)
            with compute.weight_reads("prefill") as reads:
                jaxprs = [jax.make_jaxpr(prefill)(
                    params, {"tokens": toks}, cache)]
            logits, cache = jax.jit(prefill)(params, {"tokens": toks}, cache)
            out = [logits]
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            with compute.weight_reads("decode") as dec_reads:
                jaxprs.append(jax.make_jaxpr(decode)(
                    params, tok, jnp.int32(P), cache))
            for pos in range(P, P + 2):
                tok, logits, cache = jax.jit(decode)(params, tok,
                                                     jnp.int32(pos), cache)
                out.append(logits)
        return out, jaxprs, [reads, dec_reads]

    want, _, _ = run("xla")
    got, jaxprs, tallies = run("pallas")
    for w, g in zip(want, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
    for jaxpr, tally in zip(jaxprs, tallies):
        weights = _kernel_weights(jaxpr.jaxpr)
        stacked = {site for site, _ in weights if site != "lm_head"}
        assert len(stacked) >= 7, weights
        assert {s for s, src in weights if src != "input"} == {"lm_head"}
        assert [src for s, src in weights if s == "lm_head"] == ["transpose"]
        how = {(site, h) for (_, site, h), n in tally.items() if n}
        assert how == {(s, "in_place") for s in stacked} | {
            ("lm_head", "materialized")}, sorted(how)


def _dense_moe_ref(cfg, p, x):
    """Every expert on every token, weighted by its gate: the MoE layer
    with nothing dropped, in float32."""
    from repro.models import moe
    xt = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    gate, eidx = moe.route(cfg, jax.nn.softmax(xt @ p["router"], -1))
    y = jnp.zeros_like(xt)
    for j in range(cfg.held_experts):
        e = cfg.expert_offset + j
        h = jax.nn.silu(xt @ p["ewg"][j]) * (xt @ p["ewi"][j])
        y += (h @ p["ewo"][j]) * ((eidx == e) * gate).sum(-1)[:, None]
    if cfg.n_shared_experts:
        hs = jax.nn.silu(xt @ p["shared_wg"]) * (xt @ p["shared_wi"])
        y += hs @ p["shared_wo"]
    return y.reshape(x.shape)


@pytest.mark.parametrize("mode", ["xla", "pallas"])
def test_moe_is_dropless_under_skewed_routing(mode):
    """Every token routed to the same top-2 experts (a router that sees
    only a constant feature): each route is computed, none is dropped, and
    the other experts' groups are empty."""
    from repro.models import compute, moe
    cfg = get_config("jamba_v0_1_52b").reduced(n_experts=4, moe_top_k=2,
                                               moe_d_ff=32)
    p = moe.moe_init(cfg, jax.random.PRNGKey(0), jnp.float32)
    d = cfg.d_model
    p["router"] = jnp.zeros((d, 4)).at[0].set(jnp.array([3., 2., -1., -2.]))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, d))
    x = x.at[..., 0].set(5.0)                   # the router sees only this
    with compute.compute_mode(mode, interpret=True):
        y, aux = jax.jit(lambda p, x: moe.apply_moe(cfg, p, x))(p, x)
    gate, eidx = moe.route(cfg, jax.nn.softmax(
        x.reshape(-1, d) @ p["router"], -1))
    assert set(np.unique(np.asarray(eidx))) == {0, 1}
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        _dense_moe_ref(cfg, p, x)), rtol=1e-4, atol=1e-4)
    assert float(aux["lb_loss"]) > 1.5          # ~1.0 when balanced


def test_moe_grads_match_dense_reference():
    from repro.models import moe
    cfg = get_config("jamba_v0_1_52b").reduced(n_experts=4, moe_top_k=2,
                                               moe_d_ff=32)
    key = jax.random.PRNGKey(0)
    p = moe.moe_init(cfg, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (2, 16, cfg.d_model)) * 0.5

    def loss(p):
        return (moe.apply_moe(cfg, p, x)[0] ** 2).sum()

    def ref_loss(p):
        B, S, d = x.shape
        xt = x.reshape(-1, d)
        logits = xt @ p["router"]
        gate, eidx = jax.lax.top_k(jax.nn.softmax(logits, -1),
                                   cfg.moe_top_k)
        gate = gate / gate.sum(-1, keepdims=True)
        y = jnp.zeros_like(xt, dtype=jnp.float32)
        for e in range(cfg.n_experts):
            h = xt @ p["ewi"][e]
            g = jax.nn.silu(xt @ p["ewg"][e])
            ye = (h * g) @ p["ewo"][e]
            we = ((eidx == e) * gate).sum(-1)
            y += ye.astype(jnp.float32) * we[:, None]
        return (y.astype(x.dtype).reshape(B, S, d) ** 2).sum()

    g1 = jax.grad(loss)(p)
    g2 = jax.grad(ref_loss)(p)
    for k in ("ewi", "ewg", "ewo", "router"):
        scale = float(jnp.max(jnp.abs(g2[k]))) + 1e-9
        err = float(jnp.max(jnp.abs(g1[k] - g2[k]))) / scale
        assert err < 1e-5, (k, err)


def test_ssd_chunk_matches_sequential_decode():
    from repro.models import ssm
    cfg = get_config("jamba_v0_1_52b").reduced()
    key = jax.random.PRNGKey(0)
    p = ssm.ssm_init(cfg, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, cfg.d_model))
    y_chunk, _ = ssm.apply_ssm(cfg, p, x)
    cache = ssm.make_ssm_cache(cfg, 2, jnp.float32)
    ys = []
    for t in range(32):
        yt, cache = ssm.apply_ssm(cfg, p, x[:, t:t + 1], cache=cache,
                                  decode_pos=t)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y_chunk),
                               np.asarray(jnp.concatenate(ys, 1)),
                               rtol=1e-3, atol=1e-4)


def test_mlstm_chunk_matches_sequential_decode():
    from repro.models import xlstm
    cfg = get_config("xlstm_1_3b").reduced()
    key = jax.random.PRNGKey(0)
    p = xlstm.mlstm_init(cfg, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
    cache0 = xlstm.make_mlstm_cache(cfg, 2)
    y_chunk, _ = xlstm.apply_mlstm(cfg, p, x, cache=cache0, chunk=8)
    cache = xlstm.make_mlstm_cache(cfg, 2)
    ys = []
    for t in range(16):
        yt, cache = xlstm.apply_mlstm(cfg, p, x[:, t:t + 1], cache=cache,
                                      decode_pos=t)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y_chunk),
                               np.asarray(jnp.concatenate(ys, 1)),
                               rtol=1e-3, atol=1e-4)


def test_supported_shapes_policy():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        sup = supported_shapes(cfg)
        assert sup["train_4k"] == "run"
        if cfg.family in ("ssm", "hybrid"):
            assert sup["long_500k"] == "run"
        else:
            assert sup["long_500k"].startswith("SKIP")


def test_param_counts_match_published():
    expect = {"starcoder2_7b": 7.4e9, "qwen3_8b": 8.2e9,
              "deepseek_v2_236b": 239e9, "llama4_maverick_400b": 401e9,
              "jamba_v0_1_52b": 51e9}
    for arch, n in expect.items():
        got = get_config(arch).param_count()
        assert abs(got - n) / n < 0.05, (arch, got, n)
    active = {"deepseek_v2_236b": 21.4e9, "llama4_maverick_400b": 17.2e9,
              "jamba_v0_1_52b": 12e9}
    for arch, n in active.items():
        got = get_config(arch).active_param_count()
        assert abs(got - n) / n < 0.05, (arch, got, n)


def _deepseek_small(**kw):
    """DeepSeek-V2's routing and latent attention at a small width: 32
    routed experts in 8 groups, 3 groups a token, top 6, gates times 16."""
    base = dict(n_experts=32, n_group=8, topk_group=3, moe_top_k=6,
                moe_d_ff=32, n_shared_experts=2)
    base.update(kw)
    return get_config("deepseek_v2_236b").reduced(**base)


def test_expert_shares_add_up_to_the_whole_layer():
    """Eight chips each holding 4 of 32 experts: their held-expert outputs,
    plus the shared experts counted once, give the uncut layer; each
    share's experts are the whole layer's experts at its offset."""
    import dataclasses
    from repro.models import moe
    full = _deepseek_small()
    key = jax.random.PRNGKey(7)
    p = moe.moe_init(full, key, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 24, full.d_model))
    want, _ = moe.apply_moe(full, p, x)
    total = 0.0
    for j in range(8):
        cut = dataclasses.replace(full, expert_offset=4 * j,
                                  n_held_experts=4, n_shared_experts=0)
        pj = moe.moe_init(cut, key, jnp.float32)
        for name in ("ewi", "ewg", "ewo"):
            np.testing.assert_array_equal(np.asarray(pj[name]),
                                          np.asarray(p[name][4 * j:4 * j + 4]))
        np.testing.assert_array_equal(np.asarray(pj["router"]),
                                      np.asarray(p["router"]))
        total = total + moe.apply_moe(cut, pj, x)[0]
    shared = (jax.nn.silu(x @ p["shared_wg"]) * (x @ p["shared_wi"])) \
        @ p["shared_wo"]
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_head_shares_add_up_to_the_whole_attention():
    """Eight chips each holding 2 of 16 MLA heads (the latent path whole on
    each): their outputs add up to the whole attention, in prefill and in
    an absorbed decode step through the latent cache."""
    import dataclasses
    from repro.models import mla
    full = _deepseek_small(n_heads=16, n_kv_heads=16)
    key = jax.random.PRNGKey(3)
    p = mla.mla_init(full, key, jnp.float32)
    B, S = 2, 10
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, full.d_model))
    dn, dr, dv = full.qk_nope_dim, full.qk_rope_dim, full.v_head_dim

    def run(cfg, params):
        cache = mla.make_mla_cache(cfg, B, S, jnp.float32)
        y, c = mla.apply_mla(cfg, params, x[:, :-1],
                             positions=jnp.arange(S - 1), causal=True,
                             cache=cache)
        c = {k: jnp.concatenate([v, jnp.zeros_like(v[..., -1:, :]) if
                                 k == "k_rope" else
                                 jnp.zeros_like(v[:, -1:])],
                                axis=2 if k == "k_rope" else 1)
             for k, v in c.items()}
        yd, _ = mla.apply_mla(cfg, params, x[:, -1:],
                              positions=jnp.arange(S - 1, S), causal=True,
                              cache=c, decode_pos=S - 1)
        return y, yd

    want = run(full, p)
    got = [0.0, 0.0]
    for j in range(8):
        hs = slice(2 * j, 2 * j + 2)
        cut = dataclasses.replace(full, n_heads=2, n_kv_heads=2)
        wq_b = p["wq_b"].reshape(-1, 16, dn + dr)[:, hs].reshape(
            -1, 2 * (dn + dr))
        pj = dict(p, wq_b=wq_b, w_uk=p["w_uk"][:, hs], w_uv=p["w_uv"][:, hs],
                  wo=p["wo"].reshape(16, dv, -1)[hs].reshape(2 * dv, -1))
        got = [g + y for g, y in zip(got, run(cut, pj))]
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)


def test_group_limited_routing_hand_worked():
    """8 experts in 4 groups of 2, 2 groups a token, top 3: the groups whose
    best scores are highest (0: 0.30 and 1: 0.20) hold the only candidates,
    so expert 2 (0.05) is chosen over experts 4 (0.18) and 5 (0.17); gates
    are the probabilities times 16, not renormalised."""
    import dataclasses
    from repro.models import moe
    cfg = dataclasses.replace(_deepseek_small(), n_experts=8, n_group=4,
                              topk_group=2, moe_top_k=3)
    probs = jnp.array([[0.30, 0.02, 0.05, 0.20, 0.18, 0.17, 0.04, 0.04]])
    gate, eidx = moe.route(cfg, probs)
    assert eidx.tolist() == [[0, 3, 2]]
    np.testing.assert_allclose(np.asarray(gate), [[4.8, 3.2, 0.8]],
                               rtol=1e-6)
    flat = dataclasses.replace(cfg, n_group=1, topk_group=1,
                               norm_topk_prob=True)
    gate, eidx = moe.route(flat, probs)
    assert eidx.tolist() == [[0, 3, 4]]
    np.testing.assert_allclose(np.asarray(gate), [[0.30 / 0.68, 0.20 / 0.68,
                                                   0.18 / 0.68]], rtol=1e-6)


def test_yarn_frequencies_and_softmax_scale_follow_the_formula():
    """DeepSeek-V2's rope slice (64 dims, base 10000, factor 40 over 4096):
    the ramp spans pairs floor(c(32)) = 10 to ceil(c(1)) = 23, c(r) =
    64 ln(4096 / (2 pi r)) / (2 ln 10000); pairs below keep their
    frequency, pairs above are divided by 40.  The softmax scale is
    192^-0.5 times mscale(40, 0.707)^2."""
    import math
    from repro.models import common, mla
    cfg = get_config("deepseek_v2_236b")
    c = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))
    lo, hi = math.floor(c(32)), math.ceil(c(1))
    assert (lo, hi) == (10, 23)
    f = np.asarray(common.yarn_freqs(64, 1e4, 40.0, 4096, 32.0, 1.0))
    base = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    for i in range(32):
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want = base[i] / 40 * ramp + base[i] * (1 - ramp)
        assert f[i] == pytest.approx(want, rel=1e-6), i
    assert f[10] == pytest.approx(base[10], rel=1e-6)
    assert f[23] == pytest.approx(base[23] / 40, rel=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
