"""The latent-attention MoE family (DeepSeek-V2) on the CPU at a small size:
the reference's weights are the program's, prefill then decode through the
cache matches the reference's full forward, the counters and the
grouped-matmul roofline reader read what they should, and a whole run is
correct while the float8 control, in the program's place, fails the cell's
own limit."""
import dataclasses
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import cell as cell_run  # noqa: E402
from harness import readers, spec, trace, work  # noqa: E402

reference = spec.family("reference", "mla_moe")
OPS = spec.family("ops", "mla_moe")
CELL = "deepseek_v2.decode_b128_p512_g1536"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# every mechanism of the cell at a small width: a dense layer, then MoE
# layers holding 4 of 16 routed experts (4 groups, 2 a token, top 3) at an
# offset, 4 heads of MLA with YaRN
TINY = dict(d_model=128, n_layers=3, n_heads=4, n_kv_heads=4, d_ff=256,
            vocab_size=512, kv_lora_rank=64, q_lora_rank=96, qk_nope_dim=32,
            qk_rope_dim=16, v_head_dim=32, n_experts=16, n_held_experts=4,
            expert_offset=4, moe_top_k=3, moe_d_ff=64,
            n_group=4, topk_group=2)


def _run(dtype: str, **over) -> dict:
    cfg = spec.load_json(BENCH / "configs" / "deepseek_v2.json")
    assert cfg["family"] == "mla_moe"
    return dict(cfg["run"], **TINY, dtype=dtype, **over)


def _model(run):
    from repro.configs import get_config
    from repro.models.lm import build_model

    base = get_config(run["arch"])
    names = {f.name for f in dataclasses.fields(base)}
    return build_model(dataclasses.replace(
        base, **{k: v for k, v in run.items() if k in names}))


def test_weights_from_the_seed_match_the_program():
    from repro.launch.serve import init_params

    run = _run("bfloat16")
    seed = 2 ** 31 + 11
    params = init_params(_model(run), seed)
    w = reference.Weights(run, seed)
    np.testing.assert_array_equal(np.asarray(w.embed()),
                                  np.asarray(params["embed"]))
    np.testing.assert_array_equal(np.asarray(w.head()),
                                  np.asarray(params["head"]))
    dense, moe = params["dense_blocks"][0], params["blocks"][0]
    pairs = {"wkv_a": "wkv_a", "w_uk": "w_uk", "w_uv": "w_uv", "wo": "wo",
             "wq_a": "wq_a", "wq_b": "wq_b"}
    for i in range(run["n_layers"]):
        lw = w.layer(i)
        blk, j = (dense, i) if w.dense(i) else (moe, i - 1)
        mine = {r: blk["mixer"][p] for r, p in pairs.items()}
        if w.dense(i):
            mine.update(wi=blk["mlp"]["wi"], wg=blk["mlp"]["wg"],
                        wd=blk["mlp"]["wo"])
        else:
            mine.update({n: blk["mlp"][n] for n in
                         ("router", "ewi", "ewg", "ewo")},
                        swi=blk["mlp"]["shared_wi"],
                        swg=blk["mlp"]["shared_wg"],
                        swo=blk["mlp"]["shared_wo"])
        assert set(mine) == set(lw)
        for name, prog in mine.items():
            np.testing.assert_array_equal(np.asarray(lw[name]),
                                          np.asarray(prog[j]), err_msg=name)


@pytest.mark.parametrize("mode", ["xla", "pallas"])
def test_prefill_and_decode_through_the_cache_match_the_reference(mode):
    """float32 program: prefill logits and every decode step's logits
    through the latent cache agree with one reference forward pass, on
    the XLA path and on the Pallas kernels (interpret mode)."""
    from repro.launch.serve import init_params
    from repro.models import compute
    from repro.train.steps import make_prefill_step, make_serve_step

    run = _run("float32")
    model = _model(run)
    seed, B, P, G = 5, 2, 12, 6
    params = init_params(model, seed)
    prompts = np.random.default_rng(0).integers(0, run["vocab_size"], (B, P),
                                                dtype=np.int32)
    with compute.compute_mode(mode, interpret=True):
        cache = model.make_cache(B, P + G, jnp.float32)
        logits, cache = jax.jit(make_prefill_step(model))(
            params, {"tokens": jnp.asarray(prompts)}, cache)
        prog = [np.asarray(logits)]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        served = [np.asarray(tok)[:, 0]]
        step = jax.jit(make_serve_step(model))
        for i in range(G - 1):
            tok, lg, cache = step(params, tok, jnp.int32(P + i), cache)
            prog.append(np.asarray(lg))
            served.append(np.asarray(tok)[:, 0])
    served = np.stack(served, 1)
    tokens, positions = reference.served_inputs(prompts, served)
    ref = reference.forward_logits(run, reference.Weights(run, seed), tokens,
                                   positions, rows=1)["f32"]
    prog = np.stack(prog, 1)                                   # (B, G, V)
    assert ref.shape == prog.shape
    np.testing.assert_allclose(prog, ref, atol=2e-4 * np.abs(ref).max())
    assert reference.gaps(ref, served).max() < 1e-3


def test_reference_rope_matches_the_yarn_formula():
    from repro.models import common

    run = spec.load_json(BENCH / "configs" / "deepseek_v2.json")["run"]
    np.testing.assert_allclose(
        reference.rope_freqs(run),
        np.asarray(common.yarn_freqs(64, 1e4, 40.0, 4096, 32.0, 1.0)),
        rtol=1e-6)
    assert reference.softmax_scale(run) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)


def test_held_route_margin_by_hand():
    """8 experts in 4 groups of 2, 2 groups a token, top 2, experts 2-3
    (group 1) held here.  Margins in raw score units, over each token's
    score standard deviation."""
    run = dict(n_experts=8, n_group=4, topk_group=2, moe_top_k=2,
               expert_offset=2, n_held_experts=2, norm_topk_prob=True,
               routed_scaling_factor=1.0)
    s = np.array([
        # groups 3.0, 2.5, 2.0, -1: the held group is chosen, 0.5 above
        # the best other; held experts 2.5 (in) and 1.0 (out) lie 1.5
        # from the boundary (2.5 | 1.0): the groups decide, 0.5
        [3.0, 0.0, 2.5, 1.0, 2.0, 0.5, -1.0, -2.0],
        # groups 3.0, 0.5, 2.0, 1.5: the held group is 1.5 below the worst
        # chosen group, so nothing held is in reach although the chosen
        # groups' own margin is 0.5
        [3.0, 0.0, 0.5, 0.4, 2.0, 0.5, 1.5, -2.0],
        # the held group is chosen and its experts 2.5 and 2.4 straddle
        # the top-2 boundary: 0.1
        [3.0, 0.0, 2.5, 2.4, 1.0, 0.5, -1.0, -2.0]])
    raw = np.array([0.5, 1.5, 0.1])
    m = reference.held_route_margin(run, s)
    np.testing.assert_allclose(m, raw / s.std(-1), rtol=1e-12)

    def held(scores):
        _, idx = reference.route(run, jax.nn.softmax(
            jnp.asarray(scores, jnp.float32), -1))
        return [sorted(int(e) for e in r if 2 <= e < 4)
                for r in np.asarray(idx)]

    # any change inside the margin keeps the held routes; one just past it
    # (expert 3 up and expert 2 down by 0.06 each) swaps them
    rng = np.random.default_rng(0)
    for _ in range(50):
        bump = rng.uniform(-0.49, 0.49, s.shape) * raw[:, None]
        assert held(s + bump) == held(s)
    assert held(s[2:] + np.array([0, 0, -0.06, 0.06, 0, 0, 0, 0])) == [[3]]
    # a tied position reads 0 whatever was served there
    logits = np.array([[[0.0, 1.0, 2.0, 3.0]] * 2])
    tied = reference.Logits(logits, np.array([[[reference.TIE / 2],
                                               [reference.TIE * 2]]]))
    assert reference.gaps(tied, np.array([[0, 0]]))[0, 0] == 0.0
    assert reference.gaps(tied, np.array([[0, 0]]))[0, 1] == pytest.approx(
        3.0 / logits[0, 0].std())
    # untied positions read capped at the SHARE quantile of their gaps: one
    # stray position in 40 does not set the reading, two (one in twenty) do
    logits = reference.Logits(np.tile([0.0, 1.0, 2.0, 3.0], (1, 40, 1)),
                              np.full((1, 40, 1), reference.TIE))
    served = np.full((1, 40), 3)
    served[0, 7] = 0
    assert reference.gaps(logits, served).max() == 0.0
    served[0, 30] = 0
    assert reference.gaps(logits, served).max() == pytest.approx(
        3.0 / np.std([0.0, 1.0, 2.0, 3.0]))


def test_counters_by_hand():
    gmm = spec.counter("grouped_matmul")
    # 96 rows over 20 groups of (5120, 1536) weights: each weight once
    fl, by = gmm.count({"rows": 96, "n": 1536, "k": 5120, "groups": 20}, 2)
    assert fl == 2 * 96 * 1536 * 5120
    assert by == 2 * (96 * 5120 + 20 * 5120 * 1536 + 96 * 1536)
    mla = spec.counter("mla_attention")
    dec = {"phase": "decode", "batch": 128, "heads": 16, "q_len": 1,
           "kv_len": 1280, "qk_dim": 192, "v_dim": 128, "latent": 512,
           "rope": 64}
    fl, by = mla.count(dec, 2)
    assert fl == 128 * 16 * 1280 * (2 * 576 + 2 * 512)
    assert by == 2 * (128 * 1280 * 576 + 128 * 16 * 576 + 128 * 16 * 512)
    pre = dict(dec, phase="prefill", batch=1, heads=2, q_len=4, kv_len=4)
    fl, by = mla.count(pre, 2)
    assert fl == 2 * 2 * 10 * (192 + 128)          # 10 causal pairs
    assert by == 2 * 2 * (4 * 192 + 4 * 192 + 4 * 128 + 4 * 128)


def test_cell_step_ops_put_most_bytes_in_the_grouped_products():
    """At the cell's sizes a decode step expects 128 x 6 x 20 / 160 = 96
    rows in the held experts, whose 6.6 GB of weights are about two
    thirds of the step's bytes at the traffic's mean context; the
    latent attention reads 576 elements a cached position a layer."""
    cell = spec.resolve(CELL)
    tr = cell.traffic
    pos = tr["prompt_len"] + tr["gen"] // 2 - 1
    ops = OPS.step_ops(cell.run, "decode", tr["batch"], tr["prompt_len"],
                       pos=pos)
    g = [op for op in ops if op["kind"] == "grouped_matmul"]
    assert {op["rows"] for op in g} == {96} and len(g) == 3
    _, g_bytes = work.totals(g, 2)
    assert 6.5e9 < g_bytes < 6.7e9
    _, all_bytes = work.totals(ops, 2)
    assert 0.6 < g_bytes / all_bytes < 0.7
    core = [op for op in ops if op["kind"] == "mla_attention"][0]
    assert core["kv_len"] == pos + 1 and core["mult"] == 8
    assert 9.5e9 < all_bytes < 10.5e9               # byte floor ~12.2 ms


def _summary(names_seconds, n_steps=2):
    return trace.Summary(n_steps=n_steps, window_ns=40e6, busy_ns=38e6,
                         top_ops=[list(x) for x in names_seconds],
                         idle_gaps=[])


def test_grouped_roofline_reader_on_a_synthetic_trace():
    reader = spec.load_module(
        BENCH / "metrics" / "grouped_matmul_roofline.decode.py", "gmm_r")
    cell = spec.resolve(CELL)
    ops = OPS.step_ops(cell.run, "decode", 128, 512, pos=1279)
    need = work.totals([op for op in ops if op["kind"] == "grouped_matmul"],
                       2)[1] / PEAKS["hbm_bytes_per_s"]
    kernel = trace.short_name(
        "%grouped_matmul.3 = bf16[11264,1536]{1,0} custom-call(bf16[11264,"
        "5120]{1,0} %scatter.2), custom_call_target=\"tpu_custom_call\"")
    calls = [(kernel, need), ("grouped_matmul.4 bf16[8] tpu_custom_call",
                              need / 2),
             ("grouped_matmul.5 bf16[8] tpu_custom_call", need / 2)]
    other = [("matmul.55 bf16[128,1536] tpu_custom_call", 0.5),
             ("fusion.1 bf16[128]", 0.1)]
    ctx = {"phase": "decode", "step_ops": [ops, ops], "itemsize": 2,
           "peaks": PEAKS, "plan": _summary(calls + other)}
    # two steps' need over two steps' kernel time (2 x need)
    assert reader.read(ctx) == pytest.approx(100.0)
    # one of the three sites' calls fell out of the top ops: no reading
    assert reader.read(dict(ctx, plan=_summary(calls[:2] + other))) is None
    ctx["plan"] = _summary([("matmul.55 bf16[128,1536] tpu_custom_call",
                             0.5), ("grouped_matmul_fusion.2 bf16[8]", 1.0)])
    assert reader.read(ctx) is None                 # no kernel: no reading
    assert reader.read(dict(ctx, phase="prefill")) is None
    assert readers.mfu("decode")(dict(ctx, plan=_summary([]))) > 0


def _tiny_cell(dtype="float32") -> spec.Cell:
    cell = spec.resolve(CELL)
    cell.config = dict(cell.config, run=_run(dtype),
                       agent=dict(cell.config["agent"], total_steps=64))
    cell.traffic = dict(cell.traffic, batch=4, prompt_len=8, gen=12,
                        check_requests=4, steps_left_at_open=5)
    return cell


@pytest.mark.parametrize("fault", ["", "token", "state"])
def test_whole_run_is_correct_only_when_the_timed_path_is_sound(fault,
                                                                tmp_path):
    """The whole run at a tiny size, through the serving path with the
    grouped-matmul and flash kernels in interpret mode and the latent
    cache; in float32, so that what is judged is the path and not the
    precision (the control test judges that): sound, it reads correct
    under the cell's own limit; with every served token altered or the
    decode step's cache left unwritten, it does not."""
    cell = _tiny_cell()
    limit = spec.resolve(CELL).limits["gap"]["limit"]
    out = cell_run.execute(cell, 2 ** 31 + 101, 0.5, False,
                           time.perf_counter(), CPU, PEAKS,
                           faults=(fault,) if fault else (), interpret=True,
                           agents=tmp_path)
    assert out["correct"] is (not fault), out["check"]
    assert out["check"]["gap_max"]["limit"] == limit
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}


def test_control_in_the_programs_place_fails_the_cells_limit(tmp_path):
    """``Run.check`` judging the float8 control's first choices against the
    cell's own limit, at the cell's mechanisms and expert counts (160
    routed experts in 8 groups, 3 a token, top 6, 20 held, gates x16) at a
    small width, 4 requests of 384 served tokens in bfloat16: the program
    passes it (0.007 at this seed) and the control does not (0.44)."""
    import control

    cell = spec.resolve(CELL)
    run = dict(cell.run, d_model=768, n_layers=8, n_heads=4, n_kv_heads=4,
               d_ff=1536, vocab_size=4096, kv_lora_rank=64, q_lora_rank=96,
               qk_nope_dim=32, qk_rope_dim=16, v_head_dim=32, moe_d_ff=192)
    cell.config = dict(cell.config, run=run,
                       agent=dict(cell.config["agent"], total_steps=64))
    cell.traffic = dict(cell.traffic, batch=4, prompt_len=64, gen=384,
                        check_requests=4, steps_left_at_open=10)
    limit = cell.limits["gap"]["limit"]
    r = cell_run.Run(cell, 7, 0.0, time.perf_counter(), interpret=True,
                     agents=tmp_path)
    r.setup()
    control.serve_sample(r)
    r.free()
    sound, fp8 = r.check(), r.check("fp8")
    assert sound["gap_max"]["limit"] == fp8["gap_max"]["limit"] == limit
    assert sound["_ok"] is True, sound
    assert fp8["_ok"] is False, fp8
    assert fp8["_n_tokens"] == sound["_n_tokens"] == 4 * 384
