"""The benchmark's float32 reference agrees with the program's XLA path at a
tiny size, for both configurations' mechanisms: LayerNorm with full-head
rotary over MHA, and RMSNorm with half-head rotary over grouped K/V."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import spec  # noqa: E402

reference = spec.family("reference", "dense_gated")

TINY = dict(d_model=64, n_layers=2, n_heads=4, head_dim=16, d_ff=128,
            vocab_size=256)


def _run(config: str, dtype: str) -> dict:
    cfg = spec.load_json(BENCH / "configs" / f"{config}.json")
    assert cfg["family"] == "dense_gated"
    run = dict(cfg["run"])
    run.update(TINY, n_kv_heads=min(run["n_kv_heads"], 4) if
               run["n_kv_heads"] > 2 else 2, dtype=dtype)
    return run


def _model(run):
    from repro.configs import get_config
    from repro.models.lm import build_model

    base = get_config(run["arch"])
    names = {f.name for f in dataclasses.fields(base)}
    return build_model(dataclasses.replace(
        base, **{k: v for k, v in run.items() if k in names}))


CONFIGS = ["stablelm_3b", "chatglm3_6b"]


@pytest.mark.parametrize("config", CONFIGS)
def test_weights_from_the_seed_match_the_program(config):
    from repro.launch.serve import init_params

    run = _run(config, "bfloat16")
    seed = 2 ** 31 + 11
    params = init_params(_model(run), seed)
    w = reference.Weights(run, seed)
    np.testing.assert_array_equal(np.asarray(w.embed()),
                                  np.asarray(params["embed"]))
    np.testing.assert_array_equal(np.asarray(w.head()),
                                  np.asarray(params["head"]))
    blocks = params["blocks"][0]
    for i in range(run["n_layers"]):
        lw = w.layer(i)
        for ref_name, prog in (("wq", blocks["mixer"]["wq"]),
                               ("wk", blocks["mixer"]["wk"]),
                               ("wo", blocks["mixer"]["wo"]),
                               ("wg", blocks["mlp"]["wg"]),
                               ("wd", blocks["mlp"]["wo"])):
            np.testing.assert_array_equal(np.asarray(lw[ref_name]),
                                          np.asarray(prog[i]))


@pytest.mark.parametrize("config", CONFIGS)
def test_prefill_and_decode_through_the_cache_match_the_reference(config):
    """float32 program (XLA path): prefill logits and every decode step's
    logits through the cache agree with one reference forward pass."""
    from repro.launch.serve import init_params
    from repro.train.steps import make_prefill_step, make_serve_step

    run = _run(config, "float32")
    model = _model(run)
    seed, B, P, G = 5, 2, 12, 6
    params = init_params(model, seed)
    prompts = np.random.default_rng(0).integers(0, run["vocab_size"], (B, P),
                                                dtype=np.int32)
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


def test_gaps_in_units_of_the_logit_spread():
    ref = np.array([[[0.0, 1.0, 2.0, 3.0]]])
    std = ref.std()
    g = reference.gaps(ref, np.array([[1]]))
    assert g[0, 0] == pytest.approx(2.0 / std)
    assert reference.gaps(ref, np.array([[3]]))[0, 0] == 0.0


def test_served_inputs_score_the_positions_that_predict_each_token():
    prompts = np.arange(8).reshape(2, 4)
    served = np.array([[10, 11, 12], [20, 21, 22]])
    tokens, pos = reference.served_inputs(prompts, served)
    assert tokens.tolist() == [[0, 1, 2, 3, 10, 11], [4, 5, 6, 7, 20, 21]]
    assert pos.tolist() == [3, 4, 5]
