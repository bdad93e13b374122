"""Chip smoke: the system's main path, once, on TPU v5e chips.

Default (one chip).  ``repro.launch.serve`` runs StableLM-3B at its full
published width in bf16 through the normal entry point.  It extracts the
prefill and decode kernel sites, tunes them with the ``brute`` agent,
injects the plan, then serves 4 prompts of 128 tokens for 16 greedy tokens
through the compiled Pallas kernels.  Then, in the same process:

* each extracted site's baseline and planned tile is compiled and timed
  on the chip by ``MeasureRunner``, with no failed pair;
* with the same seeded weights and prompts, the last-position prefill
  logits and the first decode step's logits of the injected model agree
  with the plain XLA path: ``max|d| <= 5e-2 * max|ref|``.

``--four-chips``: only the sharded trainer.  ``repro.launch.train`` runs
StableLM-3B at full width on a ``(data=2, model=2)`` mesh.  A 2-layer cut
takes 3 steps on the mesh and 3 on one device, and the losses agree within
1e-2 relative.  Then the 32-layer model takes 5 steps with finite,
decreasing loss.

Times printed here are smoke timings on the host clock, not metrics.  The
last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed; a failure exits non-zero without it.  Weights and prompts
come from seeds; nothing is written into the tree but the compile cache.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # a four-chip host
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent
ARCH = "stablelm_3b"
BATCH, PROMPT_LEN, GEN = 4, 128, 16
N_SITES = 17                    # 8 prefill + 9 decode kernel sites
PARITY_BOUND = 5e-2             # max|injected - xla| over max|xla|
TRAIN_BATCH, TRAIN_SEQ = 8, 512
TRAIN_PARITY_RTOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(count: int):
    """The device list, or SmokeFailure when JAX sees no TPU (no CPU
    fallback) or fewer than ``count`` chips."""
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX found only {devs[0].platform!r} devices; this smoke "
          f"runs on the chip")
    check(len(devs) >= count, f"need {count} TPU chips, JAX found {len(devs)}")
    log(f"device: {devs[0].device_kind}, count {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# one chip: extract -> tune -> inject -> serve, site timings, logit parity
# ---------------------------------------------------------------------------

def serve_phase(plan_path: str):
    """The serving entry point at full width; returns its tokens."""
    from repro.launch import serve

    argv = ["--arch", ARCH, "--full", "--batch", str(BATCH),
            "--prompt-len", str(PROMPT_LEN), "--gen", str(GEN),
            "--autotune", "brute", "--inject", "--save-tiles", plan_path]
    log(f"serve: python -m repro.launch.serve {' '.join(argv[:-2])}")
    t0 = time.perf_counter()
    seq = serve.main(argv)
    log(f"serve: returned in {time.perf_counter() - t0:.1f}s (smoke timing, "
        f"compiles included)")
    return seq


def check_tokens(seq, vocab: int) -> None:
    import numpy as np

    toks = np.asarray(seq)
    check(toks.shape == (BATCH, GEN),
          f"serve returned tokens of shape {toks.shape}, want "
          f"{(BATCH, GEN)}")
    check(bool(((toks >= 0) & (toks < vocab)).all()),
          f"token ids outside [0, {vocab})")
    log(f"serve: {GEN} tokens for each of {BATCH} rows")


def abstract_sites(model, cfg):
    """The sites serve extracted, recovered from shapes alone."""
    import jax
    import jax.numpy as jnp

    from repro.launch import serve

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = jax.eval_shape(lambda: serve.make_requests(cfg, BATCH,
                                                       PROMPT_LEN))
    cache = jax.eval_shape(lambda: model.make_cache(
        BATCH, PROMPT_LEN + GEN, jnp.dtype(cfg.dtype)))
    return serve.serving_sites(model, params, batch, cache)


def timing_phase(sites, prog) -> None:
    """Compile and time every site at its baseline and its planned tile."""
    import jax

    from repro.core.costmodel import baseline_tiles
    from repro.measure.runner import MeasureRunner

    runner = MeasureRunner()
    check(runner.interpret is False,
          "MeasureRunner would interpret the kernels, not compile them")
    check(runner.backend_key.startswith("tpu:"),
          f"timing backend is {runner.backend_key!r}, not the TPU")
    log(f"timing backend: {runner.backend_key}")
    for s in sites:
        shape = f"m{s.m} n{s.n} k{s.k} b{s.batch}"
        row = []
        for name, tiles in (("baseline", baseline_tiles(s)),
                            ("planned", prog.tiles[s.key()])):
            sec = runner.measure_one(s, tiles)
            if not math.isfinite(sec):
                # surface the compiler's or runtime's own error
                jax.block_until_ready(runner._build(s, tiles)())
                raise SmokeFailure(f"{s.site} {name} tile {tiles} failed "
                                   f"to time")
            row.append(f"{name} {tuple(tiles)} {sec * 1e6:.1f} us")
        log(f"smoke timing (not a metric): {s.kind} {s.site} {shape}: "
            + ", ".join(row))
    check(runner.failed_pairs == 0, f"{runner.failed_pairs} pairs failed")
    log(f"timed {runner.timed_pairs} (site, tile) pairs compiled, "
        f"failed_pairs {runner.failed_pairs}")


def parity_phase(model, cfg, prog) -> None:
    """Injected vs plain XLA on the same seeded weights and prompts."""
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.launch import serve
    from repro.measure.runner import default_interpret
    from repro.train.steps import make_prefill_step, make_serve_step

    params = serve.init_params(model)
    batch = serve.make_requests(cfg, BATCH, PROMPT_LEN)

    def run(tok=None):
        # fresh jits: the compute mode is read while tracing
        prefill = jax.jit(make_prefill_step(model))
        decode = jax.jit(make_serve_step(model))
        cache = model.make_cache(BATCH, PROMPT_LEN + GEN,
                                 jnp.dtype(cfg.dtype))
        logits, cache = prefill(params, batch, cache)
        if tok is None:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        _, logits1, _ = decode(params, tok, jnp.int32(PROMPT_LEN), cache)
        return logits, logits1, tok

    ref_pre, ref_dec, tok = run()
    with api.inject(prog, interpret=default_interpret()):
        got_pre, got_dec, _ = run(tok)
    for name, ref, got in (("prefill", ref_pre, got_pre),
                           ("decode", ref_dec, got_dec)):
        check(bool(jnp.isfinite(ref).all() & jnp.isfinite(got).all()),
              f"{name} logits are not finite")
        scale = float(jnp.max(jnp.abs(ref)))
        ratio = float(jnp.max(jnp.abs(got - ref))) / scale
        log(f"parity {name}: max|injected - xla| / max|xla| = {ratio:.6g} "
            f"(bound {PARITY_BOUND}, max|xla| {scale:.4g})")
        check(ratio <= PARITY_BOUND,
              f"{name} logits of the injected path differ from XLA by "
              f"{ratio:.4g} of max|ref| > {PARITY_BOUND}")


def one_chip() -> None:
    from repro.configs import get_config
    from repro.core.vectorizer import TileProgram
    from repro.models.lm import build_model

    cfg = get_config(ARCH)
    check(cfg.dtype == "bfloat16", f"{ARCH} dtype is {cfg.dtype}")
    model = build_model(cfg)
    with tempfile.TemporaryDirectory() as tmp:
        plan = str(Path(tmp) / "plan.json")
        seq = serve_phase(plan)
        prog = TileProgram.load(plan)
    check_tokens(seq, cfg.vocab_size)

    sites = abstract_sites(model, cfg)
    check(len(sites) == N_SITES,
          f"extracted {len(sites)} sites at full width, want {N_SITES}")
    missing = [s.site for s in sites if s.key() not in prog.tiles]
    check(not missing, f"plan does not cover {missing}")
    log(f"extracted {len(sites)} sites at full width, all planned")
    timing_phase(sites, prog)
    parity_phase(model, cfg, prog)


# ---------------------------------------------------------------------------
# four chips: the sharded trainer at full width
# ---------------------------------------------------------------------------

def _train(cfg, steps: int, mesh=None) -> list:
    """``repro.launch.train`` on ``cfg`` over a (data=2, model=2) mesh, or
    on ``mesh`` when given."""
    from repro.launch import train

    argv = ["--arch", ARCH, "--full", "--model-parallel", "2",
            "--steps", str(steps), "--batch", str(TRAIN_BATCH),
            "--seq", str(TRAIN_SEQ)]
    with mock.patch.object(train, "get_config", lambda arch: cfg):
        if mesh is None:
            return train.main(argv)
        with mock.patch.object(train, "make_local_mesh", lambda mp: mesh):
            return train.main(argv)


def four_chips() -> None:
    import jax
    import numpy as np
    from jax.sharding import AxisType

    from repro.configs import get_config

    full = get_config(ARCH)
    cut = dataclasses.replace(full, n_layers=2)
    one_dev = jax.make_mesh((1, 1), ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2,
                            devices=jax.devices()[:1])
    log(f"train parity: 2-layer full-width cut, 3 steps, batch "
        f"{TRAIN_BATCH} x seq {TRAIN_SEQ}")
    meshed = _train(cut, 3)
    single = _train(cut, 3, mesh=one_dev)
    rel = np.abs(np.array(meshed) - np.array(single)) / np.abs(single)
    log(f"train parity losses: mesh {meshed}, one device {single}, max "
        f"rel diff {rel.max():.3g} (bound {TRAIN_PARITY_RTOL})")
    check(bool(np.isfinite(meshed).all() and np.isfinite(single).all()),
          "non-finite loss")
    check(bool(rel.max() <= TRAIN_PARITY_RTOL),
          "mesh and one-device losses disagree")

    log("train full depth: 32 layers, 5 steps on the (2, 2) mesh")
    losses = _train(full, 5)
    log(f"train full-depth losses: {losses}")
    check(bool(np.isfinite(losses).all()), "non-finite loss")
    check(losses[-1] < losses[0], "loss did not decrease")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded trainer on four chips")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    try:
        devs = require_tpu(n_chips)
        if not (ROOT / "src" / "repro").is_dir():
            raise SmokeFailure(f"no repro package under {ROOT / 'src'}: run "
                               f"from a checkout of the repository")
        sys.path.insert(0, str(ROOT / "src"))
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        t0 = time.perf_counter()
        (four_chips if args.four_chips else one_chip)()
        log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
