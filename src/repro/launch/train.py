"""Training driver: data pipeline -> pjit train step -> checkpoints, with
fault-tolerance wiring (auto-resume, preemption checkpointing, straggler
monitor).

Runs end-to-end on this CPU container at reduced scale::

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_8b --steps 50 \
      --ckpt-dir /tmp/ckpt

On a TPU host the same driver runs the full config over a
(data, model) mesh of the local chips, e.g. StableLM-3B on four v5e
chips (``chip_smoke.py --four-chips`` drives this)::

  python -m repro.launch.train --arch stablelm_3b --full \
      --model-parallel 2 --steps 5 --batch 8 --seq 512
"""
from __future__ import annotations

import argparse
import functools

import jax

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.distributed import sharding as shd
from repro.ft.monitor import PreemptionHandler, StepMonitor
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.measure.runner import default_interpret
from repro.models.lm import build_model
from repro.optim.adamw import AdamWConfig
from repro.train.steps import make_train_state, make_train_step


def init_state(model, opt_cfg: AdamWConfig, mesh, mgr=None):
    """The train state, sharded over ``mesh`` from the start: restored from
    ``mgr``'s newest checkpoint and placed shard by shard, or created in
    one jitted program with ``out_shardings``.  No device ever holds the
    whole state.  Returns ``(state, shardings, start_step)``."""
    make = functools.partial(make_train_state, model, opt_cfg=opt_cfg)
    key = jax.random.PRNGKey(0)
    abstract = jax.eval_shape(make, key)
    sh = shd.named(mesh, shd.param_specs(abstract, mesh))
    if mgr is not None:
        restored, step = mgr.restore(abstract)
        if step is not None:
            return jax.device_put(restored, sh), sh, step
    return jax.jit(make, out_shardings=sh)(key), sh, 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_8b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--full", action="store_true",
                    help="full-size config (TPU slice), not the smoke config")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--tune", default="",
                    help="TileProgram json from repro.core.vectorizer; "
                         "routes hot ops through tuned Pallas kernels")
    args = ap.parse_args(argv)
    print(f"[train] compile cache: {enable_compile_cache()}")

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    model = build_model(cfg)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(1, args.steps // 10))
    mesh = make_local_mesh(args.model_parallel)

    pipe = SyntheticPipeline(cfg, shape, DataConfig(seed=0))
    step_fn = make_train_step(model, opt_cfg, accum=args.accum)

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    state, state_sh, start_step = init_state(model, opt_cfg, mesh, mgr)
    if start_step:
        print(f"[train] resumed from step {start_step}")
    jitted = jax.jit(step_fn, in_shardings=(state_sh, None),
                     out_shardings=(state_sh, None), donate_argnums=0)

    tune_ctx = None
    if args.tune:
        from repro.core.vectorizer import TileProgram, inject
        prog = TileProgram.load(args.tune)
        tune_ctx = inject(prog, interpret=default_interpret())
        tune_ctx.__enter__()        # active during tracing below
        print(f"[tune] injected {len(prog.tiles)} kernel-site tile choices")

    monitor = StepMonitor()
    preempt = PreemptionHandler()
    losses = []
    with mesh:
        for step in range(start_step, args.steps):
            batch = pipe.batch_at(step)
            monitor.start()
            state, metrics = jitted(state, batch)
            loss = float(metrics["loss"])
            ev = monitor.stop(step)
            losses.append(loss)
            if ev:
                print(f"[ft] straggler flagged: {ev}")
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e}")
            if mgr and ((step + 1) % args.ckpt_every == 0):
                mgr.save_async(state, step + 1)
            if preempt.should_stop:
                print("[ft] preemption signal — checkpointing and exiting")
                if mgr:
                    mgr.save(state, step + 1)
                break
    if mgr:
        mgr.wait()
    print(f"[train] done: first loss {losses[0]:.4f} -> last "
          f"{losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
