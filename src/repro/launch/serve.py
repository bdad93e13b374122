"""Serving driver: batched prefill + greedy decode with a KV/state cache,
optionally running under a NeuroVectorizer tile plan (``repro.api``).

Smoke scale on CPU::

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm_1_3b \
      --batch 4 --prompt-len 32 --gen 16

Tile tuning: ``--autotune brute`` plans tiles for the serving kernels with
any registered agent (modelled speedup is printed); ``--tiles f.json``
loads a saved :class:`~repro.api.TileProgram` instead; ``--inject`` routes
prefill and decode through the tuned Pallas kernels (compiled on TPU,
interpret mode elsewhere).  ``--measured`` swaps the analytic reward
oracle for compile-and-time measurement of the kernels themselves
(``repro.measure``; compiled on TPU, interpret-mode with capped shapes on
CPU) and ``--measure-db PATH`` persists the timings so repeat invocations
re-time nothing; a measured tune that ends on the analytic fallback
(``health() != "ok"``) exits non-zero.  ``--transport pool --workers N``
fans the measurements out to N subprocess workers (the
``WorkerPoolTransport``, CPU only: a TPU belongs to one process) instead
of timing in this process; ``--transport socket --hosts a:7761,b:7761``
ships them to remote ``python -m repro.fleet serve-worker`` daemons instead
(``repro.fleet``; a ``fleet://host:port`` ``--measure-db`` attaches the
shared artifact service).

Full width on one TPU v5e chip (``chip_smoke.py`` drives this)::

  python -m repro.launch.serve --arch stablelm_3b --full --batch 4 \
      --prompt-len 128 --gen 16 --autotune brute --inject

Warm starts (``repro.artifacts``): ``--agent-ckpt DIR`` restores a
fitted agent saved by ``nv.save()``/``save_agent`` and skips the fit
entirely (tune-only serving — the paper's train-once deployment);
``--program-store PATH`` memoizes finished tile programs, so a serving
process that has seen this site set before performs zero agent
inferences.
"""
from __future__ import annotations

import argparse
import contextlib
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.measure.runner import default_interpret
from repro.models.lm import build_model
from repro.train.steps import make_prefill_step, make_serve_step


def init_params(model, seed: int = 0):
    """Seeded parameters built in one device program: an eager init keeps
    every per-layer tree alive beside the stacked copy, about twice the
    block weights at once."""
    return jax.jit(model.init)(jax.random.PRNGKey(seed))


def make_requests(cfg, batch: int, prompt_len: int):
    """The seeded prompt batch (plus stub frontend inputs) ``main`` serves."""
    prompts = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                 0, cfg.vocab_size, jnp.int32)
    out = {"tokens": prompts}
    if cfg.frontend == "vision":
        out["frontend_embeds"] = jnp.zeros(
            (batch, cfg.n_frontend_tokens, cfg.d_model))
    if cfg.enc_dec:
        out["src_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (batch, prompt_len, cfg.d_model)) * 0.02
    return out


def serving_sites(model, params, batch, cache) -> list:
    """The kernel sites of one prefill and one decode step, deduplicated
    (prefill and decode share the weight-shaped sites' names)."""
    from repro import api

    B = batch["tokens"].shape[0]
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    sites = {s.key(): s for s in api.extract_sites(
        make_prefill_step(model), params, batch, cache)}
    sites.update((s.key(), s) for s in api.extract_sites(
        make_serve_step(model), params, tok, jnp.int32(0), cache))
    return list(sites.values())


def _warn_missing_tiles(prog, sites) -> list:
    """Sites a loaded ``TileProgram`` does not cover run at baseline
    tiles; say so on stderr (with the site names) instead of silently
    degrading.  Returns the missing site names."""
    missing = [s for s in sites if s.key() not in prog.tiles]
    # names dedup'd for readability (prefill/decode share site names)
    names = sorted({s.site for s in missing})
    if missing:
        print(f"[serve] WARNING: tile plan covers {len(sites) - len(missing)}"
              f"/{len(sites)} extracted sites; these run at baseline "
              f"tiles: {', '.join(names)}", file=sys.stderr)
    return names


def _serving_plan(args, sites):
    """Tune through ``TuningService(serving=...)``: the request is
    admitted to the deadline-aware batch server and (model/surrogate
    oracles + brute search) executes as one fused device dispatch."""
    from repro.configs.neurovec import DEFAULT
    from repro.service import TuningService

    svc_kw = {}
    if args.program_store:
        svc_kw["program_store"] = args.program_store
    oracle = "model"
    if args.measured:
        oracle = "measured"
        svc_kw.update(
            db_path=args.measure_db, transport=args.transport,
            workers=(args.workers if args.transport == "pool" else None))
        if args.transport == "socket":
            svc_kw["hosts"] = args.hosts.split(",")
        else:
            svc_kw["reps"] = args.measure_reps
    with TuningService(DEFAULT, serving={"slo_ms": args.slo_ms},
                       **svc_kw) as svc:
        sess = svc.open_session(agent=args.autotune, oracle=oracle,
                                agent_ckpt=args.agent_ckpt or None)
        if not args.agent_ckpt:
            fit_kw = ({"total_steps": args.autotune_steps}
                      if args.autotune == "ppo" else {})
            sess.fit(sites, **fit_kw)
        prog = sess.tune(sites)            # admitted under the SLO budget
        st = svc.server.stats()
        print(f"[serve] serving: p50 {st['serving_tune_p50_ms']:.2f} ms, "
              f"p99 {st['serving_tune_p99_ms']:.2f} ms "
              f"(slo {args.slo_ms:.0f} ms), shed: "
              f"{st['serving_shed_total']}, fused dispatches: "
              f"{st['serving_fused_dispatches_total']}, "
              f"health: {svc.server.health()}")
        if args.measured:
            _require_measured(sess.health())
    return prog


def _require_measured(health: str) -> None:
    """A measured tune that fell back to the analytic model did not
    measure: fail the run instead of serving a plan priced by the model."""
    if health != "ok":
        raise SystemExit(f"[serve] measured tuning ended with health "
                         f"{health!r}: the plan was priced by the analytic "
                         f"fallback, not by measurement")


def _tile_plan(args, model, params, batch, cache):
    """Extract the serving-step kernel sites and produce a TileProgram
    through the ``repro.api`` facade (or load one from disk)."""
    from repro import api

    sites = serving_sites(model, params, batch, cache)
    print(f"[serve] extracted {len(sites)} kernel sites "
          f"(prefill + decode)")
    if args.tiles:
        prog = api.TileProgram.load(args.tiles)
        _warn_missing_tiles(prog, sites)
        nv = None
    elif args.serving:
        prog = _serving_plan(args, sites)
        if args.save_tiles:
            prog.save(args.save_tiles)
        nv = None
    else:
        oracle_kw = {}
        if args.measured:
            oracle_kw = dict(oracle="measured", db_path=args.measure_db,
                             transport=args.transport,
                             workers=(args.workers
                                      if args.transport == "pool" else None),
                             hosts=(args.hosts.split(",")
                                    if args.transport == "socket" else None),
                             prune_topk=args.prune_topk,
                             surrogate=args.surrogate)
            if args.transport != "socket":
                # serve-worker hosts own their runner config; reps= on the
                # client would be rejected by make_transport
                oracle_kw["oracle_kwargs"] = dict(reps=args.measure_reps)
        nv = api.NeuroVectorizer(agent=args.autotune,
                                 program_store=args.program_store,
                                 trace=args.trace_out,
                                 **oracle_kw)
        if args.agent_ckpt:
            # warm start: the checkpointed policy replaces the fit
            api.load_agent(args.agent_ckpt, agent=nv.agent)
            if isinstance(nv.agent, api.BruteForceAgent):
                nv.agent.oracle = nv.oracle
            print(f"[serve] agent warm-start: {args.agent_ckpt} "
                  f"(fit skipped)")
        else:
            fit_kw = ({"total_steps": args.autotune_steps}
                      if args.autotune == "ppo" else {})
            nv.fit(sites, **fit_kw)
        prog = nv.tune_sites(sites)
        if args.save_tiles:
            prog.save(args.save_tiles)
    env = nv.oracle if nv is not None else None
    sp = api.program_speedup(prog, sites, env)
    how = "measured" if args.measured and nv is not None else "modelled"
    print(f"[serve] tile plan: {len(prog.tiles)} tiles over {len(sites)} "
          f"sites, {how} speedup {sp:.2f}x")
    if nv is not None and args.program_store:
        st = nv.program_store.stats()
        print(f"[serve] program store: {st['hits']} hits, "
              f"{st['misses']} misses, {nv.agent_inferences} agent "
              f"inferences ({st['entries']} stored programs)")
    if args.measured and nv is not None:
        t = env.measure_fn.transport
        st = t.stats()
        print(f"[serve] measurements: {st['transport_timed_pairs_total']} "
              f"timed, {st['transport_hits_total']} DB hits, "
              f"{st['transport_coalesced_total']} coalesced "
              f"({t.backend_key})")
        if args.prune_topk is not None:
            state = "active" if env.prune_active else \
                "inactive (DB too cold to train the surrogate)"
            print(f"[serve] pruning top-{args.prune_topk}: {state}, "
                  f"{env.pruned_pairs} pairs surrogate-priced")
        health = nv.health()
        print(f"[serve] health: {health}")
    if nv is not None:
        nv.close()                      # release pool workers / DB handles
        if args.trace_out:
            print(f"[serve] trace: {nv.tracer.n_spans} spans + "
                  f"{nv.tracer.n_events} events -> {args.trace_out} "
                  f"(chrome://tracing via repro.obs.to_chrome_trace)")
        if args.measured:
            _require_measured(health)
    return prog


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--autotune", default=None,
                    help="tune serving kernels with this repro.api agent "
                         "(ppo, dtree, nns, brute, random, polly, baseline)")
    ap.add_argument("--autotune-steps", type=int, default=2000,
                    help="RL budget when --autotune ppo")
    ap.add_argument("--serving", action="store_true",
                    help="tune through the latency-SLO serving path "
                         "(repro.serving): requests are admitted to a "
                         "deadline-aware batch server and executed as "
                         "fused device dispatches")
    ap.add_argument("--slo-ms", type=float, default=100.0,
                    help="per-request tune SLO budget for --serving")
    ap.add_argument("--tiles", default=None,
                    help="load a saved TileProgram instead of tuning")
    ap.add_argument("--save-tiles", default=None)
    ap.add_argument("--measured", action="store_true",
                    help="tune against wall-clock kernel timings "
                         "(repro.measure) instead of the analytic model")
    ap.add_argument("--measure-db", default=None,
                    help="persistent measurement-DB path (repeat runs "
                         "against the same path re-time nothing)")
    ap.add_argument("--measure-reps", type=int, default=3,
                    help="timing repetitions per (site, tile) pair")
    ap.add_argument("--prune-topk", type=int, default=None,
                    help="with --measured: only each site's top-K "
                         "surrogate-ranked tile candidates are timed; the "
                         "rest are priced by the learned cost model "
                         "(repro.surrogate, trained from --measure-db)")
    ap.add_argument("--surrogate", default=None,
                    help="surrogate checkpoint directory for --prune-topk "
                         "(default: train from the measurement DB)")
    ap.add_argument("--transport", choices=("inproc", "pool", "socket"),
                    default="inproc",
                    help="how measurements execute: this process, a "
                         "subprocess worker pool (repro.measure), or a "
                         "remote serve-worker fleet (repro.fleet)")
    ap.add_argument("--workers", type=int, default=2,
                    help="pool size for --transport pool")
    ap.add_argument("--hosts", default=None,
                    help="comma-separated serve-worker host:port list for "
                         "--transport socket (start them with "
                         "`python -m repro.fleet serve-worker`)")
    ap.add_argument("--agent-ckpt", default=None,
                    help="warm-start --autotune from a saved agent "
                         "artifact directory (repro.artifacts; skips fit)")
    ap.add_argument("--program-store", default=None,
                    help="persistent ProgramStore path: previously-tuned "
                         "site sets are answered by lookup (zero agent "
                         "inferences)")
    ap.add_argument("--inject", action="store_true",
                    help="run prefill and decode through the tuned Pallas "
                         "kernels")
    ap.add_argument("--trace-out", default=None,
                    help="append the tuning span tree (session -> fit -> "
                         "tune -> submit/drain) to this JSONL trace file "
                         "(repro.obs; convert with to_chrome_trace)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the final repro.obs metrics snapshot to "
                         "this JSON file")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the live metrics registry in Prometheus "
                         "text format on this HTTP port (0 = ephemeral)")
    args = ap.parse_args(argv)
    if args.inject and not (args.autotune or args.tiles):
        ap.error("--inject requires a tile plan: pass --autotune or --tiles")
    if args.serving and (args.tiles or not args.autotune):
        ap.error("--serving tunes through the batch server: pass "
                 "--autotune and no --tiles (which loads a finished plan)")
    if args.serving and args.prune_topk is not None:
        ap.error("--prune-topk is not supported on the --serving path")
    if args.serving and args.trace_out:
        ap.error("--trace-out records the facade span tree; it does not "
                 "apply to --serving (use --metrics-out for serving_* "
                 "series)")
    if args.measured and (args.tiles or not args.autotune):
        ap.error("--measured requires --autotune and no --tiles (it "
                 "changes the tuning oracle; --tiles loads a finished "
                 "plan)")
    if (args.agent_ckpt or args.program_store) and not args.autotune:
        ap.error("--agent-ckpt/--program-store warm-start the tuning "
                 "pipeline: pass --autotune (they do not apply to --tiles, "
                 "which loads a finished plan)")
    if args.measure_reps < 1:
        ap.error(f"--measure-reps must be >= 1, got {args.measure_reps}")
    if args.prune_topk is not None and not args.measured:
        ap.error("--prune-topk applies only to --measured tuning")
    if args.prune_topk is not None and args.prune_topk < 1:
        ap.error(f"--prune-topk must be >= 1, got {args.prune_topk}")
    if args.surrogate and args.prune_topk is None:
        ap.error("--surrogate applies only with --prune-topk")
    if args.workers < 1:
        ap.error(f"--workers must be >= 1, got {args.workers}")
    if args.transport == "socket" and not args.hosts:
        ap.error("--transport socket needs --hosts host:port[,host:port...] "
                 "naming the serve-worker daemons")
    if args.hosts and args.transport != "socket":
        ap.error("--hosts applies only to --transport socket")
    if args.trace_out and not args.autotune:
        ap.error("--trace-out records the tuning span tree: pass "
                 "--autotune (loading --tiles produces no spans)")
    if args.metrics_port is not None and not 0 <= args.metrics_port < 65536:
        ap.error(f"--metrics-port must be in [0, 65536), got "
                 f"{args.metrics_port}")
    if args.measured:
        workers = args.workers if args.transport == "pool" else "-"
        reps = args.measure_reps if args.transport != "socket" else "-"
        where = (f"hosts={args.hosts}" if args.transport == "socket"
                 else f"workers={workers}")
        print(f"[serve] measured oracle: transport={args.transport} "
              f"{where} reps={reps} "
              f"db={args.measure_db or '-'}")

    metrics_srv = None
    if args.metrics_port is not None:
        from repro.obs import MetricsServer
        metrics_srv = MetricsServer(port=args.metrics_port).start()
        print(f"[serve] metrics: http://127.0.0.1:{metrics_srv.port}"
              f"/metrics (Prometheus text format)")

    print(f"[serve] compile cache: {enable_compile_cache()}")
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = init_params(model)

    B = args.batch
    ctx = args.prompt_len + args.gen
    batch = make_requests(cfg, B, args.prompt_len)

    cache = model.make_cache(B, ctx, jnp.dtype(cfg.dtype))
    prefill = jax.jit(make_prefill_step(model))
    serve = jax.jit(make_serve_step(model), donate_argnums=(3,))

    prog = None
    if args.autotune or args.tiles:
        prog = _tile_plan(args, model, params, batch, cache)

    run_ctx = contextlib.nullcontext()
    if prog is not None and args.inject:
        from repro import api
        interpret = default_interpret()
        run_ctx = api.inject(prog, interpret=interpret)
        print(f"[serve] injected {len(prog.tiles)} tiles "
              f"({'interpret' if interpret else 'compiled'} Pallas)")

    # host-clock spans, each closed by block_until_ready; the first call
    # of each step includes its compilation.  Not device metrics.
    n_pre = cfg.n_frontend_tokens if cfg.frontend == "vision" else 0
    with run_ctx:
        t0 = time.perf_counter()
        logits, cache = prefill(params, batch, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        tok.block_until_ready()
        t_prefill = time.perf_counter() - t0
        out = [tok]
        t_first = t_steady = 0.0
        for i in range(args.gen - 1):
            t0 = time.perf_counter()
            pos = jnp.int32(n_pre + args.prompt_len + i)
            tok, logits, cache = serve(params, tok, pos, cache)
            out.append(tok)
            if i == 0:
                tok.block_until_ready()
                t_first = time.perf_counter() - t0
                t_steady0 = time.perf_counter()
        seq = jnp.concatenate(out, axis=1).block_until_ready()
        if args.gen > 2:
            t_steady = time.perf_counter() - t_steady0
    print(f"[serve] first calls (compile + run, host clock): prefill "
          f"{t_prefill:.3f}s, first decode step {t_first:.3f}s")
    n_steady = max(args.gen - 2, 0)
    if n_steady:
        print(f"[serve] steady decode (host clock, not a device metric): "
              f"{n_steady} steps x {B} requests in {t_steady:.3f}s "
              f"({B * n_steady / t_steady:.1f} tok/s)")
    print("[serve] sample:", seq[0].tolist())
    if args.metrics_out:
        import json as _json

        from repro.obs import get_registry
        with open(args.metrics_out, "w") as f:
            _json.dump(get_registry().snapshot(), f, indent=1, default=str)
        print(f"[serve] metrics snapshot -> {args.metrics_out}")
    if metrics_srv is not None:
        metrics_srv.close()
    return seq


if __name__ == "__main__":
    main()
