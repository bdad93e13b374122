"""Measured autotuning end to end: train PPO against *wall-clock* rewards.

This is the paper's actual loop (eq. 2 — the agent learns from measured
execution time, not a cost model): every reward below comes from
compiling and timing the Pallas kernels via ``oracle="measured"``.  On
a TPU the kernels compile natively; on CPU they run in Pallas interpret
mode with capped shapes, so this exact script is the CI smoke for the
whole measure→reward→train→deploy chain.

    PYTHONPATH=src python examples/measured_autotune.py \
        [--steps 96] [--db /tmp/measure.jsonl] [--agent ppo] \
        [--transport pool --workers 2]

Run it twice with the same ``--db`` and the second run performs zero
kernel timings — every (site, tile) pair is served from the persistent
measurement database (under either transport: the pool streams its
results into the same DB).  For the session-oriented service on top,
see ``examples/service_autotune.py``.
"""
import argparse
import sys

sys.path.insert(0, "src")


def small_cfg():
    """A compact action space: measured tuning sweeps real kernels, so the
    demo keeps the grid small enough for interpret-mode CI (~tens of
    pairs, each timed once ever thanks to the DB)."""
    from repro.api import NeuroVecConfig
    return NeuroVecConfig(
        bm_choices=(16, 32, 64), bn_choices=(128,), bk_choices=(128,),
        bq_choices=(64, 128), bkv_choices=(128,), chunk_choices=(32, 64),
        train_batch=32, sgd_minibatch=16, ppo_epochs=2, lr=5e-4)


def demo_sites():
    from repro.models.compute import KernelSite
    return [
        KernelSite(site="ex.qkv", kind="matmul", m=64, n=128, k=256),
        KernelSite(site="ex.ffn", kind="matmul", m=128, n=128, k=128),
        KernelSite(site="ex.attn", kind="attention", m=128, n=64, k=128,
                   batch=2, causal=True),
        KernelSite(site="ex.scan", kind="chunk_scan", m=64, n=32, k=16,
                   batch=2),
    ]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=96,
                    help="PPO environment steps (measured rewards)")
    ap.add_argument("--agent", default="ppo",
                    help="any repro.api registry name (ppo, brute, ...)")
    ap.add_argument("--db", default="/tmp/repro_measure.jsonl",
                    help="persistent measurement-DB path")
    ap.add_argument("--reps", type=int, default=1,
                    help="timing repetitions per (site, tile) pair")
    ap.add_argument("--prune-topk", type=int, default=None,
                    help="only time each site's top-K surrogate-ranked "
                         "tile candidates; the rest are priced by a "
                         "learned cost model trained from --db "
                         "(needs a warm DB — run once without it first)")
    ap.add_argument("--transport", choices=("inproc", "pool"),
                    default="inproc",
                    help="measure in this process or across a subprocess "
                         "worker pool")
    ap.add_argument("--workers", type=int, default=2,
                    help="pool size for --transport pool")
    ap.add_argument("--out", default="/tmp/repro_measured_tiles.json")
    args = ap.parse_args(argv)
    if args.reps < 1:
        ap.error(f"--reps must be >= 1, got {args.reps}")
    if args.prune_topk is not None and args.prune_topk < 1:
        ap.error(f"--prune-topk must be >= 1, got {args.prune_topk}")

    from repro.api import NeuroVectorizer, TileProgram

    cfg = small_cfg()
    sites = demo_sites()
    nv = NeuroVectorizer(cfg, agent=args.agent, oracle="measured", seed=0,
                         db_path=args.db, transport=args.transport,
                         workers=(args.workers
                                  if args.transport == "pool" else None),
                         prune_topk=args.prune_topk,
                         oracle_kwargs=dict(reps=args.reps, warmup=1))
    print(f"== fit {args.agent} vs measured oracle "
          f"(transport={args.transport}, "
          f"{nv.oracle.measure_fn.transport.backend_key}) ==")
    fit_kw = ({"total_steps": args.steps} if args.agent == "ppo" else {})
    nv.fit(sites, **fit_kw)

    prog = nv.tune_sites(sites)
    assert isinstance(prog, TileProgram) and len(prog.tiles) == len(sites)
    prog.save(args.out)

    print(f"tuned {len(prog.tiles)} sites -> {args.out}")
    for k, t in prog.tiles.items():
        print(f"  {k}: tiles={t}")
    print(f"measured speedup vs heuristic baseline: "
          f"{nv.speedup(prog, sites):.2f}x")
    st = nv.oracle.measure_fn.transport.stats()
    print(f"measurements: {st['transport_timed_pairs_total']} timed, "
          f"{st['transport_hits_total']} DB hits, "
          f"{st['transport_misses_total']} misses, "
          f"{st['transport_coalesced_total']} coalesced "
          f"(hit rate {st['transport_hit_ratio']:.2f}) — rerun with the "
          f"same --db and timed goes to 0")
    if args.prune_topk is not None:
        state = ("active" if nv.oracle.prune_active
                 else "inactive (DB too cold to train the surrogate)")
        print(f"pruning top-{args.prune_topk}: {state}, "
              f"{nv.oracle.pruned_pairs} pairs surrogate-priced")
    nv.close()                 # release pool workers / the DB file handle
    return prog


if __name__ == "__main__":
    main()
