"""Readings that set a cell's limit: the program over many seeds, and the
float8 control over a few, in one process on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--out FILE]

For each seed it sets the cell up as a run does, serves batches at the
cell's own sizes until the check's sample has finished, frees the program
and reads, against the float32 reference: the widest gap of the program's
served tokens (the lower reading) and, on the control seeds, the widest gap
of the tokens that a float8 forward of the reference puts first (the upper
reading).  One JSON line per reading goes to standard output and
to ``--out``.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run as bench_run  # noqa: E402
from harness import spec  # noqa: E402


def serve_sample(run) -> None:
    """Serve at the cell's sizes until the check's sample has finished."""
    need = run.cell.traffic["check_requests"]
    with run.planned():
        if run.kind == "prefill":
            run.seconds = 0.0
            run.finished = []
            while run.B * len(run.finished) < need:
                finished = run.finished
                run.window_prefill()
                run.finished = finished + run.finished
        else:
            run.finished = []
            while run.B * len(run.finished) < need:
                if run.batch.prompts is None or run.batch.done == run.G:
                    run._next_batch()          # the lead-in is never read
                while run.batch.done < run.G:
                    run._decode_one()
                run._batch_done()


def read_seed(cell, seed: int, control: bool = False, **run_kw) -> dict:
    """One seed's readings: the program's widest gap and, with
    ``control``, the float8 control's."""
    from harness import cell as cell_run

    t = time.perf_counter()
    run = cell_run.Run(cell, seed, 0.0, t, **run_kw)
    run.setup()
    serve_sample(run)
    run.free()
    g = run.readings(("f32", "fp8") if control else ("f32",))
    rec = {"workload": cell.name, "seed": seed, "plan": run.plan_hash,
           "program_gap_max": float(g["program"].max()),
           "n_tokens": int(g["program"].size),
           "seconds": time.perf_counter() - t}
    if "fp8" in g:
        rec["fp8_gap_max"] = float(g["fp8"].max())
    return rec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.ROOT / "src"))
    cell = spec.resolve(args.workload)
    device, _, _ = bench_run.device_info(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None
    for seed in seeds:
        rec = read_seed(cell, seed, seed in controls)
        rec["device"] = device["kind"]
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()


if __name__ == "__main__":
    main()
