"""Per-site device time of one cell's traced stretch.

    python3 bench/site_trace.py --workload <cell> --seed <n>

Sets the cell up as ``bench/run.py`` does and traces the stretch that
``bench/run.py --trace 1`` traces after its window (``TRACE_STEPS`` steps
under the plan, then as many under every site's baseline tiles), with no
window before it and no comparison after.  Each device op is labelled with
the kernel site whose ``site=`` scope it runs under, by a join with the
compiled step's HLO text (``harness/sites.py``).  Prints one stderr line
per site of each stretch and, as the last line of standard output, one
JSON object: the cell's per-layer metrics as ``bench/run.py`` reads them,
the site readings of the plan's stretch, each site's device time and
costliest ops, and the seconds the site lookup took.  It fails as
``bench/run.py`` does where JAX finds no TPU.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run as bench_run  # noqa: E402
from harness import cell as cell_run  # noqa: E402
from harness import sites, spec, trace  # noqa: E402


def step_hlo(run, program) -> str:
    """The compiled text of the cell's step traced under ``program``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    prefill, decode = run._steps(run.model, program)
    with run.planned(program):
        if run.kind == "prefill":
            x = jax.device_put(np.zeros((run.B, run.P), np.int32))
            low = prefill.lower(run.params, x, run.cache)
        else:
            low = decode.lower(run.params, run.tok, jnp.int32(run.P),
                               run.cache)
        return low.compile().as_text()


def traced_with_sites(run) -> list:
    """Run ``run.traced_stretch`` and return, per stretch (plan, baseline),
    ``(trace, device events, HLO sites, label_s)``; ``label_s`` is the time
    the site lookup took: reading the events and compiling and parsing the
    step's HLO text."""
    from repro import api

    programs = [run.program, api.baseline_program(run.sites)]
    # the stretch deletes each profile once trace.load has read it, so the
    # sites are read where it calls trace.load
    out, load = [], trace.load

    def load_and_label(log_dir):
        tr = load(log_dir)
        t = time.perf_counter()
        events = sites.device_events(log_dir)
        hlo = sites.hlo_sites(step_hlo(run, programs[len(out)]))
        joined = sum(sites.instruction(e) in hlo for e in events)
        cell_run.log(f"site join: {joined} of {len(events)} events named "
                     f"in the compiled step")
        out.append((tr, events, hlo, time.perf_counter() - t))
        return tr

    trace.load = load_and_label
    try:
        with run.planned():
            run.traced_stretch()
    finally:
        trace.load = load
    return out


def top_ops(steps: list, n_devices: int, n: int = 3) -> dict:
    """site -> its ``n`` costliest ops of ``sites.split``'s steps, [name,
    ms a step]."""
    by = {}
    for step in steps:
        for name, _, d, site in step:
            k = (site or "(no site)", name)
            by[k] = by.get(k, 0.0) + d / n_devices / len(steps) * 1e-6
    out = {}
    for (site, name), ms in sorted(by.items(), key=lambda kv: -kv[1]):
        if len(out.setdefault(site, [])) < n:
            out[site].append([name, ms])
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.ROOT / "src"))
    cell = spec.resolve(args.workload)
    device, peaks, memory_peak = bench_run.device_info(cell.chips)
    run = cell_run.Run(cell, args.seed, 0.0, T_PROCESS)
    run.setup()
    cell_run.log(f"set-up {run.setup_s:.3f} s")
    stretches = traced_with_sites(run)
    ctx = run.reader_context(peaks)
    metrics = {m["name"]: cell.readers[m["name"]].read(ctx)
               for m in cell.per_layer}
    by_stretch = {}
    for what, (tr, events, hlo, label_s) in zip(("plan", "baseline"),
                                                stretches):
        steps = sites.split(tr, events, hlo)
        s = sites.summarize(steps, run.step_ops, tr.n_devices)
        for line in sites.log_lines(s, what):
            cell_run.log(line)
        by_stretch[what] = {
            "label_s": label_s, "steps": len(s.tallies),
            "complete": len(s.complete),
            "ms_a_step": {k or "(no site)": ms
                          for k, ms in sites.ms_a_step(s).items()},
            "top_ops": top_ops(steps, s.n_devices)}
        if what == "plan":
            site_ctx = dict(ctx, sites=s)
            for name, read in sites.READERS.items():
                v = read(site_ctx)
                if v is not None:
                    metrics[name] = v
    device["memory_peak_bytes"] = memory_peak()
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "plan": run.plan_hash,
        "setup_s": run.setup_s, "metrics": metrics, "sites": by_stretch,
        "breakdown": run.plan_trace.top_ops, "device": device,
        "wall_s": time.perf_counter() - T_PROCESS}), flush=True)


if __name__ == "__main__":
    main()
