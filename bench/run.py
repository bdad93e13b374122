"""Run one cell of BENCHMARK.json once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout on a machine with the chips the cell
asks for.  It exits non-zero, printing no result, where JAX finds no TPU,
fewer chips than the cell needs, a device kind missing from
``bench/peaks.json``, or no program beside the benchmark.  The last line of
standard output is one JSON object: end-to-end metrics with ``--trace 0``,
per-layer metrics (read from a profiler trace) with ``--trace 1``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402


def fail(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def device_info(chips: int) -> tuple:
    """The accelerator as JAX reports it, and its peaks; fails without a
    TPU, with too few chips, or with a kind the peaks table lacks."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX finds {devs[0].platform} devices only")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips; JAX finds {len(devs)}")
    kind = devs[0].device_kind
    peaks = spec.load_json(BENCH / "peaks.json")
    if kind not in peaks:
        fail(f"device kind {kind!r} is not in bench/peaks.json")
    dev = devs[0]

    def memory_peak():
        return int(max(d.memory_stats()["peak_bytes_in_use"]
                       for d in devs[:chips]))

    return ({"platform": dev.platform, "kind": kind, "count": len(devs)},
            peaks[kind], memory_peak)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (spec.ROOT / "src" / "repro").is_dir():
        fail(f"no program beside the benchmark: {spec.ROOT / 'src'} "
             f"holds no repro package")
    sys.path.insert(0, str(spec.ROOT / "src"))
    cell = spec.resolve(args.workload)
    device, peaks, memory_peak = device_info(cell.chips)
    from harness import cell as cell_run

    out = cell_run.execute(cell, args.seed, args.seconds, bool(args.trace),
                           T_PROCESS, device, peaks,
                           memory_peak=memory_peak)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
