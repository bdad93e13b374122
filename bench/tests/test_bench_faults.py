"""A whole run at a tiny size on the CPU, with the harness's look for a chip
skipped: sound, it comes out correct under the cell's own limit; with the
timed path broken underneath, it does not.  Also the control: the float8
forward of the reference reads a wider gap than the program, and put in
the program's place it fails the cell's own limit."""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import cell as cell_run  # noqa: E402
from harness import spec  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _tiny(workload: str, limit: float = None) -> spec.Cell:
    """The cell at a tiny size; ``limit`` None keeps the cell's own."""
    cell = spec.resolve(workload)
    run = dict(cell.run, d_model=128, n_layers=2, n_heads=4, head_dim=32,
               d_ff=256, vocab_size=512)
    run["n_kv_heads"] = 2 if cell.run["n_kv_heads"] < cell.run["n_heads"] \
        else 4
    cell.config = dict(cell.config, run=run,
                       agent=dict(cell.config["agent"], total_steps=64))
    if cell.traffic["kind"] == "prefill":
        cell.traffic = dict(cell.traffic, batch=4, prompt_len=32,
                            check_requests=8)
    else:
        cell.traffic = dict(cell.traffic, batch=4, prompt_len=8, gen=12,
                            check_requests=4, steps_left_at_open=5)
    if limit is not None:
        cell.limits = {"gap": dict(cell.limits["gap"], limit=limit)}
    return cell


def _small(workload: str) -> spec.Cell:
    """A size at which the float8 control's widest gap clears the cells'
    limits on the CPU (at the test's seed 0.40 over 160 prefill tokens
    with 8 layers, 0.56 over 256 decode tokens with 12; the program reads
    under 0.05)."""
    cell = _tiny(workload)
    cell.config = dict(cell.config, run=dict(
        cell.run, d_model=256, n_layers=12, n_heads=4, n_kv_heads=4,
        head_dim=64, d_ff=512, vocab_size=2048))
    if cell.traffic["kind"] == "prefill":
        cell.traffic = dict(cell.traffic, prompt_len=64, check_requests=160)
    else:
        cell.traffic = dict(cell.traffic, batch=8, prompt_len=16, gen=32,
                            check_requests=8, steps_left_at_open=10)
    return cell


def _execute(cell, tmp_path, faults=()):
    return cell_run.execute(cell, 2 ** 31 + 101, 0.5, False,
                            time.perf_counter(), CPU, PEAKS, faults=faults,
                            interpret=True, agents=tmp_path)


CASES = [("stablelm_3b.prefill_b4_p2048", f) for f in ("", "token", "half")] \
    + [("stablelm_3b.decode_b16_p256_g768", f)
       for f in ("", "token", "state", "half")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_correct_only_when_the_timed_path_is_sound(workload, fault,
                                                   tmp_path):
    # the full-size cell's limit is far above what a sound tiny run reads
    # and far below what any of these faults reads
    cell = _tiny(workload)
    limit = spec.resolve(workload).limits["gap"]["limit"]
    out = _execute(cell, tmp_path, faults=(fault,) if fault else ())
    assert out["correct"] is (not fault), out["check"]
    assert list(out)[-1] == "check"
    assert out["check"]["gap_max"]["limit"] == limit
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}


def test_control_reads_wider_than_the_program(tmp_path):
    import control

    cell = _tiny("stablelm_3b.prefill_b4_p2048", limit=0.5)
    cell.traffic = dict(cell.traffic, check_requests=64)
    recs = [control.read_seed(cell, s, control=True, interpret=True,
                              agents=tmp_path) for s in (1, 2, 3)]
    # the rule that sets a limit: the control's smallest reading is three
    # times the program's largest or more
    lower = max(r["program_gap_max"] for r in recs)
    upper = min(r["fp8_gap_max"] for r in recs)
    assert upper > 3 * lower, recs


@pytest.mark.parametrize("workload", ["stablelm_3b.prefill_b4_p2048",
                                      "stablelm_3b.decode_b16_p256_g768"])
def test_control_in_the_programs_place_fails_the_cells_limit(workload,
                                                             tmp_path):
    """``Run.check`` judging the float8 control's first choices, at the
    same prompts and positions as the program's served tokens, against the
    cell's own limit: the program passes it and the control does not.
    The sample is served to its full size whatever the CPU's speed."""
    import control

    cell = _small(workload)
    limit = spec.resolve(workload).limits["gap"]["limit"]
    run = cell_run.Run(cell, 2 ** 31 + 101, 0.0, time.perf_counter(),
                       interpret=True, agents=tmp_path)
    run.setup()
    control.serve_sample(run)
    run.free()
    sound, fp8 = run.check(), run.check("fp8")
    assert sound["gap_max"]["limit"] == fp8["gap_max"]["limit"] == limit
    assert sound["_ok"] is True, sound
    assert fp8["_ok"] is False, fp8
    assert fp8["gap_max"]["value"] > limit
    assert fp8["_n_tokens"] == sound["_n_tokens"] == \
        cell.traffic["check_requests"] * (cell.traffic["gen"])
