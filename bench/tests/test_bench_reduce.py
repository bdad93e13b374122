"""The benchmark's yardstick on synthetic inputs: the trace reduction, the
operation and byte counters, the cells' files, and the refusal to run
without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import readers, spec, trace, work  # noqa: E402

OPS = spec.family("ops", "dense_gated")

MS = 1_000_000          # ns


PALLAS = ', custom_call_target="tpu_custom_call", operand_layout_constraints'
MATMUL = trace.short_name(
    "%matmul.55 = bf16[8192,7168]{1,0:T(8,128)(2,1)} custom-call("
    "bf16[8192,4096]{1,0:T(8,128)(2,1)} %pad.60)" + PALLAS)
FLASH = trace.short_name(
    "%flash_attention.7 = bf16[128,2048,80]{2,1,0:T(8,128)(2,1)} "
    "custom-call(bf16[128,2048,80]{2,1,0} %copy.81)" + PALLAS)
FUSION = trace.short_name(
    "%slice_multiply_fusion.3 = bf16[8192,6912]{1,0:T(8,128)(2,1)} "
    "fusion(bf16[8192,7168]{1,0} %matmul.55), kind=kLoop")


def _synthetic():
    """Two steps of 10 ms; in each the layer scan (a ``while`` op) encloses
    two matmul kernel calls, one flash call and one XLA fusion, with a 2 ms
    gap while the host fetches tokens and a 1 ms gap while it dispatches."""
    ops, spans = [], []
    for s in range(2):
        t = s * 10 * MS
        spans += [("bench.step", t, 10 * MS),
                  ("bench.dispatch", t, 1 * MS),
                  ("bench.fetch", t + 8 * MS, 2 * MS)]
        ops += [("while.3 (s32[])", t + 1 * MS, 7 * MS),
                (MATMUL, t + 1 * MS, 2 * MS),
                (MATMUL, t + 3 * MS, 2 * MS),
                (FLASH, t + 5 * MS, 1 * MS),
                (FUSION, t + 5.5 * MS, 2.5 * MS)]       # overlaps flash
    ops.append((FUSION, 30 * MS, 1 * MS))                 # after the window
    return trace.Trace(ops=ops, spans=spans)


def test_names_from_hlo_text():
    assert MATMUL == "matmul.55 bf16[8192,7168] tpu_custom_call"
    assert FLASH == "flash_attention.7 bf16[128,2048,80] tpu_custom_call"
    # an XLA op named like the kernel is not tagged as a Pallas kernel
    assert FUSION == "slice_multiply_fusion.3 bf16[8192,6912]"
    assert trace.short_name("%matmul.3 = bf16[4,4]{1,0} dot(bf16[4,4] %a)") \
        == "matmul.3 bf16[4,4]"


def test_union_and_gaps():
    tr = _synthetic()
    win, n = trace.window(tr, "bench.step")
    assert (win, n) == ((0, 20 * MS), 2)
    ops = trace.clip(tr.ops, win)
    assert len(ops) == 10                        # the last fusion is out
    assert len(trace.leaves(ops)) == 8           # the while ops enclose
    assert trace.union_ns(ops) == pytest.approx(14 * MS)     # 7 ms per step
    g = trace.gaps(ops, win)
    assert [(b - a) / MS for a, b in g] == [1, 3, 2]


def test_summary_kernels_and_idle_attribution():
    s = trace.summarize(_synthetic(), "bench.step")
    assert s.n_steps == 2 and s.window_ns == 20 * MS
    assert s.busy_ns == pytest.approx(14 * MS)
    # leaves only: the while ops that enclose them are not listed
    assert s.top_ops == [[MATMUL, pytest.approx(8e-3)],
                         [FUSION, pytest.approx(5e-3)],
                         [FLASH, pytest.approx(2e-3)]]
    # the 3 ms gap runs from the first step's fetch into the second step's
    # dispatch, and its middle falls in the fetch; the window opens with a
    # dispatch and closes with a fetch
    whos = [w for w, _ in s.idle_gaps]
    assert whos == ["bench.fetch", "bench.fetch", "bench.dispatch"]
    assert [g for _, g in s.idle_gaps] == pytest.approx([3e-3, 2e-3, 1e-3])


def test_readers_on_synthetic_trace():
    s = trace.summarize(_synthetic(), "bench.step")
    ctx = {"phase": "prefill", "plan": s, "baseline": s, "itemsize": 2,
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
           "step_ops": [[{"kind": "matmul", "m": 4, "n": 4, "k": 4,
                          "mult": 2}]] * 2, "plan_s": 0.5}
    assert readers.idle("prefill")(ctx) == pytest.approx(30.0)
    assert readers.idle("decode")(ctx) is None
    assert readers.plan_gain("prefill")(ctx) == pytest.approx(1.0)
    # 2 steps of 2 matmuls of 128 FLOP at 1 TFLOP/s over a 20 ms window
    assert readers.mfu("prefill")(ctx) == pytest.approx(
        100 * 2 * 2 * 128 / 1e12 / 20e-3)
    # decode: per step the larger of FLOP time and byte time (96 B at 1 GB/s)
    dec = dict(ctx, phase="decode")
    assert readers.mfu("decode")(dec) == pytest.approx(
        100 * 2 * 2 * 96 / 1e9 / 20e-3)
    assert readers.plan_seconds(ctx) == 0.5
    empty = dict(ctx, plan=None)
    assert readers.mfu("prefill")(empty) is None


def test_matmul_counter_by_hand():
    mm = spec.counter("matmul")
    assert mm.count({"m": 8, "n": 16, "k": 32}, 2) == (
        2 * 8 * 16 * 32, 2 * (8 * 32 + 32 * 16 + 8 * 16))


def test_attention_counter_causal_and_decode_by_hand():
    att = spec.counter("attention")
    op = {"batch": 2, "heads": 4, "kv_heads": 2, "q_len": 4, "kv_len": 4,
          "head_dim": 8, "causal": True}
    flops, nbytes = att.count(op, 2)
    assert flops == 4 * 2 * 4 * 10 * 8                 # 10 = 1+2+3+4 pairs
    assert nbytes == 2 * (2 * 2 * 4 * 4 * 8 + 2 * 2 * 2 * 4 * 8)
    # Sq < Skv: bottom-right aligned, query i sees i + 1 + (Skv - Sq) keys
    flops, _ = att.count(dict(op, q_len=2, kv_len=5), 2)
    assert flops == 4 * 2 * 4 * (4 + 5) * 8
    # one decode query at pos 99 reads 100 keys, not the allocated cache
    flops, nbytes = att.count(dict(op, q_len=1, kv_len=100, causal=False), 2)
    assert flops == 4 * 2 * 4 * 100 * 8
    assert nbytes == 2 * (2 * 2 * 4 * 1 * 8 + 2 * 2 * 2 * 100 * 8)


def test_step_work_counts_the_needed_shapes():
    run = {"n_layers": 2, "d_model": 8, "d_ff": 16, "n_heads": 2,
           "n_kv_heads": 1, "head_dim": 4, "vocab_size": 32}
    ops = OPS.step_ops(run, "prefill", batch=3, prompt_len=5)
    by = {o["name"]: o for o in ops}
    assert (by["attn.k"]["m"], by["attn.k"]["n"]) == (15, 4)
    assert by["lm_head"]["m"] == 3 and by["lm_head"]["mult"] == 1
    assert by["attn.core"]["causal"] and by["attn.core"]["mult"] == 2
    mms = [o for o in ops if o["kind"] == "matmul"]
    assert sum(o["mult"] for o in mms) == 7 * 2 + 1
    fl, by_ = work.totals(ops, 2)
    att_fl, att_by = spec.counter("attention").count(by["attn.core"], 2)
    assert fl == sum(2 * o["m"] * o["n"] * o["k"] * o["mult"]
                     for o in mms) + 2 * att_fl
    assert by_ == sum(2 * (o["m"] * o["k"] + o["k"] * o["n"] + o["m"] *
                           o["n"]) * o["mult"] for o in mms) + 2 * att_by
    dec = {o["name"]: o for o in OPS.step_ops(run, "decode", 3, 5, pos=9)}
    assert dec["attn.q"]["m"] == 3 and dec["attn.core"]["kv_len"] == 10


def test_every_cell_resolves_to_its_files():
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.traffic["kind"] in ("prefill", "decode")
        assert cell.run["arch"] and cell.limits["gap"]["limit"] > 0
        assert callable(cell.ops.step_ops)
        assert callable(cell.reference.forward_logits)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.readers[m["name"]].read)
            assert any(e["name"] == m["moves"] for e in cell.end_to_end)
    names = [c["name"] for c in bench["configs"]]
    for c in bench["configs"]:
        cfg = spec.load_json(spec.ROOT / c["file"])
        assert cfg["name"] in names and cfg["source"] == c["source"]


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "stablelm_3b.prefill_b4_p2048", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=spec.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()
