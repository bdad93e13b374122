"""Per-site device time on synthetic traces: where a device op's site is
read, which traced steps are complete, and the kernel rooflines and the
off-site share by hand."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import sites, spec, trace  # noqa: E402

OPS = spec.family("ops", "dense_gated")
RUN = {"n_layers": 2, "d_model": 8, "d_ff": 16, "n_heads": 2,
       "n_kv_heads": 1, "head_dim": 4, "vocab_size": 32}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
MS = 1_000_000          # ns
MATMULS = ("attn.q", "attn.k", "attn.v", "attn.o", "mlp.gate", "mlp.up",
           "mlp.down")
PALLAS = ', custom_call_target="tpu_custom_call"'
WHILE = "%while.3 = (s32[], bf16[8,8]{1,0:T(8,128)(2,1)}) while(%tuple.1), " \
    "condition=%cond, body=%body"


def _synthetic(phase="prefill", n_steps=3, drop=None, inside=False):
    """``n_steps`` steps of 30 ms.  Each layer (2) runs every matmul site's
    Pallas call (1 ms) and, for ``mlp.gate``, its operand pad (0.1 ms);
    attention (prefill: a Pallas call of 2 ms and the K/V repeat, 0.5 ms;
    decode: two XLA ops of 1.25 ms); and a norm under no site (0.25 ms).
    ``lm_head`` is one Pallas call a step, and a ``while`` encloses the
    layers.  ``drop`` = (step, site) leaves one event of that site out;
    ``inside`` puts a 10 us copy under no site inside each step's first
    ``mlp.up`` call.  Returns the trace, its events (the HLO text naming
    each op) and the compiled step's sites by instruction."""
    ops, events, spans, hlo = [], [], [], {}

    def op(site, dur, t, pallas=False, text=None):
        inst = f"op.{len(ops) + 1}"
        text = text or f"%{inst} = bf16[8,8]{{1,0:T(8,128)(2,1)}} " + (
            "custom-call(bf16[8,8] %p)" + PALLAS if pallas else "fusion(%p)")
        ops.append((trace.short_name(text), t, dur))
        events.append(text)
        hlo[trace.short_name(text).split(" ")[0]] = site
        return t + dur

    for s in range(n_steps):
        t0 = s * 30 * MS
        spans += [("bench.step", t0, 30 * MS), ("bench.fetch",
                                                t0 + 28 * MS, 2 * MS)]
        op(None, 25 * MS, t0 + 1 * MS, text=WHILE)
        t, dropped = t0 + 1 * MS, False
        for layer in range(2):
            for site in MATMULS:
                if drop == (s, site) and not dropped:
                    dropped = True
                    continue
                if inside and layer == 0 and site == "mlp.up":
                    op(None, 0.01 * MS, t + 0.5 * MS)
                t = op(site, 1 * MS, t, pallas=True)
                if site == "mlp.gate":
                    t = op(site, 0.1 * MS, t)
            if phase == "prefill":
                t = op("attn.core", 0.5 * MS, t)
                if drop == (s, "attn.core") and not dropped:
                    dropped = True
                else:
                    t = op("attn.core", 2 * MS, t, pallas=True)
            else:
                for _ in range(2):
                    if drop == (s, "attn.core") and not dropped:
                        dropped = True
                        continue
                    t = op("attn.core", 1.25 * MS, t)
            t = op(None, 0.25 * MS, t)
        op("lm_head", 1 * MS, t, pallas=True)
    return trace.Trace(ops=ops, spans=spans), events, hlo


def _step_ops(phase, n_steps, pos0=9):
    return [OPS.step_ops(RUN, phase, batch=3, prompt_len=5, pos=pos0 + i)
            for i in range(n_steps)]


def _summary(phase="prefill", n_steps=3, drop=None, inside=False):
    tr, events, hlo = _synthetic(phase, n_steps, drop, inside)
    return tr, sites.summarize(sites.split(tr, events, hlo),
                               _step_ops(phase, n_steps), tr.n_devices)


def _ctx(phase, s, n_steps=3):
    return {"phase": phase, "sites": s, "step_ops": _step_ops(phase, n_steps),
            "itemsize": 2, "peaks": PEAKS}


def _need(op):
    fl, by = spec.counter(op["kind"]).count(op, 2)
    return op["mult"] * max(fl / PEAKS["bf16_flops_per_s"],
                            by / PEAKS["hbm_bytes_per_s"])


def test_site_of_an_op_name_and_of_hlo_text():
    assert sites.site_of("jit(prefill)/while/body/closed_call/site=mlp.gate"
                         "/jit(matmul)/pad") == "mlp.gate"
    assert sites.site_of("jit(prefill)/while/body/add") is None
    text = ('HloModule jit_prefill\n'
            '  %pad.60 = bf16[8,16]{1,0} pad(%a, %z), padding=0_0x0_8, '
            'metadata={op_name="jit(f)/site=attn.q/jit(_pad)/pad"}\n'
            '  ROOT %add.1 = bf16[8,16]{1,0} add(%b, %c), '
            'metadata={op_name="jit(f)/add"}\n'
            '  %p = bf16[8] parameter(0)\n')
    assert sites.hlo_sites(text) == {"pad.60": "attn.q", "add.1": None,
                                     "p": None}


def test_control_flow_ops_by_opcode():
    assert sites.is_control(WHILE)
    assert sites.is_control("%call.2 = bf16[8]{0} call(%a), to_apply=%f")
    assert not sites.is_control(
        "%fusion.9 = bf16[8]{0:T(128)} fusion(%a), kind=kLoop, "
        "calls=%fused_computation")
    assert not sites.is_control(
        "%copy-start = (bf16[8]{0:T(128)S(1)}, bf16[8]{0}, u32[]{:S(2)}) "
        "copy-start(bf16[8]{0} %p)")


def test_per_site_time_and_events():
    _, s = _summary()
    assert s.complete == [0, 1, 2]
    t = s.tallies[0]
    assert t["mlp.gate"] == [pytest.approx(2.2 * MS), 4, 2]
    assert t["mlp.up"] == [pytest.approx(2 * MS), 2, 2]
    assert t["attn.core"] == [pytest.approx(5 * MS), 4, 2]
    assert t["lm_head"] == [pytest.approx(1 * MS), 1, 1]
    assert t[None] == [pytest.approx(0.5 * MS), 2, 0]   # not the while
    ms = sites.ms_a_step(s)
    assert ms["mlp.gate"] == pytest.approx(2.2)
    assert sum(ms.values()) == pytest.approx(2 * (7 + 0.1 + 2.5 + 0.25) + 1)
    lines = sites.log_lines(s, "plan")
    assert len(lines) == 10 and lines[-1].startswith("sites plan (no site)")
    assert "sites plan mlp.gate: 2.200 ms a step, 4 events and 2 Pallas " \
        "events a step, 3 of 3 steps complete" in lines


@pytest.mark.parametrize("phase,site,short", [
    ("prefill", "mlp.up", "mlp.up 1 of 2 Pallas"),
    ("prefill", "attn.core", "attn.core 1 of 2 Pallas"),
    ("decode", "attn.core", "attn.core 3 of 4")])
def test_a_step_missing_an_event_is_left_out(phase, site, short):
    _, s = _summary(phase, drop=(1, site))
    assert s.complete == [0, 2]
    assert s.shortfalls == [f"step 1: {short}"]


def test_rooflines_and_offsite_share_by_hand():
    _, s = _summary(drop=(1, "mlp.up"))
    ctx = _ctx("prefill", s)
    step = _step_ops("prefill", 3)[0]                   # prefill: same ops
    mm = sum(_need(o) for o in step if o["kind"] == "matmul")
    att = sum(_need(o) for o in step if o["kind"] == "attention")
    # two complete steps; matmul sites 2 * 7.1 + 1 ms, attention 2 * 2.5 ms
    assert sites.READERS["matmul_roofline.prefill"](ctx) == pytest.approx(
        100 * 2 * mm / (2 * 15.2e-3))
    assert sites.READERS["attention_roofline.prefill"](ctx) == \
        pytest.approx(100 * 2 * att / (2 * 5e-3))
    assert sites.READERS["offsite_share.prefill"](ctx) == pytest.approx(
        100 * 0.5 / 20.7)
    assert sites.READERS["attention_roofline.decode"](ctx) is None


def test_decode_attention_counts_keys_up_to_each_step():
    _, s = _summary("decode", drop=(0, "attn.core"))
    assert s.complete == [1, 2]
    ctx = _ctx("decode", s)
    need = [sum(_need(o) for o in ops if o["kind"] == "attention")
            for ops in _step_ops("decode", 3)]
    assert need[1] < need[2]                            # pos 10, then 11
    assert sites.READERS["attention_roofline.decode"](ctx) == pytest.approx(
        100 * (need[1] + need[2]) / (2 * 5e-3))
    assert sites.READERS["offsite_share.decode"](ctx) == pytest.approx(
        100 * 0.5 / 20.7)


def test_no_complete_step_reads_none():
    tr, events, hlo = _synthetic(n_steps=1, drop=(0, "mlp.up"))
    s = sites.summarize(sites.split(tr, events, hlo),
                        _step_ops("prefill", 1), 1)
    assert s.complete == []
    assert s.shortfalls == ["step 0: mlp.up 1 of 2 Pallas"]
    # a program without site scopes: the compiled step names no site
    bare = sites.summarize(sites.split(tr, events, dict.fromkeys(hlo)),
                           _step_ops("prefill", 1), 1)
    assert bare.shortfalls == ["no device op carries a site scope"]
    for summary in (s, bare):
        for name, read in sites.READERS.items():
            phase = name.split(".")[1]
            assert read(_ctx(phase, summary, 1)) is None, name
    with pytest.raises(ValueError):
        sites.split(tr, events[1:], hlo)


def test_a_kernel_enclosing_a_short_op_still_counts():
    # trace.leaves takes each step's first mlp.up call, which encloses the
    # copy, for a parent and drops it; the site tally keeps both
    tr, s = _summary(inside=True)
    assert s.complete == [0, 1, 2]
    assert [t["mlp.up"][2] for t in s.tallies] == [2, 2, 2]
    assert [t[None][1] for t in s.tallies] == [3, 3, 3]
    win, _ = trace.window(tr, "bench.step")
    kept = trace.leaves(trace.clip(tr.ops, win))
    assert sum(n.endswith(trace.PALLAS) for n, _, _ in kept) == 3 * 16


def test_the_sites_leave_the_step_summary_alone():
    tr, events, hlo = _synthetic()
    want = trace.summarize(tr, "bench.step")
    sites.summarize(sites.split(tr, events, hlo), _step_ops("prefill", 3), 1)
    got = trace.summarize(tr, "bench.step")
    assert (got.n_steps, got.window_ns, got.busy_ns, got.top_ops,
            got.idle_gaps) == (want.n_steps, want.window_ns, want.busy_ns,
                               want.top_ops, want.idle_gaps)
