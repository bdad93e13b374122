"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists: device op events ``(name, start_ns, dur_ns)`` from each TPU plane's
"XLA Ops" line, and the benchmark's own host spans (``bench.*``
``TraceAnnotation``s) from the host plane.  Both lie on one clock.  The
rest works on those lists alone, so a test can feed it a synthetic trace.

On a TPU v5e the "XLA Ops" events are named by their HLO text
(``%matmul.55 = bf16[8192,7168]{...} custom-call(...),
custom_call_target="tpu_custom_call", ...``): a Pallas kernel is a
``tpu_custom_call`` named after its ``pallas_call`` (``matmul``,
``flash_attention``), and the kernel function's own name does not appear.
A ``while`` (the layer scan) is an event that encloses its body's events,
so sums of op time count only the innermost events.
"""
from __future__ import annotations

import glob
import re
import sys
from dataclasses import dataclass, field

PALLAS = "tpu_custom_call"
HOST_PREFIX = "bench."


def short_name(hlo: str) -> str:
    """``%matmul.55 = bf16[8192,7168]{1,0:...} custom-call(...)`` ->
    ``matmul.55 bf16[8192,7168] tpu_custom_call``: instruction, result
    type without layout, and the Pallas target where there is one."""
    if " = " not in hlo:
        return hlo
    inst, rest = hlo.split(" = ", 1)
    typ = re.sub(r"\{[^{}]*\}", "", rest.split(" ", 1)[0])
    tail = f" {PALLAS}" if f'custom_call_target="{PALLAS}"' in rest else ""
    return f"{inst.lstrip('%')} {typ}{tail}"


@dataclass
class Trace:
    ops: list = field(default_factory=list)      # (name, start_ns, dur_ns)
    spans: list = field(default_factory=list)    # (name, start_ns, dur_ns)
    n_devices: int = 1


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    tr = Trace(n_devices=0)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                "SparseCore" not in plane.name:
            ops = [line for line in plane.lines if line.name == "XLA Ops"]
            if ops:
                tr.n_devices += 1
            for line in ops:
                tr.ops.extend((short_name(e.name), e.start_ns, e.duration_ns)
                              for e in line.events)
            if not ops:
                print(f"[bench] trace: {plane.name} has no 'XLA Ops' line; "
                      f"lines: {[ln.name for ln in plane.lines]}",
                      file=sys.stderr)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX))
    tr.n_devices = max(tr.n_devices, 1)
    return tr


def window(tr: Trace, step_span: str) -> tuple:
    """[start, end] of the host spans named ``step_span``, and their count."""
    s = [(t0, t0 + d) for n, t0, d in tr.spans if n == step_span]
    if not s:
        return None, 0
    return (min(a for a, _ in s), max(b for _, b in s)), len(s)


def clip(ops: list, win: tuple) -> list:
    """Ops that start inside the window, cut to its end."""
    lo, hi = win
    return [(n, t0, min(d, hi - t0)) for n, t0, d in ops if lo <= t0 < hi]


def leaves(ops: list) -> list:
    """The ops that enclose no other op (a ``while`` encloses its body)."""
    order = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent = [False] * len(order)
    stack = []                                   # indices of open ops
    for i, (_, t0, d) in enumerate(order):
        # drop open ops that ended, or that this one overlaps but
        # outlasts: those do not enclose it
        while stack and (order[stack[-1]][1] + order[stack[-1]][2] <= t0
                         or t0 + d > order[stack[-1]][1]
                         + order[stack[-1]][2]):
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(order, parent) if not p]


def union_ns(ops: list) -> float:
    """Length of the union of the ops' intervals."""
    total, end = 0.0, None
    for _, t0, d in sorted(ops, key=lambda o: o[1]):
        t1 = t0 + d
        if end is None or t0 >= end:
            total += d
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


def gaps(ops: list, win: tuple) -> list:
    """Idle intervals (start, end) of the device inside the window."""
    out, cur = [], win[0]
    for _, t0, d in sorted(ops, key=lambda o: o[1]):
        if t0 > cur:
            out.append((cur, t0))
        cur = max(cur, t0 + d)
    if win[1] > cur:
        out.append((cur, win[1]))
    return out


def host_activity(spans: list, t: float) -> str:
    """The innermost benchmark host span that covers time ``t``."""
    best = None
    for n, t0, d in spans:
        if t0 <= t < t0 + d and (best is None or d < best[1]):
            best = (n, d)
    return best[0] if best else "outside benchmark spans"


@dataclass
class Summary:
    """One traced stretch of steps: what the metric readers read."""
    n_steps: int
    window_ns: float
    busy_ns: float
    top_ops: list                                 # [[name, seconds]]
    idle_gaps: list                               # [[host activity, seconds]]


def summarize(tr: Trace, step_span: str, top: int = 10) -> Summary:
    win, n = window(tr, step_span)
    if win is None:
        return None
    ops = clip(tr.ops, win)
    if not ops:
        print(f"[bench] trace: none of {len(tr.ops)} device ops falls in the "
              f"window {win} of {n} host spans", file=sys.stderr)
    by_name = {}
    for name, _, d in leaves(ops):
        by_name[name] = by_name.get(name, 0.0) + d
    nd = tr.n_devices
    idle = sorted(((b - a, host_activity(tr.spans, (a + b) / 2))
                   for a, b in gaps(ops, win)), reverse=True)[:top]
    return Summary(
        n_steps=n, window_ns=float(win[1] - win[0]),
        busy_ns=union_ns(ops),
        top_ops=[[name, d / nd * 1e-9] for name, d in
                 sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[who, g * 1e-9] for g, who in idle])
