"""Per-site device time from a profiler trace: the kernel site of each device
op, the traced steps that hold every site's events, and the kernel rooflines
and off-site share read from those steps.

The program runs each kernel site's ops under
``jax.named_scope("site=<label>")`` (``repro/models/compute.py``), and XLA
keeps the scope in each instruction's ``op_name``.  The label is the op name
of ``bench/ops/<family>.py`` (``attn.q`` ... ``lm_head``).  On a TPU v5e the
trace does not carry it: an "XLA Ops" event is named by its HLO text without
metadata, and its stats hold only its offset and duration.  So each event's
instruction name is joined with the ``op_name`` of the same instruction in
the compiled step's HLO text (``jit(f).lower(...).compile().as_text()``,
under the same ``api.inject`` as the traced step).

A step's ops are the events that start in its ``bench.step`` span, less the
control-flow ops (``while``, ``conditional``, ``call``) that enclose their
bodies' events.  ``trace.leaves`` instead drops every event that encloses
another, and a kernel's event can enclose a short op that overlaps it.

A program without site scopes labels no op: then no step is complete and
every reading is None.  Nothing here changes what ``trace.summarize`` reads.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass

from harness import trace, work

SITE = re.compile(r"site=([^/\"]+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')
INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
CONTROL = ("while", "conditional", "call")


def site_of(op_name: str):
    """The ``site=`` component of an ``op_name``, or None."""
    m = SITE.search(op_name)
    return m.group(1) if m else None


def hlo_sites(hlo_text: str) -> dict:
    """Instruction name -> site (or None) over a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            op = OP_NAME.search(line)
            out[m.group(1)] = site_of(op.group(1)) if op else None
    return out


def device_events(log_dir: str) -> list:
    """The HLO text naming each device op event, in ``trace.load``'s
    order."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:") and \
                "SparseCore" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out.extend(e.name for e in line.events)
    return out


def instruction(text: str) -> str:
    return text.split(" = ", 1)[0].lstrip("%")


def is_control(text: str) -> bool:
    """A ``while``, ``conditional`` or ``call``: its event encloses its
    body's events."""
    m = OPCODE.search(text.split(" = ", 1)[-1])
    return bool(m) and m.group(1) in CONTROL


def split(tr: trace.Trace, events: list, hlo: dict,
          step_span: str = "bench.step") -> list:
    """Per traced step, its ops ``(name, start, dur, site)`` (control-flow
    ops left out), each assigned to the ``step_span`` span in which it
    starts and cut to the last span's end, as ``trace.clip`` cuts.
    ``events`` are ``device_events`` of the trace, ``hlo`` the
    ``hlo_sites`` of the traced step."""
    if len(events) != len(tr.ops):
        raise ValueError(f"{len(events)} device events against "
                         f"{len(tr.ops)} ops of trace.load")
    spans = sorted((t0, t0 + d) for n, t0, d in tr.spans if n == step_span)
    if not spans:
        return []
    hi = spans[-1][1]
    starts = [a for a, _ in spans]
    steps = [[] for _ in spans]
    for (name, t0, d), text in zip(tr.ops, events):
        i = bisect.bisect_right(starts, t0) - 1
        if i >= 0 and t0 < spans[i][1] and not is_control(text):
            steps[i].append((name, t0, min(d, hi - t0),
                             hlo.get(instruction(text))))
    return steps


def tally(step: list) -> dict:
    """site (None for ops under no site) -> [device ns, events, Pallas
    events] of one step."""
    out = {}
    for name, _, d, site in step:
        t = out.setdefault(site, [0.0, 0, 0])
        t[0] += d
        t[1] += 1
        t[2] += name.endswith(trace.PALLAS)
    return out


@dataclass
class SiteSummary:
    """One traced stretch by site: what the site readers read."""
    n_devices: int
    tallies: list           # per step, ``tally``
    complete: list          # indices of the complete steps
    shortfalls: list        # why each other step is not complete


def summarize(steps: list, step_ops: list, n_devices: int) -> SiteSummary:
    """``steps`` from ``split``.  A step is complete when each Pallas-routed
    site of its ``step_ops`` (one with a Pallas event in any traced step)
    shows exactly ``mult`` Pallas events, and each other site as many
    events as the most any traced step shows, and at least one."""
    tallies = [tally(s) for s in steps]
    n = max(n_devices, 1)
    if not any(s for t in tallies for s in t):
        return SiteSummary(n, tallies, [],
                           ["no device op carries a site scope"])
    pallas = {s for t in tallies for s, v in t.items() if v[2]}
    most = {}
    for t in tallies:
        for s, v in t.items():
            most[s] = max(most.get(s, 0), v[1])
    complete, short = [], []
    for i, (t, ops) in enumerate(zip(tallies, step_ops)):
        miss = []
        for op in ops:
            site = op["name"]
            _, n_ops, n_pallas = t.get(site, (0.0, 0, 0))
            if site in pallas and n_pallas != op["mult"]:
                miss.append(f"{site} {n_pallas} of {op['mult']} Pallas")
            elif site not in pallas and (n_ops == 0
                                         or n_ops != most[site]):
                miss.append(f"{site} {n_ops} of {most.get(site, 0)}")
        if miss:
            short.append(f"step {i}: " + ", ".join(miss))
        else:
            complete.append(i)
    return SiteSummary(n, tallies, complete, short)


def ms_a_step(s: SiteSummary) -> dict:
    """site (None for ops under no site) -> device ms a step, over all the
    traced steps."""
    n = max(len(s.tallies), 1)
    out = {}
    for t in s.tallies:
        for k, v in t.items():
            out[k] = out.get(k, 0.0) + v[0] / s.n_devices / n * 1e-6
    return out


def log_lines(s: SiteSummary, what: str) -> list:
    """One line per site: device ms, events and Pallas events per step over
    all traced steps, and how many of them are complete."""
    n = len(s.tallies)
    if n == 0:
        return [f"sites {what}: no traced steps"]
    out = []
    for k, ms in sorted(ms_a_step(s).items(),
                        key=lambda kv: (kv[0] is None, kv[0] or "")):
        v = [t.get(k, (0.0, 0, 0)) for t in s.tallies]
        out.append(f"sites {what} {k or '(no site)'}: {ms:.3f} ms a step, "
                   f"{sum(x[1] for x in v) / n:g} events and "
                   f"{sum(x[2] for x in v) / n:g} Pallas events a step, "
                   f"{len(s.complete)} of {n} steps complete")
    out.extend(f"sites {what} incomplete {m}" for m in s.shortfalls)
    return out


def _need_s(op: dict, itemsize: int, peaks: dict) -> float:
    fl, by = work.totals([op], itemsize)
    return max(fl / peaks["bf16_flops_per_s"], by / peaks["hbm_bytes_per_s"])


def roofline(kind: str, phase: str):
    """Share of the roofline of the ops of one ``kind`` over the complete
    steps: per op ``mult * max(FLOP / peak, bytes / peak)`` over the
    device time under their sites."""
    def read(ctx):
        s = ctx.get("sites")
        if ctx.get("phase") != phase or s is None or not s.complete:
            return None
        need = t_ns = 0.0
        for i in s.complete:
            ops = [o for o in ctx["step_ops"][i] if o["kind"] == kind]
            need += sum(_need_s(o, ctx["itemsize"], ctx["peaks"])
                        for o in ops)
            t_ns += sum(s.tallies[i].get(o["name"], (0.0,))[0] for o in ops)
        t_ns /= s.n_devices
        return None if t_ns <= 0 else 100.0 * need / (t_ns * 1e-9)
    return read


def offsite(phase: str):
    """Share of the complete steps' device time under no site."""
    def read(ctx):
        s = ctx.get("sites")
        if ctx.get("phase") != phase or s is None or not s.complete:
            return None
        off = sum(s.tallies[i].get(None, (0.0,))[0] for i in s.complete)
        total = sum(v[0] for i in s.complete for v in s.tallies[i].values())
        return None if total <= 0 else 100.0 * off / total
    return read


READERS = {
    "matmul_roofline.prefill": roofline("matmul", "prefill"),
    "attention_roofline.prefill": roofline("attention", "prefill"),
    "attention_roofline.decode": roofline("attention", "decode"),
    "offsite_share.prefill": offsite("prefill"),
    "offsite_share.decode": offsite("decode"),
}
