"""Shared arithmetic of the per-layer metric readers.

Each reader in ``bench/metrics/`` is one of these, bound to its phase.  A
reader gets the run's context (``harness.cell.Run.reader_context``) and
returns a number, or None when the run holds nothing for it to read; it
never returns 0 for a share it could not measure."""
from __future__ import annotations

from harness import work


def _traced(ctx, phase, which="plan"):
    if ctx.get("phase") != phase:
        return None
    s = ctx.get(which)
    if s is None or s.n_steps == 0 or s.window_ns <= 0 or s.busy_ns <= 0:
        return None
    return s


def idle(phase):
    """Share of the traced window in which no op ran on the device."""
    def read(ctx):
        s = _traced(ctx, phase)
        return None if s is None else 100.0 * (1.0 - s.busy_ns / s.window_ns)
    return read


def plan_gain(phase):
    """Device time of a step at the baseline tiles over the same step
    under the agent's plan (the paper's speedup, on the chip)."""
    def read(ctx):
        s, b = _traced(ctx, phase), _traced(ctx, phase, "baseline")
        if s is None or b is None:
            return None
        return (b.busy_ns / b.n_steps) / (s.busy_ns / s.n_steps)
    return read


def mfu(phase):
    """The traced steps' share of the chip's peak over their wall time.
    Prefill: the model FLOPs the steps need at bf16 peak.  Decode: per step
    the larger of FLOP time and byte time at peak, bytes being the weights
    read once and the keys and values up to each step's position."""
    def read(ctx):
        s = _traced(ctx, phase)
        if s is None:
            return None
        isz, peaks = ctx["itemsize"], ctx["peaks"]
        need = 0.0
        for ops in ctx["step_ops"]:
            fl, by = work.totals(ops, isz)
            t_fl = fl / peaks["bf16_flops_per_s"]
            need += t_fl if phase == "prefill" else \
                max(t_fl, by / peaks["hbm_bytes_per_s"])
        return 100.0 * need / (s.window_ns * 1e-9)
    return read


def plan_seconds(ctx):
    """Host seconds from the extracted sites to a finished plan."""
    return ctx.get("plan_s")
