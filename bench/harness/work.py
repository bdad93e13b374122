"""The work a step needs, summed over its ops.  The ops of a step come from
the configuration's family (``bench/ops/<family>.py``); each op's
operations and bytes from the counter of its kind
(``bench/counters/<kind>.py``)."""
from __future__ import annotations

from harness.spec import counter


def totals(ops: list, itemsize: int) -> tuple:
    """(flops, bytes) summed over ``ops``."""
    fl = by = 0.0
    for op in ops:
        f_, b_ = counter(op["kind"]).count(op, itemsize)
        fl += op["mult"] * f_
        by += op["mult"] * b_
    return fl, by


def itemsize(run: dict) -> int:
    import jax.numpy as jnp

    return jnp.dtype(run["dtype"]).itemsize
