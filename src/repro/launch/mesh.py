"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set
``--xla_force_host_platform_device_count`` *before* first jax init.

Mesh axes are ``Auto``: ``jax.make_mesh`` defaults to ``Explicit`` axes,
under which a gather from the ``P("model", "data")``-sharded embedding
raises ``ShardingTypeError``.  The model code is written for GSPMD
propagation (``sharding_hints`` pin only the hot activations).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Mesh over every local device: ``(n / model_parallel, model_parallel)``
    on ``("data", "model")``."""
    n = len(jax.devices())
    if n % model_parallel:
        raise ValueError(f"--model-parallel {model_parallel} does not divide "
                         f"the {n} local devices")
    return _auto_mesh((n // model_parallel, model_parallel), ("data", "model"))
