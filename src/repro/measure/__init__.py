"""``repro.measure`` — the hardware measurement subsystem.

Closes the paper's loop: the reward signal becomes *measured execution
time* of the compiled Pallas kernels (eq. 2) instead of the analytic
stand-in.  Layered bottom-up:

* :mod:`repro.measure.timing` — the one median-of-reps timing loop every
  consumer shares (runner + benchmarks).
* :mod:`repro.measure.runner` — :class:`MeasureRunner`, the batched
  compile-and-time primitive (compiled kernels on TPU, interpret-mode
  Pallas on CPU so CI runs the full loop; per-tile failures fail closed).
* :mod:`repro.measure.db` — :class:`MeasureDB`, the persistent JSONL
  timing store (repeat autotune runs re-time nothing).
* :mod:`repro.measure.transport` / :mod:`repro.measure.pool` — *how*
  measurements execute, behind the asynchronous
  :class:`~repro.core.protocols.MeasureTransport` contract:
  :class:`InProcessTransport` (the single-process path) and
  :class:`WorkerPoolTransport` (fan out to N subprocess workers over a
  length-prefixed JSON pipe protocol, coalescing duplicates, requeuing on
  worker death).  :class:`TransportMeasureFn` adapts any transport into
  the synchronous batched ``measure_fn`` hook the oracle consumes;
  :class:`CachedMeasureFn` keeps the historical runner+DB spelling.

:func:`make_transport` builds a transport by name;
:func:`make_measured_env` assembles a stack into a ready
:class:`~repro.core.env.MeasuredEnv` — what
``NeuroVectorizer(cfg, oracle="measured", transport=...)`` constructs.

Reliability (PR 6): :mod:`repro.measure.faults` supplies deterministic
chaos machinery (:class:`FaultInjectionTransport`, :class:`ChaosRunner`,
:class:`FaultSchedule`) used to prove the transport contract under
crashes/hangs/torn frames; :func:`respawn_backoff` is the pool's
crash-loop backoff schedule.
"""
from __future__ import annotations

from typing import Optional, Union

from repro.measure.db import MeasureDB, make_key, open_measure_db
from repro.measure.faults import (ChaosRunner, FaultInjectionTransport,
                                  FaultSchedule)
from repro.measure.pool import WorkerPoolTransport, respawn_backoff
from repro.measure.runner import (MeasureRunner, default_interpret,
                                  device_kind)
from repro.measure.transport import (CachedMeasureFn, InProcessTransport,
                                     TransportMeasureFn)
from repro.measure import timing

TRANSPORT_NAMES = ("inproc", "pool", "socket")

__all__ = ["MeasureRunner", "MeasureDB", "CachedMeasureFn", "make_key",
           "open_measure_db",
           "InProcessTransport", "WorkerPoolTransport", "TransportMeasureFn",
           "TRANSPORT_NAMES", "make_transport", "make_measured_env",
           "resolve_surrogate",
           "default_interpret", "device_kind", "timing",
           "FaultInjectionTransport", "ChaosRunner", "FaultSchedule",
           "respawn_backoff"]


def make_transport(name: str = "inproc", *, db_path: Optional[str] = None,
                   db: Optional[MeasureDB] = None,
                   runner: Optional[MeasureRunner] = None,
                   workers: Optional[int] = None,
                   hosts=None, **runner_kwargs):
    """Build a :class:`~repro.core.protocols.MeasureTransport` by name.

    ``"inproc"`` — the calling process measures (``workers`` must be
    unset); ``"pool"`` — ``workers`` subprocess workers (default 2), each
    building its own :class:`MeasureRunner` from ``runner_kwargs``;
    ``"socket"`` — a :class:`~repro.fleet.transport.SocketTransport`
    fanning out to the remote ``serve-worker`` daemons named by
    ``hosts=["host:port", ...]`` (runner configuration lives on those
    hosts, not here).  ``db_path``/``db`` attach the persistent timing
    store either way — ``db_path="fleet://host:port"`` attaches the
    shared artifact service.
    """
    if db is not None and db_path is not None:
        raise TypeError("pass either db= or db_path=, not both")
    if db is None and db_path:
        db = open_measure_db(db_path)
    if hosts is not None and name != "socket":
        raise ValueError("hosts= applies only to transport='socket'")
    if name == "inproc":
        if workers is not None:
            raise ValueError("workers= applies only to transport='pool'")
        if runner is None:
            runner = MeasureRunner(**runner_kwargs)
        elif runner_kwargs:
            raise TypeError("pass either runner= or runner kwargs, not both")
        return InProcessTransport(runner, db)
    if name == "pool":
        if runner is not None:
            raise TypeError("transport='pool' builds one runner per worker "
                            "from runner kwargs; runner= cannot be shared "
                            "across processes")
        return WorkerPoolTransport(
            workers=workers if workers is not None else 2,
            db=db, runner_kwargs=runner_kwargs)
    if name == "socket":
        if not hosts:
            raise ValueError("transport='socket' needs hosts=['host:port', "
                             "...] naming the serve-worker daemons")
        if workers is not None:
            raise ValueError("workers= applies only to transport='pool' "
                             "(each serve-worker host sets its own pool "
                             "size)")
        if runner is not None or runner_kwargs:
            raise TypeError("transport='socket' measures on the "
                            "serve-worker hosts — runner configuration "
                            "(runner=, reps=, interpret=, ...) belongs "
                            "there, not on the client")
        from repro.fleet import SocketTransport
        return SocketTransport(hosts, db=db)
    raise ValueError(f"unknown transport {name!r}; "
                     f"registered: {', '.join(TRANSPORT_NAMES)}")


def make_measured_env(cfg=None, db_path: Optional[str] = None,
                      runner: Optional[MeasureRunner] = None,
                      seed: int = 0, transport: Union[str, object, None] = None,
                      workers: Optional[int] = None, hosts=None,
                      prune_topk: Optional[int] = None,
                      surrogate=None, **runner_kwargs):
    """A :class:`~repro.core.env.MeasuredEnv` wired to a real measurement
    stack.

    ``db_path`` enables the persistent timing DB (a second run against the
    same path performs zero timings; a ``fleet://host:port`` path
    attaches the shared artifact service); ``transport`` selects how
    timings execute — ``None``/``"inproc"`` (this process), ``"pool"``
    with ``workers=N`` (subprocess pool), ``"socket"`` with
    ``hosts=["host:port", ...]`` (remote serve-worker fleet), or a
    pre-built :class:`~repro.core.protocols.MeasureTransport`.  Extra
    kwargs
    construct the :class:`MeasureRunner` (``reps=``, ``warmup=``,
    ``interpret=``, ``max_dim=``...) — per worker under the pool.  The
    assembled hook is reachable as ``env.measure_fn``
    (``.transport`` / ``.db`` for stats and lifecycle; ``.runner`` on the
    in-process path).

    ``prune_topk=N`` enables surrogate grid pruning: only each site's
    top-N predicted candidates (plus the baseline tile) are submitted to
    the transport.  ``surrogate`` may be a trained
    :class:`~repro.surrogate.model.SurrogateModel`, a checkpoint
    directory path, or ``None`` — in which case one is trained from the
    attached DB's existing records; a DB too cold to train (fewer than
    ``repro.surrogate.model.train_from_db``'s ``min_pairs``) leaves
    pruning inactive for this run.
    """
    from repro.configs.neurovec import DEFAULT
    from repro.core.env import MeasuredEnv

    if transport is None or isinstance(transport, str):
        t = make_transport(transport or "inproc", db_path=db_path,
                           runner=runner, workers=workers, hosts=hosts,
                           **runner_kwargs)
    else:
        if db_path is not None or runner is not None or workers is not None \
                or hosts is not None or runner_kwargs:
            raise TypeError("a pre-built transport carries its own "
                            "runner/db/workers/hosts — drop the extra "
                            "arguments")
        t = transport
    fn = (CachedMeasureFn(t) if isinstance(t, InProcessTransport)
          else TransportMeasureFn(t))
    if prune_topk is not None:
        surrogate = resolve_surrogate(surrogate,
                                      db=getattr(t, "db", None))
    return MeasuredEnv(cfg if cfg is not None else DEFAULT,
                       measure_fn=fn, seed=seed,
                       prune_topk=prune_topk, surrogate=surrogate)


def resolve_surrogate(surrogate, db=None):
    """Normalize the facade/service ``surrogate=`` argument: a trained
    model passes through, a string loads a checkpoint directory, and
    ``None`` trains from ``db`` (``None`` again when the DB is too cold
    — pruning simply stays inactive)."""
    if surrogate is None:
        from repro.surrogate.model import train_from_db
        return train_from_db(db)
    if isinstance(surrogate, str):
        from repro.surrogate.model import load_surrogate
        return load_surrogate(surrogate)
    return surrogate
