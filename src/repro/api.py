"""``repro.api`` — the single public surface of the NeuroVectorizer
reproduction (paper Fig. 3/4: *end-to-end, code to vectorization*).

One facade drives the whole pipeline with interchangeable decision
methods behind the :class:`Agent` protocol and interchangeable reward
sources behind the :class:`Oracle` protocol::

    from repro.api import NeuroVectorizer

    nv = NeuroVectorizer(cfg, agent="ppo", lr=5e-4, seed=0)
    nv.fit(corpus_sites, total_steps=30_000)     # train vs the oracle
    prog = nv.tune(step_fn, abstract_args)       # extract -> act -> tiles
    print(nv.speedup(prog, sites))               # modelled speedup
    with nv.inject(prog):                        # tuned Pallas BlockSpecs
        step_fn(*real_args)

Swap ``agent="ppo"`` for any registry name (``dtree`` / ``nns`` /
``brute`` / ``random`` / ``polly`` / ``baseline``) and the rest of the
code does not change; swap the default cost-model oracle for
``oracle="measured"`` (or a hand-built :class:`MeasuredEnv`) and rewards
come from wall-clock timings of the compiled Pallas kernels instead of
the analytic model — same protocol, same facade::

    nv = NeuroVectorizer(cfg, agent="ppo", oracle="measured",
                         db_path="measure.jsonl",   # persistent timings
                         transport="pool", workers=4)   # N-worker pool

A fitted facade is a *deployable artifact* (PR 5): ``nv.save(dir)``
persists the config, the agent's trained state and the oracle/transport
recipe; ``NeuroVectorizer.load(dir)`` re-assembles it in a fresh process
with bit-identical tuning decisions.  ``program_store="tiles.jsonl"``
additionally memoizes finished :class:`TileProgram`s keyed by (site set,
agent state fingerprint, oracle backend), so tuning a previously-seen
site set is a lookup — zero agent inferences, zero oracle evaluations::

    nv = NeuroVectorizer.load("artifact/", program_store="programs.jsonl")
    prog = nv.tune_sites(sites)        # first call: inference + store put
    prog = nv.tune_sites(sites)        # same sites: pure lookup

For many concurrent tuning sessions over one shared worker pool (and one
shared program store), move up one altitude to
:class:`repro.service.TuningService`.

Import tiers — ``__all__`` below documents the *supported* surface:

* **facade + protocol tier** (use this): :class:`NeuroVectorizer`,
  :class:`Agent`/:class:`Oracle`/:class:`MeasureTransport`, the
  registries (``make_agent``/``make_measured_env``/``make_transport``),
  :class:`TileProgram` + ``inject``/``program_speedup``, the artifact
  layer (``save_agent``/``load_agent``/:class:`ProgramStore`) and the
  service tier (:class:`TuningService`).
* **legacy deep-import tier**: concrete agent classes and per-method
  helpers (``PPOAgent``, ``brute_force_labels``, ...) remain importable
  from here for existing callers, but new code should reach them through
  the registries; they are deliberately *not* in ``__all__`` any more.
  (The deprecated ``polly_action`` shim completed its removal cycle in
  PR 6 — use ``make_agent("polly", cfg)``.)
"""
from __future__ import annotations

import json
import os
import shutil
import time
from typing import Optional, Sequence, Union

from repro.artifacts import (ArtifactError, ProgramStore, agent_fingerprint,
                             load_agent, open_program_store, program_key,
                             save_agent, tune_through_store)
from repro.configs.neurovec import (DEFAULT, NeuroVecConfig, cfg_from_dict,
                                    cfg_to_dict)
from repro.core.agents import (AGENT_NAMES, BaselineHeuristicAgent,
                               BruteForceAgent, DecisionTreeAgent, NNSAgent,
                               PPOAgent, PollyAgent, RandomAgent,
                               brute_force_action, brute_force_costs,
                               brute_force_labels, default_embed_fn,
                               make_agent, n_evaluations)
from repro.core.env import (ActionSpace, CostModelEnv, MeasuredEnv,
                            set_strict_actions)
from repro.core.extractor import extract_arch_sites, extract_sites
from repro.core.protocols import (Agent, AsyncOracle, MeasureTransport,
                                  Oracle, resolve_health)
from repro.core.vectorizer import (TileProgram, baseline_program, inject,
                                   program_speedup, tune, tune_step_fn)
from repro.measure import (TRANSPORT_NAMES, CachedMeasureFn,
                           InProcessTransport, MeasureDB, MeasureRunner,
                           TransportMeasureFn, WorkerPoolTransport,
                           make_measured_env, make_transport,
                           resolve_surrogate)
from repro.obs import MetricsRegistry, ObsHandle, Tracer, get_registry
from repro.obs import resolve_obs as _resolve_obs
from repro.obs import to_chrome_trace
from repro.obs.instrument import (instrument_oracle_stack,
                                  instrument_program_store)
from repro.service import SessionHandle, TuningService
from repro.surrogate import (SurrogateModel, SurrogateOracle, load_surrogate,
                             save_surrogate, train_from_db)

__all__ = [
    # -- facade + protocol tier: the supported public surface ---------------
    "NeuroVectorizer",
    "Agent", "Oracle", "MeasureTransport", "AsyncOracle",
    "AGENT_NAMES", "make_agent", "default_embed_fn",
    "NeuroVecConfig", "DEFAULT", "ActionSpace",
    "CostModelEnv", "MeasuredEnv", "set_strict_actions",
    "make_measured_env", "make_transport", "TRANSPORT_NAMES",
    "TileProgram", "baseline_program", "inject", "program_speedup",
    "extract_sites", "extract_arch_sites",
    "TuningService", "SessionHandle",
    # learned cost model + measurement pruning (PR 7)
    "SurrogateModel", "SurrogateOracle", "train_from_db",
    "save_surrogate", "load_surrogate", "resolve_surrogate",
    # artifact layer (PR 5): checkpoints + warm-start program store
    "ArtifactError", "save_agent", "load_agent", "agent_fingerprint",
    "ProgramStore", "program_key",
    # observability substrate (PR 8): metrics registry + span tracing
    "MetricsRegistry", "get_registry", "Tracer", "to_chrome_trace",
    # NOTE: the legacy deep-import tier (concrete agent classes
    # PPOAgent/BruteForceAgent/..., brute_force_* helpers,
    # MeasureRunner/MeasureDB/CachedMeasureFn/InProcessTransport/
    # WorkerPoolTransport/TransportMeasureFn, tune/tune_step_fn) stays
    # importable from this module for existing callers but is no longer
    # part of the documented surface.
]


_FACADE_FORMAT = "neurovectorizer-facade"


class NeuroVectorizer:
    """The end-to-end facade: extract → fit → tune → inject.

    The reward source and its execution backend compose as a matrix —
    every cell speaks the same :class:`Oracle` protocol, so agents and
    the rest of the pipeline never branch on the choice:

    ==================  ======================  ===========================
    ``oracle=``         ``transport=``          rewards come from
    ==================  ======================  ===========================
    ``None`` / "model"  (must be unset)         the analytic cost model,
                                                ``CostModelEnv``
    ``"measured"``      ``None`` / "inproc"     wall-clock kernel timings
                                                in *this* process
    ``"measured"``      "pool", ``workers=N``   timings fanned out to N
                                                subprocess workers
                                                (``WorkerPoolTransport``)
    ``"measured"``      "socket", ``hosts=``    timings shipped to remote
                                                ``serve-worker`` hosts
                                                (``repro.fleet``
                                                ``SocketTransport``)
    ``"measured"``      a ``MeasureTransport``  timings through your
                                                transport (borrowed — the
                                                facade won't close it)
    ``"surrogate"``     (must be unset)         the learned cost model
                                                (``SurrogateOracle``) —
                                                trained from ``db_path``
                                                or loaded via
                                                ``surrogate=``
    an ``Oracle``       (must be unset)         your oracle, verbatim
    ==================  ======================  ===========================

    ``oracle="measured"`` additionally takes ``prune_topk=N`` +
    optionally ``surrogate=`` (a trained ``SurrogateModel``, a checkpoint
    dir, or ``None`` to train from the DB): the surrogate ranks each
    site's legal grid and only the top-N candidates are ever timed, the
    rest priced by the surrogate (``env.pruned_pairs`` counts the
    savings).

    Parameters
    ----------
    cfg:    the :class:`NeuroVecConfig` (action space, PPO and penalty
            hyperparameters).
    agent:  a registry name (``"ppo"``, ``"brute"``, ...) or an already
            constructed :class:`Agent`.  Extra ``agent_kwargs`` flow to
            ``make_agent`` (e.g. ``lr=``, ``mode=``, ``embed_fn=``).
    oracle: a row of the matrix above.  ``"measured"`` assembles
            :func:`repro.measure.make_measured_env` — compiled kernels
            timed on the TPU, interpret-mode Pallas elsewhere.
    transport: a column of the matrix above (``oracle="measured"`` only).
    workers: pool size for ``transport="pool"``.
    hosts:  ``serve-worker`` addresses (``["host:port", ...]``) for
            ``transport="socket"``.
    db_path: persistent timing-DB path for ``oracle="measured"``
            (repeat runs against the same path re-time nothing — under
            any transport).  A ``fleet://host:port`` path attaches the
            shared ``serve-artifacts`` timing store instead of a local
            file; the same scheme works for ``program_store=``.
    oracle_kwargs: extra :class:`repro.measure.MeasureRunner` options for
            ``oracle="measured"`` (``reps=``, ``warmup=``, ``max_dim=``,
            ``interpret=``...) — applied per worker under the pool.
    program_store: a :class:`ProgramStore` (borrowed) or a path (opened
            and owned by this facade): finished tile programs are
            memoized per (site set, agent state, oracle backend), so
            ``tune_sites`` on a previously-tuned site set is a pure
            lookup — zero agent inferences, zero oracle evaluations.
            ``agent_inferences`` / ``store_hits`` / ``store_misses``
            count what actually ran.

    A facade that built a measured oracle owns its transport: call
    :meth:`close` (or use the facade as a context manager) to release
    pool workers and the DB/store file handles.  A closed facade raises
    ``RuntimeError`` on further ``fit``/``tune`` calls rather than
    surfacing an opaque queue error from the released transport.  For
    many concurrent sessions over one shared pool, use
    :class:`repro.service.TuningService`.
    """

    def __init__(self, cfg: NeuroVecConfig = DEFAULT,
                 agent: Union[str, Agent] = "ppo",
                 oracle: Union[str, Oracle, None] = None, seed: int = 0,
                 db_path: Optional[str] = None,
                 oracle_kwargs: Optional[dict] = None,
                 transport: Union[str, MeasureTransport, None] = None,
                 workers: Optional[int] = None,
                 hosts=None,
                 program_store: Union[str, ProgramStore, None] = None,
                 prune_topk: Optional[int] = None,
                 surrogate: Union[str, SurrogateModel, None] = None,
                 metrics: Union[MetricsRegistry, bool, None] = None,
                 trace: Union[str, Tracer, None] = None,
                 **agent_kwargs):
        self.cfg = cfg
        self._owns_oracle = False
        self._closed = False
        # obs substrate (PR 8): metrics default to the shared process-wide
        # registry (metrics=False disables); tracing is off unless trace=
        # names a JSONL path (owned — closed with the facade) or passes a
        # repro.obs.Tracer (borrowed)
        self.registry, self.tracer, self._owns_tracer = \
            _resolve_obs(metrics, trace)
        if oracle == "measured":
            self.oracle: Oracle = make_measured_env(
                cfg, db_path=db_path, seed=seed, transport=transport,
                workers=workers, hosts=hosts, prune_topk=prune_topk,
                surrogate=surrogate, **(oracle_kwargs or {}))
            # a borrowed MeasureTransport instance is not ours to close
            self._owns_oracle = transport is None or isinstance(transport,
                                                                str)
        elif oracle == "surrogate":
            if oracle_kwargs or transport is not None or \
                    workers is not None or hosts is not None:
                raise ValueError("oracle_kwargs/transport/workers/hosts "
                                 "apply only to oracle='measured'")
            if prune_topk is not None:
                raise ValueError("prune_topk applies only to "
                                 "oracle='measured' (a surrogate oracle "
                                 "performs no measurements to prune)")
            model = resolve_surrogate(surrogate, db=db_path)
            if model is None:
                raise ValueError(
                    "oracle='surrogate' needs a trained model: pass "
                    "surrogate= (a SurrogateModel or checkpoint dir) or "
                    "db_path= pointing at a MeasureDB with enough finite "
                    "records to train from")
            self.oracle = SurrogateOracle(cfg, model, seed=seed)
        else:
            if db_path is not None or oracle_kwargs or \
                    transport is not None or workers is not None or \
                    hosts is not None:
                raise ValueError("db_path/oracle_kwargs/transport/workers/"
                                 "hosts apply only to oracle='measured'")
            if prune_topk is not None or surrogate is not None:
                raise ValueError("prune_topk/surrogate apply only to "
                                 "oracle='measured' or oracle='surrogate'")
            if oracle is None or oracle == "model":
                self.oracle = CostModelEnv(cfg, seed=seed)
            elif isinstance(oracle, str):
                raise ValueError(f"unknown oracle {oracle!r}: expected "
                                 f"'model', 'measured', or 'surrogate'")
            else:
                self.oracle = oracle
        self.agent: Agent = (make_agent(agent, cfg, seed=seed,
                                        **agent_kwargs)
                             if isinstance(agent, str) else agent)
        self._owns_store = isinstance(program_store, str)
        self.program_store: Optional[ProgramStore] = (
            open_program_store(program_store) if self._owns_store
            else program_store)
        # warm-start observability: how many sites actually went through
        # agent.act vs. were answered from the store
        self.agent_inferences = 0
        self.store_hits = 0
        self.store_misses = 0
        # the re-assembly recipe nv.save() persists (strings only; a
        # hand-built oracle/transport/agent is recorded as non-portable)
        self._spec = {
            "agent": agent if isinstance(agent, str) else None,
            "agent_kwargs": agent_kwargs if isinstance(agent, str) else {},
            "oracle": (oracle if isinstance(oracle, str) or oracle is None
                       else "custom"),
            "transport": (transport if isinstance(transport, str)
                          or transport is None else "custom"),
            "workers": workers, "db_path": db_path,
            "hosts": list(hosts) if hosts else None,
            "oracle_kwargs": dict(oracle_kwargs or {}), "seed": seed,
            "prune_topk": prune_topk,
            # a live SurrogateModel instance is not serializable; measured
            # facades retrain from the DB on load, surrogate facades
            # require an explicit surrogate= override
            "surrogate": (surrogate if isinstance(surrogate, str)
                          or surrogate is None else "custom"),
        }
        # wire the oracle stack (env counters, breaker gauge, transport,
        # DB, surrogate) and the program store into the registry, and open
        # the facade's root span — ended by close()
        self._obs = ObsHandle(self.registry)
        self._obs.adopt(instrument_oracle_stack(self.oracle, self.registry,
                                                self.tracer))
        self._obs.adopt(instrument_program_store(self.program_store,
                                                 self.registry))
        self._m_fit_s = self.registry.histogram(
            "facade_fit_seconds", "NeuroVectorizer.fit() latency")
        self._m_tune_s = self.registry.histogram(
            "facade_tune_seconds", "NeuroVectorizer.tune_sites() latency")
        self._span = self.tracer.begin("session", detached=True,
                                       kind="facade",
                                       agent=self.agent.name)

    # -- training ----------------------------------------------------------
    def fit(self, corpus_sites: Sequence, **fit_kwargs) -> "NeuroVectorizer":
        """Fit the agent against this facade's oracle (RL training, brute
        labelling, or a no-op for search-free methods).  Extra kwargs flow
        to the agent (e.g. ``total_steps=`` for ppo, ``labels=`` for
        nns/dtree)."""
        self._check_open("fit")
        corpus_sites = list(corpus_sites)
        t0 = time.monotonic()
        with self.tracer.span("fit", parent=self._span,
                              n_sites=len(corpus_sites)):
            self.agent.fit(corpus_sites, self.oracle, **fit_kwargs)
        self._m_fit_s.observe(time.monotonic() - t0)
        return self

    # -- tuning ------------------------------------------------------------
    def tune(self, step_fn, abstract_args: Sequence = ()) -> TileProgram:
        """Extract kernel sites from ``step_fn`` traced over
        ``abstract_args`` and tune them (greedy inference, paper §4.2)."""
        return self.tune_sites(extract_sites(step_fn, *abstract_args))

    def tune_sites(self, sites: Sequence) -> TileProgram:
        self._check_open("tune")
        sites = list(sites)
        t0 = time.monotonic()
        with self.tracer.span("tune", parent=self._span,
                              n_sites=len(sites)) as sp:
            prog, hit = tune_through_store(sites, self.agent,
                                           self.oracle.space,
                                           self.oracle, self.program_store)
            sp.set(store_hit=bool(hit))
        self._m_tune_s.observe(time.monotonic() - t0)
        if self.program_store is not None and sites:
            if hit:
                self.store_hits += 1
            else:
                self.store_misses += 1
        if not hit:
            self.agent_inferences += len(sites)
        return prog

    def tune_arch(self, arch: str, batch: int = 8,
                  seq: int = 2048) -> TileProgram:
        """Tune every site of one training step of a named architecture."""
        return self.tune_sites(extract_arch_sites(arch, batch=batch,
                                                  seq=seq))

    # -- deployment --------------------------------------------------------
    def inject(self, program: TileProgram, interpret: bool = False):
        """Context manager: run model code with the tuned tiles routed
        through the Pallas kernels (the pragma-injection analogue)."""
        return inject(program, interpret=interpret)

    def baseline(self, sites: Sequence) -> TileProgram:
        return baseline_program(list(sites))

    def speedup(self, program: TileProgram, sites: Sequence) -> float:
        """Aggregate speedup of ``program`` over the heuristic baseline,
        priced by this facade's oracle semantics."""
        return program_speedup(program, list(sites), env=self.oracle)

    def health(self) -> str:
        """``ok | degraded | down`` of this facade's reward path.

        ``degraded`` means tuning still completes but rewards come from
        the analytic cost model (the :class:`MeasuredEnv` circuit
        breaker opened, or the transport collapsed under an oracle that
        can fall back); the model-oracle facade is always ``ok``."""
        fn = getattr(self.oracle, "measure_fn", None)
        return resolve_health(self.oracle, getattr(fn, "transport", None))

    # -- persistence (PR 5) -------------------------------------------------
    def save(self, path: str) -> str:
        """Persist this facade as an artifact directory: the config, the
        agent's full trained state (``repro.artifacts`` format, atomic +
        fingerprinted) and the oracle/transport re-assembly recipe.
        Returns the agent-state fingerprint.

        A hand-built :class:`Oracle`/transport instance cannot be
        serialized — :meth:`load` will then require an explicit
        ``oracle=``/``transport=`` override."""
        spec = dict(self._spec)
        if spec["agent"] is None:
            # an agent passed as an instance: record its registry name so
            # load() can reconstruct it before restoring the state.  The
            # embedding-based methods are the exception — a hand-passed
            # embed_fn is a live callable outside state_dict(), and
            # reconstructing with the default embedder would *silently*
            # change act(); refuse rather than break the bitwise guarantee.
            if isinstance(self.agent, (NNSAgent, DecisionTreeAgent)):
                raise ArtifactError(
                    f"cannot record the construction of a hand-built "
                    f"{type(self.agent).__name__} (its embed_fn is a live "
                    f"callable) — construct via agent="
                    f"{self.agent.name!r} on the facade, or pass agent= "
                    f"to NeuroVectorizer.load()")
            spec["agent"] = self.agent.name
        payload = {"format": _FACADE_FORMAT, "version": 1,
                   "cfg": cfg_to_dict(self.cfg), **spec}
        try:
            blob = json.dumps(payload, indent=1)
        except TypeError as e:
            raise ArtifactError(
                f"facade spec is not serializable ({e}); agent_kwargs and "
                f"oracle_kwargs must be plain JSON values to save") from e
        path = str(path)
        tmp = path.rstrip(os.sep) + f".tmp-{os.getpid()}"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        fp = save_agent(self.agent, os.path.join(tmp, "agent"))
        with open(os.path.join(tmp, "facade.json"), "w") as f:
            f.write(blob)
        # manifest last: a partial directory is never restorable
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump({"format": _FACADE_FORMAT, "version": 1,
                       "agent": payload["agent"], "agent_fingerprint": fp,
                       "time": time.time()}, f, indent=1)
        # never destroy a valid artifact before its replacement has fully
        # landed: move the old directory aside, swing the new one in, then
        # drop the old — a crash mid-save leaves either the old or the new
        # artifact restorable at `path` (or the old one parked at .old-*)
        old = None
        if os.path.isdir(path):
            old = path.rstrip(os.sep) + f".old-{os.getpid()}"
            shutil.rmtree(old, ignore_errors=True)
            os.replace(path, old)
        os.replace(tmp, path)
        if old is not None:
            shutil.rmtree(old, ignore_errors=True)
        return fp

    @classmethod
    def load(cls, path: str,
             agent: Optional[Agent] = None,
             oracle: Union[str, Oracle, None] = None,
             transport: Union[str, MeasureTransport, None] = None,
             workers: Optional[int] = None, hosts=None,
             db_path: Optional[str] = None,
             program_store: Union[str, ProgramStore, None] = None,
             seed: Optional[int] = None,
             prune_topk: Optional[int] = None,
             surrogate: Union[str, SurrogateModel, None] = None,
             **agent_kwargs
             ) -> "NeuroVectorizer":
        """Re-assemble a facade saved by :meth:`save` in a (possibly
        fresh) process: config + agent construction + verified state
        restore + oracle/transport from the recorded recipe.  The loaded
        facade's ``tune_sites`` is bit-identical to the saver's.

        Keyword overrides replace the recorded recipe (e.g. point
        ``db_path`` at a local timing DB, or attach a shared
        ``program_store``); ``agent=`` supplies a pre-constructed agent
        to restore the state into (required when the saved agent cannot
        be rebuilt from the registry, e.g. nns/dtree with a custom
        ``embed_fn``), and ``oracle=``/``transport=`` are required when
        the original facade was built around hand-built instances."""
        path = str(path)
        mpath = os.path.join(path, "manifest.json")
        if not os.path.exists(mpath):
            raise ArtifactError(f"no restorable facade artifact at "
                                f"{path!r} (manifest.json missing)")
        with open(os.path.join(path, "facade.json")) as f:
            spec = json.load(f)
        if spec.get("format") != _FACADE_FORMAT:
            raise ArtifactError(f"{path!r} is not a facade artifact "
                                f"(format={spec.get('format')!r})")
        cfg = cfg_from_dict(spec["cfg"])
        if spec["oracle"] == "custom" and oracle is None:
            raise ArtifactError(
                "this artifact was saved around a hand-built Oracle, which "
                "cannot be re-assembled automatically — pass oracle= to "
                "load()")
        oracle = spec["oracle"] if oracle is None else oracle
        # pre-PR-7 artifacts carry no pruning fields; a recorded live
        # model ("custom") is not reloadable — measured facades retrain
        # from the DB, a surrogate facade needs an explicit override
        spec_sur = spec.get("surrogate")
        if surrogate is None and spec_sur != "custom":
            surrogate = spec_sur
        kw = {}
        if oracle == "measured":
            # the transport only matters once the resolved oracle needs
            # one — an oracle='model' override never reads it
            if spec["transport"] == "custom" and transport is None:
                raise ArtifactError(
                    "this artifact was saved around a hand-built "
                    "transport — pass transport= to load()")
            kw = {"transport": (spec["transport"] if transport is None
                                else transport),
                  "workers": spec["workers"] if workers is None else workers,
                  "hosts": spec.get("hosts") if hosts is None else hosts,
                  "db_path": spec["db_path"] if db_path is None else db_path,
                  "oracle_kwargs": spec["oracle_kwargs"] or None,
                  "prune_topk": (spec.get("prune_topk")
                                 if prune_topk is None else prune_topk),
                  "surrogate": surrogate}
        elif oracle == "surrogate":
            if spec_sur == "custom" and surrogate is None:
                raise ArtifactError(
                    "this artifact was saved around a live SurrogateModel "
                    "instance, which cannot be re-assembled automatically "
                    "— pass surrogate= (a model or checkpoint dir) to "
                    "load()")
            kw = {"db_path": spec["db_path"] if db_path is None else db_path,
                  "surrogate": surrogate}
        merged_kwargs = {**spec["agent_kwargs"], **agent_kwargs}
        nv = cls(cfg, agent=spec["agent"] if agent is None else agent,
                 oracle=oracle,
                 seed=spec["seed"] if seed is None else seed,
                 program_store=program_store,
                 **kw, **(merged_kwargs if agent is None else {}))
        load_agent(os.path.join(path, "agent"), agent=nv.agent)
        if isinstance(nv.agent, BruteForceAgent):
            # brute captures a live oracle at fit time; re-bind ours so a
            # loaded exhaustive search prices tiles with the same oracle
            nv.agent.oracle = nv.oracle
        return nv

    # -- lifecycle ---------------------------------------------------------
    def _check_open(self, verb: str) -> None:
        if self._closed:
            raise RuntimeError(
                f"cannot {verb}: this NeuroVectorizer is closed (its "
                f"transport/store handles were released) — build a new "
                f"facade or NeuroVectorizer.load() a saved one")

    def close(self) -> None:
        """Release the measured oracle's transport (pool workers, DB file
        handle) and an owned program store, and mark the facade closed:
        subsequent ``fit``/``tune`` calls raise a clear ``RuntimeError``
        instead of an opaque error from the released transport.
        Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._span.end()
        if self._owns_oracle:
            self.oracle.measure_fn.transport.close()
        if self._owns_store and self.program_store is not None:
            self.program_store.close()
        self._obs.close()
        if self._owns_tracer:
            self.tracer.close()

    def __enter__(self) -> "NeuroVectorizer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
