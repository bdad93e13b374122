"""One run of one cell: set-up, the measured window, the traced stretch and
the comparison with the reference that decides ``correct``.

The window drives the system's own serving path: ``serve.init_params``
makes the weights on the device from the seed, ``serve.serving_sites``
extracts the kernel sites, ``repro.api.NeuroVectorizer`` with the cell's
agent plans their tiles, ``api.inject`` routes the model through the tuned
Pallas kernels, and the jitted ``make_prefill_step`` / ``make_serve_step``
serve one static batch at a time in a closed loop.  Every step's tokens
are fetched to the host, as a streaming server does.

A decode window opens at a fixed point of the batch cycle, set by the
traffic's ``steps_left_at_open``: a lead-in batch that many steps from its
end, so that the timed steps straddle the end of one batch, the prefill of
the next and its first steps, and their mean context is the traffic's.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import json
import math
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from harness import spec, trace, work
from harness.spec import BENCH

AGENTS = BENCH / ".cache" / "agents"
TRACE_STEPS = {"prefill": 3, "decode": 16}
FAULTS = ("token", "state", "half")


def log(*a):
    print("[bench]", *a, file=sys.stderr, flush=True)


def p95(xs) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


@dataclass
class Batch:
    """One static batch of requests.  A lead-in batch (``prompts`` None)
    stands for requests that were ``skipped`` tokens into their answers
    before the run: it starts the window at a fixed point of the cycle and
    is never compared (its context is not a real request's)."""
    prompts: np.ndarray | None              # (B, P) host token ids
    served: list = field(default_factory=list)   # per step, (B,) tokens
    skipped: int = 0

    @property
    def done(self) -> int:
        return self.skipped + len(self.served)

    def tokens(self) -> np.ndarray:
        return np.stack(self.served, axis=1)       # (B, G)


class Run:
    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 t_process: float, faults=(), interpret: bool = False,
                 agents=AGENTS):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.agents = agents
        self.t_process = t_process
        unknown = set(faults) - set(FAULTS)
        if unknown:
            raise ValueError(f"unknown faults {sorted(unknown)}; known: "
                             f"{FAULTS}")
        self.faults, self.interpret = tuple(faults), interpret
        tr = cell.traffic
        self.kind = tr["kind"]
        self.B, self.P, self.G = tr["batch"], tr["prompt_len"], tr["gen"]
        self.V = cell.run["vocab_size"]
        self._traffic = np.random.default_rng([seed, 1])
        self.compiles_in_window = 0
        self.finished, self.lead_ins = [], []
        self._in_window = False

    # -- set-up ------------------------------------------------------------
    def model_config(self):
        from repro.configs import get_config

        run = self.cell.run
        base = get_config(run["arch"])
        fields = {f.name for f in dataclasses.fields(base)}
        over = {k: v for k, v in run.items() if k in fields}
        return dataclasses.replace(base, **over)

    def next_prompts(self) -> np.ndarray:
        return self._traffic.integers(0, self.V, (self.B, self.P),
                                      dtype=np.int32)

    def plan(self, sites):
        """The cell's agent, fit once per checkout and cell and saved with
        ``api.save_agent``, plans every site; returns (program, plan_s)."""
        from repro import api

        agent = self.cell.config["agent"]
        keys = sorted(s.key() for s in sites)
        digest = hashlib.sha256(json.dumps(
            [agent, keys]).encode()).hexdigest()[:12]
        ckpt = self.agents / f"{self.cell.name}-{digest}"
        nv = api.NeuroVectorizer(agent=agent["name"], seed=agent["seed"],
                                 metrics=False)
        try:
            if (ckpt / "manifest.json").exists():
                api.load_agent(str(ckpt), agent=nv.agent)
                log(f"agent loaded from {ckpt}")
            else:
                t = time.perf_counter()
                nv.fit(sites, total_steps=agent["total_steps"])
                fit_s = time.perf_counter() - t
                self.agents.mkdir(parents=True, exist_ok=True)
                api.save_agent(nv.agent, str(ckpt))
                print(f"[bench] agent fit: {agent['name']} "
                      f"{agent['total_steps']} steps in {fit_s:.3f} s, "
                      f"saved to {ckpt}", flush=True)
            t = time.perf_counter()
            prog = nv.tune_sites(sites)
            plan_s = time.perf_counter() - t
        finally:
            nv.close()
        return prog, plan_s

    def _steps(self, model, program):
        """The jitted prefill (first tokens) and decode steps, traced under
        ``program``'s tiles; the caches are donated."""
        import jax
        import jax.numpy as jnp
        from repro import api
        from repro.train.steps import make_prefill_step, make_serve_step

        prefill_step = make_prefill_step(model)
        serve_step = make_serve_step(model)
        faults, B, V = self.faults, self.B, self.V

        def broken(tok):
            if "token" in faults:
                tok = (tok + 1) % V
            if "half" in faults:
                tok = jnp.concatenate([tok[:B // 2], tok[:B - B // 2]])
            return tok

        def prefill(params, tokens, cache):
            logits, cache = prefill_step(params, {"tokens": tokens}, cache)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            return broken(tok), cache

        def decode(params, tok, pos, cache):
            nxt, _, new = serve_step(params, tok, pos, cache)
            return broken(nxt), (cache if "state" in faults else new)

        return (jax.jit(prefill, donate_argnums=(2,)),
                jax.jit(decode, donate_argnums=(3,)))

    def planned(self, program=None):
        """A fresh ``api.inject`` context for the plan (or ``program``)."""
        from repro import api

        return api.inject(program or self.program, interpret=self.interpret)

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro import api
        from repro.launch import serve
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models.lm import build_model

        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event)
        cfg = self.model_config()
        self.model = model = build_model(cfg)
        self.params = serve.init_params(model, self.seed)
        ctx = self.P + self.G if self.kind == "decode" else self.P
        make_cache = jax.jit(
            lambda: model.make_cache(self.B, ctx, jnp.dtype(cfg.dtype)))
        batch = {"tokens": jax.ShapeDtypeStruct((self.B, self.P), jnp.int32)}
        cache_shape = jax.eval_shape(make_cache)
        self.sites = serve.serving_sites(model, self.params, batch,
                                         cache_shape)
        self.program, self.plan_s = self.plan(self.sites)
        self.plan_hash = hashlib.sha256(json.dumps(
            sorted(self.program.tiles.items())).encode()).hexdigest()[:16]
        print(f"[bench] plan {self.plan_hash}: {len(self.program.tiles)} "
              f"tiles over {len(self.sites)} sites, modelled speedup "
              f"{api.program_speedup(self.program, self.sites):.3f}x, "
              f"planned in {self.plan_s:.4f} s", flush=True)
        for key, tiles in sorted(self.program.tiles.items()):
            print(f"[bench]   {key} -> {tuple(tiles)}", flush=True)
        self.prefill, self.decode = self._steps(model, self.program)
        self.cache = make_cache()
        warm = np.random.default_rng([self.seed, 2]).integers(
            0, self.V, (self.B, self.P), dtype=np.int32)
        with self.planned():
            self.tok, self.cache = self.prefill(
                self.params, jax.device_put(warm), self.cache)
            np.asarray(self.tok)
            if self.kind == "decode":
                self.lead_in(self.cell.traffic["steps_left_at_open"])
                # compiles the decode step; the first timed step rewrites
                # the same position from the same inputs
                tok, self.cache = self.decode(self.params, self.tok,
                                              jnp.int32(self.next_pos()),
                                              self.cache)
                np.asarray(tok)
        self.setup_s = time.perf_counter() - self.t_process

    def _on_event(self, name, secs, **_):
        if self._in_window and "compile" in name:
            self.compiles_in_window += 1

    # -- the measured window --------------------------------------------------
    def window_prefill(self):
        import jax

        self.finished, self.ttft = [], []
        t_start = time.perf_counter()
        while True:
            prompts = self.next_prompts()
            t0 = time.perf_counter()
            tok, self.cache = self.prefill(self.params,
                                           jax.device_put(prompts),
                                           self.cache)
            tok = np.asarray(tok)
            t1 = time.perf_counter()
            b = Batch(prompts, [tok[:, 0]])
            self.finished.append(b)
            self.ttft.extend([t1 - t0] * self.B)
            if t1 - t_start >= self.seconds:
                break
        self.window_s = t1 - t_start
        self.attempted = self.B * len(self.finished)
        n_tok = self.B * self.P * len(self.finished)
        return {"prefill_tok_s": n_tok / self.window_s,
                "ttft_p95_ms": 1e3 * p95(self.ttft)}

    def lead_in(self, steps_left: int):
        """Put a lead-in batch in flight with ``steps_left`` decode steps to
        go, fed the last token on the device.  Its skipped positions hold
        whatever the cache held; each step costs what a real request's step
        at that position costs, and it is never compared."""
        self.batch = Batch(None, [np.asarray(self.tok)[:, 0]],
                           skipped=self.G - steps_left - 1)
        self.lead_ins.append(self.batch)

    def next_pos(self) -> int:
        """The position the next decode step writes."""
        return self.P + self.batch.done - 1

    def _decode_one(self):
        import jax.numpy as jnp

        self.tok, self.cache = self.decode(self.params, self.tok,
                                           jnp.int32(self.next_pos()),
                                           self.cache)
        self.batch.served.append(np.asarray(self.tok)[:, 0])

    def _batch_done(self):
        if self.batch.prompts is not None:
            self.finished.append(self.batch)

    def _next_batch(self):
        import jax

        self.batch = Batch(self.next_prompts())
        self.tok, self.cache = self.prefill(
            self.params, jax.device_put(self.batch.prompts), self.cache)
        self.batch.served.append(np.asarray(self.tok)[:, 0])

    def window_decode(self):
        self.finished, self.itl = [], []
        n_batches, generated = 1, 0
        t_start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            self._decode_one()
            t1 = time.perf_counter()
            self.itl.append(t1 - t0)
            generated += self.B
            if self.batch.done == self.G:
                self._batch_done()
                if t1 - t_start < self.seconds:
                    self._next_batch()
                    n_batches += 1
                    generated += self.B
                    t1 = time.perf_counter()
            if t1 - t_start >= self.seconds:
                break
        self.window_s = t1 - t_start
        self.attempted = self.B * n_batches
        self.window_steps = len(self.itl)
        return {"decode_tok_s": generated / self.window_s,
                "itl_p95_ms": 1e3 * p95(self.itl)}

    def finish_in_flight(self):
        """Past the window, untimed: run the batch in flight to its end
        (after a lead-in, a fresh one) so that at least one batch of real
        requests has finished for the comparison."""
        if self.kind != "decode" or self.finished:
            return
        n = 0
        while not self.finished:
            if self.batch.prompts is None or self.batch.done == self.G:
                self._next_batch()
            while self.batch.done < self.G:
                self._decode_one()
                n += 1
            self._batch_done()
        log(f"no batch finished inside the window; ran {n} decode steps "
            f"after it to finish one ({self.G} tokens per request)")

    # -- the traced stretch -------------------------------------------------
    def _traced_steps(self, prefill, decode, n):
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            for _ in range(n):
                with TraceAnnotation("bench.step"):
                    if self.kind == "prefill":
                        with TraceAnnotation("bench.input"):
                            x = jax.device_put(self.next_prompts())
                        with TraceAnnotation("bench.dispatch"):
                            tok, self.cache = prefill(self.params, x,
                                                      self.cache)
                    else:
                        with TraceAnnotation("bench.dispatch"):
                            tok, self.cache = decode(
                                self.params, self.tok,
                                jnp.int32(self.next_pos()), self.cache)
                        self.tok = tok
                    with TraceAnnotation("bench.fetch"):
                        tok = np.asarray(tok)
                    if self.kind == "decode":
                        self.batch.served.append(tok[:, 0])
            jax.profiler.stop_trace()
            return trace.summarize(trace.load(d), "bench.step")

    def traced_stretch(self):
        """Trace ``TRACE_STEPS`` steps under the plan, then the same number
        under every site's baseline tiles (compiled here, untraced)."""
        from repro import api

        n = TRACE_STEPS[self.kind]
        self.step_ops = []
        with self.planned():
            if self.kind == "decode":
                # the traced steps straddle the traffic's mean context
                self.lead_in(self.G // 2 + n // 2)
            p0 = self.next_pos() if self.kind == "decode" else 0
            self.plan_trace = self._traced_steps(self.prefill, self.decode, n)
        baseline = api.baseline_program(self.sites)
        base_prefill, base_decode = self._steps(self.model, baseline)
        with self.planned(baseline):
            if self.kind == "prefill":
                tok, self.cache = base_prefill(
                    self.params, self.next_prompts(), self.cache)
            else:
                import jax.numpy as jnp
                self.lead_in(self.G // 2 + n // 2 + 1)
                tok, self.cache = base_decode(self.params, self.tok,
                                              jnp.int32(self.next_pos()),
                                              self.cache)
            np.asarray(tok)
            self.base_trace = self._traced_steps(base_prefill, base_decode,
                                                 n)
        for i in range(n):
            self.step_ops.append(self.cell.ops.step_ops(
                self.cell.run, self.kind, self.B, self.P, pos=p0 + i))

    def reader_context(self, peaks: dict) -> dict:
        return {"phase": self.kind, "plan": self.plan_trace,
                "baseline": self.base_trace, "step_ops": self.step_ops,
                "itemsize": work.itemsize(self.cell.run), "peaks": peaks,
                "plan_s": self.plan_s}

    # -- correctness ----------------------------------------------------------
    def free(self):
        import jax

        for name in ("params", "cache", "tok", "prefill", "decode"):
            self.__dict__.pop(name, None)
        gc.collect()
        jax.clear_caches()

    def sample(self):
        """A seeded sample of the finished requests: (prompts, served)."""
        reqs = [(b.prompts[r], b.tokens()[r]) for b in self.finished
                for r in range(self.B)]
        rng = np.random.default_rng([self.seed, 3])
        n = min(self.cell.traffic["check_requests"], len(reqs))
        pick = sorted(rng.choice(len(reqs), size=n, replace=False))
        return (np.stack([reqs[i][0] for i in pick]),
                np.stack([reqs[i][1] for i in pick]))

    def readings(self, precisions=("f32",)) -> dict:
        """Gaps (requests, served tokens) of the program's served tokens
        against the float32 reference, and for each other precision the
        gaps of the tokens that precision would put first."""
        reference = self.cell.reference
        prompts, served = self.sample()
        t = time.perf_counter()
        tokens, positions = reference.served_inputs(prompts, served)
        logits = reference.forward_logits(
            self.cell.run, reference.Weights(self.cell.run, self.seed),
            tokens, positions, precisions=("f32",) + tuple(
                p for p in precisions if p != "f32"))
        ref = logits.pop("f32")
        out = {"program": reference.gaps(ref, served)}
        for p, lg in logits.items():
            out[p] = reference.gaps(ref, lg.argmax(-1))
        log(f"reference over {len(served)} requests, {served.size} served "
            f"tokens in {time.perf_counter() - t:.1f} s")
        return out

    def check(self, judge: str = "program") -> dict:
        """The widest gap of a served token below the reference's best,
        over a seeded sample of finished requests.  ``judge`` "fp8" puts
        the control in the program's place: the tokens that the float8
        forward of the reference puts first at the same positions."""
        limit = self.cell.limits["gap"]["limit"]
        if judge == "program" and self.failed():
            return {"gap_max": {"value": math.inf, "limit": limit},
                    "_ok": False, "_n_tokens": 0}
        g = self.readings(("f32", judge) if judge != "program"
                          else ("f32",))[judge]
        gap = float(g.max())
        return {"gap_max": {"value": gap, "limit": limit},
                "_ok": bool(math.isfinite(gap) and gap <= limit),
                "_n_tokens": int(g.size)}

    def failed(self) -> int:
        """Requests, finished or led in, that were served a token outside
        the vocabulary."""
        return sum(int(((b.tokens() < 0) | (b.tokens() >= self.V))
                       .any(axis=1).sum())
                   for b in self.finished + self.lead_ins)


def execute(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            t_process: float, device: dict, peaks: dict, faults=(),
            interpret: bool = False, memory_peak=None,
            agents=AGENTS) -> dict:
    """Run ``cell`` once; returns the result line's object.  ``faults``
    breaks the timed path on purpose (tests of the comparison):
    "token" alters every served token where it is produced, "state" makes
    the decode step return its cache unwritten, "half" serves the second
    half of each batch the first half's tokens."""
    run = Run(cell, seed, seconds, t_process, faults, interpret, agents)
    run.setup()
    log(f"set-up {run.setup_s:.3f} s; window of {seconds} s starts")
    run._in_window = True
    with run.planned():
        e2e = (run.window_prefill() if run.kind == "prefill"
               else run.window_decode())
        run._in_window = False
        run.finish_in_flight()
        if traced:
            run.traced_stretch()
    e2e["setup_s"] = run.setup_s
    if run.kind == "decode":
        slow = int(np.argmax(run.itl))
        log(f"window {run.window_s:.3f} s: {run.window_steps} decode steps, "
            f"{len(run.finished)} batches finished, median step "
            f"{1e3 * float(np.median(run.itl)):.3f} ms, slowest "
            f"{1e3 * run.itl[slow]:.3f} ms (step {slow}), steps summed "
            f"{float(np.sum(run.itl)):.3f} s")
    else:
        log(f"window {run.window_s:.3f} s: {len(run.finished)} batches, "
            f"median batch {1e3 * float(np.median(run.ttft)):.3f} ms")
    if run.compiles_in_window:
        log(f"{run.compiles_in_window} compile events inside the window")
    device = dict(device)
    device["memory_peak_bytes"] = memory_peak() if memory_peak else 0
    out = {"correct": False, "attempted": run.attempted,
           "failed": run.failed()}
    if traced:
        ctx = run.reader_context(peaks)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = run.plan_trace.busy_ns * 1e-9
        device["window_s"] = run.plan_trace.window_ns * 1e-9
        out["breakdown"] = {"device_ops": run.plan_trace.top_ops,
                            "idle_gaps": run.plan_trace.idle_gaps}
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    run.free()
    chk = run.check()
    out["correct"] = chk.pop("_ok")
    n_tok = chk.pop("_n_tokens")
    out["metrics"] = metrics
    out["device"] = device
    out["plan"] = run.plan_hash
    for name, c in chk.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    log(f"check compared {n_tok} served tokens; correct {out['correct']}")
    out["check"] = chk
    return out
