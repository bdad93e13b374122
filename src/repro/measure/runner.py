"""Hardware measurement runner — turn ``(KernelSite, tiles)`` into seconds.

This is the real ``measure_fn`` for :class:`~repro.core.env.MeasuredEnv`
(paper eq. 2: the reward is *measured* execution time, not a model).  For
every pair it materializes inputs from the site's shapes/dtype, builds the
corresponding Pallas kernel from :mod:`repro.kernels` with the candidate
tile factors — the exact jitted wrappers deployment injects through — and
times it with warmup + ``block_until_ready`` + median-of-reps
(:mod:`repro.measure.timing`).

Backend selection is automatic: on TPU the kernels compile natively and
shapes are measured at full size; elsewhere Pallas runs in
``interpret=True`` mode so the complete measure→reward→train loop runs in
CI, with site dimensions capped (``max_dim``/``max_batch``) to keep the
interpreted grids tractable.  Interpret-mode timings are a *proxy* — they
scale with grid size and arithmetic volume, not MXU behaviour — which is
exactly enough to exercise every integration seam (measured-vs-model rank
agreement is tracked by ``benchmarks/bench_measure.py``).

Failure isolation is per pair: a tile whose kernel fails to build, compile
or run (VMEM overflow on hardware, shape-constraint violations, OOM)
yields ``inf`` — the same fail-closed marker the oracle maps to the
paper's compile-timeout penalty.  A failure never aborts the batch.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.measure import timing

_JNP_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
               "float16": jnp.float16}


def _ceil_mult(x: int, m: int) -> int:
    return -(-x // m) * m


def default_interpret() -> bool:
    """Compiled kernels on TPU (the ``pltpu`` kernels target only it),
    interpret-mode Pallas elsewhere."""
    return jax.default_backend() != "tpu"


def device_kind() -> str:
    """The first device's kind; raises when JAX has no device, so a
    timing is never filed under an unknown backend."""
    return jax.devices()[0].device_kind


class MeasureRunner:
    """Batched compile-and-time hook: ``runner(sites, tiles) -> (n,) s``.

    Parameters
    ----------
    reps, warmup: the timing loop (median of ``reps`` after ``warmup``
        discarded calls — the warmup also pays jit compilation).
    interpret:  force Pallas interpret mode; ``None`` auto-selects
        (compiled on TPU, interpreted elsewhere).
    max_dim, max_batch: per-dimension caps applied when interpreting
        (``None`` = auto: 128/2 interpreted, uncapped compiled).  Capped
        shapes are snapped to tile multiples, so every model-legal tile
        still builds and runs.
    seed:   input materialization seed.
    """

    def __init__(self, *, reps: int = 3, warmup: int = 1,
                 interpret: Optional[bool] = None,
                 max_dim: Optional[int] = None,
                 max_batch: Optional[int] = None, seed: int = 0):
        self.interpret = default_interpret() if interpret is None \
            else interpret
        self.max_dim = (128 if self.interpret else 0) if max_dim is None \
            else max_dim
        self.max_batch = (2 if self.interpret else 0) if max_batch is None \
            else max_batch
        self.reps = reps
        self.warmup = warmup
        self.seed = seed
        self.timed_pairs = 0            # successful timings performed
        self.failed_pairs = 0           # build/compile/run failures (-> inf)

    # -- identity ------------------------------------------------------------
    @property
    def backend_key(self) -> str:
        """Measurement-conditions fingerprint for the persistent DB key.

        Two timings are comparable only under the same backend, device,
        jax version and shape caps — anything else must miss the cache."""
        mode = (f"interpret(dim<={self.max_dim},b<={self.max_batch})"
                if self.interpret else "compiled")
        return f"{jax.default_backend()}:{device_kind()}:{mode}" \
               f":jax{jax.__version__}"

    # -- shape capping -------------------------------------------------------
    def _cap(self, v: int) -> int:
        return min(v, self.max_dim) if self.max_dim else v

    def _cap_b(self, v: int) -> int:
        return min(v, self.max_batch) if self.max_batch else v

    # -- per-kind kernel closures --------------------------------------------
    def _build(self, site, tiles):
        """Return a zero-arg callable running the site's Pallas kernel
        under the candidate tiles (inputs pre-materialized on device)."""
        from repro.kernels import ops
        key = jax.random.PRNGKey(self.seed)
        dt = _JNP_DTYPES.get(str(site.dtype), jnp.bfloat16)
        t = tuple(int(x) for x in tiles)
        interp = self.interpret

        if site.kind == "matmul":
            M, N, K = self._cap(site.m), self._cap(site.n), self._cap(site.k)
            x = jax.random.normal(key, (M, K), dt)
            w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), dt)
            return lambda: ops.matmul(x, w, tiles=t[:3], interpret=interp)

        if site.kind == "attention":
            # site semantics: m=Sq, k=Skv, n=D, batch=B*H
            H = self._cap_b(site.batch)
            D = self._cap(site.n)
            bq, bkv = max(t[0], 1), max(t[1], 1)
            # the kernel requires Sq % min(bq, Sq) == 0: snap capped
            # lengths up to the tile multiple so every model-legal tile
            # runs (a no-op for the pow2 shapes real models extract)
            Sq = _ceil_mult(self._cap(site.m), min(bq, self._cap(site.m)))
            Skv = _ceil_mult(self._cap(site.k), min(bkv, self._cap(site.k)))
            q = jax.random.normal(key, (1, H, Sq, D), dt)
            k = jax.random.normal(jax.random.fold_in(key, 1),
                                  (1, H, Skv, D), dt)
            v = jax.random.normal(jax.random.fold_in(key, 2),
                                  (1, H, Skv, D), dt)
            scale = 1.0 / math.sqrt(D)
            causal = site.causal
            return lambda: ops.flash_attention(
                q, k, v, causal=causal, scale=scale, tiles=t[:2],
                interpret=interp)

        if site.kind == "chunk_scan":
            # site semantics: m=configured chunk, n=P, k=N,
            # batch=#instances; total scanned tokens = batch * m
            P, N = self._cap(site.n), self._cap(site.k)
            S = self._cap(site.batch * site.m)
            Q = max(t[0], 1)
            S = _ceil_mult(S, min(Q, S))
            x = jax.random.normal(key, (1, S, P), dt)
            Bm = jax.random.normal(jax.random.fold_in(key, 1),
                                   (1, S, N), dt) * 0.3
            Cm = jax.random.normal(jax.random.fold_in(key, 2),
                                   (1, S, N), dt) * 0.3
            la = -jax.nn.softplus(jax.random.normal(
                jax.random.fold_in(key, 3), (1, S))).astype(dt)
            return lambda: ops.chunk_scan(x, Bm, Cm, la, chunk=Q,
                                          interpret=interp)

        raise ValueError(site.kind)

    # -- measurement ---------------------------------------------------------
    def measure_one(self, site, tiles) -> float:
        """Seconds for one (site, tile) pair; ``inf`` on any failure."""
        try:
            fn = self._build(site, tiles)
            s = timing.median_time(fn, reps=self.reps, warmup=self.warmup)
        except Exception:
            # fail closed: a kernel that cannot build/compile/run is the
            # compile-timeout analogue — inf maps to the oracle's penalty
            self.failed_pairs += 1
            return float("inf")
        self.timed_pairs += 1
        return s

    def __call__(self, sites: Sequence, tiles) -> np.ndarray:
        """The batched ``MeasuredEnv.measure_fn`` hook: ``(n,) seconds``."""
        tiles = np.asarray(tiles, np.int64)
        return np.array([self.measure_one(s, t)
                         for s, t in zip(sites, tiles)], np.float64)
