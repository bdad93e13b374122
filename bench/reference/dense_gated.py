"""Plain float32 forward of a dense decoder, the yardstick for `correct`.

It covers what both configurations run: token embedding (scaled by
sqrt(d_model)), pre-norm blocks (LayerNorm with bias, or RMSNorm), full or
grouped-query attention with rotary embedding over the whole head ("1d") or
over its first half ("2d", adjacent pairs), a SiLU-gated MLP, a final norm
and an untied output head.  Every matmul runs in float32 at
``Precision.HIGHEST``.

The weights are made here from the seed, by the recipe the configuration
states (normal draws scaled by 1/sqrt(fan_in), rounded to bfloat16, keys
split as the model's initialiser splits them), and never taken from the
program under test.  The forward runs layer by layer, each layer's weights
made just before use, so that a full-width model needs one layer in float32
at a time.

``precision="fp8"`` is the control: the same forward with every matmul
operand rounded to float8 e4m3 (per-tensor scale), the precision below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8 e4m3fn


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _layer_weights(key, shapes, dtype):
    """One block's weights, keyed as the model's initialiser keys them:
    split(layer_key, 4) -> (mixer, mlp, ...); mixer -> (q, k, v, o);
    mlp -> (up, gate, down)."""
    k_mix, k_mlp = jax.random.split(key, 4)[:2]
    km = jax.random.split(k_mix, 5)
    kf = jax.random.split(k_mlp, 3)
    w = {}
    for name, k in (("wq", km[0]), ("wk", km[1]), ("wv", km[2]),
                    ("wo", km[3]), ("wi", kf[0]), ("wg", kf[1]),
                    ("wd", kf[2])):
        shape = dict(shapes)[name]
        w[name] = _normal(k, shape, 1.0 / math.sqrt(shape[0]), dtype)
    return w


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _table(key, shape, scale, dtype):
    return _normal(key, shape, scale, dtype)


def _shapes(run):
    d, f = run["d_model"], run["d_ff"]
    hq = run["n_heads"] * run["head_dim"]
    hkv = run["n_kv_heads"] * run["head_dim"]
    return (("wq", (d, hq)), ("wk", (d, hkv)), ("wv", (d, hkv)),
            ("wo", (hq, d)), ("wi", (d, f)), ("wg", (d, f)), ("wd", (f, d)))


class Weights:
    """The configuration's weights from ``seed``, made on demand."""

    def __init__(self, run: dict, seed: int):
        self.run = run
        self.dtype = jnp.dtype(run["dtype"])
        root = jax.random.split(jax.random.PRNGKey(seed), 8)
        self._k_embed, self._k_head = root[0], root[1]
        self._layer_keys = jax.random.split(
            jax.random.fold_in(root[3], 0), run["n_layers"])

    def embed(self):
        V, d = self.run["vocab_size"], self.run["d_model"]
        return _table(self._k_embed, (V, d), d ** -0.5, self.dtype)

    def head(self):
        V, d = self.run["vocab_size"], self.run["d_model"]
        return _table(self._k_head, (V, d), 1.0 / math.sqrt(V), self.dtype)

    def layer(self, i: int):
        return _layer_weights(self._layer_keys[i], _shapes(self.run),
                              self.dtype)


def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(precision):
    if precision == "fp8":
        def mm(spec, a, b):
            return jnp.einsum(spec, _round_fp8(a), _round_fp8(b),
                              precision=HIGHEST)
    else:
        def mm(spec, a, b):
            return jnp.einsum(spec, a, b, precision=HIGHEST)
    return mm


def _norm(run, x):
    """Pre-norm with unit scale and zero bias, as the initialiser makes
    them (LayerNorm's bias is zero, both scales are one)."""
    eps = run["norm_eps"]
    if run["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)
    return x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + eps)


def _rope(x, theta, mode):
    """x: (n, H, T, D) at positions 0..T-1; rotates adjacent pairs over
    the whole head ("1d") or its first half ("2d")."""
    n, H, T, D = x.shape
    rd = D // 2 if mode == "2d" else D
    freqs = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :rd:2], x[..., 1:rd:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                    axis=-1).reshape(n, H, T, rd)
    return jnp.concatenate([rot, x[..., rd:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("runkey", "precision"))
def _block(x, w, runkey, precision):
    run = dict(runkey)
    mm = _mm(precision)
    n, T, d = x.shape
    H, Hkv, D = run["n_heads"], run["n_kv_heads"], run["head_dim"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _norm(run, x)

    def heads(y, nh):
        return y.reshape(n, T, nh, D).transpose(0, 2, 1, 3)

    q = _rope(heads(mm("ntd,de->nte", h, w["wq"]), H), run["rope_theta"],
              run["rope"])
    k = _rope(heads(mm("ntd,de->nte", h, w["wk"]), Hkv), run["rope_theta"],
              run["rope"])
    v = heads(mm("ntd,de->nte", h, w["wv"]), Hkv)
    k = jnp.repeat(k, H // Hkv, axis=1)       # query head i reads kv i // g
    v = jnp.repeat(v, H // Hkv, axis=1)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def attend(qkv):
        qi, ki, vi = qkv                        # (H, T, D)
        s = mm("hqd,hkd->hqk", qi, ki) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm("hqk,hkd->hqd", p, vi)

    o = jax.lax.map(attend, (q, k, v))          # one request at a time
    o = o.transpose(0, 2, 1, 3).reshape(n, T, H * D)
    x = x + mm("nte,ed->ntd", o, w["wo"])
    h = _norm(run, x)
    g = mm("ntd,df->ntf", h, w["wg"])
    u = mm("ntd,df->ntf", h, w["wi"])
    return x + mm("ntf,fd->ntd", jax.nn.silu(g) * u, w["wd"])


@functools.partial(jax.jit, static_argnames=("runkey", "precision"))
def _logits(x, head, runkey, precision):
    run = dict(runkey)
    return _mm(precision)("npd,vd->npv", _norm(run, x),
                          head.astype(jnp.float32))


def forward_logits(run: dict, weights: Weights, tokens: np.ndarray,
                   positions: np.ndarray, precisions=("f32",),
                   rows: int = 4) -> dict:
    """Logits at ``positions`` of every row of ``tokens`` (n, T), for each
    precision in ``precisions``; returns {precision: (n, len(pos), V)}.

    Rows go through the stack ``rows`` at a time, and each layer's weights
    are made once for all rows."""
    runkey = tuple(sorted(run.items()))
    tokens = np.asarray(tokens, np.int32)
    n = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        embed = weights.embed()
        scale = math.sqrt(run["d_model"])
        xs = {p: [jnp.take(embed, jnp.asarray(tokens[i:i + rows]), axis=0)
                  .astype(jnp.float32) * scale for i in range(0, n, rows)]
              for p in precisions}
        del embed
        for li in range(run["n_layers"]):
            w = weights.layer(li)
            for p in precisions:
                xs[p] = [_block(x, w, runkey, p) for x in xs[p]]
            del w
        head = weights.head()
        pos = jnp.asarray(np.asarray(positions, np.int32))
        out = {p: np.concatenate([np.asarray(_logits(x[:, pos], head, runkey,
                                                     p)) for x in xs[p]])
               for p in precisions}
    return out


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best, in units of that position's logit standard deviation.
    ref_logits: (n, P, V); tokens: (n, P) -> (n, P)."""
    ref = np.asarray(ref_logits, np.float64)
    picked = np.take_along_axis(ref, np.asarray(tokens)[..., None], -1)[..., 0]
    return (ref.max(-1) - picked) / ref.std(-1)


def served_inputs(prompts: np.ndarray, served: np.ndarray):
    """The reference's input rows and scored positions for requests that
    were served ``served`` (n, G) greedy tokens after ``prompts`` (n, P):
    the prompt plus all but the last served token, scored at the positions
    that predict each served token."""
    P, G = prompts.shape[1], served.shape[1]
    tokens = np.concatenate([prompts, served[:, :G - 1]], axis=1)
    return tokens, np.arange(P - 1, P + G - 1)
