"""Plain float32 forward of a latent-attention MoE decoder (DeepSeek-V2), the
yardstick for `correct`.

It covers what the configuration runs: token embedding (scaled by
sqrt(d_model)), pre-norm RMSNorm blocks; latent attention (MLA) in its
expanded form — queries through the low-rank q path with its RMSNorm, keys
and values expanded from the RMS-normed joint latent, a rope key shared by
the heads, rotary over adjacent pairs of the rope slice at YaRN's
frequencies (the cos/sin factor is 1: the published mscale and
mscale_all_dim are equal), softmax scale (qk_nope + qk_rope)^-0.5 times
mscale(factor, mscale_all_dim)^2; the leading dense layers' SiLU-gated
MLP; the MoE layers' float32 router over all ``n_experts``, top-k by
group-limited greedy choice, softmax gates
times ``routed_scaling_factor`` (or renormalised where
``norm_topk_prob``), the held experts ``expert_offset`` .. ``+
n_held_experts - 1`` each applied to every token and weighted by that
token's gate for it (routes to other experts add nothing), plus the
shared experts; a final norm and an untied output head.  Every matmul runs
in float32 at ``Precision.HIGHEST``.

The weights are made here from the seed, by the recipe the configuration
states (normal draws scaled by 1/sqrt(fan_in), rounded to bfloat16 but the
router, keys split as the model's initialiser splits them, each routed
expert from its own key), and never taken from the program under test.
The forward runs layer by layer, each layer's weights made just before
use.

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
EPS = 1e-6
BETA_FAST, BETA_SLOW = 32, 1     # the published rope_scaling's betas
TIE = 0.05          # router-score std devs within which a route is tied
SHARE = 0.95        # the quantile of the untied positions' gaps read


def _normal(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(shape[0])).astype(dtype)


def _held(run):
    return run["n_held_experts"] or run["n_experts"]


@functools.partial(jax.jit, static_argnames=("runkey", "dense", "dtype"))
def _layer_weights(key, runkey, dense, dtype):
    """One block's weights, keyed as the model's initialiser keys them:
    split(layer_key, 4) -> (mixer, mlp, ...); mixer split 8 -> (wkv_a,
    w_uk, w_uv, wo, wq_a, wq_b, ...); a dense MLP split 3 -> (wi, wg, wo);
    an MoE split 7 -> (router, ewi, ewg, ewo, shared wi, wg, wo), routed
    expert e of a stack from fold_in(stack key, e)."""
    run = dict(runkey)
    d, H, r, rq = (run["d_model"], run["n_heads"], run["kv_lora_rank"],
                   run["q_lora_rank"])
    dn, dr, dv = run["qk_nope_dim"], run["qk_rope_dim"], run["v_head_dim"]
    k_mix, k_mlp = jax.random.split(key, 4)[:2]
    km = jax.random.split(k_mix, 8)
    w = {"wkv_a": _normal(km[0], (d, r + dr), dtype),
         "w_uk": _normal(km[1], (r, H, dn), dtype),
         "w_uv": _normal(km[2], (r, H, dv), dtype),
         "wo": _normal(km[3], (H * dv, d), dtype),
         "wq_a": _normal(km[4], (d, rq), dtype),
         "wq_b": _normal(km[5], (rq, H * (dn + dr)), dtype)}
    if dense:
        kf = jax.random.split(k_mlp, 3)
        f = run["d_ff"]
        w.update(wi=_normal(kf[0], (d, f), dtype),
                 wg=_normal(kf[1], (d, f), dtype),
                 wd=_normal(kf[2], (f, d), dtype))
        return w
    ke = jax.random.split(k_mlp, 7)
    f = run["moe_d_ff"]
    fs = run["n_shared_experts"] * f
    ids = run["expert_offset"] + jnp.arange(_held(run))

    def experts(k, shape):
        return jax.vmap(lambda e: _normal(jax.random.fold_in(k, e), shape,
                                          dtype))(ids)

    w.update(router=_normal(ke[0], (d, run["n_experts"]), jnp.float32),
             ewi=experts(ke[1], (d, f)), ewg=experts(ke[2], (d, f)),
             ewo=experts(ke[3], (f, d)))
    if run["n_shared_experts"]:
        w.update(swi=_normal(ke[4], (d, fs), dtype),
                 swg=_normal(ke[5], (d, fs), dtype),
                 swo=_normal(ke[6], (fs, d), dtype))
    return w


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _table(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class Weights:
    """The configuration's weights from ``seed``, made on demand."""

    def __init__(self, run: dict, seed: int):
        self.run = run
        self.dtype = jnp.dtype(run["dtype"])
        self.runkey = tuple(sorted(run.items()))
        root = jax.random.split(jax.random.PRNGKey(seed), 8)
        self._k_embed, self._k_head = root[0], root[1]
        Ld = run["n_dense_layers"]
        # dense layers: their own stack (root[5]); then the period stack
        self._keys = (
            list(jax.random.split(jax.random.fold_in(root[5], 0), Ld))
            if Ld else []) + list(jax.random.split(
                jax.random.fold_in(root[3], 0), run["n_layers"] - Ld))

    def embed(self):
        V, d = self.run["vocab_size"], self.run["d_model"]
        return _table(self._k_embed, (V, d), d ** -0.5, self.dtype)

    def head(self):
        V, d = self.run["vocab_size"], self.run["d_model"]
        return _table(self._k_head, (V, d), 1.0 / math.sqrt(V), self.dtype)

    def dense(self, i: int) -> bool:
        return i < self.run["n_dense_layers"]

    def layer(self, i: int):
        return _layer_weights(self._keys[i], self.runkey, self.dense(i),
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


def _rms(x):
    return x * jax.lax.rsqrt((x ** 2).mean(-1, keepdims=True) + EPS)


def rope_freqs(run) -> np.ndarray:
    """Inverse frequencies of the rope slice's adjacent pairs; with YaRN,
    pair i keeps theta's frequency below the ramp [floor(c(beta_fast)),
    ceil(c(beta_slow))], is divided by the factor above it and blends
    linearly inside, c(r) = dim ln(original / (2 pi r)) / (2 ln theta)."""
    dim, theta = run["qk_rope_dim"], run["rope_theta"]
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    f = run.get("yarn_factor", 0.0)
    if not f:
        return base.astype(np.float32)
    orig = run["yarn_original_ctx"]

    def c(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(c(BETA_FAST)), 0)
    hi = min(math.ceil(c(BETA_SLOW)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (base / f * ramp + base * (1 - ramp)).astype(np.float32)


def _mscale(f, m):
    return 0.1 * m * math.log(f) + 1.0 if f > 1 else 1.0


def softmax_scale(run) -> float:
    s = (run["qk_nope_dim"] + run["qk_rope_dim"]) ** -0.5
    f, m = run.get("yarn_factor", 0.0), run.get("yarn_mscale", 0.0)
    return s * _mscale(f, m) ** 2 if f and m else s


def _rope(x, freqs):
    """x: (n, H, T, D) at positions 0..T-1, adjacent pairs rotated."""
    n, H, T, D = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(n, H, T, D)


def route(run, probs):
    """(T, E) probabilities -> (gate, experts), each (T, top_k)."""
    T, E = probs.shape
    G, Kg = run["n_group"], run["topk_group"]
    if G > 1:
        best = probs.reshape(T, G, E // G).max(-1)
        _, top = jax.lax.top_k(best, Kg)
        keep = (jnp.arange(G)[None, None, :] == top[:, :, None]).any(1)
        probs = jnp.where(jnp.repeat(keep, E // G, axis=1), probs, 0.0)
    gate, idx = jax.lax.top_k(probs, run["moe_top_k"])
    if run["norm_topk_prob"]:
        return gate / gate.sum(-1, keepdims=True), idx
    return gate * run["routed_scaling_factor"], idx


@functools.partial(jax.jit, static_argnames=("runkey", "dense", "precision"))
def _block(x, w, runkey, dense, precision):
    run = dict(runkey)
    mm = _mm(precision)
    n, T, d = x.shape
    H, r = run["n_heads"], run["kv_lora_rank"]
    dn, dr, dv = run["qk_nope_dim"], run["qk_rope_dim"], run["v_head_dim"]
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    freqs = jnp.asarray(rope_freqs(run))
    h = _rms(x)
    q = mm("ntd,de->nte", _rms(mm("ntd,dr->ntr", h, w["wq_a"])), w["wq_b"])
    q = q.reshape(n, T, H, dn + dr).transpose(0, 2, 1, 3)
    kv = mm("ntd,de->nte", h, w["wkv_a"])
    c = _rms(kv[..., :r])                                    # (n, T, r)
    k_rope = _rope(kv[..., None, r:].transpose(0, 2, 1, 3), freqs)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], freqs)], -1)
    k = jnp.concatenate([mm("ntr,rhd->nhtd", c, w["w_uk"]),
                         jnp.broadcast_to(k_rope, (n, H, T, dr))], -1)
    v = mm("ntr,rhd->nhtd", c, w["w_uv"])
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = softmax_scale(run)

    def attend(qkv):
        qi, ki, vi = qkv                        # (H, T, ·)
        s = mm("hqd,hkd->hqk", qi, ki) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm("hqk,hkd->hqd", p, vi)

    o = jax.lax.map(attend, (q, k, v))          # one request at a time
    o = o.transpose(0, 2, 1, 3).reshape(n, T, H * dv)
    x = x + mm("nte,ed->ntd", o, w["wo"])
    h = _rms(x)
    if dense:
        g = mm("ntd,df->ntf", h, w["wg"])
        u = mm("ntd,df->ntf", h, w["wi"])
        return x + mm("ntf,fd->ntd", jax.nn.silu(g) * u, w["wd"]), None
    ht = h.reshape(n * T, d)
    scores = mm("td,de->te", ht, w["router"])
    gate, idx = route(run, jax.nn.softmax(scores, axis=-1))
    y = jnp.zeros_like(ht)
    for j in range(_held(run)):
        e = run["expert_offset"] + j
        weight = jnp.where(idx == e, gate, 0.0).sum(-1)       # (T,)
        a = jax.nn.silu(mm("td,df->tf", ht, w["ewg"][j])) \
            * mm("td,df->tf", ht, w["ewi"][j])
        y = y + weight[:, None] * mm("tf,fd->td", a, w["ewo"][j])
    if run["n_shared_experts"]:
        a = jax.nn.silu(mm("td,df->tf", ht, w["swg"])) \
            * mm("td,df->tf", ht, w["swi"])
        y = y + mm("tf,fd->td", a, w["swo"])
    return x + y.reshape(n, T, d), scores.reshape(n, T, -1)


@functools.partial(jax.jit, static_argnames=("precision",))
def _logits(x, head, precision):
    return _mm(precision)("npd,vd->npv", _rms(x), head.astype(jnp.float32))


def held_route_margin(run, scores) -> np.ndarray:
    """(..., E) router scores -> (...,) the least change, in units of that
    token's router-score standard deviation, that can change which held
    experts its routes reach: a route is tied where a band narrower than
    this margin holds every score.

    Held experts are out of reach while every group holding one stays below
    the worst chosen group by more than the band; otherwise the held routes
    stand while the chosen groups stand (their worst above the best other
    by the band) and every held expert of them lies the band away from the
    top-k boundary (the ``moe_top_k``-th and the next-best score of the
    chosen groups).  A change among experts held elsewhere changes nothing
    here."""
    s = np.asarray(scores, np.float64)
    s = s / s.std(-1, keepdims=True)
    E, K = run["n_experts"], run["moe_top_k"]
    G, Kg = run["n_group"], run["topk_group"]
    e = np.arange(E)
    held = (e >= run["expert_offset"]) & (e < run["expert_offset"]
                                          + _held(run))
    gs = s.reshape(*s.shape[:-1], G, E // G).max(-1)        # (..., G)
    held_group = held.reshape(G, E // G).any(-1)
    if Kg < G:
        top = -np.sort(-gs, axis=-1)
        g_in = top[..., Kg - 1:Kg]
        groups = top[..., Kg - 1] - top[..., Kg]
        out = np.where(held_group, g_in - gs, np.inf).min(-1)
    else:
        g_in = np.full_like(gs[..., :1], -np.inf)
        groups = np.full(gs.shape[:-1], np.inf)
        out = np.where(held_group.any(), -np.inf, np.inf)
    pool = np.repeat(gs >= g_in, E // G, axis=-1)           # (..., E)
    ps = -np.sort(-np.where(pool, s, -np.inf), axis=-1)
    s_k, s_k1 = ps[..., K - 1:K], ps[..., K:K + 1]
    dist = np.where(s >= s_k, s - s_k1, s_k - s)
    experts = np.where(held & pool, dist, np.inf).min(-1)
    return np.maximum(out, np.minimum(groups, experts))


class Logits(np.ndarray):
    """Reference logits (n, P, V) that carry ``margins`` (n, P, MoE
    layers): each scored position's ``held_route_margin`` at each MoE layer
    of the float32 forward."""

    def __new__(cls, logits, margins):
        obj = np.asarray(logits).view(cls)
        obj.margins = margins
        return obj

    def __array_finalize__(self, obj):
        self.margins = getattr(obj, "margins", None)


def forward_logits(run: dict, weights: Weights, tokens: np.ndarray,
                   positions: np.ndarray, precisions=("f32",),
                   rows: int = 4) -> dict:
    """Logits at ``positions`` of every row of ``tokens`` (n, T), for each
    precision in ``precisions``; returns {precision: (n, len(pos), V)},
    the float32 logits as ``Logits`` with the float32 forward's held-route
    margins at each MoE layer.

    Rows go through the stack ``rows`` at a time, and each layer's weights
    are made once for all rows."""
    runkey = weights.runkey
    tokens = np.asarray(tokens, np.int32)
    n = tokens.shape[0]
    pos = np.asarray(positions, np.int32)
    margins = []
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
                new, m = [], []
                for x in xs[p]:
                    x, scores = _block(x, w, runkey, weights.dense(li), p)
                    new.append(x)
                    if p == "f32" and scores is not None:
                        m.append(held_route_margin(
                            run, np.asarray(scores[:, pos])))
                xs[p] = new
                if m:
                    margins.append(np.concatenate(m))
            del w
        head = weights.head()
        out = {p: np.concatenate([np.asarray(_logits(x[:, pos], head, p))
                                  for x in xs[p]])
               for p in precisions}
    if "f32" in out:
        out["f32"] = Logits(out["f32"], np.stack(
            margins, -1) if margins else np.full((n, len(pos), 0), np.inf))
    return out


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best, in units of that position's logit standard deviation.
    ref_logits: (n, P, V); tokens: (n, P) -> (n, P).

    Where ``ref_logits`` carries held-route margins, a position whose
    routing is tied at some MoE layer (margin below ``TIE``) reads 0: its
    best token is not defined to the configuration's precision.  Random
    MoE weights amplify a bfloat16 rounding layer by layer (about 1.3x a
    layer), so now and then an untied position's route changes deep in
    the stack too; the other untied positions read their gap capped at
    the ``SHARE`` quantile of the untied gaps, which the widest then
    reads.  A fault that moves one position in twenty or more still
    shows; a float8 forward moves about one in four."""
    margins = getattr(ref_logits, "margins", None)
    ref = np.asarray(ref_logits, np.float64)
    picked = np.take_along_axis(ref, np.asarray(tokens)[..., None], -1)[..., 0]
    g = (ref.max(-1) - picked) / ref.std(-1)
    if margins is None:
        return g
    untied = ~(margins < TIE).any(-1)
    if not untied.any():
        return g
    cap = np.quantile(g[untied], SHARE, method="higher")
    return np.where(untied, np.minimum(g, cap), 0.0)


def served_inputs(prompts: np.ndarray, served: np.ndarray):
    """The reference's input rows and scored positions for requests that
    were served ``served`` (n, G) greedy tokens after ``prompts`` (n, P):
    the prompt plus all but the last served token, scored at the positions
    that predict each served token."""
    P, G = prompts.shape[1], served.shape[1]
    tokens = np.concatenate([prompts, served[:, :G - 1]], axis=1)
    return tokens, np.arange(P - 1, P + G - 1)
