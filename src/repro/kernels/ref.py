"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.dot(x.astype(jnp.float32),
                   w.astype(jnp.float32)).astype(x.dtype)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
                  scale: float) -> jax.Array:
    """q: (B,H,Sq,D); k,v: (B,H,Skv,D) (heads already expanded)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        Sq, Skv = q.shape[2], k.shape[2]
        mask = (jnp.arange(Skv)[None, :] <= jnp.arange(Sq)[:, None]
                + (Skv - Sq))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, layer: int, pos: int, *,
                         scale: float) -> jax.Array:
    """q: (B,Hq,D); k_cache, v_cache: (L,B,Hkv,D,ctx).  Positions 0..pos of
    layer ``layer``, in float32."""
    B, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    k = k_cache[layer, ..., :pos + 1].astype(jnp.float32)
    v = v_cache[layer, ..., :pos + 1].astype(jnp.float32)
    qg = q.reshape(B, Hkv, Hq // Hkv, D).astype(jnp.float32)
    p = jax.nn.softmax(jnp.einsum("bhgd,bhdk->bhgk", qg, k) * scale, axis=-1)
    return jnp.einsum("bhgk,bhdk->bhgd", p, v).reshape(B, Hq, D)


def chunk_scan_ref(x: jax.Array, Bm: jax.Array, Cm: jax.Array,
                   la: jax.Array) -> jax.Array:
    """Sequential oracle for the SSD scan.  x (G,S,P); Bm/Cm (G,S,N);
    la (G,S)."""
    G, S, P = x.shape
    N = Bm.shape[-1]

    def step(state, inp):
        xt, bt, ct, lat = inp                    # (G,P),(G,N),(G,N),(G,)
        state = (state * jnp.exp(lat)[:, None, None]
                 + xt[:, :, None] * bt[:, None, :])
        y = jnp.einsum("gpn,gn->gp", state, ct)
        return state, y

    init = jnp.zeros((G, P, N), jnp.float32)
    _, ys = jax.lax.scan(
        step, init,
        (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
         jnp.moveaxis(Bm, 1, 0).astype(jnp.float32),
         jnp.moveaxis(Cm, 1, 0).astype(jnp.float32),
         jnp.moveaxis(la, 1, 0).astype(jnp.float32)))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)
