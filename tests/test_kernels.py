"""Per-kernel allclose sweeps + hypothesis property tests vs ref.py oracles
(interpret mode executes the kernel bodies in Python on CPU).

Includes the action-space correctness sweeps: every *distinct effective*
tile the DEFAULT NeuroVec action grid can produce on a test shape (after
the kernels' internal clamping) is executed once against the pure-jnp
oracle — the guard for every tile the measurement runner
(``repro.measure``) will ever compile and time."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                                   # property-based when available ...
    from hypothesis import given, settings, strategies as st
except ImportError:                    # ... deterministic sweep on bare envs
    from _hypothesis_compat import given, settings, st

from repro.configs.neurovec import DEFAULT as NV
from repro.kernels import ops, ref
from repro.kernels.grouped_matmul import grouped_matmul_pallas
from repro.kernels.matmul import block


def _rel_err(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

MM_SHAPES = [(64, 128, 128), (128, 256, 512), (100, 300, 200), (8, 128, 64),
             (513, 129, 257), (16, 384, 48)]
MM_TILES = [(32, 128, 128), (64, 256, 128), (8, 128, 512)]


@pytest.mark.parametrize("shape", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes(shape, dtype):
    M, N, K = shape
    k1, k2 = jax.random.split(jax.random.PRNGKey(M + N + K))
    x = jax.random.normal(k1, (M, K), dtype)
    w = jax.random.normal(k2, (K, N), dtype)
    y = ops.matmul(x, w, tiles=(64, 128, 128), interpret=True)
    yr = ref.matmul_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert y.shape == (M, N)
    assert _rel_err(y.astype(jnp.float32), yr.astype(jnp.float32)) < tol


@pytest.mark.parametrize("tiles", MM_TILES)
def test_matmul_tile_invariance(tiles):
    """Property: the result must not depend on the tile choice."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (96, 160), jnp.float32)
    w = jax.random.normal(k2, (160, 192), jnp.float32)
    y0 = ops.matmul(x, w, tiles=(96, 192, 160), interpret=True)
    y1 = ops.matmul(x, w, tiles=tiles, interpret=True)
    assert _rel_err(y1, y0) < 1e-5


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 96), n=st.integers(1, 160), k=st.integers(1, 128),
       bm=st.sampled_from([8, 16, 32, 64]),
       bn=st.sampled_from([128, 256]),
       bk=st.sampled_from([128, 256]))
def test_matmul_property(m, n, k, bm, bn, bk):
    k1, k2 = jax.random.split(jax.random.PRNGKey(m * 7 + n * 3 + k))
    x = jax.random.normal(k1, (m, k), jnp.float32)
    w = jax.random.normal(k2, (k, n), jnp.float32)
    y = ops.matmul(x, w, tiles=(bm, bn, bk), interpret=True)
    assert y.shape == (m, n)
    assert _rel_err(y, ref.matmul_ref(x, w)) < 1e-4


# ---------------------------------------------------------------------------
# grouped (ragged) matmul
# ---------------------------------------------------------------------------

GMM_CASES = [
    # rows, K, N, group sizes (empty groups, rows left over past the groups)
    (37, 256, 384, (7, 0, 13, 1, 9)),
    (64, 128, 256, (0, 0, 64, 0)),
    (50, 384, 128, (5, 5, 5, 5, 5, 5, 5, 5, 5, 5)),
    (19, 64, 80, (0, 3, 16)),
    (16, 128, 128, (0, 0, 0)),                  # no row routed here
]


@pytest.mark.parametrize("tiles", [(8, 128, 128), (16, 256, 512),
                                   (128, 128, 256)])
@pytest.mark.parametrize("case", GMM_CASES)
def test_grouped_matmul_matches_plain_product(case, tiles):
    """Each group's rows times its own weights, on ragged groups with empty
    ones and row counts that ``bm`` does not divide; rows past the groups
    come out zero."""
    M, K, N, sizes = case
    key = jax.random.PRNGKey(len(sizes))
    x = jax.random.normal(key, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (len(sizes), K, N))
    gs = jnp.asarray(sizes, jnp.int32)
    bm, bn, bk = tiles
    got = jax.jit(lambda x, w, gs: grouped_matmul_pallas(
        x, w, gs, block_m=bm, block_n=bn, block_k=bk, interpret=True))(
            x, w, gs)
    want = np.zeros((M, N), np.float32)
    start = 0
    for g, n in enumerate(sizes):
        want[start:start + n] = np.asarray(x[start:start + n]) @ np.asarray(
            w[g])
        start += n
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    ragged = jax.lax.ragged_dot(x, w, gs)
    np.testing.assert_allclose(np.asarray(ragged), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


# ---------------------------------------------------------------------------
# weights read where they lie in a stack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", [None, 0, 2, 4],
                         ids=["plain", "first", "middle", "last"])
@pytest.mark.parametrize("kernel", ["matmul", "grouped_matmul"])
def test_stacked_weight_is_read_in_place(kernel, layer):
    """A weight stack (L, ...) and a layer index give the product with that
    layer; the other layers are NaN, so a read of a neighbour shows.  The
    tiles divide none of M, N and K (K is 5 x 128 against bk 256 for the
    matmul), so the edge blocks run past the operands, where interpret mode
    puts NaN: the last K block is masked, the rows and columns past the
    output are not written.  ``plain`` is the unstacked weight."""
    L, M, K, N, sizes = 5, 37, 5 * 128, 200, (7, 0, 13, 9)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (M, K), jnp.float32)
    shape = (K, N) if kernel == "matmul" else (len(sizes), K, N)
    w = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    if kernel == "matmul":
        want = ref.matmul_ref(x, w)
        run = lambda w, at: ops.matmul(x, w, tiles=(16, 128, 256),
                                       interpret=True, layer=at)
    else:
        gs = jnp.asarray(sizes, jnp.int32)
        want = ref.grouped_matmul_ref(x, w, sizes)
        run = lambda w, at: ops.grouped_matmul(
            x, w, gs, tiles=(8, 128, 256), interpret=True, layer=at)
    if layer is not None:
        w = jnp.full((L,) + shape, jnp.nan).at[layer].set(w)
        layer = jnp.int32(layer)
    got = run(w, layer)
    assert got.shape == (M, N)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _rel_err(got, want) < 1e-5


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("tiles", [(64, 128), (128, 128)])
def test_flash_attention(causal, hq, hkv, tiles):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, hq, 256, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, hkv, 256, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, hkv, 256, 64))
    y = ops.flash_attention(q, k, v, causal=causal, scale=0.125,
                            tiles=tiles, interpret=True)
    rep = hq // hkv
    yr = ref.attention_ref(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1),
                           causal=causal, scale=0.125)
    assert float(jnp.max(jnp.abs(y - yr))) < 2e-5


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dqk,dv", [(192, 128), (48, 32)])
def test_flash_attention_v_head_dim_apart(causal, dqk, dv):
    """MLA's expanded prefill: queries and keys at 192 (nope + rope), values
    and outputs at 128."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (2, 4, 256, dqk))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 4, 256, dqk))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 4, 256, dv))
    y = ops.flash_attention(q, k, v, causal=causal, scale=dqk ** -0.5,
                            tiles=(64, 128), interpret=True)
    yr = ref.attention_ref(q, k, v, causal=causal, scale=dqk ** -0.5)
    assert y.shape == (2, 4, 256, dv)
    assert float(jnp.max(jnp.abs(y - yr))) < 2e-5


@settings(max_examples=8, deadline=None)
@given(sq=st.sampled_from([64, 128, 256]), d=st.sampled_from([32, 64]),
       bq=st.sampled_from([32, 64]), bkv=st.sampled_from([64, 128]),
       causal=st.booleans())
def test_flash_property(sq, d, bq, bkv, causal):
    key = jax.random.PRNGKey(sq + d)
    q = jax.random.normal(key, (1, 2, sq, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, sq, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, sq, d))
    y = ops.flash_attention(q, k, v, causal=causal, scale=d ** -0.5,
                            tiles=(bq, bkv), interpret=True)
    yr = ref.attention_ref(q, k, v, causal=causal, scale=d ** -0.5)
    assert float(jnp.max(jnp.abs(y - yr))) < 2e-5


# ---------------------------------------------------------------------------
# decode attention over the stacked cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv,d", [(32, 32, 80), (32, 2, 128)],
                         ids=["mha_hd80", "gqa16_hd128"])
@pytest.mark.parametrize("pos", [0, 127, 128, 383])
@pytest.mark.parametrize("ctx", [384, 200])
def test_decode_attention(hq, hkv, d, pos, ctx):
    """ctx 200 ends in a partial block: positions 200..255 of its last
    block lie past the caches (NaN in interpret mode) and must not leak."""
    from repro.kernels.decode_attention import BK

    L, B, layer = 3, 2, 1
    assert BK == 128                              # pos 127 and 128 straddle
    pos = min(pos, ctx - 1)
    key = jax.random.PRNGKey(pos)
    q = jax.random.normal(key, (B, hq, d)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              (L, B, hkv, d, ctx)).astype(jnp.bfloat16)
            for i in (1, 2))
    # what the kernel must not read: positions past pos (the previous
    # batch's keys) and the other layers; at pos, it writes this step's
    # key and value
    stale = (jnp.arange(ctx) >= pos) | (jnp.arange(L) != layer)[
        :, None, None, None, None]
    k_new, v_new = k[layer, ..., pos], v[layer, ..., pos]
    y, k_out, v_out = ops.decode_attention(
        q, k_new, v_new, jnp.where(stale, 1e4, k), jnp.where(stale, 1e4, v),
        layer, pos, scale=d ** -0.5, interpret=True)
    yr = ref.decode_attention_ref(q, k, v, layer, pos, scale=d ** -0.5)
    assert y.shape == q.shape and y.dtype == q.dtype
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - yr))) < 2e-2
    # the caches come back with the column written and nothing else moved
    for got, new, old in ((k_out, k_new, k), (v_out, v_new, v)):
        want = jnp.where(stale, 1e4, old).at[layer, ..., pos].set(new)
        assert bool(jnp.all(got == want))


# ---------------------------------------------------------------------------
# chunk scan (SSD)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_chunk_scan(chunk):
    key = jax.random.PRNGKey(1)
    G, S, P, N = 3, 128, 32, 16
    x = jax.random.normal(key, (G, S, P))
    Bm = jax.random.normal(jax.random.fold_in(key, 1), (G, S, N)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(key, 2), (G, S, N)) * 0.3
    la = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3),
                                            (G, S)))
    y = ops.chunk_scan(x, Bm, Cm, la, chunk=chunk, interpret=True)
    yr = ref.chunk_scan_ref(x, Bm, Cm, la)
    assert _rel_err(y, yr) < 1e-4


def test_chunk_scan_chunk_invariance():
    """Chunk size is a pure performance knob — results must agree."""
    key = jax.random.PRNGKey(2)
    G, S, P, N = 2, 64, 16, 8
    x = jax.random.normal(key, (G, S, P))
    Bm = jax.random.normal(jax.random.fold_in(key, 1), (G, S, N)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(key, 2), (G, S, N)) * 0.3
    la = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3),
                                            (G, S)))
    outs = [ops.chunk_scan(x, Bm, Cm, la, chunk=c, interpret=True)
            for c in (8, 16, 64)]
    for o in outs[1:]:
        assert _rel_err(o, outs[0]) < 1e-4


# ---------------------------------------------------------------------------
# action-space sweeps: the full DEFAULT tile grid, deduplicated by the
# kernels' internal clamping (what the measurement runner executes)
# ---------------------------------------------------------------------------

# non-pow2 test shape: stresses the edge blocks under every tile
_MM_SHAPE = (48, 160, 136)


def _mm_sweep():
    M, N, K = _MM_SHAPE
    eff = {(block(M, bm), block(N, bn), block(K, bk))
           for bm, bn, bk in itertools.product(
               NV.bm_choices, NV.bn_choices, NV.bk_choices)}
    return sorted(eff)


@pytest.mark.parametrize("tiles", _mm_sweep())
def test_matmul_action_space_sweep(tiles):
    M, N, K = _MM_SHAPE
    k1, k2 = jax.random.split(jax.random.PRNGKey(42))
    x = jax.random.normal(k1, (M, K), jnp.float32)
    w = jax.random.normal(k2, (K, N), jnp.float32)
    y = ops.matmul(x, w, tiles=tiles, interpret=True)
    assert y.shape == (M, N)
    assert _rel_err(y, ref.matmul_ref(x, w)) < 1e-5


# Rectangular Sq != Skv: kernel, XLA path, and ref all share bottom-right
# aligned causal semantics (query row i sees keys 0..i + Skv - Sq), so the
# sweep covers cross-attention shapes too.  Skv >= Sq: under bottom-right
# alignment a query block with Sq > Skv would attend to nothing, which the
# ref softmax maps to NaN — not a shape the model layer ever emits.
_ATTN_SQ, _ATTN_SKV, _ATTN_D = 128, 256, 64


def _attn_sweep():
    eff = {(min(bq, _ATTN_SQ), min(bkv, _ATTN_SKV))
           for bq, bkv in itertools.product(NV.bq_choices, NV.bkv_choices)}
    return sorted(eff)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("tiles", _attn_sweep())
def test_attention_action_space_sweep(tiles, causal):
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (1, 2, _ATTN_SQ, _ATTN_D))
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, 2, _ATTN_SKV, _ATTN_D))
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (1, 2, _ATTN_SKV, _ATTN_D))
    y = ops.flash_attention(q, k, v, causal=causal,
                            scale=_ATTN_D ** -0.5, tiles=tiles,
                            interpret=True)
    yr = ref.attention_ref(q, k, v, causal=causal, scale=_ATTN_D ** -0.5)
    assert float(jnp.max(jnp.abs(y - yr))) < 2e-5


_SCAN_S = 128


@pytest.mark.parametrize("chunk",
                         sorted({min(c, _SCAN_S) for c in NV.chunk_choices}))
def test_chunk_scan_action_space_sweep(chunk):
    key = jax.random.PRNGKey(11)
    G, S, P, N = 2, _SCAN_S, 32, 16
    x = jax.random.normal(key, (G, S, P))
    Bm = jax.random.normal(jax.random.fold_in(key, 1), (G, S, N)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(key, 2), (G, S, N)) * 0.3
    la = -jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3),
                                            (G, S)))
    y = ops.chunk_scan(x, Bm, Cm, la, chunk=chunk, interpret=True)
    assert _rel_err(y, ref.chunk_scan_ref(x, Bm, Cm, la)) < 1e-4


# ---------------------------------------------------------------------------
# the XLA flash path (custom VJP) vs oracle — gradients included
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sq,skv", [(128, 128), (64, 128)])
def test_mem_efficient_attention_grads(causal, sq, skv):
    from repro.models import compute
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 4, sq, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, skv, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2, skv, 32))

    def fn(q, k, v):
        return compute.flash_attention(q, k, v, site="t", causal=causal,
                                       q_chunk=32, kv_chunk=64).sum()

    def naive(q, k, v):
        ke, ve = jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1)
        return ref.attention_ref(q, ke, ve, causal=causal,
                                 scale=32 ** -0.5).sum()

    g1 = jax.grad(fn, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
