"""Code-embedding generator — the code2vec analogue (paper §3.1).

code2vec decomposes a snippet into AST *path contexts* (leaf, path, leaf),
learns token/path embeddings, and attention-pools them into one fixed-length
code vector (340 features).  Our "AST" is the canonicalized kernel site
(DESIGN.md §2): leaves are name-free operand descriptors (dim buckets,
dtype, layout, causality, fusion), the root is the primitive kind, and a
path context is (leaf_i, role-pair path, leaf_j).  The embedder is trained
end-to-end with the RL agent, exactly as in the paper.
"""
from __future__ import annotations

import itertools
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.compute import KernelSite

# ---------------------------------------------------------------------------
# token vocabulary (name-free by construction — paper §3.2 found identifier
# names bias the embedding; our descriptors never contain them)
# ---------------------------------------------------------------------------

_KINDS = ("matmul", "attention", "chunk_scan")
_ROLES = ("m", "n", "k", "batch")
_N_BUCKETS = 26              # log2 buckets for dims up to 2^25
_DTYPES = ("bfloat16", "float32", "float16", "int8")
_LAYOUTS = ("nn", "nt", "tn", "tt")


def _build_vocab():
    toks: List[str] = ["<pad>"]
    for r in _ROLES:
        toks += [f"{r}:b{i}" for i in range(_N_BUCKETS)]
        toks += [f"{r}:align{a}" for a in (0, 1)]   # 128-aligned or not
    toks += [f"dtype:{d}" for d in _DTYPES]
    toks += [f"layout:{l}" for l in _LAYOUTS]
    toks += ["causal:0", "causal:1"]
    toks += [f"fused:{i}" for i in range(4)]
    return {t: i for i, t in enumerate(toks)}


_VOCAB = _build_vocab()
N_TOKENS = len(_VOCAB)

_PATHS = ["<pad>"] + [f"{k}|{a}-{b}" for k in _KINDS
                      for a, b in itertools.combinations_with_replacement(
                          ("dim", "dtype", "layout", "flag"), 2)]
_PATH_IDX = {p: i for i, p in enumerate(_PATHS)}
N_PATHS = len(_PATHS)

MAX_PATHS = 32
EMBED_DIM = 340              # the paper's code-vector width
TOK_DIM = 64


def _bucket(v: int) -> int:
    return min(_N_BUCKETS - 1, int(math.log2(max(1, v))))


def _leaf_tokens(site: KernelSite) -> List[Tuple[str, str]]:
    """(token, category) leaves of the site's mini-AST."""
    leaves = []
    for r, v in (("m", site.m), ("n", site.n), ("k", site.k),
                 ("batch", site.batch)):
        leaves.append((f"{r}:b{_bucket(v)}", "dim"))
        leaves.append((f"{r}:align{int(v % 128 == 0)}", "dim"))
    leaves.append((f"dtype:{site.dtype}", "dtype"))
    leaves.append((f"layout:{site.transpose}", "layout"))
    leaves.append((f"causal:{int(site.causal)}", "flag"))
    leaves.append((f"fused:{min(site.fused_ops, 3)}", "flag"))
    return leaves


def _path_kind(site: KernelSite) -> str:
    """The root of a site's paths.  A grouped matmul is a matmul with a
    batch of groups: it shares the matmul paths (and the vocabulary and
    the embedder's shapes stay those of the three primitive kinds)."""
    return "matmul" if site.kind == "grouped_matmul" else site.kind


# featurization is a pure function of the site, and training resamples the
# same corpus sites every batch — memoize (read-only arrays; bounded)
_FEAT_CACHE: dict = {}
_FEAT_CACHE_MAX = 65536


def featurize(site: KernelSite,
              cache: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    """-> (contexts (MAX_PATHS, 3) int32, mask (MAX_PATHS,) f32).

    ``cache=False`` bypasses the memo (the legacy/benchmark-reference path
    recomputes features every call, like the original implementation)."""
    key = site.key()
    if cache:
        hit = _FEAT_CACHE.get(key)
        if hit is not None:
            return hit
    leaves = _leaf_tokens(site)
    ctxs = []
    for (ta, ca), (tb, cb) in itertools.combinations(leaves, 2):
        pa, pb = sorted((ca, cb))
        path = f"{_path_kind(site)}|{pa}-{pb}"
        ctxs.append((_VOCAB[ta], _PATH_IDX.get(path, 0), _VOCAB[tb]))
    # deterministic subsample to MAX_PATHS (keep coverage of all leaves)
    if len(ctxs) > MAX_PATHS:
        step = len(ctxs) / MAX_PATHS
        ctxs = [ctxs[int(i * step)] for i in range(MAX_PATHS)]
    arr = np.zeros((MAX_PATHS, 3), np.int32)
    mask = np.zeros((MAX_PATHS,), np.float32)
    for i, c in enumerate(ctxs):
        arr[i] = c
        mask[i] = 1.0
    if cache:
        arr.flags.writeable = False
        mask.flags.writeable = False
        if len(_FEAT_CACHE) >= _FEAT_CACHE_MAX:
            _FEAT_CACHE.clear()
        _FEAT_CACHE[key] = (arr, mask)
    return arr, mask


def featurize_batch(sites, cache: bool = True
                    ) -> Tuple[np.ndarray, np.ndarray]:
    fs = [featurize(s, cache=cache) for s in sites]
    return (np.stack([f[0] for f in fs]), np.stack([f[1] for f in fs]))


# ---------------------------------------------------------------------------
# the embedding network (learned; trained jointly with the agent)
# ---------------------------------------------------------------------------

def embedder_init(key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "tok": jax.random.normal(k1, (N_TOKENS, TOK_DIM)) * 0.1,
        "path": jax.random.normal(k2, (N_PATHS, TOK_DIM)) * 0.1,
        "W": jax.random.normal(k3, (3 * TOK_DIM, EMBED_DIM))
        * (1.0 / math.sqrt(3 * TOK_DIM)),
        "att": jax.random.normal(k4, (EMBED_DIM,)) * 0.1,
    }


def embed_sites(params, contexts, mask):
    """contexts: (B, MAX_PATHS, 3) int32; mask (B, MAX_PATHS).
    -> (B, EMBED_DIM) code vectors (code2vec attention pooling).

    The projection is factored through the (tiny) vocab tables:
    ``gather(tok) @ W_slot == gather(tok @ W_slot)``, so each token/path
    row is projected once per call instead of once per path-context —
    identical math to the reference below at a fraction of the FLOPs
    (the projection matmul dominated the whole PPO step)."""
    W = params["W"]
    tok_a = params["tok"] @ W[:TOK_DIM]              # (N_TOKENS, EMBED_DIM)
    pth_w = params["path"] @ W[TOK_DIM:2 * TOK_DIM]  # (N_PATHS, EMBED_DIM)
    tok_b = params["tok"] @ W[2 * TOK_DIM:]
    c = jnp.tanh(tok_a[contexts[..., 0]] + pth_w[contexts[..., 1]]
                 + tok_b[contexts[..., 2]])
    score = c @ params["att"]                        # (B, MAX_PATHS)
    score = jnp.where(mask > 0, score, -1e30)
    alpha = jax.nn.softmax(score, axis=-1)
    return jnp.einsum("bp,bpe->be", alpha, c)


def embed_sites_ref(params, contexts, mask):
    """The original (seed) formulation: per-context concat then project.
    Kept as the benchmark reference path (``PPOAgent(fused=False)``)."""
    t1 = params["tok"][contexts[..., 0]]
    pth = params["path"][contexts[..., 1]]
    t2 = params["tok"][contexts[..., 2]]
    c = jnp.tanh(jnp.concatenate([t1, pth, t2], -1) @ params["W"])
    score = c @ params["att"]                        # (B, MAX_PATHS)
    score = jnp.where(mask > 0, score, -1e30)
    alpha = jax.nn.softmax(score, axis=-1)
    return jnp.einsum("bp,bpe->be", alpha, c)
