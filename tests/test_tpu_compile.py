"""The main path's Pallas kernels compile for a TPU v5e at StableLM-3B widths.

Compiled ahead of time against a described ``v5e:2x2`` topology: the TPU
compiler runs here and refuses what the chip would refuse (block shapes
off the (8, 128) tiling, scoped-VMEM overflow, unsupported primitives),
with no chip attached.  Nothing executes.  The topology is described inside
a fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import math
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.costmodel import baseline_attn_tiles, baseline_matmul_tiles
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# StableLM-3B: d_model 2560, d_ff 6912, vocab 50304, 32 heads of 80;
# prefill is batch 4 x 128 tokens, decode is batch 4 x 1 token
_M_PREFILL, _M_DECODE = 4 * 128, 4


@pytest.mark.parametrize("m,k,n", [(_M_PREFILL, 2560, 6912),
                                   (_M_DECODE, 2560, 50304)],
                         ids=["prefill_mlp_up", "decode_lm_head"])
def test_matmul_compiles_at_baseline_tiles(one_chip, m, k, n):
    tiles = baseline_matmul_tiles(m, n, k)
    hlo = _compiled_hlo(lambda x, w: ops.matmul(x, w, tiles=tiles),
                        [(m, k), (k, n)], one_chip)
    assert "tpu_custom_call" in hlo


def test_causal_flash_attention_compiles(one_chip):
    shape = (4, 32, 128, 80)                    # (B, H, S, head_dim)
    tiles = baseline_attn_tiles(128, 128)
    hlo = _compiled_hlo(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            scale=80 ** -0.5, tiles=tiles),
        [shape] * 3, one_chip)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("chunk", [64, 256])
def test_chunk_scan_compiles(one_chip, chunk):
    # xLSTM-1.3B mLSTM: 4 heads of 1024 (query/key and value widths),
    # batch 2 x 1024 tokens; chunk 256 is the model's own
    G, S, P = 2 * 4, 1024, 1024
    hlo = _compiled_hlo(
        lambda x, b, c, la: ops.chunk_scan(x, b, c, la, chunk=chunk),
        [(G, S, P), (G, S, P), (G, S, P), (G, S)], one_chip)
    assert "tpu_custom_call" in hlo


COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = "
    r"(?:\((?:[^()]|\([^()]*\))*\)|\w+\[([\d,]*)\]\S*) ([\w\-]+)\(([^)]*)\)")
WHILE = re.compile(r" while\(.*condition=%([\w.\-]+), body=%([\w.\-]+)")
TRIPS = re.compile(r"s32\[\]\S* constant\((\d+)\)")
SCOPED_VMEM = re.compile(
    r'scoped_memory_configs\\?":\[\{[^\]]*?size\\?":\\?"(\d+)')


def _instructions(hlo: str) -> list:
    """(computation, name, dims, opcode, operand names, line) per
    instruction of a compiled module's text; a tuple has no dims."""
    out, comp = [], None
    for line in hlo.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = INSTRUCTION.match(line)
        if m:
            dims = tuple(int(d) for d in (m.group(2) or "").split(",") if d)
            operands = re.findall(r"%([\w.\-]+)", m.group(4))
            out.append((comp, m.group(1), dims, m.group(3), operands, line))
    return out


def _runs_per_step(hlo: str) -> dict:
    """Computation -> trip count of the while loop whose body it is."""
    bodies, conds = {}, {}
    for line in hlo.splitlines():
        m = WHILE.search(line)
        if m:
            bodies[m.group(2)] = m.group(1)
    comp = None
    for line in hlo.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
        elif comp in bodies.values():
            t = TRIPS.search(line)
            if t:
                conds[comp] = int(t.group(1))
    return {body: conds[cond] for body, cond in bodies.items()}


@pytest.mark.parametrize("arch,B,ctx", [
    ("stablelm_3b", 16, 1024), ("stablelm_3b", 8, 2064),
    ("chatglm3_6b", 32, 1536)],
    ids=["stablelm_b16_ctx1024", "stablelm_b8_ctx2064",
         "chatglm_b32_ctx1536"])
def test_decode_step_reads_the_stacked_cache_in_place(one_chip, arch, B,
                                                      ctx):
    # full-width decode under api.inject at baseline tiles: StableLM-3B
    # (MHA, head_dim 80) at the benchmark's decode cell, and at a ctx 128
    # does not divide (serve's prompt 2048 + 16 generated); ChatGLM3-6B
    # (GQA group 16, head_dim 128) at batch 32 against 1536 positions
    from repro import api
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models.lm import build_model
    from repro.train.steps import make_serve_step

    model = build_model(get_config(arch))
    L, H, D = model.cfg.n_layers, model.cfg.n_kv_heads, model.cfg.head_dim
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.make_cache(B, ctx, jnp.bfloat16))
    sites = serve.serving_sites(
        model, params, {"tokens": jax.ShapeDtypeStruct((B, 256), jnp.int32)},
        cache)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    with api.inject(api.baseline_program(sites)):
        hlo = jax.jit(make_serve_step(model), donate_argnums=(3,)).lower(
            on_chip(params), on_chip(jax.ShapeDtypeStruct((B, 1), jnp.int32)),
            on_chip(jax.ShapeDtypeStruct((), jnp.int32)),
            on_chip(cache)).compile().as_text()
    instrs = _instructions(hlo)
    dims_of = {name: dims for _, name, dims, _, _, _ in instrs}

    # no copy, slice or update of a whole layer's K or V (either order of
    # head_dim and ctx), but the one-column update of the stacked carry
    layer = Counter((B, H, D, ctx))
    moves = ("copy", "dynamic-slice", "dynamic-update-slice")
    whole = []
    for _, name, dims, op, operands, line in instrs:
        if not (op in moves or (op == "fusion"
                                and any(m in name for m in moves))):
            continue
        if Counter(d for d in dims if d != 1) & layer != layer:
            continue
        column = (op == "dynamic-update-slice" and dims == (L, B, H, D, ctx)
                  and math.prod(dims_of[operands[1]]) == B * H * D)
        if not column:
            whole.append(line.strip()[:160])
    assert whole == []

    # one decode-kernel call a layer, in the layer scan's body
    runs = _runs_per_step(hlo)
    calls = [comp for comp, _, _, op, _, line in instrs
             if op == "custom-call" and "tpu_custom_call" in line
             and "site=attn.core" in line]
    assert sum(runs.get(comp, 1) for comp in calls) == L

    # its blocks hold 128 positions whatever ctx is, so the scoped VMEM it
    # asks for does not grow with the cache: at most half the v5e's 128 MiB
    vmem = [int(n) for _, _, _, op, _, line in instrs
            if op == "custom-call" and "site=attn.core" in line
            for n in SCOPED_VMEM.findall(line)]
    assert vmem and max(vmem) <= 64 << 20
