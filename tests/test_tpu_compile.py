"""The main path's Pallas kernels compile for a TPU v5e at StableLM-3B widths.

Compiled ahead of time against a described ``v5e:2x2`` topology: the TPU
compiler runs here and refuses what the chip would refuse (block shapes
off the (8, 128) tiling, scoped-VMEM overflow, unsupported primitives),
with no chip attached.  Nothing executes.  The topology is described inside
a fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import os

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
