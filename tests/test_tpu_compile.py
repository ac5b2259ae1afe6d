"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed without a chip,
lowers each kernel at the widths the chip path uses and refuses what
the chip would refuse (block tiling, unsupported reductions, fast
memory), which interpret mode never checks. The topology is described
inside a fixture, so only the worker that runs these tests loads the
TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

PAPER_DEMO = get_config("paper-demo")
QWEN2 = get_config("qwen2-7b")
FALCON_MAMBA = get_config("falcon-mamba-7b")
# words of one paper-demo embedding leaf (32768 x 768 float32)
EMBED_WORDS = PAPER_DEMO.vocab_size * PAPER_DEMO.d_model
D_INNER = FALCON_MAMBA.ssm_expand * FALCON_MAMBA.d_model      # 8192
SEQ = 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler to describe it with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_checksum_kernel_compiles(one_chip):
    from repro.kernels.checksum.kernel import checksum_kernel
    text = _compiled_text(checksum_kernel,
                          _shape(one_chip, (EMBED_WORDS,), jnp.uint32))
    assert "tpu_custom_call" in text


def test_tile_checksum_kernel_compiles(one_chip):
    from repro.kernels.checksum.kernel import tile_checksum_kernel
    text = _compiled_text(tile_checksum_kernel,
                          _shape(one_chip, (EMBED_WORDS,), jnp.uint32))
    assert "tpu_custom_call" in text


# half the tiles of a paper-demo embedding leaf dirty; every tile of a
# qwen2-7b embedding leaf (532,224 tiles) dirty, gathered in chunks
@pytest.mark.parametrize("words,dirty", [
    (EMBED_WORDS, 0.5),
    (QWEN2.vocab_size * QWEN2.d_model, 1.0)])
def test_gather_tiles_kernel_compiles(one_chip, words, dirty):
    from repro.kernels.checksum.kernel import gather_tiles_kernel
    from repro.kernels.checksum.ref import TILE_WORDS
    n_dirty = int(words // TILE_WORDS * dirty)
    text = _compiled_text(
        gather_tiles_kernel,
        _shape(one_chip, (words // 128, 128), jnp.uint32),
        _shape(one_chip, (n_dirty,), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_attention_forward_compiles(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    hd = QWEN2.head_dim
    q = _shape(one_chip, (1, SEQ, QWEN2.n_heads, hd), jnp.bfloat16)
    kv = _shape(one_chip, (1, SEQ, QWEN2.n_kv_heads, hd), jnp.bfloat16)
    text = _compiled_text(lambda q, k, v: flash_attention(q, k, v),
                          q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("q_shape,kv_shape", [
    pytest.param((4, 1024, 14, 64), (4, 1024, 2, 64), id="train.ckpt"),
    pytest.param((2, 4096, 32, 128), (2, 4096, 4, 128),
                 id="train.moe.ckpt")])
def test_flash_attention_backward_compiles(one_chip, q_shape, kv_shape):
    """Forward and backward (jax.grad through the custom VJP) at the
    widths of the training cells, with the blocks the model path picks:
    qwen2-0.5b-l4 (batch 4 x 1024, 14 query and 2 KV heads of 64) and
    qwen3-30b-a3b-l4e8 (batch 2 x 4096, 32 query and 4 KV heads of
    128)."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.models.attention import flash_blocks
    bq, bk = flash_blocks(q_shape, kv_shape, platform="tpu")

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        return jnp.sum(o.astype(jnp.float32))

    q = _shape(one_chip, q_shape, jnp.bfloat16)
    kv = _shape(one_chip, kv_shape, jnp.bfloat16)
    text = _compiled_text(jax.grad(loss, (0, 1, 2)), q, kv, kv)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ("flash_fwd", "flash_bwd_dkv"):
        assert any(kernel in line.split("=")[0] for line in calls), kernel


@pytest.mark.parametrize("d_in,d_out", [(2048, 768), (768, 2048)])
def test_grouped_matmul_forward_and_backward_compile(one_chip, d_in, d_out):
    """The MoE layer's grouped matmul at the widths of train.moe.ckpt:
    4,096 rows (the 8 held experts' expected rows of a step's layer) and
    a tail of rows of no held expert, over 8 groups, forward and
    backward, with the tiling the layer picks. The kernels' names hold
    what gmm_roofline matches: `gmm` (forward, input gradient) and
    `tgmm` (weight gradient), after the scope they are called in."""
    from repro.models.moe import grouped_matmul, gmm_tiling
    rows = 4096 + 512
    tiling = gmm_tiling(rows, d_in, d_out, platform="tpu")
    assert tiling is not None

    def loss(x, w, sizes):
        y = grouped_matmul(x, w, sizes, tiling=tiling)
        return jnp.sum(y.astype(jnp.float32))

    text = _compiled_text(
        jax.grad(loss, (0, 1)),
        _shape(one_chip, (rows, d_in), jnp.bfloat16),
        _shape(one_chip, (8, d_in, d_out), jnp.bfloat16),
        _shape(one_chip, (9,), jnp.int32))
    named = {line.split("=")[0].strip(): 'custom_call_target="tpu_' \
             'custom_call"' in line for line in text.splitlines()
             if re.match(r"\s*(ROOT )?%\S*gmm\S* = ", line)}
    # the reader's pattern finds the kernels and nothing else
    assert named and all(named.values()), named
    assert any("tgmm" in n for n in named), named
    assert any("gmm" in n.replace("tgmm", "") for n in named), named


def test_selective_scan_compiles(one_chip):
    from repro.kernels.mamba_scan.kernel import selective_scan
    ds = FALCON_MAMBA.ssm_state
    x = _shape(one_chip, (1, SEQ, D_INNER), jnp.float32)
    bc = _shape(one_chip, (1, SEQ, ds), jnp.float32)
    a = _shape(one_chip, (D_INNER, ds), jnp.float32)
    text = _compiled_text(selective_scan, x, x, bc, bc, a)
    assert "tpu_custom_call" in text
