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


def _computations(text):
    """{computation: its lines} of a compiled HLO module's text."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%\S+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name:
            comps[name].append(line)
    return comps


def _reached(comps, roots):
    """The computations `roots` call, themselves included."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += [ref for line in comps[name]
                     for ref in re.findall(r"%[\w.\-]+", line)
                     if ref in comps]
    return seen


def test_moe_train_step_bounds_its_sorted_rows(one_chip, monkeypatch):
    """The gradient of a reduced MoE model's loss for a v5e, with the
    grouped-matmul kernel the layer takes on a TPU: 512 tokens, top-4 of
    16 experts, 4 held, so the sorted rows are bounded at 1,024 of the
    2,048 the tokens can send. The layer scan holds one conditional
    forward and one backward (the full remat's recompute of the forward
    is dead), no buffer of 2,048 rows lies outside their full-size
    branches, and only the kernels match gmm_roofline's pattern."""
    import dataclasses
    import functools

    from repro.configs import reduced
    from repro.models import moe
    from repro.models.model import Model
    cfg = dataclasses.replace(
        reduced(get_config("qwen3-moe-30b-a3b")), d_model=256, d_ff=384,
        n_experts=16, experts_per_token=4, experts_held=4, expert_offset=4)
    B, S, M = 2, 256, 2048
    assert moe.row_bound(B * S, 4, 4, 16) == 1024
    monkeypatch.setattr(moe, "gmm_tiling", functools.partial(
        moe.gmm_tiling, platform="tpu"))
    model = Model(cfg)
    params = jax.tree.map(lambda a: _shape(one_chip, a.shape, a.dtype),
                          model.abstract_params())
    batch = {k: _shape(one_chip, (B, S), jnp.int32)
             for k in ("tokens", "labels")}
    text = _compiled_text(jax.value_and_grad(model.loss_fn, has_aux=True),
                          params, batch)
    comps = _computations(text)
    branches = [re.search(r"branch_computations=\{([^}]*)\}", line)
                .group(1).split(", ") for line in text.splitlines()
                if " conditional(" in line]
    assert len(branches) == 2, branches
    full = _reached(comps, [b[-1] for b in branches])      # cond's true
    wide = {name: [line for line in lines if f"[{M},{cfg.d_model}]" in line
                   or f"[{M},{cfg.d_ff}]" in line]
            for name, lines in comps.items()}
    assert any(wide[name] for name in full)
    outside = [line for name in comps if name not in full
               for line in wide[name]]
    assert not outside, outside[:3]
    named = {line.split("=")[0].strip(): 'custom_call_target="tpu_' \
             'custom_call"' in line for line in text.splitlines()
             if re.match(r"\s*(ROOT )?%\S*gmm\S* = ", line)}
    assert named and all(named.values()), named


def test_selective_scan_compiles(one_chip):
    from repro.kernels.mamba_scan.kernel import selective_scan
    ds = FALCON_MAMBA.ssm_state
    x = _shape(one_chip, (1, SEQ, D_INNER), jnp.float32)
    bc = _shape(one_chip, (1, SEQ, ds), jnp.float32)
    a = _shape(one_chip, (D_INNER, ds), jnp.float32)
    text = _compiled_text(selective_scan, x, x, bc, bc, a)
    assert "tpu_custom_call" in text
