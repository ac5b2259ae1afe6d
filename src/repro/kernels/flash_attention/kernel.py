"""Pallas TPU flash-attention kernel (forward).

Tiling (BlockSpec → VMEM):
  grid = (B·H, Sq/bq, Sk/bk), k-blocks innermost ("arbitrary" semantics so
  the online-softmax carry in VMEM scratch is legal).
  q tile  (bq, hd)   — one VMEM-resident query block per (bh, qi)
  k tile  (bk, hd)   — streamed over the ki axis
  v tile  (bk, hd)
  scratch: acc (bq, hd) f32, m (bq, 128) f32, l (bq, 128) f32

GQA is handled in the k/v index_map: query head h reads kv head h // rep,
so K/V tiles are never replicated in HBM — the MXU sees the shared tile.
Causal masking is two-level: whole k-blocks strictly above the diagonal are
skipped with @pl.when (no FLOPs for masked tiles), and the diagonal block is
masked element-wise with iota.

MXU alignment: bq, bk default to 128; hd ∈ {64, 112, 128} keeps the last
dim on the 128-lane axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, sm_scale: float, block_q: int, block_k: int,
                  q_offset: int, num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute (key-aligned) position of this tile's first query/key
    q_start = qi * block_q + q_offset
    k_start = ki * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)                       # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                       # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                        # (bq, bk)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_ref[:, 0]                                    # (bq,)
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])                         # (bq, bk)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        v = v_ref[0].astype(jnp.float32)                        # (bk, hd)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    if causal:
        # skip k-blocks entirely above the diagonal of this q tile
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "n_q_heads",
                     "interpret", "q_offset"))
def flash_attention_bhsd(q, k, v, *, causal: bool, n_q_heads: int,
                         block_q: int = 128, block_k: int = 128,
                         q_offset: int = 0, interpret: bool = False):
    """Flattened layout: q (B·H, Sq, hd); k, v (B·Hkv, Sk, hd)."""
    BH, Sq, hd = q.shape
    BHkv, Sk, _ = k.shape
    H = n_q_heads
    B = BH // H
    Hkv = BHkv // B
    rep = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    sm_scale = 1.0 / (hd ** 0.5)

    def kv_index(bh, qi, ki):
        b = bh // H
        kvh = (bh % H) // rep
        return (b * Hkv + kvh, ki, 0)

    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, q_offset=q_offset + (Sk - Sq), num_k_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
