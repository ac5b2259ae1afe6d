"""Pallas TPU flash attention: forward with the row log-sum-exp, and backward.

Layout: q (B·H, Sq, hd); k, v (B·Hkv, Sk, hd). Query row i sits at key
position i + (Sk - Sq) + q_offset, so a query suffix against a longer KV
prefix masks correctly.

Every kernel works on transposed score tiles, s^T = K·Q^T of shape
(bk, bq): the per-query numbers (the running max and sum, lse, D) are
then (1, bq) rows along the lanes, broadcast over sublanes for free,
where (bq, 1) columns would take a lane broadcast for every use.

Forward, `flash_fwd`, grid (B·H, Sq/bq, Sk/bk), k-blocks innermost
("arbitrary", so the online-softmax carry in VMEM scratch is legal):
q tile (bq, hd), k and v tiles (bk, hd); scratch acc^T (hd, bq), m and l
(1, bq), all float32. It writes o and lse = m + log(l), the residual the
backward needs.

Backward, with D = rowsum(dO ∘ O) computed outside the kernels:
  `flash_bwd_dkv` grid (B·Hkv, Sk/bk, rep, Sq/bq): one kv head's tile
       accumulates dK and dV over its `rep` query heads and every q block
       in (bk, hd) scratch; where `_fuse_dq` holds it also accumulates dQ
       for all rep heads, so p and dS are computed once.
  `flash_bwd_dq` grid (B·H, Sq/bq, Sk/bk): dQ on its own, recomputing p
       from q, k and lse, where the fused dQ would not fit in VMEM.
No S × S tensor reaches HBM in either pass.

The MXU takes the inputs' own dtype (bfloat16 in the model) and
accumulates in float32 (`preferred_element_type`); p and dS are cast
to that dtype for their products, as flash attention does.

GQA is handled in the index maps: query head h reads kv head h // rep, so
K/V are never replicated in HBM. Causal masking is two-level: tiles
wholly above the diagonal are skipped with @pl.when and their index map
repeats the last tile fetched (no DMA), and only tiles that cross the
diagonal are masked element-wise with iota.

lse and D travel as (B·H, 8, Sq) float32: a row broadcast over one
sublane tile, so a (8, bq) block is legal on the (8, 128) tiling.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SUBLANES = 8
FUSED_DQ_BYTES = 8 << 20       # VMEM for the fused dQ (see _fuse_dq)
NT = (((1,), (1,)), ((), ()))          # a @ b.T
NN = (((1,), (0,)), ((), ()))          # a @ b
TN = (((0,), (0,)), ((), ()))          # a.T @ b


class _Spec(NamedTuple):
    """Static description of one call (hashable: the custom VJP's
    non-differentiable argument)."""
    causal: bool
    n_q_heads: int
    block_q: int
    block_k: int
    offset: int          # key position of query row 0
    interpret: bool


def _dims(q, k, spec: _Spec):
    BH, Sq, hd = q.shape
    BHkv, Sk, _ = k.shape
    H = spec.n_q_heads
    B = BH // H
    Hkv = BHkv // B
    return BH, Sq, Sk, hd, H, Hkv, H // Hkv


def _last_live_k(qi, spec: _Spec):
    """Index of the last k block that q block qi sees (causal)."""
    last = (qi * spec.block_q + spec.block_q - 1 + spec.offset) \
        // spec.block_k
    return jnp.maximum(last, 0)


def _first_live_q(ki, spec: _Spec, nq: int):
    """Index of the first q block that sees k block ki (causal)."""
    first = (ki * spec.block_k - spec.offset) // spec.block_q
    return jnp.clip(first, 0, nq - 1)


def _mask(s, q_start, k_start, transposed: bool):
    """Causal mask of one tile: (bq, bk), or (bk, bq) when transposed."""
    qa, ka = (1, 0) if transposed else (0, 1)
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, qa)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, ka)
    return jnp.where(kpos <= qpos, s, NEG_INF)


def _on_live_tiles(compute, q_start, k_start, spec: _Spec):
    """Run compute(masked) on a tile that some query sees: masked only
    where the tile crosses the diagonal, skipped where it lies wholly
    above it."""
    if not spec.causal:
        compute(False)
        return
    live = k_start <= q_start + spec.block_q - 1
    below = k_start + spec.block_k - 1 <= q_start
    pl.when(live & below)(lambda: compute(False))
    pl.when(live & jnp.logical_not(below))(lambda: compute(True))


def _scores(a, b, sm_scale: float):
    """a @ b.T * sm_scale in float32. A power-of-two scale (head width 64,
    256) is exact in any float format, so it scales the (rows, hd) operand
    instead of the (rows, cols) product."""
    exact = sm_scale == 2.0 ** round(math.log2(sm_scale))
    if exact:
        a = a * jnp.asarray(sm_scale, a.dtype)
    s = jax.lax.dot_general(a, b, NT, preferred_element_type=jnp.float32)
    return s if exact else s * sm_scale


# ------------------------------------------------------------------ forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, spec: _Spec, sm_scale: float, num_k_blocks: int):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * spec.block_q + spec.offset
    k_start = ki * spec.block_k

    def compute(masked: bool):
        st = _scores(k_ref[0], q_ref[0], sm_scale)              # (bk, bq)
        if masked:
            st = _mask(st, q_start, k_start, transposed=True)
        m_prev = m_ref[...]                                     # (1, bq)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pt, axis=0, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0]
        pv = jax.lax.dot_general(v, pt.astype(v.dtype), TN,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv                 # (hd, bq)

    _on_live_tiles(compute, q_start, k_start, spec)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).T.astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_ref[...] + jnp.log(l),
                                      (SUBLANES, spec.block_q))


def flash_fwd(q, k, v, spec: _Spec):
    """(o (B·H, Sq, hd), lse (B·H, 8, Sq) float32)."""
    BH, Sq, Sk, hd, H, Hkv, rep = _dims(q, k, spec)
    bq, bk = spec.block_q, spec.block_k
    nq, nk = Sq // bq, Sk // bk

    def kv_index(bh, qi, ki):
        if spec.causal:
            ki = jnp.minimum(ki, _last_live_k(qi, spec))
        return ((bh // H) * Hkv + (bh % H) // rep, ki, 0)

    kernel = functools.partial(_fwd_kernel, spec=spec,
                               sm_scale=1.0 / (hd ** 0.5), num_k_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, SUBLANES, bq), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
                   jax.ShapeDtypeStruct((BH, SUBLANES, Sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, bq), jnp.float32),     # acc^T
                        pltpu.VMEM((1, bq), jnp.float32),      # m
                        pltpu.VMEM((1, bq), jnp.float32)],     # l
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=spec.interpret,
        name="flash_fwd",
    )(q, k, v)


# ----------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               acc_ref, *, spec: _Spec, sm_scale: float, num_k_blocks: int):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * spec.block_q + spec.offset
    k_start = ki * spec.block_k

    def compute(masked: bool):
        k = k_ref[0]
        s = _scores(q_ref[0], k, sm_scale)                      # (bq, bk)
        if masked:
            s = _mask(s, q_start, k_start, transposed=False)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, 0][:, None])
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, NN, preferred_element_type=jnp.float32)

    _on_live_tiles(compute, q_start, k_start, spec)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def flash_bwd_dq(q, k, v, do, lse, di, spec: _Spec):
    BH, Sq, Sk, hd, H, Hkv, rep = _dims(q, k, spec)
    bq, bk = spec.block_q, spec.block_k
    nq, nk = Sq // bq, Sk // bk

    def kv_index(bh, qi, ki):
        if spec.causal:
            ki = jnp.minimum(ki, _last_live_k(qi, spec))
        return ((bh // H) * Hkv + (bh % H) // rep, ki, 0)

    q_spec = pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0))
    row_spec = pl.BlockSpec((1, SUBLANES, bq), lambda bh, qi, ki: (bh, 0, qi))
    kernel = functools.partial(_dq_kernel, spec=spec,
                               sm_scale=1.0 / (hd ** 0.5), num_k_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[q_spec,
                  pl.BlockSpec((1, bk, hd), kv_index),
                  pl.BlockSpec((1, bk, hd), kv_index),
                  q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=spec.interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, di)


def _fuse_dq(q, k, spec: _Spec) -> bool:
    """Whether the dK/dV kernel also accumulates dQ, which spares the dQ
    kernel's second pass over the scores. It then holds the dQ of all
    `rep` query heads of its kv head in VMEM: a float32 accumulator and
    two buffers of the output, (rep, Sq, hd) each, hd padded to 128
    lanes."""
    _, Sq, _, hd, _, _, rep = _dims(q, k, spec)
    per_row = max(hd, 128) * (4 + 2 * q.dtype.itemsize)
    return rep * Sq * per_row <= FUSED_DQ_BYTES


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, *refs,
                spec: _Spec, sm_scale: float, rep: int, num_q_blocks: int,
                num_k_blocks: int, fuse_dq: bool):
    if fuse_dq:
        dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    ki, r, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    bq = spec.block_q

    @pl.when((r == 0) & (qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if fuse_dq:
        @pl.when((ki == 0) & (r == 0) & (qi == 0))
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = qi * bq + spec.offset
    k_start = ki * spec.block_k

    def compute(masked: bool):
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        st = _scores(k, q, sm_scale)                            # (bk, bq)
        if masked:
            st = _mask(st, q_start, k_start, transposed=True)
        pt = jnp.exp(st - lse_ref[0, :1, :])
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, NT,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - di_ref[0, :1, :])).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            dst, q, NN, preferred_element_type=jnp.float32)
        if fuse_dq:
            rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)
            dq_acc[r, rows, :] += jax.lax.dot_general(
                dst, k, TN, preferred_element_type=jnp.float32)

    _on_live_tiles(compute, q_start, k_start, spec)

    @pl.when((r == rep - 1) & (qi == num_q_blocks - 1))
    def _finalize():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if fuse_dq:
        @pl.when((ki == num_k_blocks - 1) & (r == rep - 1)
                 & (qi == num_q_blocks - 1))
        def _finalize_dq():
            dq_ref[...] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def flash_bwd_dkv(q, k, v, do, lse, di, spec: _Spec):
    """(dk, dv, dq), dq None where `_fuse_dq` does not hold."""
    BH, Sq, Sk, hd, H, Hkv, rep = _dims(q, k, spec)
    bq, bk = spec.block_q, spec.block_k
    nq, nk = Sq // bq, Sk // bk

    def q_row(bkv, ki, r, qi):
        """(query head row of q, its q block): the r-th query head of kv
        head bkv; a skipped q block repeats the first live one."""
        if spec.causal:
            qi = jnp.maximum(qi, _first_live_q(ki, spec, nq))
        return (bkv // Hkv) * H + (bkv % Hkv) * rep + r, qi

    def q_index(bkv, ki, r, qi):
        row, qi = q_row(bkv, ki, r, qi)
        return (row, qi, 0)

    def lse_index(bkv, ki, r, qi):
        row, qi = q_row(bkv, ki, r, qi)
        return (row, 0, qi)

    q_spec = pl.BlockSpec((1, bq, hd), q_index)
    row_spec = pl.BlockSpec((1, SUBLANES, bq), lse_index)
    kv_spec = pl.BlockSpec((1, bk, hd), lambda bkv, ki, r, qi: (bkv, ki, 0))
    out_specs = [kv_spec, kv_spec]
    out_shape = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                 jax.ShapeDtypeStruct(v.shape, v.dtype)]
    scratch = [pltpu.VMEM((bk, hd), jnp.float32),
               pltpu.VMEM((bk, hd), jnp.float32)]
    sem = ("parallel", "parallel", "arbitrary", "arbitrary")
    fuse_dq = _fuse_dq(q, k, spec)
    if fuse_dq:
        out_specs.append(pl.BlockSpec((rep, Sq, hd),
                                      lambda bkv, ki, r, qi: (bkv, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(q.shape, q.dtype))
        scratch.append(pltpu.VMEM((rep, Sq, hd), jnp.float32))
        sem = ("parallel", "arbitrary", "arbitrary", "arbitrary")
    kernel = functools.partial(_dkv_kernel, spec=spec,
                               sm_scale=1.0 / (hd ** 0.5), rep=rep,
                               num_q_blocks=nq, num_k_blocks=nk,
                               fuse_dq=fuse_dq)
    out = pl.pallas_call(
        kernel,
        grid=(k.shape[0], nk, rep, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=sem),
        interpret=spec.interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, di)
    return tuple(out) if fuse_dq else (*out, None)


# ------------------------------------------------------------- custom VJP

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, spec: _Spec):
    o, lse = flash_fwd(q, k, v, spec)
    return o, lse[:, 0, :]


def _flash_fwd(q, k, v, spec: _Spec):
    o, lse = flash_fwd(q, k, v, spec)
    return (o, lse[:, 0, :]), (q, k, v, o, lse)


def _flash_bwd(spec: _Spec, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    # dS = P ∘ (dP - D) with D = rowsum(dO ∘ O); a cotangent on lse adds
    # P ∘ dlse, which is the same as taking dlse off D.
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to((di - dlse)[:, None, :], lse.shape)
    do = do.astype(q.dtype)
    dk, dv, dq = flash_bwd_dkv(q, k, v, do, lse, di, spec)
    if dq is None:
        dq = flash_bwd_dq(q, k, v, do, lse, di, spec)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_bhsd(q, k, v, *, causal: bool, n_q_heads: int,
                         block_q: int = 128, block_k: int = 128,
                         q_offset: int = 0, interpret: bool = False):
    """q (B·H, Sq, hd); k, v (B·Hkv, Sk, hd) -> (o (B·H, Sq, hd),
    lse (B·H, Sq) float32). Differentiable in q, k and v (and through
    lse) by the Pallas backward kernels."""
    Sq, Sk = q.shape[1], k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    spec = _Spec(causal=causal, n_q_heads=n_q_heads, block_q=block_q,
                 block_k=block_k, offset=q_offset + Sk - Sq,
                 interpret=interpret)
    return _flash(q, k, v, spec)
