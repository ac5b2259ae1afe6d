"""Pallas TPU selective-scan (Mamba1) kernel.

The recurrence h_t = exp(dt_t⊙A)·h_{t-1} + (dt_t⊙x_t)⊗B_t is sequential in
t but embarrassingly parallel over the d_inner channel axis. The GPU
implementation in the Mamba paper parallelizes with a work-efficient
prefix scan in shared memory; the TPU adaptation instead:

  - tiles d_inner into `block_d`-wide VMEM-resident stripes (grid axis 1),
  - streams the sequence in `chunk`-length tiles (grid axis 2, "arbitrary"
    semantics) carrying the (block_d, ds) state stripe in VMEM scratch,
  - runs the time recurrence as a fori_loop of VPU element-wise ops — on
    TPU the bottleneck is HBM streaming of x/dt (ds≤64 keeps the state in
    registers/VMEM), so a sequential-in-t loop at full VPU width is the
    roofline-appropriate schedule, not a tree scan.

VMEM per program: x,dt tiles 2·(chunk·block_d)·4B, B,C tiles 2·(chunk·ds)·4B,
A stripe block_d·ds·4B, state block_d·ds·4B → ≈1.1 MB at the default
chunk=256, block_d=512, ds=16 — comfortably inside 16 MB VMEM with double
buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, hout_ref, h_ref,
                 *, chunk: int, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...].astype(jnp.float32)                  # (block_d, ds)

    def step(t, h):
        dt_t = dt_ref[0, t, :].astype(jnp.float32)      # (block_d,)
        x_t = x_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)        # (ds,)
        c_t = c_ref[0, t, :].astype(jnp.float32)
        dA = jnp.exp(dt_t[:, None] * A)                 # (block_d, ds)
        h = h * dA + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1)         # (block_d,)
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == num_chunks - 1)
    def _final():
        hout_ref[0, ...] = h_ref[...]


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def selective_scan(x, dt, B, C, A, *, chunk: int = 256, block_d: int = 512,
                   interpret: bool = False):
    """x, dt: (batch,S,di); B, C: (batch,S,ds); A: (di,ds) →
    (y (batch,S,di), h_final (batch,di,ds))."""
    bsz, S, di = x.shape
    ds = B.shape[-1]
    chunk = min(chunk, S)
    while S % chunk != 0:
        chunk //= 2
    block_d = min(block_d, di)
    while di % block_d != 0:
        block_d //= 2
    nc, nd = S // chunk, di // block_d

    kernel = functools.partial(_scan_kernel, chunk=chunk, num_chunks=nc)
    y, h = pl.pallas_call(
        kernel,
        grid=(bsz, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, ds), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((block_d, ds), lambda b, d, c: (d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, block_d, ds), lambda b, d, c: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, S, di), x.dtype),
            jax.ShapeDtypeStruct((bsz, di, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, B, C, A)
    return y, h
