"""Public wrapper around the Pallas flash-attention kernels.

Accepts the model-layer layout q (B,Sq,H,hd), k/v (B,Sk,Hkv,hd), flattens
batch×head and dispatches to the kernel, which is differentiable through
its own backward kernels. `interpret=True` runs the kernel bodies in
Python on the CPU (how the tests check them); on a TPU they compile to
Mosaic.
"""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import flash_attention_bhsd
from .ref import flash_attention_ref


def _pick_block(s: int, target: int = 128) -> int:
    b = min(target, s)
    while s % b != 0:
        b //= 2
    return max(b, 1)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, q_offset: int = 0,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B,Sq,H,hd); k,v: (B,Sk,Hkv,hd) -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    if bq < 8 or bk < 8:
        # degenerate tiny shapes: not worth a kernel launch
        return flash_attention_ref(q, k, v, causal=causal)

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, hd)
    of, _ = flash_attention_bhsd(qf, kf, vf, causal=causal, n_q_heads=H,
                                 block_q=bq, block_k=bk, q_offset=q_offset,
                                 interpret=interpret)
    return of.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
