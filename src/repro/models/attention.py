"""Attention: MHA/GQA/MQA with qk-norm, QKV bias, RoPE, KV-cache decode.

Interchangeable inner implementations (same math):
  - "naive":   materializes (B,H,S,S) scores — reference / tiny tests only.
  - "chunked": flash-style streaming over KV blocks in pure jnp — bounded
               memory. The default: `inner_attention` runs it as the
               Pallas flash kernel (repro.kernels.flash_attention, with
               its own backward) wherever `flash_blocks` says the kernel
               applies — on a TPU, for shapes it tiles, off a multi-device
               mesh — and as the jnp scan everywhere else (the CPU).
  - "pallas":  the Pallas flash kernel, whatever the platform.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.sharding.partition import active_mesh, shard_constraint

from .config import ModelConfig
from .layers import _init, apply_rope, rmsnorm, rmsnorm_init

Params = Any

NEG_INF = -1e30


def attention_init(key, cfg: ModelConfig, dtype):
    hd = cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init(ks[0], (cfg.d_model, cfg.n_heads * hd), dtype),
        "wk": _init(ks[1], (cfg.d_model, cfg.n_kv_heads * hd), dtype),
        "wv": _init(ks[2], (cfg.d_model, cfg.n_kv_heads * hd), dtype),
        "wo": _init(ks[3], (cfg.n_heads * hd, cfg.d_model), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions, compute_dtype):
    B, S, _ = x.shape
    hd = cfg.head_dim
    xc = x.astype(compute_dtype)
    q = xc @ p["wq"].astype(compute_dtype)
    k = xc @ p["wk"].astype(compute_dtype)
    v = xc @ p["wv"].astype(compute_dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(compute_dtype)
        k = k + p["bk"].astype(compute_dtype)
        v = v + p["bv"].astype(compute_dtype)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # keep batch data-sharded and heads model-sharded through the attention
    # core — without these constraints GSPMD re-shards activations when the
    # head count doesn't divide the model axis (28/56-head archs) and the
    # batch axis silently replicates.
    q = shard_constraint(q, "batch", None, "heads", None)
    k = shard_constraint(k, "batch", None, "kv_heads", None)
    v = shard_constraint(v, "batch", None, "kv_heads", None)
    return q, k, v


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def naive_attention(q, k, v, *, causal: bool, q_offset=0) -> jnp.ndarray:
    """q: (B,Sq,H,hd); k,v: (B,Sk,Hkv,hd). Returns (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    k = _repeat_kv(k, H // Hkv)
    v = _repeat_kv(v, H // Hkv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    if causal:
        qpos = jnp.arange(Sq)[:, None] + q_offset
        kpos = jnp.arange(Sk)[None, :]
        scores = jnp.where(kpos <= qpos, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def chunked_attention(q, k, v, *, causal: bool, q_offset=0,
                      kv_chunk: int = 1024) -> jnp.ndarray:
    """Flash-style online-softmax over KV chunks. Same math as naive.

    Peak memory is O(Sq * kv_chunk) per head instead of O(Sq * Sk).
    """
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    kv_chunk = min(kv_chunk, Sk)
    if Sk % kv_chunk != 0:
        return naive_attention(q, k, v, causal=causal, q_offset=q_offset)
    n_chunks = Sk // kv_chunk

    kc = k.reshape(B, n_chunks, kv_chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, kv_chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    qf = q.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qpos = jnp.arange(Sq)[:, None] + q_offset

    def body(carry, ckv):
        acc, m, denom, idx = carry
        kb, vb = ckv
        # the GQA expansion happens AFTER the heads constraint: K/V are
        # replicated over the model axis (small), so each chip expands
        # only its local q-heads' slice — no repeated-tensor gathers.
        kb = _repeat_kv(kb, n_rep).astype(jnp.float32)
        vb = _repeat_kv(vb, n_rep).astype(jnp.float32)
        kb = shard_constraint(kb, "batch", None, "heads", None)
        vb = shard_constraint(vb, "batch", None, "heads", None)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb) * scale
        if causal:
            kpos = idx * kv_chunk + jnp.arange(kv_chunk)[None, :]
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        denom = denom * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
        return (acc, m_new, denom, idx + 1), None

    # flash-backward semantics: recompute the (B,H,Sq,chunk) score/softmax
    # tensors per chunk in the backward pass instead of stacking them over
    # all chunks as scan residuals (which costs n_chunks × B·H·Sq·chunk·4B
    # of HBM and defeats the point of streaming attention).
    body = jax.checkpoint(body,
                          policy=jax.checkpoint_policies.nothing_saveable)

    acc0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    d0 = jnp.zeros((B, H, Sq), jnp.float32)
    (acc, _, denom, _), _ = jax.lax.scan(body, (acc0, m0, d0, 0), (kc, vc))
    out = acc / jnp.maximum(denom[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


FLASH_HEAD_DIMS = (64, 128)      # head widths the flash kernel is built for
FLASH_MIN_BLOCK = 128            # a block spans whole (8, 128) tiles
FLASH_BLOCK = 512                # the largest block; see flash_blocks


def _flash_block(s: int) -> int:
    b = FLASH_BLOCK
    while s % b:
        b //= 2
    return b


def flash_blocks(q_shape, k_shape, *, platform: Optional[str] = None,
                 mesh=None) -> Optional[tuple[int, int]]:
    """(block_q, block_k) of the Pallas flash kernel for these shapes, or
    None where the jnp scan runs instead: off a TPU; on a multi-device
    mesh, since a Mosaic kernel is not partitioned; for a head width the
    kernel is not built for; where Sq or Sk does not tile by 128.

    q_shape (B, Sq, H, hd), k_shape (B, Sk, Hkv, hd). `platform` and `mesh`
    default to what the traced code runs on: `jax.default_backend()` and
    the constraint scope's mesh, which the trainer arms for a sharded step.
    """
    platform = platform or jax.default_backend()
    mesh = active_mesh() if mesh is None else mesh
    if platform != "tpu" or (mesh is not None and mesh.size > 1):
        return None
    Sq, hd, Sk = q_shape[1], q_shape[3], k_shape[1]
    if hd not in FLASH_HEAD_DIMS or Sq % FLASH_MIN_BLOCK \
            or Sk % FLASH_MIN_BLOCK:
        return None
    return _flash_block(Sq), _flash_block(Sk)


def inner_attention(q, k, v, *, causal: bool,
                    impl: str = "chunked") -> jnp.ndarray:
    """The attention core, q (B,Sq,H,hd), k/v (B,Sk,Hkv,hd) -> (B,Sq,H,hd).
    "chunked" takes the flash kernel where `flash_blocks` allows it and
    the jnp scan elsewhere."""
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal)
    if impl not in ("chunked", "pallas"):
        raise ValueError(impl)
    blocks = flash_blocks(q.shape, k.shape) if impl == "chunked" \
        else (FLASH_MIN_BLOCK, FLASH_MIN_BLOCK)
    if blocks is None:
        return chunked_attention(q, k, v, causal=causal)
    from repro.kernels.flash_attention import ops as fa_ops
    return fa_ops.flash_attention(q, k, v, causal=causal, block_q=blocks[0],
                                  block_k=blocks[1])


def attention(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
              positions: Optional[jnp.ndarray] = None,
              causal: bool = True,
              impl: str = "chunked",
              kv_input: Optional[jnp.ndarray] = None,
              compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Full attention block: proj -> inner attention -> output proj.

    kv_input: encoder output (B, S_enc, D) for cross-attention; K/V are then
    projected from it (no RoPE, non-causal).
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if kv_input is not None:
        q, _, _ = _project_qkv(p, x, cfg, None, compute_dtype)
        _, k, v = _project_qkv(p, kv_input, cfg, None, compute_dtype)
        causal = False
    else:
        q, k, v = _project_qkv(p, x, cfg, positions, compute_dtype)
    o = inner_attention(q, k, v, causal=causal, impl=impl)
    o = o.reshape(B, S, cfg.n_heads * cfg.head_dim)
    o = shard_constraint(o, "batch", None, "heads")
    return o @ p["wo"].astype(compute_dtype)


def attention_with_kv(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                      positions=None, impl: str = "chunked",
                      compute_dtype=jnp.bfloat16):
    """Prefill path: returns (out, k, v) so the caller can build a KV cache."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions, compute_dtype)
    o = inner_attention(q, k, v, causal=True, impl=impl)
    o = o.reshape(B, S, cfg.n_heads * cfg.head_dim)
    return o @ p["wo"].astype(compute_dtype), k, v


def project_cross_kv(p: Params, enc_out: jnp.ndarray, cfg: ModelConfig,
                     compute_dtype=jnp.bfloat16):
    """Cross-attention K/V from encoder output (computed once, then cached)."""
    _, k, v = _project_qkv(p, enc_out, cfg, None, compute_dtype)
    return k, v


# ------------------------------------------------------------- decode paths

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  dtype=jnp.bfloat16, abstract: bool = False):
    shape = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    if abstract:
        return {"k": jax.ShapeDtypeStruct(shape, dtype),
                "v": jax.ShapeDtypeStruct(shape, dtype)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cross_decode_attention(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                           cross_k: jnp.ndarray, cross_v: jnp.ndarray,
                           compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Decode-time cross-attention over a static encoder K/V cache."""
    B = x.shape[0]
    q, _, _ = _project_qkv(p, x, cfg, None, compute_dtype)
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kf = _repeat_kv(cross_k.astype(compute_dtype), H // Hkv)
    vf = _repeat_kv(cross_v.astype(compute_dtype), H // Hkv)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32)
    s = s / jnp.sqrt(hd).astype(jnp.float32)
    w = jax.nn.softmax(s, axis=-1).astype(compute_dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, vf).reshape(B, 1, H * hd)
    return o @ p["wo"].astype(compute_dtype)


def decode_attention(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     pos: jnp.ndarray, compute_dtype=jnp.bfloat16):
    """One-token decode. x: (B,1,D); cache_*: (B,Smax,Hkv,hd); pos is a
    scalar (every row at the same position — the training/roofline decode
    cells) or a (B,) vector of *per-row* positions (continuous-batching
    serving: each slot carries its own clock, so ragged occupancy decodes
    exactly like B independent single-sequence streams).

    Returns (out (B,1,D), new_cache_k, new_cache_v). GQA-grouped einsums —
    K/V heads are never replicated to H (a `repeat_kv` here would multiply
    the dominant HBM read of the roofline by H/Hkv). The cache sequence
    axis may be mesh-sharded (flash-decode): the softmax then reduces over
    a sharded axis and GSPMD emits tiny normalizer all-reduces instead of
    gathering the cache.
    """
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions, compute_dtype)
    if per_row:
        # row i's K/V lands at its own position: one batched scatter
        rows = jnp.arange(B)
        cache_k = cache_k.at[rows, pos].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, pos].set(v[:, 0].astype(cache_v.dtype))
    else:
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
    Smax = cache_k.shape[1]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rep = H // Hkv
    qg = q.reshape(B, Hkv, rep, hd)                       # (B,g,r,hd)
    kf = cache_k.astype(compute_dtype)                    # (B,S,g,hd)
    vf = cache_v.astype(compute_dtype)
    s = jnp.einsum("bgrd,bsgd->bgrs", qg, kf).astype(jnp.float32)
    s = s / jnp.sqrt(hd).astype(jnp.float32)
    if per_row:
        mask = (jnp.arange(Smax)[None, :] <= pos[:, None])[:, None, None, :]
    else:
        mask = (jnp.arange(Smax) <= pos)[None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrs,bsgd->bgrd", w.astype(compute_dtype), vf)
    o = o.reshape(B, 1, H * hd)
    return o @ p["wo"].astype(compute_dtype), cache_k, cache_v
