"""Plain reference of the dense family, in jax.numpy and float32.

A decoder-only transformer as Qwen2 describes it (arXiv:2407.10671):
pre-norm RMSNorm blocks, grouped-query attention with a bias on the Q, K
and V projections, rotary position embedding over the two halves of each
head, a SiLU-gated MLP, a final RMSNorm, and an output head; then
token-mean cross-entropy and AdamW with global-norm clipping and a
linear-warmup cosine schedule. No kernels, no cache, no batching tricks:
full causal softmax per block of rows. It imports nothing of the program.

The output head is the transposed embedding (tied), as in Qwen2-0.5B.

`prec` is "f32" (every product at `Precision.HIGHEST`) or "fp8", the
control: every matrix product's operands rounded to float8 (e4m3 forward,
e5m2 for the cotangents of the backward pass) with per-tensor scaling,
which is the step below the bfloat16 compute the configurations state.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------- precision

def _round_to(x, dtype):
    """Round to `dtype` with a per-tensor scale, back in float32."""
    amax = jnp.max(jnp.abs(x))
    top = jnp.float32(jnp.finfo(dtype).max)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _round_to(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round_to(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _q(x, prec: str):
    return _fp8(x) if prec == "fp8" else x


def mm(spec: str, a, b, prec: str):
    """einsum of two float32 operands at the stated precision."""
    return jnp.einsum(spec, _q(a, prec), _q(b, prec), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


# ----------------------------------------------------------------- model

def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope(x, positions, theta):
    """x: (B,S,H,hd); rotate the first half of each head against the
    second by angle position * theta**(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]   # (S,hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, lp: dict, cfg: dict, prec: str):
    B, S, _ = x.shape
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    a = lp["attn"]
    h = rmsnorm(x, lp["ln1"]["scale"], eps)
    q = mm("bsd,de->bse", h, a["wq"], prec)
    k = mm("bsd,de->bse", h, a["wk"], prec)
    v = mm("bsd,de->bse", h, a["wv"], prec)
    if cfg["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    pos = jnp.arange(S)
    q = rope(q.reshape(B, S, H, hd), pos, theta)
    k = rope(k.reshape(B, S, Hkv, hd), pos, theta)
    v = v.reshape(B, S, Hkv, hd)
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=2)             # query head j reads kv j//rep
    v = jnp.repeat(v, rep, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, prec) / np.sqrt(hd).astype(np.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", w, v, prec).reshape(B, S, H * hd)
    x = x + mm("bse,ed->bsd", o, a["wo"], prec)
    m = lp["mlp"]
    h = rmsnorm(x, lp["ln2"]["scale"], eps)
    g = mm("bsd,df->bsf", h, m["wi_gate"], prec)
    u = mm("bsd,df->bsf", h, m["wi_up"], prec)
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["wo"], prec)


def hidden(params: dict, tokens, cfg: dict, prec: str = "f32"):
    """(B,S) tokens -> (B,S,D) hidden after the final norm."""
    x = params["embedding"]["table"].astype(jnp.float32)[tokens]
    layers = params["stack"]["layers"]
    for i in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda t: t[i].astype(jnp.float32), layers)
        x = jax.checkpoint(functools.partial(layer, cfg=cfg, prec=prec))(
            x, lp)
    return rmsnorm(x, params["ln_f"]["scale"].astype(jnp.float32),
                   cfg["rms_norm_eps"])


def logits(params: dict, h, prec: str = "f32"):
    """Output head: the tied embedding, transposed."""
    return mm("...d,vd->...v", h, params["embedding"]["table"].astype(
        jnp.float32), prec)


# -------------------------------------------------------------- training

def cfg_items(cfg: dict) -> tuple:
    """The configuration's scalars, hashable for a static jit argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def _nll_sum(params, tokens, labels, cfg, prec):
    lg = logits(params, hidden(params, tokens, cfg, prec), prec)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


@functools.partial(jax.jit, static_argnames=("cfg_items", "prec"))
def _block_grad(params, tokens, labels, cfg_items, prec):
    return jax.value_and_grad(_nll_sum)(params, tokens, labels,
                                        dict(cfg_items), prec)


def loss_and_grads(params, tokens, labels, cfg: dict, prec: str = "f32",
                   block_rows: int = 1):
    """Token-mean cross-entropy and its gradient, `block_rows` rows of the
    batch at a time so that the float32 attention fits."""
    items = cfg_items(cfg)
    B, S = tokens.shape
    total, grads = 0.0, None
    for r in range(0, B, block_rows):
        nll, g = _block_grad(params, tokens[r:r + block_rows],
                             labels[r:r + block_rows], items, prec)
        total = total + nll
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = B * S
    return total / n, jax.tree.map(lambda g: g / n, grads)


def lr_at(opt: dict, count: int) -> float:
    """Linear warmup to `lr` over `warmup_steps`, then cosine decay to
    `min_lr_ratio * lr` at `total_steps`."""
    lr, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if count < warm:
        return lr * count / max(warm, 1)
    prog = min(max((count - warm) / max(total - warm, 1), 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return lr * (r + (1 - r) * 0.5 * (1 + np.cos(np.pi * prog)))


@jax.jit
def _adamw(params, grads, m, v, lr, count, b1, b2, eps, wd, clip):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / bc1) / (jnp.sqrt(b / bc2) + eps)
                                  + wd * p), params, m, v)
    return params, m, v, g


def train(params, batches, cfg: dict, opt: dict, prec: str = "f32",
          block_rows: int = 1):
    """AdamW from `params` over `batches` ({"tokens", "labels"} each).
    Returns (losses, clipped first gradient, final params)."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, b in enumerate(batches):
        loss, grads = loss_and_grads(params, b["tokens"], b["labels"], cfg,
                                     prec, block_rows)
        count = i + 1
        params, m, v, g = _adamw(
            params, grads, m, v, jnp.float32(lr_at(opt, count)),
            jnp.float32(count), opt["b1"], opt["b2"], opt["eps"],
            opt["weight_decay"], opt["clip_norm"])
        losses.append(float(loss))
        if first is None:
            first = g
    return losses, first, params
