"""Plain reference of the Qwen3-MoE family, in jax.numpy and float32.

A decoder-only transformer as the Qwen3 Technical Report (arXiv:2505.09388)
and the Hugging Face `Qwen3MoeForCausalLM` describe it: pre-norm RMSNorm
blocks; grouped-query attention with no bias, an RMSNorm over each head of
q and of k (QK-norm) before the rotary embedding over the two halves of
each head; a sparse MLP in every layer, whose router (no bias) takes a
softmax over all `router_experts` logits in float32, keeps the top
`num_experts_per_tok` and renormalises their weights (`norm_topk_prob`),
and whose experts are SiLU-gated MLPs of width `moe_intermediate_size`; a
final RMSNorm and an untied output head. The loss is the token-mean
cross-entropy plus `router_aux_loss_coef` times HF's
`load_balancing_loss_func` over all layers' router logits; then AdamW as
the dense reference runs it. It imports nothing of the program.

The chip's share (the configuration's deployment): of the router's
experts, this chip holds `num_experts` from `first_expert`. Each held
expert is computed over every token and weighted by the token's
renormalised gate for it, which is 0 for a token that did not choose it
(a mask, no sort, no kernel). Choices of experts held elsewhere add
nothing, here as in the program: what goes on to the next layer is this
chip's part of the MoE output. The load-balancing loss needs only the
router, which every chip holds whole, so it is the whole model's.

Departures from HF, each one of form and not of value: attention runs in
blocks of query rows (each block's scores whole, softmax exact) so that
float32 scores at sequence 4096 fit on the chip; HF concatenates the
layers' router logits before the softmax and top-k, which act per row, so
summing each layer's counts and probabilities is the same loss.

`prec` is "f32" or "fp8", the control, as in the dense reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import _adamw, cfg_items, lr_at, mm, rmsnorm, rope

QUERY_BLOCK = 512


def _attention(x, a: dict, cfg: dict, prec: str):
    B, S, _ = x.shape
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = mm("bsd,de->bse", x, a["wq"], prec).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", x, a["wk"], prec).reshape(B, S, Hkv, hd)
    v = mm("bsd,de->bse", x, a["wv"], prec).reshape(B, S, Hkv, hd)
    pos = jnp.arange(S)
    q = rope(rmsnorm(q, a["q_norm"]["scale"], eps), pos, theta)
    k = rope(rmsnorm(k, a["k_norm"]["scale"], eps), pos, theta)
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=2)             # query head j reads kv j//rep
    v = jnp.repeat(v, rep, axis=2)
    bq = min(S, QUERY_BLOCK)
    nb = S // bq
    qb = q.reshape(B, nb, bq, H, hd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = mm("bqhd,bkhd->bhqk", qi, k, prec) / np.sqrt(hd).astype(
            np.float32)
        causal = (i * bq + jnp.arange(bq))[:, None] >= pos[None, :]
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm("bhqk,bkhd->bqhd", w, v, prec)

    o = jax.lax.map(block, (jnp.arange(nb), qb))      # (nb, B, bq, H, hd)
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, S, H * hd)
    return mm("bse,ed->bsd", o, a["wo"], prec)


def route(x, router, cfg: dict, prec: str):
    """Router probabilities over every expert, the top-k weights
    (renormalised) and the chosen experts' ids."""
    probs = jax.nn.softmax(mm("bsd,de->bse", x, router, prec), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return probs, top, idx


def moe(x, m: dict, cfg: dict, prec: str):
    """This chip's part of the sparse MLP, and the router's sums for the
    loss: ((B,S,D), counts (E,), probability sums (E,))."""
    held, first = cfg["num_experts"], cfg["first_expert"]
    probs, top, idx = route(x, m["router"], cfg, prec)
    y = jnp.zeros_like(x)
    for j in range(held):
        gate = jnp.sum(jnp.where(idx == first + j, top, 0.0), axis=-1)
        g = mm("bsd,df->bsf", x, m["wi_gate"][j], prec)
        u = mm("bsd,df->bsf", x, m["wi_up"][j], prec)
        y = y + gate[..., None] * mm("bsf,fd->bsd", jax.nn.silu(g) * u,
                                     m["wo"][j], prec)
    counts = jnp.sum(jax.nn.one_hot(idx, cfg["router_experts"]),
                     axis=(0, 1, 2))
    return y, counts, jnp.sum(probs, axis=(0, 1))


def layer(x, lp: dict, cfg: dict, prec: str):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(rmsnorm(x, lp["ln1"]["scale"], eps), lp["attn"], cfg,
                       prec)
    y, counts, probs = moe(rmsnorm(x, lp["ln2"]["scale"], eps), lp["moe"],
                           cfg, prec)
    return x + y, counts, probs


def _forward(params: dict, tokens, cfg: dict, prec: str):
    x = params["embedding"]["table"].astype(jnp.float32)[tokens]
    layers = params["stack"]["layers"]
    counts, probs = 0.0, 0.0
    for i in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda t: t[i].astype(jnp.float32), layers)
        x, c, p = jax.checkpoint(functools.partial(layer, cfg=cfg,
                                                   prec=prec))(x, lp)
        counts, probs = counts + c, probs + p
    h = rmsnorm(x, params["ln_f"]["scale"].astype(jnp.float32),
                cfg["rms_norm_eps"])
    return h, counts, probs


def hidden(params: dict, tokens, cfg: dict, prec: str = "f32"):
    """(B,S) tokens -> (B,S,D) hidden after the final norm."""
    return _forward(params, tokens, cfg, prec)[0]


def logits(params: dict, h, prec: str = "f32"):
    """The untied output head."""
    return mm("...d,vd->...v", h, params["lm_head"]["table"].astype(
        jnp.float32), prec)


def aux_loss(counts, probs, n_tokens: int):
    """HF's load_balancing_loss_func: E * sum_e f_e * P_e over the tokens
    of all layers, f_e the share of them that chose e, P_e its mean
    router probability."""
    return counts.shape[-1] * jnp.sum((counts / n_tokens)
                                      * (probs / n_tokens))


def _loss(params, tokens, labels, cfg, prec):
    h, counts, probs = _forward(params, tokens, cfg, prec)
    lg = logits(params, h, prec)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    aux = aux_loss(counts, probs, tokens.size * cfg["num_hidden_layers"])
    return jnp.mean(lse - gold) + cfg["router_aux_loss_coef"] * aux


@functools.partial(jax.jit, static_argnames=("cfg_items", "prec"))
def _loss_and_grads(params, tokens, labels, cfg_items, prec):
    return jax.value_and_grad(_loss)(params, tokens, labels,
                                     dict(cfg_items), prec)


def loss_and_grads(params, tokens, labels, cfg: dict, prec: str = "f32"):
    """The loss (token-mean cross-entropy plus the weighted load-balancing
    loss, which couples the rows of a batch) and its gradient, over the
    whole batch at once."""
    return _loss_and_grads(params, tokens, labels, cfg_items(cfg), prec)


def train(params, batches, cfg: dict, opt: dict, prec: str = "f32"):
    """AdamW from `params` over `batches` ({"tokens", "labels"} each).
    Returns (losses, clipped first gradient, final params)."""
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, b in enumerate(batches):
        loss, grads = loss_and_grads(params, b["tokens"], b["labels"], cfg,
                                     prec)
        count = i + 1
        params, m, v, g = _adamw(
            params, grads, m, v, jnp.float32(lr_at(opt, count)),
            jnp.float32(count), opt["b1"], opt["b2"], opt["eps"],
            opt["weight_decay"], opt["clip_norm"])
        losses.append(float(loss))
        if first is None:
            first = g
    return losses, first, params
