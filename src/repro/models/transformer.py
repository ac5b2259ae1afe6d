"""Layer-stack composition for every architecture family.

All stacks scan over layers with stacked parameters (leading L axis) so the
compiled HLO contains one while-loop body per homogeneous block type — this
keeps 512-way GSPMD compiles fast and memory-bounded. Hybrid (zamba2-style)
stacks scan over *groups* of `attn_every` mamba layers followed by one
application of a weight-shared attention block.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import attention as attn_mod
from . import mamba as mamba_mod
from . import moe as moe_mod
from .config import ModelConfig
from .layers import mlp, mlp_init, rmsnorm, rmsnorm_init

Params = Any


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Execution knobs (hillclimb levers) — static under jit."""
    attn_impl: str = "chunked"        # naive | chunked | pallas (see
                                      # attention.inner_attention)
    remat_policy: str = "full"        # none | full | dots
    xent_chunks: int = 4
    scan_layers: bool = True
    microbatches: int = 1             # grad-accumulation inner loop
    seq_parallel: bool = False        # sequence-shard the residual stream


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if policy == "outs":
        # save each sublayer's post-all-reduce output: backward recompute
        # then skips re-running the forward TP collectives (≈1/3 of the
        # activation all-reduce traffic) for ~2×(B,S,D) bf16 per layer
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.
                              save_only_these_names(
                                  "attn_out", "mlp_out", "moe_out",
                                  "mamba_out"))
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    raise ValueError(policy)


# -------------------------------------------------------------- block defs

def dense_block_init(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": attn_mod.attention_init(k1, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype,
                        cfg.mlp_gated),
    }


def dense_block(p, x, cfg: ModelConfig, ec: ExecConfig, positions, dt):
    from repro.sharding.partition import shard_constraint

    def sp(t):
        # Megatron-style sequence parallelism: the residual stream lives
        # sequence-sharded over the model axis between sublayers; GSPMD
        # turns the row-parallel all-reduce into reduce-scatter(+gather)
        # and norms/adds run 1/TP-sized.
        return shard_constraint(t, "batch", "seq", None) \
            if ec.seq_parallel else t

    h = sp(x + checkpoint_name(
        attn_mod.attention(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                           cfg, positions=positions, impl=ec.attn_impl,
                           compute_dtype=dt), "attn_out"))
    h = sp(h + checkpoint_name(
        mlp(p["mlp"], rmsnorm(p["ln2"], h, cfg.norm_eps), dt), "mlp_out"))
    return h


def moe_block_init(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": attn_mod.attention_init(k1, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "moe": moe_mod.moe_init(k2, cfg, dtype),
    }


def moe_block(p, x, cfg: ModelConfig, ec: ExecConfig, positions, dt):
    h = x + checkpoint_name(
        attn_mod.attention(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                           cfg, positions=positions, impl=ec.attn_impl,
                           compute_dtype=dt), "attn_out")
    y, stats = moe_mod.moe_mlp(p["moe"], rmsnorm(p["ln2"], h, cfg.norm_eps),
                               cfg, dt)
    return h + checkpoint_name(y, "moe_out"), stats


def mamba_block_init(key, cfg: ModelConfig, dtype):
    return {
        "ln": rmsnorm_init(cfg.d_model, dtype),
        "mamba": mamba_mod.mamba_init(key, cfg, dtype),
    }


def mamba_block(p, x, cfg: ModelConfig, dt):
    return x + checkpoint_name(
        mamba_mod.mamba_forward(p["mamba"],
                                rmsnorm(p["ln"], x, cfg.norm_eps),
                                cfg, dt), "mamba_out")


def encdec_block_init(key, cfg: ModelConfig, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": attn_mod.attention_init(k1, cfg, dtype),
        "ln_x": rmsnorm_init(cfg.d_model, dtype),
        "cross": attn_mod.attention_init(k2, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, dtype,
                        cfg.mlp_gated),
    }


def encdec_block(p, x, enc_out, cfg: ModelConfig, ec: ExecConfig, positions, dt):
    h = x + attn_mod.attention(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps),
                               cfg, positions=positions, impl=ec.attn_impl,
                               compute_dtype=dt)
    h = h + attn_mod.attention(p["cross"], rmsnorm(p["ln_x"], h, cfg.norm_eps),
                               cfg, kv_input=enc_out, impl=ec.attn_impl,
                               compute_dtype=dt)
    h = h + mlp(p["mlp"], rmsnorm(p["ln2"], h, cfg.norm_eps), dt)
    return h


# ------------------------------------------------------------- stack: init

def _stack_init(key, n: int, block_init, *args):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: block_init(k, *args))(keys)


def stack_init(key, cfg: ModelConfig, dtype) -> Params:
    """Stacked layer params for the decoder stack of any family."""
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return {"layers": _stack_init(key, cfg.n_layers, dense_block_init, cfg, dtype)}
    if fam == "moe":
        return {"layers": _stack_init(key, cfg.n_layers, moe_block_init, cfg, dtype)}
    if fam == "ssm":
        return {"layers": _stack_init(key, cfg.n_layers, mamba_block_init, cfg, dtype)}
    if fam == "hybrid":
        k1, k2, k3 = jax.random.split(key, 3)
        G, tail = divmod(cfg.n_layers, cfg.attn_every)
        p = {"shared": dense_block_init(k1, cfg, dtype)}
        grouped = _stack_init(k2, G * cfg.attn_every, mamba_block_init, cfg, dtype)
        p["layers"] = jax.tree.map(
            lambda a: a.reshape(G, cfg.attn_every, *a.shape[1:]), grouped)
        if tail:
            p["tail"] = _stack_init(k3, tail, mamba_block_init, cfg, dtype)
        return p
    if fam == "encdec":
        k1, k2 = jax.random.split(key)
        return {
            "enc_layers": _stack_init(k1, cfg.n_enc_layers, dense_block_init, cfg, dtype),
            "layers": _stack_init(k2, cfg.n_layers, encdec_block_init, cfg, dtype),
        }
    raise ValueError(fam)


# ---------------------------------------------------------- stack: forward

def _scan_layers(body, x, layers, ec: ExecConfig):
    """(x, each layer's second output stacked on a leading axis)."""
    body = _remat(body, ec.remat_policy)
    if ec.scan_layers:
        return jax.lax.scan(body, x, layers)
    n = jax.tree.leaves(layers)[0].shape[0]
    outs = []
    for i in range(n):
        x, out = body(x, jax.tree.map(lambda a: a[i], layers))
        outs.append(out)
    return x, jax.tree.map(lambda *o: jnp.stack(o), *outs)


def _scan_blocks(body, x, layers, ec: ExecConfig):
    x, aux = _scan_layers(body, x, layers, ec)
    return x, jnp.sum(aux)


def stack_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig, ec: ExecConfig,
                  positions, dt, enc_out: Optional[jnp.ndarray] = None):
    """x: (B,S,D) -> ((B,S,D), aux): a loss scalar (0 but for moe); for
    moe the router's per-layer sums (`moe_mlp`'s stats, stacked)."""
    fam = cfg.family

    if fam in ("dense", "vlm"):
        def body(h, lp):
            return dense_block(lp, h, cfg, ec, positions, dt), jnp.zeros((), jnp.float32)
        return _scan_blocks(body, x, p["layers"], ec)

    if fam == "moe":
        def body(h, lp):
            return moe_block(lp, h, cfg, ec, positions, dt)
        return _scan_layers(body, x, p["layers"], ec)

    if fam == "ssm":
        def body(h, lp):
            return mamba_block(lp, h, cfg, dt), jnp.zeros((), jnp.float32)
        return _scan_blocks(body, x, p["layers"], ec)

    if fam == "hybrid":
        shared = p["shared"]

        def group_body(h, gp):
            def inner(hh, lp):
                return mamba_block(lp, hh, cfg, dt), None
            h, _ = jax.lax.scan(inner, h, gp)
            h = dense_block(shared, h, cfg, ec, positions, dt)
            return h, jnp.zeros((), jnp.float32)

        x, aux = _scan_blocks(group_body, x, p["layers"], ec)
        if "tail" in p:
            def tail_body(h, lp):
                return mamba_block(lp, h, cfg, dt), jnp.zeros((), jnp.float32)
            x, aux2 = _scan_blocks(tail_body, x, p["tail"], ec)
            aux = aux + aux2
        return x, aux

    if fam == "encdec":
        assert enc_out is not None

        def body(h, lp):
            return encdec_block(lp, h, enc_out, cfg, ec, positions, dt), \
                jnp.zeros((), jnp.float32)
        return _scan_blocks(body, x, p["layers"], ec)

    raise ValueError(fam)


def encoder_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                    ec: ExecConfig, dt):
    """Bidirectional encoder for enc-dec archs. x: (B,S_enc,D)."""
    positions = jnp.arange(x.shape[1])[None, :]

    def body(h, lp):
        h2 = h + attn_mod.attention(
            lp["attn"], rmsnorm(lp["ln1"], h, cfg.norm_eps), cfg,
            positions=positions, causal=False, impl=ec.attn_impl,
            compute_dtype=dt)
        h2 = h2 + mlp(lp["mlp"], rmsnorm(lp["ln2"], h2, cfg.norm_eps), dt)
        return h2, jnp.zeros((), jnp.float32)

    out, _ = _scan_blocks(body, x, p["enc_layers"], ec)
    return out
