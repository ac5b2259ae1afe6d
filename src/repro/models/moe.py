"""Top-k routed Mixture-of-Experts MLP over the experts held here, dropless.

The layer is told which experts it holds (`ModelConfig.n_held` of the
router's `n_experts`, from `expert_offset`): an expert-parallel deployment
puts a slice of every layer's experts on each chip. The router keeps its
full width: softmax over all `n_experts` logits in float32, top-k, the k
weights renormalised. Every (token, choice) pair whose expert is held here
is computed, whatever the imbalance (no capacity, nothing dropped); pairs
routed to experts held elsewhere add nothing here. This layer has no
exchange of its own: on one chip it computes its own experts' part of the
result and that partial result goes on to the next layer.

The pairs are sorted by expert (held experts first, in order; pairs of
absent experts last), the three products run as one grouped matmul each
over the held experts, and the weighted rows are gathered back per token.
Both moves are gathers by a permutation, so the backward pass is a gather
by the inverse permutation and never a scatter.

The grouped matmul is the Pallas kernel `gmm` (megablox, with its own
backward: `gmm` for the input, `tgmm` for the weights) on a TPU, off a
multi-device mesh, for shapes it tiles (`gmm_tiling`); `jax.lax.ragged_dot`
everywhere else.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.sharding.partition import active_mesh

from .config import ModelConfig
from .layers import _init

Params = Any

GMM_ROW_TILE = 256         # rows a tile of the grouped matmul spans


def moe_init(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    E, Eh, D, F = cfg.n_experts, cfg.n_held, cfg.d_model, cfg.d_ff
    return {
        "router": _init(ks[0], (D, E), dtype),
        "wi_gate": _init(ks[1], (Eh, D, F), dtype),
        "wi_up": _init(ks[2], (Eh, D, F), dtype),
        "wo": _init(ks[3], (Eh, F, D), dtype),
    }


def route(logits: jnp.ndarray, k: int):
    """Router logits (T, E) -> (probs (T, E) float32, renormalised top-k
    weights (T, k) float32, expert ids (T, k))."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    return probs, gate / jnp.sum(gate, axis=-1, keepdims=True), idx


def load_balance_loss(counts: jnp.ndarray, prob_sums: jnp.ndarray,
                      n_tokens) -> jnp.ndarray:
    """The load-balancing loss of Qwen3-MoE (HF `load_balancing_loss_func`):
    E * sum_e f_e * P_e, where f_e is the share of tokens that chose expert
    e (summed over the k choices) and P_e its mean router probability, over
    the tokens of every layer taken together. `counts` and `prob_sums` are
    (..., E) sums over `n_tokens` tokens; leading axes (the layers) are
    summed first, as the concatenated router logits are."""
    E = counts.shape[-1]
    c = jnp.sum(counts.reshape(-1, E), axis=0) / n_tokens
    p = jnp.sum(prob_sums.reshape(-1, E), axis=0) / n_tokens
    return E * jnp.sum(c * p)


# ---------------------------------------------------------- permutations

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k: int):
    """Rows x[order // k]: each token once per choice, in `order` (a
    permutation of the T*k pairs, `inv` its inverse)."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, k):
    return x[order // k], inv


def _dispatch_bwd(k, inv, g):
    T = inv.shape[0] // k
    return g[inv].reshape(T, k, -1).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(x, idx, inv):
    """Rows x[idx] for a permutation `idx` whose inverse is `inv`."""
    return x[idx]


def _permute_fwd(x, idx, inv):
    return x[idx], inv


def _permute_bwd(inv, g):
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


# --------------------------------------------------------- grouped matmul

def gmm_tiling(m: int, k: int, n: int, *, platform: Optional[str] = None,
               mesh=None) -> Optional[tuple[int, int, int]]:
    """(tm, tk, tn) of the Pallas grouped matmul for lhs (m, k) and rhs
    (groups, k, n), or None where `ragged_dot` runs instead: off a TPU, on
    a multi-device mesh (a Mosaic kernel is not partitioned), or where a
    width does not tile by 128."""
    platform = platform or jax.default_backend()
    mesh = active_mesh() if mesh is None else mesh
    if platform != "tpu" or (mesh is not None and mesh.size > 1):
        return None
    if m % GMM_ROW_TILE or k % 128 or n % 128:
        return None

    def block(d, top):
        if d <= top:
            return d
        b = top
        while d % b:
            b -= 128
        return b
    return GMM_ROW_TILE, block(k, 1024), block(n, 1024)


def grouped_matmul(lhs, rhs, group_sizes, *, tiling=None,
                   interpret: bool = False):
    """lhs (m, k) rows sorted by group, rhs (g, k, n), group_sizes (g+1,)
    int32 whose last entry counts the rows of no group here -> (m, n) in
    lhs's dtype, zero on the rows of no group. A `tiling` (from
    `gmm_tiling`) takes the Pallas kernel; None takes `ragged_dot`."""
    if tiling is None:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes[:-1],
                                  preferred_element_type=lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    # rhs holds fewer groups than group_sizes counts, so the kernel zeroes
    # the rows of the last group in its forward and backward products
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, None,
                        None, False, interpret)


# ------------------------------------------------------------------ layer

def moe_mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig,
            compute_dtype=jnp.bfloat16):
    """x: (B, S, D) -> (y (B, S, D), stats).

    y is the held experts' part of the layer's output. stats: "counts"
    (E,) and "probs" (E,), the (token, choice) pairs and the router
    probability each expert drew over the T tokens (the load-balancing
    loss's sums), and "rows" (n_held,) int32, the rows each held expert
    computed."""
    B, S, D = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.experts_per_token
    Eh, e0 = cfg.n_held, cfg.expert_offset
    xt = x.reshape(T, D).astype(compute_dtype)

    with jax.named_scope("moe.route"):
        logits = xt @ p["router"].astype(compute_dtype)
        probs, gate, idx = route(logits, k)
        counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                         axis=(0, 1))
        stats = {"counts": counts, "probs": jnp.sum(probs, axis=0)}

    with jax.named_scope("moe.permute"):
        local = idx.reshape(-1) - e0                           # (T*k,)
        group = jnp.where((local >= 0) & (local < Eh), local, Eh)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        sizes = jnp.sum(group[:, None] == jnp.arange(Eh + 1), axis=0,
                        dtype=jnp.int32)
        # at most min(k, Eh) of a token's choices are held here
        M = T * min(k, Eh)
        xs = _dispatch(xt, order, inv, k)[:M]                  # (M, D)
        stats["rows"] = sizes[:Eh]

    with jax.named_scope("moe.experts"):
        tiling = gmm_tiling(M, D, cfg.d_ff)

        def gmm(a, w):
            return grouped_matmul(a, w.astype(compute_dtype), sizes,
                                  tiling=tiling)
        g = gmm(xs, p["wi_gate"])
        u = gmm(xs, p["wi_up"])
        ys = gmm(jax.nn.silu(g) * u, p["wo"])                 # (M, D)

    with jax.named_scope("moe.unpermute"):
        ys = jnp.pad(ys, ((0, T * k - M), (0, 0)))
        pairs = _permute(ys, inv, order).reshape(T, k, D)
        held = (group < Eh).reshape(T, k)
        w = jnp.where(held, gate, 0.0).astype(compute_dtype)
        y = jnp.einsum("tkd,tk->td", pairs, w)
    return y.reshape(B, S, D), stats
