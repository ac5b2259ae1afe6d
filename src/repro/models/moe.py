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
absent experts last) and the three products run as one grouped matmul
each over the held experts. T tokens can send up to T·min(k, n_held) rows
here, but they are expected to send T·k·n_held/n_experts (4,096 of 65,536
for 8,192 tokens, top-8, 8 of 128 experts held). So the sorted buffer has
`row_bound` rows: twice the expected rows, in whole row tiles. Its rows
past the held count are pairs of absent experts; they carry weight 0 and
the grouped matmul leaves them zero. The core gathers its rows by token
and sums the weighted products back into their tokens (a segment sum in
float32); each backward pass is the other move, so no buffer of T·k rows
is written.

A call whose held rows exceed the bound runs the same core on every row
the tokens can send instead (a `lax.cond` on the held count;
`stats["full"]`), so the layer stays dropless for any routing. Where the
bound is T·min(k, n_held) (every expert held, decode-sized batches) the
layer is that core alone, with no branch. The branch is a
`jax.custom_vjp` that saves only the core's inputs and, backward, runs
`jax.vjp` of the core its forward ran: a differentiated `lax.cond` would
write the untaken core's residuals, at full size, as zeros.

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


# ---------------------------------------------------------- row moves

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_rows(x, idx, n: int):
    """Rows x[idx] of x's n rows; the backward sums each row's gradient
    back into its row of x, in float32."""
    return x[idx]


def _take_rows_fwd(x, idx, n):
    return x[idx], idx


def _take_rows_bwd(n, idx, g):
    return jax.ops.segment_sum(g.astype(jnp.float32), idx, n) \
        .astype(g.dtype), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


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

def row_bound(T: int, k: int, n_held: int, n_experts: int) -> int:
    """Rows of the sorted buffer on the common path: twice the rows that
    T tokens are expected to send to n_held of n_experts,
    T·k·n_held/n_experts, in whole `GMM_ROW_TILE`s, and at most
    T·min(k, n_held), the most they can send."""
    expected = -(-T * k * n_held // n_experts)
    tiles = -(-2 * expected // GMM_ROW_TILE)
    return min(tiles * GMM_ROW_TILE, T * min(k, n_held))


def _experts(xs, weights, sizes):
    """The held experts' SwiGLU over rows xs sorted by expert."""
    with jax.named_scope("moe.experts"):
        wg, wu, wo = weights
        tiling = gmm_tiling(xs.shape[0], wg.shape[1], wg.shape[2])

        def product(a, w):
            return grouped_matmul(a, w, sizes, tiling=tiling)
        return product(jax.nn.silu(product(xs, wg)) * product(xs, wu), wo)


def _core(rows, xt, w, weights, order, sizes):
    """y (T, D) from the first `rows` sorted rows, which hold every held
    pair: rows xt (T, D), gate weights w (T, k) (0 where the expert is
    not held), the experts' (wi_gate, wi_up, wo), the sorted pairs
    `order` (T·k,) and the rows per group `sizes`. The rows are gathered
    by token and their weighted products summed back into their tokens."""
    T, k = w.shape
    with jax.named_scope("moe.permute"):
        pair = order[:rows]
        token = pair // k
        xs = _take_rows(xt, token, T)                          # (rows, D)
    ys = _experts(xs, weights, sizes)
    with jax.named_scope("moe.unpermute"):
        weighted = ys.astype(jnp.float32) \
            * w.reshape(-1)[pair].astype(jnp.float32)[:, None]
        return jax.ops.segment_sum(weighted, token, T).astype(xt.dtype)


def _cores(rows, w, weights):
    """`_core` on every row the tokens can send and on `rows` rows, in
    `lax.cond`'s order (true first)."""
    T, k = w.shape
    return (functools.partial(_core, T * min(k, weights[0].shape[0])),
            functools.partial(_core, rows))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sized_core(rows, full, xt, w, weights, order, sizes):
    """`_core` on every row where `full`, else on the first `rows`."""
    return jax.lax.cond(full, *_cores(rows, w, weights),
                        xt, w, weights, order, sizes)


def _sized_core_fwd(rows, full, xt, w, weights, order, sizes):
    res = (full, xt, w, weights, order, sizes)
    return _sized_core(rows, *res), res


def _sized_core_bwd(rows, res, g):
    full, xt, w, weights, order, sizes = res

    def grads(core):
        def run(xt, w, weights):
            return core(xt, w, weights, order, sizes)
        return lambda: jax.vjp(run, xt, w, weights)[1](g)
    dx, dw, dweights = jax.lax.cond(
        full, *map(grads, _cores(rows, w, weights)))
    return None, dx, dw, dweights, None, None


_sized_core.defvjp(_sized_core_fwd, _sized_core_bwd)


def moe_mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig,
            compute_dtype=jnp.bfloat16):
    """x: (B, S, D) -> (y (B, S, D), stats).

    y is the held experts' part of the layer's output. stats: "counts"
    (E,) and "probs" (E,), the (token, choice) pairs and the router
    probability each expert drew over the T tokens (the load-balancing
    loss's sums), "rows" (n_held,) int32, the rows each held expert
    computed, and "full" int32, 1 where they were more than `row_bound`
    and the call ran its core on every row the tokens can send."""
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
        sizes = jnp.sum(group[:, None] == jnp.arange(Eh + 1), axis=0,
                        dtype=jnp.int32)
        stats["rows"] = sizes[:Eh]
        rows = row_bound(T, k, Eh, E)
        full = jnp.sum(sizes[:Eh]) > rows
        stats["full"] = full.astype(jnp.int32)

    with jax.named_scope("moe.unpermute"):
        held = (group < Eh).reshape(T, k)
        w = jnp.where(held, gate, 0.0).astype(compute_dtype)
    weights = tuple(p[n].astype(compute_dtype)
                    for n in ("wi_gate", "wi_up", "wo"))
    if rows == T * min(k, Eh):              # no routing can exceed it
        y = _core(rows, xt, w, weights, order, sizes)
    else:
        y = _sized_core(rows, full, xt, w, weights, order, sizes)
    return y.reshape(B, S, D), stats
