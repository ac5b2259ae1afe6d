"""Applying sharding rules: NamedShardings for states, constraint scope.

`constraint_scope(mesh, rules)` arms `shard_constraint` so model code can
annotate intermediates (e.g. the MoE dispatch tensor) with *logical* axes;
outside a scope the annotation is a no-op, which keeps single-device smoke
tests mesh-free.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .rules import ShardingRules, tree_specs

_CTX: contextvars.ContextVar = contextvars.ContextVar("shard_ctx", default=None)


@contextlib.contextmanager
def constraint_scope(mesh: Mesh, rules: ShardingRules):
    tok = _CTX.set((mesh, rules))
    try:
        yield
    finally:
        _CTX.reset(tok)


def active_mesh() -> Optional[Mesh]:
    """The mesh of the current constraint scope, None outside one."""
    ctx = _CTX.get()
    return None if ctx is None else ctx[0]


def shard_constraint(x: jnp.ndarray, *logical_axes) -> jnp.ndarray:
    """with_sharding_constraint by logical axes; identity outside a scope."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = rules.spec(*logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_shardings(mesh: Mesh, params, rules: ShardingRules):
    """NamedSharding pytree for a parameter pytree."""
    specs = tree_specs(params, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def _divisible(spec: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes that do not divide the corresponding dim evenly."""
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(ax if dim % size == 0 else None)
    return P(*out)


def state_shardings(mesh: Mesh, state, rules: ShardingRules):
    """Shardings for a full train/serve state pytree.

    Falls back to dropping any axis that does not divide the dim — this is
    what keeps odd head counts (e.g. 56 heads on a 16-way model axis) legal:
    the rule is applied where it divides and dropped where it doesn't.
    """
    specs = tree_specs(state, rules)

    def fix(spec, leaf):
        shape = getattr(leaf, "shape", ())
        return _divisible(spec, shape, mesh)

    fixed = jax.tree.map(fix, specs, state,
                         is_leaf=lambda s: isinstance(s, P))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), fixed,
                        is_leaf=lambda s: isinstance(s, P))


def batch_spec(rules: ShardingRules, *, seq_axis: bool = False) -> P:
    """(B, S) token batches: batch over DP axes, optionally seq-parallel."""
    return P(rules.batch, rules.seq if seq_axis else None)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
