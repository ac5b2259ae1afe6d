"""Logical-axis → mesh-axis sharding rules.

Parameters are named by their pytree path (e.g. "layers/attn/wq"); each rule
maps a path *pattern* plus array rank to a tuple of logical axes, and a
preset maps logical axes onto physical mesh axes. This keeps the model code
free of mesh knowledge: the same pytree lowers under a 1-device CPU test, the
(16,16) pod mesh, or the (2,16,16) multi-pod mesh.

Logical axes used across the codebase:
  "batch"    — per-example axis (data parallel; "pod"+"data" on multi-pod)
  "embed"    — d_model / residual stream (FSDP axis: sharded over "data")
  "heads"    — attention heads / d_ff / d_inner (tensor parallel: "model")
  "kv_heads" — KV heads; sharded over "model" only when it divides evenly
  "expert"   — MoE expert axis (expert parallel: "model")
  "vocab"    — vocabulary (sharded over "model" for the big tables)
  "seq"      — sequence axis (sequence parallel, opt-in)
  None       — replicated
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional

import jax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Mapping of logical axis names to physical mesh axes."""
    batch: Any = None
    embed: Any = None
    heads: Any = None
    kv_heads: Any = None
    expert: Any = None
    vocab: Any = None
    seq: Any = None
    kv_seq: Any = None     # decode KV-cache sequence axis (flash-decode)

    def physical(self, logical: Optional[str]):
        if logical is None:
            return None
        return getattr(self, logical)

    def spec(self, *logical_axes) -> P:
        return P(*(self.physical(a) for a in logical_axes))


# Presets keyed by mesh flavour. "model" carries TP + EP; "data" carries
# FSDP + DP; "pod" extends DP across pods.
PRESETS = {
    # single CPU device / smoke tests: everything replicated
    "single": ShardingRules(),
    # one pod: (data, model). kv_heads are REPLICATED over the model axis
    # (Megatron GQA convention): kv head counts (1/4/8) never divide a
    # 16-way TP axis, and replicating the small K/V lets the GQA head
    # expansion happen locally instead of as a per-chunk all-gather of the
    # repeated tensor (measured: 2×30 GB/step on qwen2-7b train_4k).
    "pod": ShardingRules(
        batch="data", embed="data", heads="model", kv_heads=None,
        expert="model", vocab="model", seq=None),
    # two pods: (pod, data, model); batch over both DP axes
    "multipod": ShardingRules(
        batch=("pod", "data"), embed="data", heads="model", kv_heads=None,
        expert="model", vocab="model", seq=None),
    # serving presets: weights are TP-sharded over "model" but REPLICATED
    # over the data axis (embed=None). There is no optimizer state to
    # justify FSDP at inference, and FSDP-sharded weights cost a full
    # weight all-gather per decoded token (measured: 424 GB/token on
    # yi-34b decode_32k under the train rules).
    # The decode KV cache is sequence-sharded over "model" (kv_seq):
    # kv-head counts rarely divide a 16-way TP axis, and flash-decode
    # (partial softmax per shard + tiny all-reduce of the normalizers)
    # shards the 1 TB 32k-cache 256-way instead of 16-way.
    "pod_serve": ShardingRules(
        batch="data", embed=None, heads="model", kv_heads=None,
        expert="model", vocab="model", seq=None, kv_seq="model"),
    "multipod_serve": ShardingRules(
        batch=("pod", "data"), embed=None, heads="model",
        kv_heads=None, expert="model", vocab="model", seq=None,
        kv_seq="model"),
}


# ------------------------------------------------------------- param rules
#
# (path-regex, logical axes per dim). The FIRST match wins. Patterns match
# the "/"-joined pytree path *suffix*. A leading "L/" dim is added
# automatically for stacked-layer params (rank == len(axes) + 1).

PARAM_RULES: list[tuple[str, tuple]] = [
    # embeddings / the untied output head: vocab × embed
    (r"(embedding|lm_head)/table$", ("vocab", "embed")),
    # attention projections
    (r"attn/wq$|cross/wq$",      ("embed", "heads")),
    (r"attn/wk$|cross/wk$",      ("embed", "kv_heads")),
    (r"attn/wv$|cross/wv$",      ("embed", "kv_heads")),
    (r"attn/wo$|cross/wo$",      ("heads", "embed")),
    (r"attn/b[qkv]$|cross/b[qkv]$", ("heads",)),
    (r"(q|k)_norm/scale$",       (None,)),
    # dense mlp
    (r"mlp/wi_(gate|up)$",       ("embed", "heads")),
    (r"mlp/wo$",                 ("heads", "embed")),
    # MoE: the held experts' tables, expert-sharded; the router replicated
    # on its output axis (it spans every expert)
    (r"moe/router$",             ("embed", None)),
    (r"moe/wi_(gate|up)$",       ("expert", "embed", None)),
    (r"moe/wo$",                 ("expert", None, "embed")),
    # mamba (projections are split per output — see mamba.py)
    (r"mamba/in_(x|z)$",         ("embed", "heads")),
    (r"mamba/in_dt$",            ("embed", "heads")),
    (r"mamba/in_bc$",            ("embed", None)),
    (r"mamba/out_proj$",         ("heads", "embed")),
    (r"mamba/x_proj$",           ("heads", None)),
    (r"mamba/dt_proj$",          (None, "heads")),
    (r"mamba/(conv_w|conv_b|conv_bc_w|conv_bc_b|dt_bias|A_log|D)$", None),
    (r"mamba/norm/scale$",       (None,)),
    # norms and any other small vectors: replicated
    (r"(ln\d?|ln_x|norm)/scale$", (None,)),
    (r"frontend_proj/w$",        ("embed", "heads")),
    (r"frontend_proj/b$",        ("heads",)),
]


def _match_rule(path: str, rank: int):
    for pat, axes in PARAM_RULES:
        if re.search(pat, path):
            if axes is None:
                return P()
            if len(axes) == rank:
                return tuple(axes)
            if len(axes) + 1 == rank:          # stacked-layer leading dim(s)
                return (None,) + tuple(axes)
            if len(axes) + 2 == rank:          # hybrid grouped (G, K, ...)
                return (None, None) + tuple(axes)
    return None


def spec_for_path(path: str, rank: int, rules: ShardingRules) -> P:
    """PartitionSpec for a parameter leaf given its path and rank."""
    m = _match_rule(path, rank)
    if m is None or isinstance(m, P):
        return P()
    return rules.spec(*m)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(p.name)
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def tree_specs(params, rules: ShardingRules):
    """PartitionSpec pytree matching a parameter pytree."""
    def leaf_spec(path, leaf):
        rank = len(getattr(leaf, "shape", ()))
        return spec_for_path(_path_str(path), rank, rules)
    return jax.tree_util.tree_map_with_path(leaf_spec, params)
