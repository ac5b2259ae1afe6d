"""Weights of a dense configuration, made from the seed on the device in
one jitted call, in the type they are served in.

The tree has the layout the program's dense `Model` takes (layers stacked
on a leading axis); drivers check it against the program's own abstract
parameters before use, so a change of layout fails loudly. The plain
reference reads the same tree, made again from the same seed after the
program's state is freed.

Scales follow trained models more than a fresh init: the embedding (tied
to the output head) at 0.02, so logits sit near unit scale; matrices at
1/sqrt(fan-in); norm scales around 1 and QKV biases away from 0, so that
both take part in every comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def shapes(cfg: dict) -> dict:
    """{path: (shape, kind)} of every leaf."""
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    out = {
        "embedding/table": ((V, D), "embed"),
        "ln_f/scale": ((D,), "norm"),
        "stack/layers/ln1/scale": ((L, D), "norm"),
        "stack/layers/ln2/scale": ((L, D), "norm"),
        "stack/layers/attn/wq": ((L, D, H * hd), "matrix"),
        "stack/layers/attn/wk": ((L, D, Hkv * hd), "matrix"),
        "stack/layers/attn/wv": ((L, D, Hkv * hd), "matrix"),
        "stack/layers/attn/wo": ((L, H * hd, D), "matrix"),
        "stack/layers/mlp/wi_gate": ((L, D, F), "matrix"),
        "stack/layers/mlp/wi_up": ((L, D, F), "matrix"),
        "stack/layers/mlp/wo": ((L, F, D), "matrix"),
    }
    if cfg["qkv_bias"]:
        out["stack/layers/attn/bq"] = ((L, H * hd), "bias")
        out["stack/layers/attn/bk"] = ((L, Hkv * hd), "bias")
        out["stack/layers/attn/bv"] = ((L, Hkv * hd), "bias")
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def _leaf(key, shape, kind):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "embed":
        return 0.02 * z
    if kind == "norm":
        return 1.0 + 0.1 * z
    if kind == "bias":
        return 0.1 * z
    return z / jnp.sqrt(jnp.float32(shape[-2]))


def make(cfg: dict, key) -> dict:
    """The weights as a nested dict, on the default device."""
    spec = shapes(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])

    @jax.jit
    def build(key):
        flat = {}
        for i, (path, (shape, kind)) in enumerate(sorted(spec.items())):
            flat[path] = _leaf(jax.random.fold_in(key, i), shape,
                               kind).astype(dtype)
        return _nest(flat)

    return build(key)


def check_layout(tree, abstract) -> None:
    """Raise unless `tree` has exactly the paths, shapes and dtypes of the
    program's abstract parameters."""
    def flat(t):
        return {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
                for p, x in jax.tree_util.tree_flatten_with_path(t)[0]}
    mine, theirs = flat(tree), flat(abstract)
    if mine != theirs:
        diff = sorted(set(mine.items()) ^ set(theirs.items()))
        raise ValueError(f"weights do not match the program's parameter "
                         f"layout: {diff[:6]}")
