"""The Qwen3-MoE family on one chip of an expert-parallel deployment: what
the drivers, tools and tests use of the architecture (the names are
those of bench/arch/dense.py), bound to its weights, its float32
reference (bench/reference/qwen3_moe.py) and its operation count.

The configuration's `num_experts` counts the experts held here, from
`first_expert`; the router spans `router_experts`. `vocab_size` is the
chip's slice of the vocabulary, for the embedding and the untied head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights
from bench.reference import qwen3_moe as reference

train = reference.train
hidden = reference.hidden
logits = reference.logits
loss_and_grads = reference.loss_and_grads


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family=cfg["family"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qk_norm=True,
        qkv_bias=cfg["attention_bias"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        n_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        experts_held=cfg["num_experts"], expert_offset=cfg["first_expert"],
        router_aux_coef=cfg["router_aux_loss_coef"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


def shapes(cfg: dict) -> dict:
    """{path: (shape, kind)} of every leaf, in the program's layout."""
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    E, Eh = cfg["router_experts"], cfg["num_experts"]
    F, V = cfg["moe_intermediate_size"], cfg["vocab_size"]
    return {
        "embedding/table": ((V, D), "embed"),
        "lm_head/table": ((V, D), "head"),
        "ln_f/scale": ((D,), "norm"),
        "stack/layers/ln1/scale": ((L, D), "norm"),
        "stack/layers/ln2/scale": ((L, D), "norm"),
        "stack/layers/attn/wq": ((L, D, H * hd), "matrix"),
        "stack/layers/attn/wk": ((L, D, Hkv * hd), "matrix"),
        "stack/layers/attn/wv": ((L, D, Hkv * hd), "matrix"),
        "stack/layers/attn/wo": ((L, H * hd, D), "matrix"),
        "stack/layers/attn/q_norm/scale": ((L, hd), "norm"),
        "stack/layers/attn/k_norm/scale": ((L, hd), "norm"),
        "stack/layers/moe/router": ((L, D, E), "matrix"),
        "stack/layers/moe/wi_gate": ((L, Eh, D, F), "matrix"),
        "stack/layers/moe/wi_up": ((L, Eh, D, F), "matrix"),
        "stack/layers/moe/wo": ((L, Eh, F, D), "matrix"),
    }


def _leaf(key, shape, kind):
    """bench/weights.py's scales; the untied head at 1/sqrt(hidden), so
    logits sit near unit scale."""
    if kind == "head":
        return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(
            jnp.float32(shape[-1]))
    return weights._leaf(key, shape, kind)


def make_weights(cfg: dict, key) -> dict:
    """The weights as a nested dict, on the default device, in one jitted
    call."""
    spec = shapes(cfg)
    dtype = jnp.dtype(cfg["param_dtype"])

    @jax.jit
    def build(key):
        flat = {path: _leaf(jax.random.fold_in(key, i), shape,
                            kind).astype(dtype)
                for i, (path, (shape, kind)) in enumerate(sorted(
                    spec.items()))}
        return weights._nest(flat)

    return build(key)


# ------------------------------------------------------------- counting

def expert_rows(cfg: dict, rows: int, seq: int) -> float:
    """Rows the held experts expect in one layer of one step: every token's
    k choices, the held share of them."""
    return rows * seq * cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]


def forward(cfg: dict, rows: int, seq: int) -> float:
    """Operations of the forward pass (bench/flops.py's convention: half
    the causal square, nothing recomputed): attention, the router at its
    full width, the held experts at their expected rows, the head over the
    slice."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    tokens = rows * seq
    attn = 2 * tokens * (2 * D * H * hd + 2 * D * Hkv * hd) \
        + 2 * 2 * rows * H * hd * seq * seq / 2
    router = 2 * tokens * D * cfg["router_experts"]
    experts = 3 * 2 * expert_rows(cfg, rows, seq) * D \
        * cfg["moe_intermediate_size"]
    return L * (attn + router + experts) + 2 * tokens * D * cfg["vocab_size"]


def train_step_flops(cfg: dict, rows: int, seq: int) -> float:
    """One optimizer step: the forward pass and twice it for the backward."""
    return 3 * forward(cfg, rows, seq)


def gmm_step_flops(cfg: dict, rows: int, seq: int) -> float:
    """Operations the grouped-matmul kernel runs in one step at the
    expected rows: three products a layer, each run four times (the
    forward, its recompute under the step's full rematerialisation, and
    the backward's products for the input and for the weights)."""
    return cfg["num_hidden_layers"] * 4 * 3 * 2 \
        * expert_rows(cfg, rows, seq) * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"]


def n_params(cfg: dict) -> int:
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    per = D * H * hd + 2 * D * Hkv * hd + H * hd * D + 2 * hd \
        + D * cfg["router_experts"] \
        + 3 * cfg["num_experts"] * D * cfg["moe_intermediate_size"] + 2 * D
    heads = 1 if cfg["tie_word_embeddings"] else 2
    return L * per + heads * D * cfg["vocab_size"] + D
