"""The dense decoder family (Qwen2): what the drivers, tools and tests use
of an architecture, bound to the code that implements it.

A configuration file names its architecture by its "arch" key, "dense"
where it has none; `harness.arch(cfg)` loads bench/arch/<arch>.py. A
module of another architecture provides the same names:

    model_config(cfg)                  the program's ModelConfig
    make_weights(cfg, key)             the seeded tree in the program's layout
    train(params, batches, cfg, opt, prec)
                                       the float32 reference ("f32") or its
                                       control ("fp8") over the first steps
    hidden, logits, loss_and_grads     the reference's parts, for the tests
    train_step_flops(cfg, rows, seq)   operations of one optimizer step
    n_params(cfg)                      parameters
"""
from __future__ import annotations

from bench import flops, weights
from bench.reference import dense as reference

make_weights = weights.make
train = reference.train
hidden = reference.hidden
logits = reference.logits
loss_and_grads = reference.loss_and_grads
train_step_flops = flops.train_step
n_params = flops.n_params


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family=cfg["family"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=cfg["qkv_bias"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])
