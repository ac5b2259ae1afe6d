"""The plain reference against the program's Model at a small size on the
CPU, with the program computing in float32 so that the two must agree to
rounding: logits, loss, gradients and AdamW, with QKV bias, grouped-query
attention and the tied head. The model, weights and reference come from
the configuration's architecture module, as a run finds them."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import dense
from bench.weights import check_layout
from bench.tests.tiny import TINY_DENSE

CONFIGS = {
    "gqa-bias": TINY_DENSE,
    "mha": {**TINY_DENSE, "num_key_value_heads": 4, "qkv_bias": False,
            "rope_theta": 1e6, "rms_norm_eps": 1e-6},
}


def _program(cfg):
    harness.load_repro()
    from repro.models.model import Model
    mc = dataclasses.replace(harness.arch(cfg).model_config(cfg),
                             compute_dtype="float32")
    return Model(mc)


def _batch(cfg, seed=0, rows=2, seq=24):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab_size"], (rows, seq + 1))
    return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32)}


@pytest.fixture(params=sorted(CONFIGS))
def case(request):
    cfg = CONFIGS[request.param]
    arch = harness.arch(cfg)
    return (cfg, _program(cfg), arch.make_weights(cfg, harness.seed_key(3, 1)),
            arch)


def test_weights_match_program_layout(case):
    cfg, model, params, _ = case
    check_layout(params, model.abstract_params())


def test_logits(case):
    cfg, model, params, arch = case
    b = _batch(cfg)
    with jax.default_matmul_precision("highest"):
        got, _ = model.logits(params, b)
        ref = arch.logits(params, arch.hidden(params, b["tokens"], cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_loss_and_grads(case):
    cfg, model, params, arch = case
    b = _batch(cfg, seed=1)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, b), has_aux=True)(params)
        rloss, rgrads = arch.loss_and_grads(params, b["tokens"],
                                            b["labels"], cfg)
    assert abs(float(loss) - float(rloss)) < 1e-4 * abs(float(rloss))
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(rgrads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-3, atol=1e-6)


def test_adamw_matches_program_optimizer(case):
    """The AdamW that the dense reference's `train` runs."""
    cfg, model, params, _ = case
    from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update
    opt = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "weight_decay": 0.1, "clip_norm": 1.0, "warmup_steps": 2,
           "total_steps": 10, "min_lr_ratio": 0.1}
    ocfg = AdamWConfig(**opt)
    p, st = params, adamw_init(params)
    rp = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    for count in (1, 2, 3):
        g = jax.tree.map(lambda x: jnp.full_like(x, 0.01 * count) + 0.1 * x,
                         params)
        p, st, _ = adamw_update(p, g, st, ocfg)
        rp, m, v, _ = dense._adamw(rp, g, m, v,
                                   jnp.float32(dense.lr_at(opt, count)),
                                   jnp.float32(count), opt["b1"], opt["b2"],
                                   opt["eps"], opt["weight_decay"],
                                   opt["clip_norm"])
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(rp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_fp8_control_departs_from_float32(case):
    cfg, model, params, arch = case
    b = _batch(cfg, seed=2)
    with jax.default_matmul_precision("highest"):
        hi = arch.hidden(params, b["tokens"], cfg, "f32")
        lo = arch.hidden(params, b["tokens"], cfg, "fp8")
    rel = float(jnp.linalg.norm(hi - lo) / jnp.linalg.norm(hi))
    assert 1e-3 < rel < 0.5
