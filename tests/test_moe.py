"""The routed-expert layer: the held share of the experts, dropless routing
through a grouped matmul, the load-balancing loss, and the untied head.

The layer is compared with a plain per-token mixture written here: every
token, for each of its top-k experts that is held, adds the expert's
SwiGLU output times its renormalised gate."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import moe
from repro.models.flops import moe_flops
from repro.models.model import Model

E, K, HELD = 16, 4, 4
QWEN3 = get_config("qwen3-moe-30b-a3b")
TINY = dataclasses.replace(
    reduced(QWEN3), n_experts=E, experts_per_token=K, experts_held=HELD,
    expert_offset=4, compute_dtype="float32")


def _share(cfg, offset, held=HELD):
    return dataclasses.replace(cfg, experts_held=held, expert_offset=offset)


def _params(cfg, key=0):
    return moe.moe_init(jax.random.PRNGKey(key), cfg, jnp.float32)


def _slice(p, offset, held=HELD):
    return {"router": p["router"],
            **{k: p[k][offset:offset + held]
               for k in ("wi_gate", "wi_up", "wo")}}


def plain_mixture(p, x, cfg):
    """(T, D) float64: the held experts' part of the layer, token by
    token."""
    x = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    logits = x @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:cfg.experts_per_token]
        gate = probs[t, top] / probs[t, top].sum()
        for e, w in zip(top, gate):
            j = e - cfg.expert_offset
            if 0 <= j < cfg.n_held:
                g = x[t] @ np.asarray(p["wi_gate"][j], np.float64)
                u = x[t] @ np.asarray(p["wi_up"][j], np.float64)
                h = g / (1 + np.exp(-g)) * u
                y[t] += w * (h @ np.asarray(p["wo"][j], np.float64))
    return y


def _x(cfg, B=2, S=8, key=1):
    return jax.random.normal(jax.random.PRNGKey(key), (B, S, cfg.d_model))


def test_held_experts_match_the_plain_mixture():
    x = _x(TINY)
    p = _params(TINY)
    with jax.default_matmul_precision("highest"):
        y, stats = moe.moe_mlp(p, x, TINY, jnp.float32)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, TINY.d_model),
                               plain_mixture(p, x, TINY), rtol=1e-4,
                               atol=1e-5)
    assert stats["rows"].shape == (HELD,)
    assert int(jnp.sum(stats["counts"])) == x.shape[0] * x.shape[1] * K


def test_the_shares_add_up_to_the_whole_layer():
    """Four chips, each holding its own four of the sixteen experts with
    the same router, give parts that add up to the uncut layer."""
    whole = dataclasses.replace(TINY, experts_held=0, expert_offset=0)
    p = _params(whole)
    x = _x(whole)
    with jax.default_matmul_precision("highest"):
        full, _ = moe.moe_mlp(p, x, whole, jnp.float32)
        parts = [moe.moe_mlp(_slice(p, o), x, _share(whole, o),
                             jnp.float32) for o in range(0, E, HELD)]
    np.testing.assert_allclose(np.asarray(sum(y for y, _ in parts)),
                               np.asarray(full), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(full).reshape(-1, whole.d_model),
        plain_mixture(p, x, whole), rtol=1e-4, atol=1e-5)
    rows = np.concatenate([np.asarray(s["rows"]) for _, s in parts])
    np.testing.assert_array_equal(rows, np.asarray(parts[0][1]["counts"]))


def test_routing_is_dropless():
    """A router that sends every token to one held expert: all of them
    are computed (a capacity of 1.25 k T / E rows would drop most)."""
    p = _params(TINY)
    chosen = TINY.expert_offset + 1
    p["router"] = p["router"].at[:, chosen].add(50.0)
    x = jnp.abs(_x(TINY)) + 0.1           # positive: the column wins
    T = x.shape[0] * x.shape[1]
    with jax.default_matmul_precision("highest"):
        y, stats = moe.moe_mlp(p, x, TINY, jnp.float32)
    assert int(stats["rows"][1]) == T
    assert int(stats["rows"][1]) > 1.25 * K * T / E
    np.testing.assert_allclose(np.asarray(y).reshape(T, -1),
                               plain_mixture(p, x, TINY), rtol=1e-4,
                               atol=1e-5)


def test_experts_held_elsewhere_add_nothing():
    p = _params(TINY)
    x = _x(TINY)
    far = dataclasses.replace(TINY, expert_offset=E - HELD)
    p["router"] = p["router"].at[:, :E - HELD].add(50.0)   # never chosen
    y, stats = moe.moe_mlp(p, jnp.abs(x) + 0.1, far, jnp.float32)
    assert float(jnp.max(jnp.abs(y))) == 0.0
    assert int(jnp.sum(stats["rows"])) == 0


def test_load_balance_loss_is_the_hf_definition():
    """E * sum_e f_e P_e over the tokens of every layer together."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(3, 10, E))        # 3 layers of 10 tokens
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    top = np.argsort(-probs, axis=-1)[..., :K]
    counts = np.stack([np.bincount(t.ravel(), minlength=E) for t in top])
    flat_p = probs.reshape(-1, E)
    mask = np.zeros((30, K, E))
    for i, t in enumerate(top.reshape(-1, K)):
        mask[i, np.arange(K), t] = 1
    want = E * np.sum(mask.mean(0) * flat_p.mean(0)[None])
    got = moe.load_balance_loss(jnp.asarray(counts, jnp.float32),
                                jnp.asarray(probs.sum(1), jnp.float32), 30)
    assert float(got) == pytest.approx(want, rel=1e-5)


def test_grouped_matmul_kernel_matches_ragged_dot():
    """The Pallas kernel (interpret mode) against ragged_dot, forward and
    backward, with rows of no held group left zero."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (64, 128))
    w = jax.random.normal(k2, (4, 128, 256))
    sizes = jnp.array([10, 0, 17, 20, 17], jnp.int32)

    def loss(x, w, tiling, interpret):
        return jnp.sum(jnp.sin(moe.grouped_matmul(
            x, w, sizes, tiling=tiling, interpret=interpret)))
    kernel = moe.grouped_matmul(x, w, sizes, tiling=(16, 128, 128),
                                interpret=True)
    np.testing.assert_allclose(np.asarray(kernel),
                               np.asarray(moe.grouped_matmul(x, w, sizes)),
                               rtol=1e-5, atol=1e-4)
    assert float(jnp.max(jnp.abs(kernel[47:]))) == 0.0
    got = jax.grad(loss, (0, 1))(x, w, (16, 128, 128), True)
    want = jax.grad(loss, (0, 1))(x, w, None, False)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_the_model_takes_the_kernel_where_it_tiles(monkeypatch):
    """On a TPU the layer runs the Pallas kernel (forced here, in
    interpret mode) and agrees with the ragged_dot path."""
    p = _params(TINY)
    x = _x(TINY, S=16)
    want, _ = moe.moe_mlp(p, x, TINY, jnp.float32)
    calls = []
    inner = moe.grouped_matmul

    def kernel(a, w, sizes, *, tiling=None):
        calls.append(tiling)
        return inner(a, w, sizes, tiling=(32, a.shape[1], w.shape[2]),
                     interpret=True)
    monkeypatch.setattr(moe, "grouped_matmul", kernel)
    got, _ = moe.moe_mlp(p, x, TINY, jnp.float32)
    assert len(calls) == 3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n,platform,tiling", [
    (65536, 2048, 768, "tpu", (256, 1024, 768)),
    (65536, 768, 2048, "tpu", (256, 768, 1024)),
    (65536, 2048, 768, "cpu", None),
    (100, 2048, 768, "tpu", None),
])
def test_gmm_tiling_follows_platform_and_shape(m, k, n, platform, tiling):
    assert moe.gmm_tiling(m, k, n, platform=platform) == tiling


def test_moe_flops_count_the_dropless_rows():
    B, S = 2, 4096
    cfg = dataclasses.replace(QWEN3, experts_held=8)
    T, D, F = B * S, cfg.d_model, cfg.d_ff
    assert moe_flops(cfg, B, S) == 2 * T * D * 128 + 3 * 2 * (T * 8 * 8 / 128) \
        * D * F
    # holding every expert, each token's eight choices are all computed
    assert moe_flops(QWEN3, B, S) == 2 * T * D * 128 + 3 * 2 * T * 8 * D * F


def test_param_count_holds_the_router_and_the_held_experts():
    cfg = dataclasses.replace(QWEN3, n_layers=4, vocab_size=18992,
                              experts_held=8)
    model = Model(cfg)
    leaves = jax.tree.leaves(model.abstract_params())
    assert cfg.param_count() == sum(int(np.prod(a.shape)) for a in leaves) \
        == 305_351_680
    assert Model(TINY).abstract_params()["stack"]["layers"]["moe"][
        "router"].shape == (TINY.n_layers, TINY.d_model, E)


def test_untied_head_prefill_and_decode_match_the_forward():
    cfg = dataclasses.replace(TINY, compute_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert "lm_head" in params
    assert not np.allclose(np.asarray(params["lm_head"]["table"]),
                           np.asarray(params["embedding"]["table"]))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0,
                              cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        full, _ = model.logits(params, {"tokens": toks})
        logits, state = model.prefill(params, {"tokens": toks[:, :8]},
                                      max_len=12)
        np.testing.assert_allclose(np.asarray(logits[0, -1]),
                                   np.asarray(full[0, 7]), rtol=1e-4,
                                   atol=1e-4)
        for i in range(8, 12):
            logits, state = model.decode_step(params, toks[:, i:i + 1],
                                              state, jnp.int32(i))
            np.testing.assert_allclose(np.asarray(logits[0, 0]),
                                       np.asarray(full[0, i]), rtol=1e-4,
                                       atol=1e-4)


def test_tied_configurations_keep_their_tree():
    cfg = dataclasses.replace(reduced(get_config("qwen2-7b")),
                              tie_embeddings=True)
    assert "lm_head" not in Model(cfg).abstract_params()


def test_trainer_keeps_the_step_metrics_on_the_device():
    from repro.train import AdamWConfig, TokenPipeline, TrainConfig, Trainer
    model = Model(TINY)
    data = TokenPipeline(TINY.vocab_size, 2, 16, seed=1)
    tr = Trainer(model, data, AdamWConfig(), TrainConfig(
        total_steps=2, ckpt_every=100))
    tr.run()
    rows = tr.last_metrics["expert_rows"]
    assert isinstance(rows, jax.Array)
    assert rows.shape == (TINY.n_layers, HELD)
    assert float(tr.last_metrics["aux"]) > 0
