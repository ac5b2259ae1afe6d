"""The qwen3_moe architecture module against the program at a small size on
the CPU: 16 routed experts of which 4 are held here, top-4, QK-norm, an
untied head. The program computes in float32 here, so it must agree with
the float32 reference to rounding; the float8 control must not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import qwen3_moe as ref
from bench.run import execute
from bench.tests.tiny import context
from bench.weights import check_layout

CELL = "train.moe.ckpt"
TINY_MOE = {
    **harness.config("qwen3-30b-a3b-l4e8"),
    "name": "tiny-moe", "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "router_experts": 16, "num_experts": 4,
    "num_experts_per_tok": 4, "first_expert": 4, "vocab_size": 256,
    "compute_dtype": "float32",
}


def _program(cfg):
    harness.load_repro()
    from repro.models.model import Model
    return Model(harness.arch(cfg).model_config(cfg))


def _batch(cfg, seed=0, rows=2, seq=24):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg["vocab_size"], (rows, seq + 1))
    return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32)}


@pytest.fixture(scope="module")
def case():
    arch = harness.arch(TINY_MOE)
    return (_program(TINY_MOE),
            arch.make_weights(TINY_MOE, harness.seed_key(3, 1)), arch)


def test_weights_match_program_layout(case):
    model, params, _ = case
    check_layout(params, model.abstract_params())


def test_logits_through_the_untied_head(case):
    model, params, arch = case
    b = _batch(TINY_MOE)
    with jax.default_matmul_precision("highest"):
        got, _ = model.logits(params, b)
        want = arch.logits(params, arch.hidden(params, b["tokens"],
                                                TINY_MOE))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_loss_and_grads(case):
    model, params, arch = case
    b = _batch(TINY_MOE, seed=1)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, b), has_aux=True)(params)
        rloss, rgrads = arch.loss_and_grads(params, b["tokens"],
                                            b["labels"], TINY_MOE)
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    assert float(metrics["aux"]) > 0
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(rgrads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-3, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_reference(case):
    """Four shares of four experts each, with the same router, add up to
    the reference's whole layer over all sixteen."""
    from repro.models import moe
    _, params, _ = case
    lp = jax.tree.map(lambda t: t[0], params["stack"]["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64))
    whole = dict(TINY_MOE, num_experts=16, first_expert=0)
    k = jax.random.split(jax.random.PRNGKey(6), 3)
    full = {"router": lp["router"],
            **{n: jax.random.normal(kk, (16,) + lp[n].shape[1:]) * 0.1
               for n, kk in zip(("wi_gate", "wi_up", "wo"), k)}}
    parts = []
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.moe(x, full, whole, "f32")
        for first in range(0, 16, 4):
            share = {"router": full["router"],
                     **{n: full[n][first:first + 4]
                        for n in ("wi_gate", "wi_up", "wo")}}
            parts.append(ref.moe(x, share, dict(whole, num_experts=4,
                                                first_expert=first),
                                 "f32")[0])
            mc = harness.arch(whole).model_config(
                dict(whole, num_experts=4, first_expert=first))
            y, _ = moe.moe_mlp(share, x, mc, jnp.float32)
            np.testing.assert_allclose(np.asarray(y), np.asarray(parts[-1]),
                                       rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_counts_match_the_program():
    cfg = harness.config("qwen3-30b-a3b-l4e8")
    arch = harness.arch(cfg)
    model = _program(cfg)
    sizes = sum(int(np.prod(a.shape)) for a in
                jax.tree.leaves(model.abstract_params()))
    assert arch.n_params(cfg) == sizes == 305_351_680
    assert arch.expert_rows(cfg, 2, 4096) == 2 * 4096 * 8 * 8 / 128
    from repro.models.flops import forward_flops
    # the program's analytic count: the same forward, the causal square
    # halved (flash)
    assert arch.forward(cfg, 2, 4096) == pytest.approx(
        forward_flops(arch.model_config(cfg), 2, 4096, flash=True),
        rel=1e-12)
    assert arch.train_step_flops(cfg, 2, 4096) == 3 * arch.forward(
        cfg, 2, 4096)


def test_gmm_roofline_reads_the_kernel_ops():
    read = harness.metric_reader("gmm_roofline")
    cfg = harness.config("qwen3-30b-a3b-l4e8")
    spec = harness.workload(CELL)
    per_step = harness.arch(cfg).gmm_step_flops(cfg, 2, 4096)
    view = {"config": cfg, "workload": spec,
            "peaks": {"bf16_flops_per_s": 1e15},
            "trace": {"devices": 1,
                      "programs": {"jit_train_step": [10, 1.0, []]},
                      "ops": {"%gmm.43 = bf16[65536,768]": [30, 0.02],
                              "%tgmm.12 = bf16[8,2048,768]": [10, 0.02],
                              "%fusion.1 = bf16[8]": [10, 0.5]}}}
    assert read(view) == pytest.approx(100 * 10 * per_step / 0.04 / 1e15)
    view["trace"]["ops"] = {"%fusion.1 = bf16[8]": [10, 0.5]}
    assert read(view) is None
    view["config"] = harness.config("qwen2-0.5b-l4")
    assert read(view) is None


def test_the_cell_runs_on_the_cpu(tmp_path):
    """The whole cell at a tiny size: set-up, the window with a save, and
    the comparison with the reference, which the program passes."""
    res = execute(context(CELL, seed=5, seconds=1.0, cfg=TINY_MOE,
                          work_dir=str(tmp_path)))
    assert res["correct"], res["checks"]


def test_control_in_the_programs_place_is_not_correct(tmp_path):
    from bench.tools import control
    out = control.train_readings(context(CELL, seed=5, cfg=TINY_MOE,
                                         work_dir=str(tmp_path)), True)
    assert out["program"]["correct"], out["program"]
    assert not out["control"]["correct"], out["control"]
    assert not out["half_batch"]["correct"], out["half_batch"]
