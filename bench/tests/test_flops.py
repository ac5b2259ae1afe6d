"""FLOP and parameter counts against hand counts."""
from bench import flops, harness


def test_qwen2_05b_l4_train_step_by_hand():
    cfg = harness.config("qwen2-0.5b-l4")
    # per layer: q,o 2*896*896, k,v 2*896*(2*64), MLP 3*896*4864;
    # the tied head 896*151936
    per_layer = 2 * 896 * 896 + 2 * 896 * 128 + 3 * 896 * 4864
    matmul = 4 * per_layer + 896 * 151936
    assert flops.matmul_params(cfg) == matmul == 195_772_416
    tokens = 8 * 1024
    # causal core: QK^T and AV, 2 flops a MAC, half the 1024^2 square
    core = 4 * 2 * 2 * 8 * 14 * 64 * 1024 * 1024 / 2
    assert flops.attention_core(cfg, 8, 1024) == core
    assert flops.train_step(cfg, 8, 1024) == 3 * (2 * matmul * tokens + core)
    assert abs(flops.train_step(cfg, 8, 1024) - 9.803e12) < 0.001e12


def test_parameters_and_state_by_hand():
    q = harness.config("qwen2-0.5b-l4")
    d, kv, ff, v = 896, 2 * 64, 4864, 151936
    per_layer = d * d + 2 * d * kv + d * d + 3 * d * ff
    assert flops.forward(q, 1, 1) == 2 * (4 * per_layer + d * v) \
        + 4 * 2 * 2 * 14 * 64 / 2
    bias = (14 + 2 * 2) * 64
    norms = 2 * d
    assert flops.n_params(q) == 4 * (per_layer + bias + norms) + d * v + d
    assert flops.n_params(q) == 195_785_088
    # parameters and both AdamW moments in float32: the 2.35 GB state
    # that each save copies twice on the device and writes to file
    assert abs(3 * 4 * flops.n_params(q) - 2.3494e9) < 0.001e9
