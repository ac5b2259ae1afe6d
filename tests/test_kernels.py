"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.models import attention as attn_mod
from repro.sharding.partition import constraint_scope
from repro.sharding.rules import ShardingRules
from repro.kernels.mamba_scan.ops import mamba_scan
from repro.kernels.mamba_scan.ref import selective_scan_ref

RNG = np.random.default_rng(42)


def _mk(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


@pytest.mark.parametrize("B,Sq,Sk,H,Hkv,hd", [
    (2, 128, 128, 4, 4, 64),        # MHA
    (1, 256, 256, 8, 2, 64),        # GQA 4:1
    (2, 128, 256, 4, 1, 128),       # MQA, longer KV (decode-suffix case)
    (1, 128, 128, 4, 4, 128),
    (1, 512, 512, 2, 2, 64),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, Sq, Sk, H, Hkv, hd, causal, dtype):
    q = _mk((B, Sq, H, hd), dtype)
    k = _mk((B, Sk, Hkv, hd), dtype)
    v = _mk((B, Sk, Hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_block_sizes():
    q = _mk((1, 256, 2, 64), jnp.float32)
    k = _mk((1, 256, 2, 64), jnp.float32)
    v = _mk((1, 256, 2, 64), jnp.float32)
    ref = flash_attention_ref(q, k, v, causal=True)
    for bq, bk in [(64, 64), (128, 256), (256, 128)]:
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_flash_attention_tiny_fallback():
    """Degenerate shapes fall back to the reference (no kernel launch)."""
    q = _mk((1, 4, 2, 16), jnp.float32)
    k = _mk((1, 4, 2, 16), jnp.float32)
    v = _mk((1, 4, 2, 16), jnp.float32)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def _grads(attn, q, k, v, w):
    """d/d(q, k, v) of sum(attn(q, k, v) * w), in float32."""
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
    with jax.default_matmul_precision("highest"):
        return [np.asarray(g, np.float32)
                for g in jax.grad(loss, (0, 1, 2))(q, k, v)]


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# (B, Sq, Sk, H, Hkv, hd, q_offset): GQA 14/2 (rep 7) at hd 64 and 128,
# MHA 4/4, a query suffix against a longer KV, and an explicit q_offset
@pytest.mark.parametrize("B,Sq,Sk,H,Hkv,hd,q_offset", [
    (1, 256, 256, 14, 2, 64, 0),
    (1, 256, 256, 14, 2, 128, 0),
    (2, 256, 256, 4, 4, 64, 0),
    (1, 128, 256, 4, 4, 128, 0),
    (1, 256, 256, 4, 2, 64, 64),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grads_match(B, Sq, Sk, H, Hkv, hd, q_offset,
                                     causal, dtype):
    """jax.grad through the kernel's custom VJP (forward with lse, dQ and
    dK/dV kernels) against the full-materialisation oracle and against
    the jnp scan the CPU runs."""
    q = _mk((B, Sq, H, hd), dtype)
    k = _mk((B, Sk, Hkv, hd), dtype)
    v = _mk((B, Sk, Hkv, hd), dtype)
    w = _mk((B, Sq, H, hd), jnp.float32)
    # query row i sits at key position i + (Sk - Sq) + q_offset
    offset = Sk - Sq + q_offset
    got = _grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=64, q_offset=q_offset,
        interpret=True), q, k, v, w)
    if q_offset:
        ref = _grads(lambda q, k, v: attn_mod.naive_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=causal, q_offset=offset),
            q, k, v, w)
    else:
        ref = _grads(lambda q, k, v: flash_attention_ref(
            q, k, v, causal=causal), q, k, v, w)
    scan = _grads(lambda q, k, v: attn_mod.chunked_attention(
        q, k, v, causal=causal, q_offset=offset, kv_chunk=128), q, k, v, w)
    tol = 1e-5 if dtype == jnp.float32 else 1.5e-2
    for name, g, r, c in zip("qkv", got, ref, scan):
        assert _rel(g, r) < tol, (name, _rel(g, r))
        assert _rel(g, c) < tol, (name, _rel(g, c))


@pytest.mark.parametrize("causal,dtype", [(True, jnp.float32),
                                          (False, jnp.bfloat16)])
def test_flash_attention_grads_unfused(monkeypatch, causal, dtype):
    """Where the fused dQ would not fit in VMEM, the dQ kernel runs on
    its own; GQA 14/2 with a query suffix."""
    from repro.kernels.flash_attention import kernel as fa_kernel
    monkeypatch.setattr(fa_kernel, "FUSED_DQ_BYTES", 0)
    calls = []
    dq_kernel = fa_kernel.flash_bwd_dq
    monkeypatch.setattr(fa_kernel, "flash_bwd_dq",
                        lambda *a: calls.append(1) or dq_kernel(*a))
    q = _mk((1, 128, 14, 64), dtype)
    k = _mk((1, 256, 2, 64), dtype)
    v = _mk((1, 256, 2, 64), dtype)
    w = _mk((1, 128, 14, 64), jnp.float32)
    got = _grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=64, block_k=128, interpret=True),
        q, k, v, w)
    ref = _grads(lambda q, k, v: flash_attention_ref(q, k, v, causal=causal),
                 q, k, v, w)
    assert calls == [1]
    tol = 1e-5 if dtype == jnp.float32 else 1.5e-2
    for name, g, r in zip("qkv", got, ref):
        assert _rel(g, r) < tol, (name, _rel(g, r))


def test_flash_attention_lse_cotangent():
    """The log-sum-exp output is differentiable too: its cotangent takes
    the dlse term off D inside the backward kernels."""
    from repro.kernels.flash_attention.kernel import flash_attention_bhsd
    q = _mk((4, 256, 64), jnp.float32)
    k = _mk((2, 256, 64), jnp.float32)
    v = _mk((2, 256, 64), jnp.float32)

    def flash(q, k, v):
        o, lse = flash_attention_bhsd(q, k, v, causal=True, n_q_heads=2,
                                      block_q=128, block_k=128,
                                      interpret=True)
        return jnp.sum(o) + jnp.sum(jnp.sin(lse))

    def ref(q, k, v):
        kk, vv = (jnp.repeat(x, 2, axis=0) for x in (k, v))
        s = jnp.einsum("hqd,hkd->hqk", q, kk) / 8.0
        s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, -1e30)
        o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), vv)
        return jnp.sum(o) + jnp.sum(jnp.sin(jax.nn.logsumexp(s, -1)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(flash, (0, 1, 2))(q, k, v)
        want = jax.grad(ref, (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert _rel(np.asarray(g), np.asarray(r)) < 1e-5


# the path choice: train.ckpt's shapes (B 4, S 1024, H 14, Hkv 2, hd 64)
CELL_Q, CELL_KV = (4, 1024, 14, 64), (4, 1024, 2, 64)


def test_flash_chosen_on_tpu_for_the_cell():
    assert attn_mod.flash_blocks(CELL_Q, CELL_KV, platform="tpu") \
        == (attn_mod.FLASH_BLOCK, attn_mod.FLASH_BLOCK)


@pytest.mark.parametrize("q_shape,kv_shape,platform,mesh", [
    (CELL_Q, CELL_KV, None, None),                      # this CPU
    (CELL_Q, CELL_KV, "cpu", None),
    (CELL_Q, (4, 1000, 2, 64), "tpu", None),            # Sk does not tile
    ((4, 1000, 14, 64), (4, 1000, 2, 64), "tpu", None),
    ((4, 1024, 14, 96), (4, 1024, 2, 96), "tpu", None),  # head width
    (CELL_Q, CELL_KV, "tpu", AbstractMesh((4,), ("data",))),
])
def test_flash_falls_back(q_shape, kv_shape, platform, mesh):
    assert attn_mod.flash_blocks(q_shape, kv_shape, platform=platform,
                                 mesh=mesh) is None


def test_flash_blocks_follow_the_shape():
    # a block divides its sequence; cross-attention keys tile on their own
    assert attn_mod.flash_blocks((2, 384, 4, 128), (2, 640, 4, 128),
                                 platform="tpu") == (128, 128)
    assert attn_mod.flash_blocks((2, 2048, 4, 64), (2, 768, 4, 64),
                                 platform="tpu") \
        == (attn_mod.FLASH_BLOCK, 256)


def test_flash_falls_back_inside_a_mesh_scope():
    """The mesh the trainer arms for its step is seen without being
    passed: four devices keep the jnp scan, one keeps the kernel."""
    rules = ShardingRules(batch="data")
    with constraint_scope(AbstractMesh((4,), ("data",)), rules):
        assert attn_mod.flash_blocks(CELL_Q, CELL_KV, platform="tpu") is None
    with constraint_scope(AbstractMesh((1,), ("data",)), rules):
        assert attn_mod.flash_blocks(CELL_Q, CELL_KV, platform="tpu") \
            is not None


def test_attention_takes_the_chosen_path(monkeypatch):
    """attention() and attention_with_kv() run the kernel where the choice
    says so (forced here, in interpret mode) and the scan where it does
    not, with the same result."""
    from repro.configs import get_config, reduced
    cfg = reduced(get_config("qwen2-7b"))
    p = attn_mod.attention_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = _mk((2, 128, cfg.d_model), jnp.float32)
    calls = []

    def kernel(q, k, v, **kw):
        calls.append(kw)
        return flash_attention(q, k, v, interpret=True, **kw)

    scan = attn_mod.attention(p, x, cfg, compute_dtype=jnp.float32)
    scan_kv = attn_mod.attention_with_kv(p, x, cfg, compute_dtype=jnp.float32)
    assert not calls                             # the CPU takes the scan
    monkeypatch.setattr(attn_mod, "flash_blocks", lambda *a, **kw: (64, 64))
    monkeypatch.setattr(fa_ops, "flash_attention", kernel)
    flash = attn_mod.attention(p, x, cfg, compute_dtype=jnp.float32)
    flash_kv = attn_mod.attention_with_kv(p, x, cfg,
                                          compute_dtype=jnp.float32)
    assert [c["block_q"] for c in calls] == [64, 64]
    np.testing.assert_allclose(np.asarray(flash), np.asarray(scan),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(flash_kv, scan_kv):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,S,di,ds", [
    (2, 64, 32, 8),
    (1, 256, 128, 16),
    (2, 128, 64, 16),
    (1, 128, 256, 32),
])
@pytest.mark.parametrize("chunk,block_d", [(32, 32), (64, 128)])
def test_mamba_scan_matches_ref(b, S, di, ds, chunk, block_d):
    x = _mk((b, S, di), jnp.float32) * 0.5
    dt = jnp.abs(_mk((b, S, di), jnp.float32)) * 0.1
    B = _mk((b, S, ds), jnp.float32)
    C = _mk((b, S, ds), jnp.float32)
    A = -jnp.abs(_mk((di, ds), jnp.float32)) - 0.1
    y, h = mamba_scan(x, dt, B, C, A, interpret=True, chunk=chunk,
                      block_d=block_d)
    yr, hr = selective_scan_ref(x, dt, B, C, A)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=1e-4, rtol=1e-4)


def test_mamba_scan_state_continuity():
    """Scanning two halves with carried state == one full scan."""
    b, S, di, ds = 1, 128, 32, 8
    x = _mk((b, S, di), jnp.float32) * 0.5
    dt = jnp.abs(_mk((b, S, di), jnp.float32)) * 0.1
    B = _mk((b, S, ds), jnp.float32)
    C = _mk((b, S, ds), jnp.float32)
    A = -jnp.abs(_mk((di, ds), jnp.float32)) - 0.1
    y_full, h_full = selective_scan_ref(x, dt, B, C, A)
    half = S // 2
    y1, h1 = selective_scan_ref(x[:, :half], dt[:, :half], B[:, :half],
                                C[:, :half], A)
    y2, h2 = selective_scan_ref(x[:, half:], dt[:, half:], B[:, half:],
                                C[:, half:], A, h0=h1)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, half:]),
                               atol=1e-5)
