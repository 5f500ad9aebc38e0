"""Model-math correctness: SSD vs naive recurrence, MoE dispatch properties,
rope/window invariants, CNN trace totals vs published numbers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.configs import get_config
from repro.models import cnn, moe as MOE, ssm as SSM
from repro.models.layers import apply_rope


# ---------------------------------------------------------------------------
# Mamba-2 SSD: chunked == naive sequential recurrence
# ---------------------------------------------------------------------------


def _naive_ssd(cfg, p, x):
    """Token-by-token reference using ssm_decode."""
    B = x.shape[0]
    cache = SSM.init_ssm_cache(cfg, B, jnp.float32)
    outs = []
    for t in range(x.shape[1]):
        o, cache = SSM.ssm_decode(p, cfg, x[:, t:t + 1], cache)
        outs.append(o)
    return jnp.concatenate(outs, axis=1), cache


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_ssd_chunked_matches_sequential(chunk):
    cfg = get_config("mamba2_130m", smoke=True).replace(
        ssm_chunk=chunk, dtype="float32")
    p = SSM.init_ssm(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y_chunk, cache = SSM.ssm_block(p, cfg, x)
    y_naive, cache_n = _naive_ssd(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_naive),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(cache["state"], np.float32),
                               np.asarray(cache_n["state"], np.float32),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# MoE dispatch properties
# ---------------------------------------------------------------------------


@given(st.integers(0, 10_000))
@example(1035)  # a draw whose aux is 0.9948
@settings(max_examples=10, deadline=None)
def test_moe_capacity_and_combine(seed):
    cfg = get_config("qwen3_moe_30b_a3b", smoke=True).replace(
        dtype="float32", capacity_factor=8.0)  # no dropping at cf=8
    p = MOE.init_moe(jax.random.PRNGKey(seed % 97), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 16, cfg.d_model))
    out, aux = MOE.moe_block(p, cfg, x)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())

    # with no dropping, output == dense-gated mixture computed directly
    xf = x.reshape(-1, cfg.d_model)
    logits = xf @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    gate, eid = jax.lax.top_k(probs, cfg.top_k)

    # the Switch aux loss on this draw, E * sum_e f_e * p_e (f_e the share
    # of tokens whose first choice is e, p_e the mean router probability):
    # a convex combination of the p_e scaled by E, so it lies between E
    # times the least and the largest p_e.  It need not reach 1 (top-1
    # shares can fall on the experts of lower mean probability)
    E = cfg.n_experts
    p_mean = np.asarray(probs.mean(0), np.float64)
    f = np.bincount(np.asarray(eid[:, 0]), minlength=E) / xf.shape[0]
    assert float(aux) == pytest.approx(E * float(f @ p_mean), rel=1e-5)
    assert E * p_mean.min() - 1e-6 <= float(aux) <= E * p_mean.max() + 1e-6
    gate = gate / gate.sum(-1, keepdims=True)
    ref = np.zeros_like(xf)
    for e in range(cfg.n_experts):
        h = jax.nn.silu(xf @ p["w1"][e]) * (xf @ p["w3"][e])
        ye = h @ p["w2"][e]
        for k in range(cfg.top_k):
            m = (np.asarray(eid[:, k]) == e)
            ref[m] += np.asarray(gate[m, k:k + 1] * ye[m])
    np.testing.assert_allclose(np.asarray(out).reshape(-1, cfg.d_model), ref,
                               rtol=2e-3, atol=2e-3)


def test_moe_group_scan_matches_single_group():
    cfg = get_config("qwen3_moe_30b_a3b", smoke=True).replace(
        dtype="float32", capacity_factor=8.0)
    p = MOE.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    out1, _ = MOE.moe_block(p, cfg, x, group_tokens=64)   # 1 group
    out2, _ = MOE.moe_block(p, cfg, x, group_tokens=16)   # 4 groups
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# rotary invariants
# ---------------------------------------------------------------------------


@given(st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_rope_preserves_norm_and_relativity(shift):
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 64))
    pos = jnp.arange(8)[None, :]
    r0 = apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(r0)),
                               np.linalg.norm(np.asarray(x)), rtol=1e-5)
    # relative property: <R(p)q, R(p+d)k> == <R(0)q, R(d)k>
    q = x[:, :1]
    k = x[:, 1:2]
    d = 3
    lhs = (apply_rope(q, pos[:, :1] + shift, 1e4)
           * apply_rope(k, pos[:, :1] + shift + d, 1e4)).sum()
    rhs = (apply_rope(q, pos[:, :1], 1e4)
           * apply_rope(k, pos[:, :1] + d, 1e4)).sum()
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# CNN traces vs published totals
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,gflops,mb", [
    ("vgg16", 30.9, 553),      # ~30.9 GFLOP, 138M params fp32
    ("resnet50", 7.7, 102),    # ~7.7 GFLOP (2xMAC), 25.5M params
    ("googlenet", 3.0, 28),    # ~3 GFLOP, 7M params
])
def test_cnn_trace_totals_match_literature(name, gflops, mb):
    tr = cnn.model_traces(name)
    g = sum(t.flops_per_img for t in tr if t.kind in ("conv", "fc")) / 1e9
    w = sum(t.weight_bytes for t in tr) / 1e6
    assert abs(g - gflops) / gflops < 0.12, g
    assert abs(w - mb) / mb < 0.12, w


@pytest.mark.slow
def test_cnn_forward_all():
    for name in ("vgg16", "googlenet", "resnet50"):
        params = cnn.init_cnn(jax.random.PRNGKey(0), name, img=32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        out = jax.jit(lambda p, x, n=name: cnn.apply_cnn(p, n, x))(params, x)
        assert out.shape == (2, 1000)
        assert bool(jnp.isfinite(out).all())
