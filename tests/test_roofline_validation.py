"""Validate the analytic roofline FLOPs model against XLA cost_analysis.

XLA counts while-loop bodies once, so exact comparison requires a program
whose loops all have trip count 1: n_layers=1, attention chunks = S, CE
chunk = S.  On such a config cost_analysis is exact and must match
``repro.core.traffic.cell_flops`` closely.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.core.traffic import cell_flops, model_params
from repro.models import api as mapi
from repro.models import transformer as TF


@pytest.mark.parametrize("arch,tol", [
    ("qwen2_7b", 0.30),      # dense GQA
    ("mamba2_130m", 0.45),   # ssd einsum accounting is coarser
])
def test_analytic_flops_vs_cost_analysis(arch, tol):
    B, S = 2, 128
    cfg = get_config(arch, smoke=True).replace(
        n_layers=1, attn_q_chunk=S, attn_kv_chunk=S, ssm_chunk=S,
        remat="none", dtype="float32")
    shape = ShapeCell("t", S, B, "train")
    api = mapi.build(cfg)
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    specs = api.input_specs(shape)

    def fwd_loss(p, batch):
        return TF.loss_fn(p, cfg, batch, loss_chunk=S)[0]

    comp = jax.jit(jax.grad(fwd_loss)).lower(params, specs).compile()
    measured = float(comp.cost_analysis().get("flops", 0.0))
    analytic = cell_flops(cfg, shape)["total"]
    assert measured > 0
    ratio = analytic / measured
    assert 1 - tol < ratio < 1 + tol, (analytic, measured, ratio)


def test_model_params_match_eval_shape():
    """Analytic parameter counts == actual pytree sizes (full configs)."""
    for arch in ("qwen2_7b", "qwen1p5_110b", "qwen3_moe_30b_a3b",
                 "mamba2_130m", "hymba_1p5b"):
        cfg = get_config(arch)
        api = mapi.build(cfg)
        sds = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(sds))
        analytic = model_params(cfg)["total"]
        err = abs(analytic - actual) / actual
        assert err < 0.02, (arch, analytic, actual, err)


def test_published_param_counts():
    """Sanity against the published model sizes (name plates)."""
    expect = {"qwen1p5_110b": 111e9, "qwen2_7b": 7.6e9,
              "mistral_nemo_12b": 12.2e9, "dbrx_132b": 132e9,
              "mamba2_130m": 0.13e9, "qwen3_moe_30b_a3b": 30.5e9}
    for arch, n in expect.items():
        got = model_params(get_config(arch))["total"]
        assert abs(got - n) / n < 0.12, (arch, got, n)
