"""The harness's DeepSeek-V2 architecture (``arch/deepseek_v2.py``) on the
CPU: the program against its float32 reference (prefill, then paged decode
through the latent pool), the expert shares against the uncut reference
layer, the parameter layout against the program's, the work counts, and a
whole run of a cell at a tiny size with its controls."""
import copy
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arch, model, run, work
from chipbench.arch import deepseek_v2 as ds
from chipbench.tests import tiny

SEED = 2**33 + 17

# the published block at a width a test can hold: 1 dense layer, 2 expert
# layers of 4 held experts of the router's 16, top-4, one shared expert
DOC = {
    "_name": "tiny-dsv2", "model_type": "deepseek_v2", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "published": {"n_routed_experts": 16},
    "expert_offset": 4, "num_experts_per_tok": 4, "n_shared_experts": 1,
    "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "scoring_func": "softmax", "topk_method": "greedy", "moe_layer_freq": 1,
    "q_lora_rank": None, "attention_bias": False,
    "tie_word_embeddings": False, "vocab_size": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "torch_dtype": "bfloat16",
    "serving": {"slots": 4, "max_len": 64, "block_size": 8},
    "program": {"attn_q_chunk": 16, "attn_kv_chunk": 32},
    "check": {"logit_gap_max": 0.07, "logit_err_mean": 0.012},
}


def _doc(**kw):
    return {**copy.deepcopy(DOC), **kw}


def _shapes(tree):
    return jax.tree.map(lambda a: tuple(a.shape), tree)


@pytest.mark.parametrize("name", ["tiny", "deepseek-v2-lite.ep4"])
def test_param_shapes_are_the_programs(name):
    doc = _doc() if name == "tiny" else model.load_config(name)
    mod = arch.arch_for(doc)
    from repro.models import api as mapi

    init = mapi.build(mod.program_config(doc)).init
    want = _shapes(jax.eval_shape(init, jax.random.PRNGKey(0)))
    assert want == mod.param_shapes(mod.dims(doc))


def test_the_cut_keeps_every_published_width():
    doc = model.load_config("deepseek-v2-lite.ep4")
    m = ds.dims(doc)
    assert (m.experts, m.experts_all, m.top_k, m.layers, m.dense_layers) \
        == (16, 64, 6, 27, 1)
    assert (m.d, m.heads, m.nope, m.rope, m.v_head, m.kv_rank, m.d_ff,
            m.expert_ff, m.vocab) == (2048, 16, 128, 64, 128, 512, 10944,
                                      1408, 102400)
    # 4.91 B parameters on this chip: 9.82 GB in bf16
    n = sum(math.prod(s) for s in jax.tree.leaves(
        ds.param_shapes(m), is_leaf=lambda x: isinstance(x, tuple)))
    assert n == m.params
    assert 4.90e9 < m.params < 4.92e9
    assert m.latent_bytes_per_token == 27 * 1152
    cfg = ds.program_config(doc)
    assert (cfg.n_experts, cfg.n_router_experts, cfg.expert_offset) == \
        (16, 64, 0)


@pytest.mark.parametrize("key,value", [("q_lora_rank", 1536),
                                       ("topk_method", "group_limited_greedy"),
                                       ("scoring_func", "sigmoid")])
def test_program_config_refuses_what_it_cannot_map(key, value):
    with pytest.raises(ValueError, match=key):
        ds.program_config(_doc(**{key: value}))


# --- the program against the reference ----------------------------------

def _float32(doc):
    doc = copy.deepcopy(doc)
    doc["torch_dtype"] = "float32"
    return doc


def test_prefill_then_paged_decode_match_the_reference():
    """A prompt prefilled, its latent rows installed in the pool, then
    decoded step by step through the pool (absorbed attention, dropless
    expert share): each step's logits against the reference's full forward
    over the same tokens, in float32 on both sides."""
    from repro.models import api as mapi
    from repro.serving.kv_pool import init_pages, write_latent_pages

    doc = _float32(_doc())
    m = ds.dims(doc)
    params = model.make_params(m, SEED, "float32")
    cfg = ds.program_config(doc)
    api = mapi.build(cfg)
    rng = np.random.default_rng(1)
    seq = rng.integers(1, m.vocab, size=40).astype(np.int32)
    P, bs, T = 24, 8, 8
    ref_h = ds.hidden(params, jnp.asarray(seq), m, None)
    ref = np.asarray(ds.head(params, ref_h, None))

    logits, cache = api.prefill(params, {"tokens": jnp.asarray(seq[None, :P])},
                                max_len=P)
    pages = init_pages(cfg, 1 + T, bs)
    tables = jnp.arange(1, 1 + T, dtype=jnp.int32)[None]
    pages = {"k_pages": write_latent_pages(pages["k_pages"], cache["k"],
                                           tables, jnp.arange(1))}
    got = [np.asarray(logits[0])]
    pc = dict(pages, tables=tables, lens=jnp.asarray([P], jnp.int32))
    for t in range(P, len(seq)):
        lg, pc = api.decode_paged(params, jnp.asarray([[seq[t]]]), pc)
        got.append(np.asarray(lg[0, 0]))
    got = np.stack(got)
    want = ref[P - 1:len(seq)]
    # float32 both ways at one-unit logits: the forms (decompressed and
    # absorbed against the reference's decompressed) and XLA's summation
    # orders differ by a few ulps per layer; a route that flipped on a
    # near tie would move a logit by orders more
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)


@pytest.mark.parametrize("tokens", [24, 100])
def test_expert_shares_sum_to_the_uncut_reference_layer(tokens):
    """Four chips' shares of the 16 experts (4 each, the program's
    ``moe_share``, a decode step's few tokens or a prefill's many), the
    shared expert counted once, add up to the reference's layer that holds
    all 16."""
    from repro.models import moe as MOE

    whole = ds.dims(_doc(n_routed_experts=16, expert_offset=0))
    cfg = ds.program_config(_float32(_doc(n_routed_experts=16,
                                          expert_offset=0)))
    params = model.make_params(whole, SEED, "float32")
    w = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, whole.d))
    want = np.asarray(ds._experts(x, w, whole, None))

    shared = np.asarray(ds._swiglu(x, w["ws1"], w["ws3"], w["ws2"], None))
    total = shared
    for r in range(4):
        c = cfg.replace(n_experts=4, expert_offset=4 * r)
        q = dict(w, w1=w["w1"][4 * r:4 * r + 4], w3=w["w3"][4 * r:4 * r + 4],
                 w2=w["w2"][4 * r:4 * r + 4])
        out, _ = MOE.moe_share(q, c, x[None])
        total = total + (np.asarray(out[0]) - shared)
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


# --- work ----------------------------------------------------------------

def test_decode_work_is_the_expert_stream_and_the_latent_rows():
    """32 slots at a mean context of 800: 9.40 GB of weights when every
    held expert is read, of which the routed experts' 7.20 GB count at the
    0.957 that 32 tokens reach under uniform routing, and 0.80 GB of
    latent rows: 12.1 ms at 819 GB/s, 70% of it the routed experts."""
    m = ds.dims(model.load_config("deepseek-v2-lite.ep4"))
    share = ds.reached_share(m, 32)
    assert share == pytest.approx(1 - (58 / 64) ** 32)
    w = work.decode_step(m, [800] * 32)
    latent = 32 * 800 * 27 * 1152
    logits = 4 * 32 * 102400
    weights = w.byts - latent - logits - 2 * 32 * 2048
    routed = 2 * 26 * 16 * 3 * 2048 * 1408
    assert routed == pytest.approx(7.20e9, rel=1e-3)
    assert weights + (1 - share) * routed == pytest.approx(9.40e9, rel=2e-3)
    assert share * routed / w.byts == pytest.approx(0.70, abs=0.01)
    assert w.seconds(197e12, 819e9) == pytest.approx(12.1e-3, rel=0.01)
    # memory-bound: the absorbed attention's FLOPs are far under the bytes
    assert w.flops / 197e12 < 0.2 * w.byts / 819e9


def test_decode_flops_count_the_absorbed_form():
    m = ds.dims(_doc())
    base = work.decode_step(m, [1])
    more = work.decode_step(m, [11])
    # ten more positions: scores over the latent row, values over c
    assert more.flops - base.flops == pytest.approx(
        10 * 2 * m.layers * m.heads * (m.kv_rank + m.rope + m.kv_rank))
    assert more.byts - base.byts == 10 * m.latent_bytes_per_token


def test_prefill_counts_the_decompressed_form():
    m = ds.dims(_doc())
    one, two = work.prefill(m, [5]), work.prefill(m, [5, 5])
    pairs = 15
    attn = 2 * m.layers * m.heads * (m.nope + m.rope + m.v_head) * pairs
    # a second prompt adds its tokens' projections, its pairs, its logits
    # and its latent rows; the weights are read once either way
    assert two.flops - one.flops == pytest.approx(one.flops)
    assert attn < one.flops
    rows = 5 * m.latent_bytes_per_token
    assert two.byts - one.byts == pytest.approx(
        rows + 2 * 5 * m.d + 4 * m.vocab
        + 2 * m.moe_layers * m.experts * m.expert_params
        * (ds.reached_share(m, 10) - ds.reached_share(m, 5)))


# --- the readers repeated for the dsv2l cell ---------------------------

class _View:
    """What the step, idle and occupancy readers read of a run."""

    def __init__(self, m):
        from types import SimpleNamespace as NS

        self.m = m
        self.slots = 4
        self.trace = NS(busy_s=8.5, window_s=10.0)
        self.window = NS(t_open=2.0, t_close=12.0)
        self.peaks = NS(bf16_flops=197e12)
        self._ops = {"decode": [NS(lens=[300, 41, 800]), NS(lens=[5])],
                     "refill": [NS(lens=[512])], "wave": []}

    def ops(self, *kinds):
        return [op for k in kinds for op in self._ops[k]]


@pytest.mark.parametrize("name", ["idle_pct", "decode_occupancy_pct",
                                  "step_mfu"])
def test_dsv2l_reader_reads_what_its_original_reads(name):
    """``dsv2l_<name>`` repeats ``<name>`` for the cell its list does not
    name: the same number from the same view, and in the cell's spec."""
    view = _View(ds.dims(model.load_config("deepseek-v2-lite.ep4")))
    got = run.reader(f"dsv2l_{name}")(view)
    assert got is not None and got == run.reader(name)(view)
    spec = run.load_spec("dsv2l.decode.offline")
    assert f"dsv2l_{name}" in {x["name"] for x in spec["per_layer"]}


# --- a whole run ---------------------------------------------------------

def _spec():
    spec = tiny.spec("offline")
    spec["config"] = _doc()
    return spec


def test_served_tokens_match_the_reference():
    """Wave prefill, batch-1 refill and paged decode through the latent
    pool, as the window served them, against the float32 reference."""
    res = run.run_cell(_spec(), seed=SEED, seconds=1.0, trace=False,
                       require_chip=False, log=lambda *a: None)
    assert res["correct"] is True and res["failed"] == 0
    c = res["checks"]
    assert c["sampled_decode_tokens"]["value"] > 0
    assert res["metrics"]["tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_control_comes_out_not_correct(control):
    res = run.run_cell(_spec(), seed=SEED, seconds=1.0, trace=False,
                       require_chip=False, log=lambda *a: None,
                       controls=(control,))
    assert res["correct"] is False
    c = res["checks"]
    assert c["logit_err_mean"]["value"] > c["logit_err_mean"]["limit"]
