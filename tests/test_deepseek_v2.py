"""DeepSeek-V2's mechanisms in the program, at the smoke size in float32:
multi-head latent attention (absorbed decode against the decompressed
form, the interleaved YaRN rotary), the dropless expert share, the latent
page pool, the refusals of what a latent pool does not serve, and the
per-layer count of the experts a decode step reached."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api as mapi
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import transformer as TF
from repro.serving import kv_pool as KV


@pytest.fixture(scope="module")
def cfg():
    return get_config("deepseek-v2-lite", smoke=True).replace(
        dtype="float32")


def _attn_params(cfg, seed=0):
    p = L.init_mla(jax.random.PRNGKey(seed), cfg, jnp.float32)
    # a norm gain away from one, so that its code path changes the result
    p["kv_norm"] = 1.0 + 0.2 * jax.random.uniform(
        jax.random.PRNGKey(seed + 1), p["kv_norm"].shape, minval=-1.0)
    return p


def test_published_config_maps_every_width():
    c = get_config("deepseek-v2-lite")
    assert (c.n_layers, c.n_dense_layers, c.d_model, c.vocab) == \
        (27, 1, 2048, 102400)
    assert (c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, c.n_heads) == (512, 128, 64, 128, 16)
    assert (c.n_experts, c.n_router_experts, c.top_k, c.n_shared_experts,
            c.d_ff, c.dense_d_ff) == (64, 64, 6, 2, 1408, 10944)
    assert c.norm_topk_prob is False and c.routed_scaling_factor == 1.0
    # 576 lanes of [c_kv, k_pe] stored in the 640 a TPU tile holds
    assert c.latent_width == 640


def test_yarn_frequencies_follow_the_published_formula():
    """DeepSeek-V2-Lite's rotary half: 64 dims, theta 1e4, factor 40 over
    an original 4096 positions, beta_fast 32 and beta_slow 1 put the ramp
    between pair 10 (floor of 10.47) and pair 23 (ceil of 22.51)."""
    c = get_config("deepseek-v2-lite")
    inv, msc = L.mla_rope(c)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], base[:10], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], base[23:] / 40.0, rtol=1e-12)
    keep = 1.0 - (16 - 10) / 13
    np.testing.assert_allclose(inv[16], base[16] * keep
                               + base[16] / 40.0 * (1 - keep), rtol=1e-12)
    assert msc == 1.0   # mscale == mscale_all_dim: cos and sin unscaled
    # DeepSeek's softmax scale: 1/sqrt(192) * (0.1 * 0.707 * ln 40 + 1)^2
    want = 192 ** -0.5 * (0.1 * 0.707 * math.log(40.0) + 1.0) ** 2
    assert L.mla_softmax_scale(c) == pytest.approx(want, rel=1e-12)
    assert L.mla_softmax_scale(c) == pytest.approx(0.1147214, rel=1e-6)


def test_interleaved_rotary_is_a_complex_rotation():
    """Each pair (x[2i], x[2i+1]) as x[2i] + i x[2i+1], times
    exp(i pos w_i) and the cos/sin scale: the published
    ``view_as_complex`` form."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 3, 8)).astype(np.float32)
    pos = np.array([[0, 1, 7, 100, 4095], [3, 9, 2, 0, 1]])
    inv = np.array([1.0, 0.3, 0.01, 1e-4])
    got = np.asarray(L.apply_rope_pairs(jnp.asarray(x), jnp.asarray(pos),
                                        inv, 0.9))
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * 0.9 * np.exp(
        1j * pos[..., None, None] * inv)
    want = np.stack([z.real, z.imag], -1).reshape(x.shape)
    # float32 angles of up to 4095 rad round to a few 1e-4 rad
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-3)
    np.testing.assert_allclose(got[:, :3], want[:, :3], rtol=0, atol=1e-5)


def test_absorbed_decode_equals_decompressed_attention(cfg):
    """The decode form (W_UK folded into the query, attention over the
    latent rows, W_UV after) gives each position what the decompressed
    prefill form gives it."""
    p = _attn_params(cfg)
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model))
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    full, rows = L.mla_block(p, cfg, x, positions=pos)
    for t in (0, 5, S - 1):
        q, row, w_uv = L.mla_absorb(p, cfg, x[:, t:t + 1], pos[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(row[:, 0]),
                                   np.asarray(rows[:, t]), atol=1e-6)
        kc = rows[:, :, None]
        o = L.decode_attention(q, kc, kc[..., :cfg.kv_lora_rank],
                               jnp.full((B,), t, jnp.int32),
                               scale=L.mla_softmax_scale(cfg))
        got = L.mla_values(o, w_uv).reshape(B, -1) @ p["wo"]
        # float32 both ways; the two forms sum in different orders
        np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, t]),
                                   rtol=1e-4, atol=1e-5)


def _share(p, cfg, r, n):
    """Expert-parallel rank ``r`` of ``n``: the router and shared experts
    whole, routed experts [r*E/n, (r+1)*E/n)."""
    k = cfg.n_experts // n
    c = cfg.replace(n_experts=k, expert_offset=r * k)
    q = dict(p)
    for w in ("w1", "w2", "w3"):
        q[w] = p[w][r * k:(r + 1) * k]
    return q, c


def _shared_only(p, cfg, x):
    xf = x.reshape(-1, cfg.d_model)
    hs = jax.nn.silu(xf @ p["ws1"]) * (xf @ p["ws3"])
    return (hs @ p["ws2"]).reshape(x.shape)


# one decode step's few tokens and a prefill's many, both through the
# grouped matmul over the tokens sorted by expert
TOKENS = pytest.mark.parametrize("shape", [(3, 10), (5, 30)])


@TOKENS
def test_expert_shares_add_up_to_the_whole_layer(cfg, shape):
    """Four shares of the 16 experts (4 each), the shared experts counted
    once, sum to the layer that holds all 16."""
    p = MOE.init_moe(jax.random.PRNGKey(1), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), shape + (cfg.d_model,))
    whole, reached = MOE.moe_share(p, cfg, x)
    parts = []
    for r in range(4):
        q, c = _share(p, cfg, r, 4)
        out, got = MOE.moe_share(q, c, x)
        parts.append(out - _shared_only(q, c, x))
        assert 0 <= int(got) <= 4
    total = sum(parts) + _shared_only(p, cfg, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # the routing: softmax, top-k, the weights not renormalised
    _, eid = MOE.route(p, cfg, x.reshape(-1, cfg.d_model))
    assert int(reached) == len(np.unique(np.asarray(eid)))


@TOKENS
def test_dropless_when_every_token_picks_the_same_experts(cfg, shape):
    """A router that sends every token to experts 0..k-1: a capacity layer
    would drop most of them; the share computes every pair, in one group
    of tokens or several."""
    p = MOE.init_moe(jax.random.PRNGKey(4), cfg, jnp.float32)
    k = cfg.top_k
    router = np.zeros((cfg.d_model, cfg.n_router_experts), np.float32)
    router[:, :k] = np.linspace(4.0, 3.0, k)       # a fixed order of k
    p["router"] = jnp.asarray(router)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5),
                                  shape + (cfg.d_model,)))
    out, reached = MOE.moe_share(p, cfg, x)
    assert int(reached) == k
    xf = np.asarray(x.reshape(-1, cfg.d_model), np.float64)
    probs = jax.nn.softmax(jnp.asarray(xf @ router, jnp.float32), -1)
    want = np.asarray(_shared_only(p, cfg, x), np.float64).reshape(xf.shape)
    for e in range(k):
        h = jax.nn.silu(xf @ np.asarray(p["w1"][e], np.float64)) * (
            xf @ np.asarray(p["w3"][e], np.float64))
        want += np.asarray(probs[:, e:e + 1]) * (
            np.asarray(h) @ np.asarray(p["w2"][e], np.float64))
    np.testing.assert_allclose(np.asarray(out).reshape(xf.shape), want,
                               rtol=1e-4, atol=1e-5)
    grouped, _ = MOE.moe_share(p, cfg, x, group_tokens=24)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(out),
                               rtol=1e-5, atol=1e-6)


def test_router_matmul_runs_at_highest_precision(cfg):
    """The router's float32 matmul asks for ``HIGHEST``: a TPU's default
    precision would round its operands to bfloat16.  The one dot whose
    result is (tokens, n_router_experts) in float32 carries the flag."""
    p = MOE.init_moe(jax.random.PRNGKey(6), cfg, jnp.bfloat16)
    x = jnp.zeros((4, 8, cfg.d_model), jnp.bfloat16)
    text = jax.jit(lambda p, x: MOE.moe_share(p, cfg, x)).lower(
        p, x).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln
            and f"tensor<32x{cfg.n_router_experts}xf32>" in ln]
    assert len(dots) == 1, dots
    assert "precision = [HIGHEST, HIGHEST]" in dots[0]


def test_latent_pages_round_trip(cfg):
    """Prefix rows scattered into their blocks read back in table order,
    and a decode append lands in the slot's current block at its row."""
    bs, N = 4, 12
    pages = KV.init_pages(cfg, N, bs)
    assert set(pages) == {"k_pages"}
    assert pages["k_pages"].shape == (cfg.n_layers, N, bs, cfg.latent_width)
    rng = np.random.default_rng(0)
    S = 7
    rows = jnp.asarray(rng.standard_normal(
        (cfg.n_layers, 2, S, cfg.latent_width)), jnp.float32)
    tables = jnp.asarray([[3, 5, 0], [7, 1, 2]], jnp.int32)
    got = KV.write_latent_pages(pages["k_pages"], rows, tables,
                                jnp.arange(2))
    for b in range(2):
        chain = np.asarray(got[:, tables[b]]).reshape(cfg.n_layers, -1,
                                                      cfg.latent_width)
        np.testing.assert_array_equal(chain[:, :S], np.asarray(rows[:, b]))
    new = jnp.asarray(rng.standard_normal((2, 1, cfg.latent_width)),
                      jnp.float32)
    pos = jnp.asarray([S, 2], jnp.int32)
    blk = tables[jnp.arange(2), pos // bs]
    at_row = (jnp.arange(bs) == (pos % bs)[:, None])[:, None, :, None]
    app, _ = TF._append_block(got, None, 1, blk, at_row, new, None)
    np.testing.assert_array_equal(np.asarray(app[1, 5, 3]),
                                  np.asarray(new[0, 0]))
    np.testing.assert_array_equal(np.asarray(app[1, 7, 2]),
                                  np.asarray(new[1, 0]))
    # nothing else moved
    mask = np.ones(app.shape[:3], bool)
    mask[1, 5, 3] = mask[1, 7, 2] = False
    np.testing.assert_array_equal(np.asarray(app)[mask],
                                  np.asarray(got)[mask])


@pytest.mark.parametrize("kw", [{"kv_dtype": "int8"}, {"kv_dtype": "fp8"},
                                {"sparse_threshold": 0.1},
                                {"prefix_cache": True}])
def test_latent_pool_refuses_what_it_does_not_serve(cfg, kw):
    from repro.serving import PartitionEngine

    api = mapi.build(cfg)
    with pytest.raises(ValueError, match="latent"):
        PartitionEngine(cfg, api, None, slots=2, max_len=32, paged=True,
                        block_size=4, **kw)


def test_latent_pages_refuse_a_packed_dtype(cfg):
    with pytest.raises(ValueError, match="latent"):
        KV.init_pages(cfg, 4, 4, kv_dtype="int8")


def test_paged_decode_reports_the_experts_it_reached(cfg):
    """(L,) held experts reached per layer: none in the dense layer, and in
    each expert layer the distinct experts its tokens chose."""
    api = mapi.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    B, bs, T = 3, 4, 4
    pages = KV.init_pages(cfg, 1 + B * T, bs)
    pc = dict(pages, tables=jnp.arange(1, 1 + B * T,
                                       dtype=jnp.int32).reshape(B, T),
              lens=jnp.asarray([0, 3, 6], jnp.int32))
    tok = jnp.asarray([[5], [9], [11]], jnp.int32)
    _, out = api.decode_paged(params, tok, pc)
    reached = np.asarray(out["experts_reached"])
    assert reached.shape == (cfg.n_layers,) and reached[0] == 0
    assert all(1 <= r <= min(cfg.n_experts, B * cfg.top_k)
               for r in reached[1:])


def test_dense_configurations_return_no_expert_count():
    c = get_config("qwen2-7b", smoke=True)
    api = mapi.build(c)
    params = api.init(jax.random.PRNGKey(0))
    pages = KV.init_pages(c, 5, 4)
    pc = dict(pages, tables=jnp.arange(1, 5, dtype=jnp.int32)[None],
              lens=jnp.zeros((1,), jnp.int32))
    _, out = api.decode_paged(params, jnp.ones((1, 1), jnp.int32), pc)
    assert "experts_reached" not in out


def test_engine_emits_the_expert_count_on_its_decode_span(cfg):
    """Under a wall-clock tracer each paged decode step of an expert share
    records ``experts_reached``, the sum over layers."""
    from repro.obs import Tracer, WallClock
    from repro.serving import (PartitionEngine, RequestQueue,
                               make_scheduler)

    api = mapi.build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = PartitionEngine(cfg, api, params, slots=2, max_len=32, paged=True,
                          block_size=4)
    q = RequestQueue()
    sched = make_scheduler([eng], q, policy="uniform", clock="event")
    tracer = Tracer(clock=WallClock())
    sched.attach_tracer(tracer)
    rng = np.random.default_rng(0)
    for n in (5, 9):
        q.submit(rng.integers(1, cfg.vocab, size=n, dtype=np.int32), 4)
    for _ in range(100):
        if not (eng.busy or eng.backlog or len(q)):
            break
        sched.run(max_spans=1)
    counts = [ev["args"]["experts_reached"] for ev in tracer.events
              if ev["ph"] == "C" and ev["name"] == "experts_reached"]
    assert len(counts) == eng.n_decode_steps > 0
    most = (cfg.n_layers - cfg.n_dense_layers) * cfg.n_experts
    assert all(0 < c <= most for c in counts)
