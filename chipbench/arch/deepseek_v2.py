"""DeepseekV2ForCausalLM (``model_type`` ``deepseek_v2``), one chip's expert
share.

Written from the published architecture (DeepSeek-V2, arXiv:2405.04434,
and the ``modeling_deepseek.py`` published with the checkpoint):

  attention  multi-head latent attention without a query LoRA: q = x Wq
             per head split into q_nope and q_pe; [c, k_pe] = x Wkv_a, c
             RMS-normed; [k_nope, v] = c Wkv_b per head; q_pe and the one
             k_pe of all heads rotate by interleaved pairs (x[2i], x[2i+1])
             at YaRN frequencies; softmax scale 1/sqrt(nope + rope) times
             YaRN's mscale(factor, mscale_all_dim) squared, as DeepSeek's
             own code has it (the transformers port leaves the square out);
  layers     the first ``first_k_dense_replace`` a SwiGLU of
             ``intermediate_size``, the rest expert layers;
  experts    a float32 router over all published experts: softmax, greedy
             top-k, the weights renormalised only under ``norm_topk_prob``,
             times ``routed_scaling_factor``; each chosen expert a SwiGLU of
             ``moe_intermediate_size``; ``n_shared_experts`` shared experts
             as one SwiGLU of that many times the width, added to every
             token.

The chip holds ``n_routed_experts`` of the router's ``published``
``n_routed_experts`` (from ``expert_offset`` on); the reference, like the
program, routes over all of them and adds the held experts' part only, the
part this chip computes under expert parallelism (the model-configs guide,
section 4).  The parameter layout is the program's
(``models.transformer.init_lm``): the dense layers stacked under
``dense_blocks``, the expert layers under ``blocks``, experts stacked on a
leading axis after the layer axis.  The reference forward imports nothing
of the program.

Work, counted as ``work.py`` says:

  decode   the absorbed form, the fewest FLOPs for one query: W_UK folded
           into each query, attention over the cached latent rows (scores
           over the whole row, values over its first ``kv_lora_rank``),
           W_UV after; every weight read once, the routed experts as far
           as the step's tokens reach them under uniform routing (a held
           expert is reached by n tokens with probability
           1 - (1 - k/E)^n, E the published count), the live latent rows
           read (``latent_bytes_per_token``: 576 bf16 values per token and
           layer, 1,152 B), the logits of the active slots written;
  prefill  the decompressed form, per head k = [k_nope, k_pe] and v, causal
           attention counted as half the square; weights as in decode over
           the call's tokens, the prompt's latent rows written, the last
           position's logits written.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference import HIGHEST, _low, _mm, _rms
from chipbench.work import BF16, F32, Work


@dataclass(frozen=True)
class Dims:
    """The sizes the harness itself counts with (work, bytes, reference)."""
    arch: str
    vocab: int
    layers: int
    dense_layers: int
    d: int
    heads: int
    nope: int
    rope: int
    v_head: int
    kv_rank: int
    d_ff: int            # the dense layers' width
    expert_ff: int
    experts: int         # held here
    experts_all: int     # the router's, as published
    expert_offset: int
    top_k: int
    shared: int
    norm_topk: bool
    routed_scale: float
    rope_theta: float
    eps: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def latent_width(self) -> int:
        return self.kv_rank + self.rope

    @property
    def latent_bytes_per_token(self) -> int:
        """The bf16 latent row [c, k_pe] of one position over every layer."""
        return BF16 * self.layers * self.latent_width

    @property
    def attn_params(self) -> int:
        d, H = self.d, self.heads
        return (d * H * (self.nope + self.rope) + d * self.latent_width
                + self.kv_rank + self.kv_rank * H * (self.nope + self.v_head)
                + H * self.v_head * d)

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.expert_ff

    @property
    def fixed_moe_params(self) -> int:
        """An expert layer's parameters outside its routed experts: the
        router and the shared experts."""
        return (self.d * self.experts_all
                + 3 * self.d * self.shared * self.expert_ff)

    @property
    def params(self) -> int:
        """Parameters held on this chip."""
        norms = 2 * self.d
        dense = self.attn_params + 3 * self.d * self.d_ff + norms
        moe = (self.attn_params + self.fixed_moe_params
               + self.experts * self.expert_params + norms)
        return (self.dense_layers * dense + self.moe_layers * moe
                + 2 * self.vocab * self.d + self.d)


def dims(doc: dict) -> Dims:
    y = doc["rope_scaling"]
    held = int(doc["n_routed_experts"])
    return Dims(
        arch=doc["model_type"], vocab=doc["vocab_size"],
        layers=doc["num_hidden_layers"],
        dense_layers=doc["first_k_dense_replace"], d=doc["hidden_size"],
        heads=doc["num_attention_heads"], nope=doc["qk_nope_head_dim"],
        rope=doc["qk_rope_head_dim"], v_head=doc["v_head_dim"],
        kv_rank=doc["kv_lora_rank"], d_ff=doc["intermediate_size"],
        expert_ff=doc["moe_intermediate_size"], experts=held,
        experts_all=int(doc.get("published", {}).get("n_routed_experts",
                                                     held)),
        expert_offset=int(doc.get("expert_offset", 0)),
        top_k=doc["num_experts_per_tok"], shared=doc["n_shared_experts"],
        norm_topk=bool(doc["norm_topk_prob"]),
        routed_scale=float(doc["routed_scaling_factor"]),
        rope_theta=float(doc["rope_theta"]), eps=float(doc["rms_norm_eps"]),
        yarn_factor=float(y["factor"]),
        yarn_original=int(y["original_max_position_embeddings"]),
        yarn_beta_fast=float(y["beta_fast"]),
        yarn_beta_slow=float(y["beta_slow"]),
        yarn_mscale=float(y["mscale"]),
        yarn_mscale_all_dim=float(y["mscale_all_dim"]))


def program_config(doc: dict):
    """The program's ``LatentMoEConfig`` for a configuration file; refuses
    what the program does not implement."""
    from repro.configs.base import LatentMoEConfig

    want = {"hidden_act": "silu", "q_lora_rank": None,
            "scoring_func": "softmax", "topk_method": "greedy",
            "moe_layer_freq": 1, "attention_bias": False,
            "tie_word_embeddings": False}
    for k, v in want.items():
        if doc.get(k) != v:
            raise ValueError(f"{doc['_name']}: {k}={doc.get(k)!r} is not "
                             f"mapped (the program serves {k}={v!r})")
    if doc["rope_scaling"].get("type") != "yarn":
        raise ValueError(f"{doc['_name']}: only YaRN rope_scaling is mapped")
    m = dims(doc)
    scaling = (("factor", m.yarn_factor),
               ("original_max_position_embeddings", m.yarn_original),
               ("beta_fast", m.yarn_beta_fast),
               ("beta_slow", m.yarn_beta_slow), ("mscale", m.yarn_mscale),
               ("mscale_all_dim", m.yarn_mscale_all_dim))
    return LatentMoEConfig(
        name=doc["_name"], family="moe", n_layers=m.layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=doc["num_key_value_heads"],
        d_ff=m.expert_ff, vocab=m.vocab, d_head=m.nope + m.rope,
        rope_theta=m.rope_theta, norm_eps=m.eps, act="silu",
        tie_embeddings=False, dtype=doc["torch_dtype"],
        n_experts=m.experts, top_k=m.top_k, n_shared_experts=m.shared,
        n_dense_layers=m.dense_layers, dense_d_ff=m.d_ff,
        n_router_experts=m.experts_all, expert_offset=m.expert_offset,
        norm_topk_prob=m.norm_topk, routed_scaling_factor=m.routed_scale,
        kv_lora_rank=m.kv_rank, qk_nope_head_dim=m.nope,
        qk_rope_head_dim=m.rope, v_head_dim=m.v_head, rope_scaling=scaling,
        **doc.get("program", {}))


def param_shapes(m: Dims) -> dict:
    """The program's parameter layout (``models.transformer.init_lm``)."""
    d, H = m.d, m.heads

    def attn(n):
        return {"wq": (n, d, H * (m.nope + m.rope)),
                "wkv_a": (n, d, m.latent_width), "kv_norm": (n, m.kv_rank),
                "wkv_b": (n, m.kv_rank, H * (m.nope + m.v_head)),
                "wo": (n, H * m.v_head, d)}

    nd, nm, f = m.dense_layers, m.moe_layers, m.expert_ff
    fs = m.shared * f
    shapes = {
        "embed": (m.vocab, d),
        "blocks": {
            "ln1": (nm, d), "ln2": (nm, d), "attn": attn(nm),
            "moe": {"router": (nm, d, m.experts_all),
                    "w1": (nm, m.experts, d, f), "w3": (nm, m.experts, d, f),
                    "w2": (nm, m.experts, f, d),
                    "ws1": (nm, d, fs), "ws3": (nm, d, fs),
                    "ws2": (nm, fs, d)}},
        "ln_f": (d,),
        "lm_head": (d, m.vocab),
    }
    if nd:
        shapes["dense_blocks"] = {
            "ln1": (nd, d), "ln2": (nd, d), "attn": attn(nd),
            "mlp": {"w1": (nd, d, m.d_ff), "w3": (nd, d, m.d_ff),
                    "w2": (nd, m.d_ff, d)}}
    return shapes


def leaf_scale(path: str, shape) -> tuple[float, float]:
    """(mean, half-width) of the uniform draw for one leaf: matrices keep
    unit-variance activations (1/sqrt(fan_in)), so that the router's
    logits spread about one unit and its top-k is decided by margins
    wider than rounding; norm gains are small perturbations so that their
    code paths change the result."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("ln1", "ln2", "ln_f", "kv_norm"):
        return 1.0, 0.2
    if leaf == "embed":
        return 0.0, np.sqrt(3.0)
    fan_in = shape[-2]
    return 0.0, float(np.sqrt(3.0 / fan_in))


# --- the plain float32 reference -----------------------------------------

def _mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_frequencies(m) -> np.ndarray:
    """DeepSeek's ``DeepseekV2YarnRotaryEmbedding`` inverse frequencies of
    the rotary half (``rope`` dims): extrapolated (theta**-i) where a
    dimension turns more than ``beta_fast`` times over the original
    context, interpolated (divided by ``factor``) below ``beta_slow``
    turns, a linear ramp between."""
    dim, base = m.rope, m.rope_theta

    def correction_dim(rotations):
        return (dim * math.log(m.yarn_original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(m.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(m.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (m.yarn_factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def softmax_scale(m) -> float:
    return ((m.nope + m.rope) ** -0.5
            * _mscale(m.yarn_factor, m.yarn_mscale_all_dim) ** 2)


def _rope(x, pos, m):
    """x: (S, H, rope): each interleaved pair (a, b) as the complex number
    a + ib, multiplied by (cos + i sin)(pos * frequency), scaled by YaRN's
    cos/sin factor (``apply_rotary_emb`` with ``view_as_complex``)."""
    inv = jnp.asarray(yarn_frequencies(m), jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * inv              # (S, rope/2)
    g = (_mscale(m.yarn_factor, m.yarn_mscale)
         / _mscale(m.yarn_factor, m.yarn_mscale_all_dim))
    c, s = (g * jnp.cos(ang))[:, None, :], (g * jnp.sin(ang))[:, None, :]
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(x.shape)


def _swiglu(x, w1, w3, w2, low):
    return _mm(jax.nn.silu(_mm(x, w1, low)) * _mm(x, w3, low), w2, low)


def _attention(h, w, pos, m, low):
    S, H, R = h.shape[0], m.heads, m.kv_rank
    x = _rms(h, w["ln1"], m.eps)
    q = _mm(x, w["wq"], low).reshape(S, H, m.nope + m.rope)
    kv = _mm(x, w["wkv_a"], low)
    c = _rms(kv[:, :R], w["kv_norm"], m.eps)
    k_pe = _rope(kv[:, None, R:], pos, m)                     # (S, 1, rope)
    kvb = _mm(c, w["wkv_b"], low).reshape(S, H, m.nope + m.v_head)
    q = jnp.concatenate([q[..., :m.nope], _rope(q[..., m.nope:], pos, m)],
                        -1)
    k = jnp.concatenate([kvb[..., :m.nope],
                         jnp.broadcast_to(k_pe, (S, H, m.rope))], -1)
    v = kvb[..., m.nope:]
    q, k, v = _low(q, low), _low(k, low), _low(v, low)
    s = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST) * softmax_scale(m)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = _low(jax.nn.softmax(s, axis=-1), low)
    o = jnp.einsum("hst,thd->shd", p, v, precision=HIGHEST)
    return h + _mm(o.reshape(S, H * m.v_head), w["wo"], low)


def route(x, router, m, low):
    """The published router: weights (S, k) and expert ids (S, k)."""
    scores = jax.nn.softmax(_mm(x, router, low), axis=-1)
    w, idx = lax.top_k(scores, m.top_k)
    if m.norm_topk:
        w = w / w.sum(-1, keepdims=True)
    return w * m.routed_scale, idx


def _experts(x, w, m, low):
    """The held experts' part of the routed sum plus the shared experts,
    over every token (each held expert is run on all of them and weighted
    by the router's weight for it, 0 where it was not chosen)."""
    gw, idx = route(x, w["router"], m, low)

    def one(acc, e):
        gate = jnp.sum(jnp.where(idx == m.expert_offset + e, gw, 0.0), -1)
        y = _swiglu(x, w["w1"][e], w["w3"][e], w["w2"][e], low)
        return acc + gate[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(m.experts))
    return out + _swiglu(x, w["ws1"], w["ws3"], w["ws2"], low)


def _layer_weights(blocks, i):
    """Layer ``i`` of a stack of layers, in float32."""
    flat = {**blocks["attn"], **blocks.get("mlp", {}),
            **blocks.get("moe", {}), "ln1": blocks["ln1"],
            "ln2": blocks["ln2"]}
    return jax.tree.map(lambda a: a[i].astype(jnp.float32), flat)


@partial(jax.jit, static_argnames=("m", "low"))
def hidden(params, tokens, m, low):
    """Final-normed hidden states (S, d) of one padded sequence."""
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    h = params["embed"][tokens].astype(jnp.float32)

    def dense(h, i):
        w = _layer_weights(params["dense_blocks"], i)
        h = _attention(h, w, pos, m, low)
        x = _rms(h, w["ln2"], m.eps)
        return h + _swiglu(x, w["w1"], w["w3"], w["w2"], low), None

    def sparse(h, i):
        w = _layer_weights(params["blocks"], i)
        h = _attention(h, w, pos, m, low)
        return h + _experts(_rms(h, w["ln2"], m.eps), w, m, low), None

    if m.dense_layers:
        h, _ = lax.scan(dense, h, jnp.arange(m.dense_layers))
    h, _ = lax.scan(sparse, h, jnp.arange(m.moe_layers))
    return _rms(h, params["ln_f"].astype(jnp.float32), m.eps)


@partial(jax.jit, static_argnames=("low",))
def head(params, hidden, low):
    """Logits of ``hidden`` rows (ROWS, d)."""
    return _mm(hidden, params["lm_head"].astype(jnp.float32), low)


# --- work ----------------------------------------------------------------

def reached_share(m, tokens: float) -> float:
    """The share of the held experts that ``tokens`` tokens reach under
    uniform routing: each token chooses top_k of the experts_all, so a
    given expert is missed by all of them with (1 - k/E)**tokens."""
    return 1.0 - (1.0 - m.top_k / m.experts_all) ** tokens


def _weight_bytes(m, tokens: float) -> float:
    """Every weight but the embedding table, the routed experts as far as
    ``tokens`` tokens reach them."""
    d, norms = m.d, 2 * m.d
    fixed = (m.dense_layers * (m.attn_params + 3 * d * m.d_ff + norms)
             + m.moe_layers * (m.attn_params + m.fixed_moe_params + norms)
             + d * m.vocab + d)
    routed = (m.moe_layers * m.experts * m.expert_params
              * reached_share(m, tokens))
    return BF16 * (fixed + routed)


def _mlp_flops_per_token(m) -> float:
    """The dense layers' SwiGLU, and each expert layer's router, shared
    experts and the k * E_held / E_all held experts a token chooses on
    average under uniform routing."""
    d = m.d
    held_pairs = m.top_k * m.experts / m.experts_all
    moe = (d * m.experts_all + 3 * d * m.shared * m.expert_ff
           + held_pairs * m.expert_params)
    return 2.0 * (m.dense_layers * 3 * d * m.d_ff + m.moe_layers * moe)


def decode_step(m, ctx: list) -> Work:
    """One decode step in the absorbed form; ``ctx`` holds each active
    slot's context read, the new position included."""
    b = len(ctx)
    live = float(sum(ctx))
    d, H, R = m.d, m.heads, m.kv_rank
    proj = (d * H * (m.nope + m.rope) + d * m.latent_width
            + H * m.nope * R + H * R * m.v_head + H * m.v_head * d)
    per_token = 2.0 * m.layers * proj + _mlp_flops_per_token(m) \
        + 2.0 * d * m.vocab
    attn = 2.0 * m.layers * H * (m.latent_width + R) * live
    byts = (_weight_bytes(m, b) + BF16 * b * d
            + live * m.latent_bytes_per_token + F32 * b * m.vocab)
    return Work(per_token * b + attn, byts)


def prefill(m, lens: list) -> Work:
    """One prefill call over prompts of ``lens`` real tokens each, in the
    decompressed form."""
    n = float(sum(lens))
    seqs = len(lens)
    d, H, R = m.d, m.heads, m.kv_rank
    proj = (d * H * (m.nope + m.rope) + d * m.latent_width
            + R * H * (m.nope + m.v_head) + H * m.v_head * d)
    pairs = float(sum(x * (x + 1) / 2 for x in lens))
    flops = (2.0 * m.layers * proj * n + _mlp_flops_per_token(m) * n
             + 2.0 * d * m.vocab * seqs
             + 2.0 * m.layers * H * (m.nope + m.rope + m.v_head) * pairs)
    byts = (_weight_bytes(m, n) + BF16 * n * d
            + n * m.latent_bytes_per_token + F32 * seqs * m.vocab)
    return Work(flops, byts)
