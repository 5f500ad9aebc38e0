"""Qwen2ForCausalLM (``model_type`` ``qwen2``), a dense decoder.

Written from the published architecture: RMSNorm, attention with a bias on
q/k/v and none on o, rotary embeddings on the halves of each head
(``rotate_half``), grouped KV heads, a SwiGLU MLP
(``down(silu(gate(x)) * up(x))``), a final RMSNorm and an untied LM head.
The parameter layout is the program's (``models.transformer.init_lm``):
per-layer leaves stacked on a leading layer axis, ``w1`` the gate, ``w3``
the up projection, ``w2`` the down projection.  The reference forward
imports nothing of the program.

Work, counted as ``work.py`` says:

  decode   every weight read once per step (the embedding only for the
           active rows), the live KV of each active slot read once and its
           new row written, the logits of the active slots written; the
           matmul and attention FLOPs of the active slots only;
  prefill  the real prompt tokens, with no padding: weights read once, the
           prompt's KV written, the last position's logits written; causal
           attention counted as half the square.
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
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one position over every layer."""
        return 2 * 2 * self.layers * self.kv_heads * self.head_dim

    @property
    def layer_params(self) -> int:
        d, hd = self.d, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        bias = self.heads * hd + 2 * self.kv_heads * hd
        return attn + bias + 3 * d * self.d_ff + 2 * d

    @property
    def matmul_params(self) -> int:
        """Parameters a token multiplies by: every layer's projections and
        the LM head (the embedding is a lookup)."""
        d, hd = self.d, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return self.layers * (attn + 3 * d * self.d_ff) + d * self.vocab

    @property
    def params(self) -> int:
        head = 0 if self.tied else self.d * self.vocab
        return self.layers * self.layer_params + self.vocab * self.d + head \
            + self.d


def dims(doc: dict) -> Dims:
    heads = doc["num_attention_heads"]
    return Dims(arch=doc["model_type"], layers=doc["num_hidden_layers"],
                d=doc["hidden_size"],
                heads=heads, kv_heads=doc["num_key_value_heads"],
                head_dim=doc.get("head_dim") or doc["hidden_size"] // heads,
                d_ff=doc["intermediate_size"], vocab=doc["vocab_size"],
                rope_theta=float(doc["rope_theta"]),
                eps=float(doc["rms_norm_eps"]),
                tied=bool(doc["tie_word_embeddings"]))


def program_config(doc: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    if doc.get("hidden_act") != "silu":
        raise ValueError(f"{doc['_name']}: only qwen2-type SwiGLU models "
                         "are mapped")
    m = dims(doc)
    return ModelConfig(
        name=doc["_name"], family="dense", n_layers=m.layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=m.kv_heads, d_ff=m.d_ff, vocab=m.vocab,
        d_head=m.head_dim, qkv_bias=True, rope_theta=m.rope_theta,
        norm_eps=m.eps, act="silu", tie_embeddings=m.tied,
        dtype=doc["torch_dtype"],
        **doc.get("program", {}))


def param_shapes(m: Dims) -> dict:
    """The program's parameter layout (``models.transformer.init_lm``):
    per-layer leaves stacked on a leading layer axis."""
    L, d, hd = m.layers, m.d, m.head_dim
    q, kv = m.heads * hd, m.kv_heads * hd
    shapes = {
        "embed": (m.vocab, d),
        "blocks": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
                     "wo": (L, q, d), "bq": (L, q), "bk": (L, kv),
                     "bv": (L, kv)},
            "mlp": {"w1": (L, d, m.d_ff), "w3": (L, d, m.d_ff),
                    "w2": (L, m.d_ff, d)},
        },
        "ln_f": (d,),
    }
    if not m.tied:
        shapes["lm_head"] = (d, m.vocab)
    return shapes


def leaf_scale(path: str, shape) -> tuple[float, float]:
    """(mean, half-width) of the uniform draw for one leaf: matrices keep
    unit-variance activations (1/sqrt(fan_in)), biases and norm gains are
    small perturbations so that their code paths change the result."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("ln1", "ln2", "ln_f"):
        return 1.0, 0.2
    if leaf.startswith("b"):
        return 0.0, 0.2
    if leaf == "embed":
        return 0.0, np.sqrt(3.0)
    fan_in = shape[-2]
    return 0.0, float(np.sqrt(3.0 / fan_in))


# --- the plain float32 reference -----------------------------------------

def _rope(x, pos, theta):
    """x: (S, H, D) with the rotation applied to the two halves of D."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, w, pos, m, low):
    S = h.shape[0]
    H, KV, D = m.heads, m.kv_heads, m.head_dim
    x = _rms(h, w["ln1"], m.eps)
    q = (_mm(x, w["wq"], low) + w["bq"]).reshape(S, H, D)
    k = (_mm(x, w["wk"], low) + w["bk"]).reshape(S, KV, D)
    v = (_mm(x, w["wv"], low) + w["bv"]).reshape(S, KV, D)
    q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
    q = q.reshape(S, KV, H // KV, D)
    q, k, v = _low(q, low), _low(k, low), _low(v, low)
    s = jnp.einsum("skgd,tkd->kgst", q, k, precision=HIGHEST) / math.sqrt(D)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = _low(jax.nn.softmax(s, axis=-1), low)
    o = jnp.einsum("kgst,tkd->skgd", p, v,
                   precision=HIGHEST).reshape(S, H * D)
    h = h + _mm(o, w["wo"], low)
    x = _rms(h, w["ln2"], m.eps)
    gate = jax.nn.silu(_mm(x, w["w1"], low))
    return h + _mm(gate * _mm(x, w["w3"], low), w["w2"], low)


def _layer_weights(blocks, i):
    """Layer ``i`` of the stacked weights, in float32."""
    flat = {**blocks["attn"], **blocks["mlp"],
            "ln1": blocks["ln1"], "ln2": blocks["ln2"]}
    return jax.tree.map(lambda a: a[i].astype(jnp.float32), flat)


@partial(jax.jit, static_argnames=("m", "low"))
def hidden(params, tokens, m, low):
    """Final-normed hidden states (S, d) of one padded sequence."""
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    h = params["embed"][tokens].astype(jnp.float32)

    def body(h, i):
        return _layer(h, _layer_weights(params["blocks"], i), pos, m,
                      low), None

    h, _ = lax.scan(body, h, jnp.arange(m.layers))
    return _rms(h, params["ln_f"].astype(jnp.float32), m.eps)


@partial(jax.jit, static_argnames=("low",))
def head(params, hidden, low):
    """Logits of ``hidden`` rows (ROWS, d)."""
    return _mm(hidden, params["lm_head"].astype(jnp.float32), low)


# --- work ----------------------------------------------------------------

def _weight_bytes(m) -> float:
    """Every weight but the embedding table."""
    head = 0 if m.tied else m.d * m.vocab
    return BF16 * (m.layers * m.layer_params + m.d + head)


def _attn_flops(m, kv_pairs: float) -> float:
    """QK^T and PV over ``kv_pairs`` (query, key) pairs, every layer."""
    return 4.0 * m.layers * m.heads * m.head_dim * kv_pairs


def decode_step(m, ctx: list) -> Work:
    """One decode step; ``ctx`` holds each active slot's context read,
    the new position included."""
    b = len(ctx)
    live = float(sum(ctx))
    flops = 2.0 * m.matmul_params * b + _attn_flops(m, live)
    byts = (_weight_bytes(m) + BF16 * b * m.d
            + live * m.kv_bytes_per_token + F32 * b * m.vocab)
    return Work(flops, byts)


def prefill(m, lens: list) -> Work:
    """One prefill call over prompts of ``lens`` real tokens each."""
    n = float(sum(lens))
    seqs = len(lens)
    head = m.d * m.vocab
    pairs = float(sum(x * (x + 1) / 2 for x in lens))
    flops = (2.0 * (m.matmul_params - head) * n + 2.0 * head * seqs
             + _attn_flops(m, pairs))
    byts = (_weight_bytes(m) + BF16 * n * m.d + n * m.kv_bytes_per_token
            + F32 * seqs * m.vocab)
    return Work(flops, byts)
