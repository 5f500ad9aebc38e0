"""Core neural-net layers, pure-functional JAX (no flax).

Conventions
-----------
* params are nested dicts of jnp arrays; an ``init_*`` returns params, an
  ``apply``-style function takes ``(params, ...)``.
* activations flow as (batch, seq, ...) unless noted.
* attention uses a chunked online-softmax ("flash") formulation written in
  plain ``lax.scan`` so it lowers on every backend with O(chunk^2) memory;
  the Pallas kernel in ``repro.kernels.flash_attention`` is the TPU-optimized
  drop-in for the same math (``repro.kernels.flash_attention.ops``).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .pspec import pbatch, pkv, pmodel

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, d_in, d_out, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab, d, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x, weight, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: dict) -> np.ndarray:
    """YaRN's (dim/2,) rotary frequencies: the original frequencies on the
    dimensions that turn fast over the original context
    (``original_max_position_embeddings``), the frequencies divided by
    ``factor`` on the slow ones, and a linear ramp between them (``beta_fast``
    and ``beta_slow`` rotations bound it)."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** pos
    inter = 1.0 / (factor * theta ** pos)

    def corr(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(corr(float(scaling.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(corr(float(scaling.get("beta_slow", 1)))), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def apply_rope_pairs(x, positions, inv_freq, mscale: float = 1.0):
    """Rotary embedding of interleaved pairs ``(x[2i], x[2i+1])`` (the
    DeepSeek-V2 layout), with the frequencies ``inv_freq`` (D/2,) and cos
    and sin scaled by ``mscale``.  x: (..., S, H, D); positions: (..., S)."""
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv_freq, jnp.float32)
    cos = (jnp.cos(ang) * mscale)[..., None, :]              # (..., S, 1, D/2)
    sin = (jnp.sin(ang) * mscale)[..., None, :]
    xp = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x0, x1 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked online-softmax attention ("flash" in plain XLA)
# ---------------------------------------------------------------------------

_NEG = -1e30
_NO_WINDOW = 1 << 30


def _chunk_sizes(s: int, want: int) -> int:
    c = min(want, s)
    while s % c:
        c -= 1
    return c


def _window_len(window):
    """window may be None (static: no window), a python int, or a traced
    int32 scalar where <= 0 means "no window" (lets hymba scan over layers
    with per-layer window sizes)."""
    if window is None:
        return None
    w = jnp.asarray(window, jnp.int32)
    return jnp.where(w > 0, w, jnp.int32(_NO_WINDOW))


def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window=None,
    q_offset=0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    logits_soft_cap: float = 0.0,
    scale: float | None = None,
):
    """Chunked attention with online softmax.

    q: (B, Sq, Hq, D);  k: (B, Skv, Hkv, D), v: (B, Skv, Hkv, Dv) with
    Hq % Hkv == 0 (GQA).  ``window`` > 0 restricts each query to the last
    ``window`` keys (SWA).  ``q_offset`` is the absolute position of q[0]
    (for chunked prefill).  ``scale`` defaults to 1/sqrt(D).
    Returns (B, Sq, Hq, Dv).
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hkv
    qc = _chunk_sizes(Sq, q_chunk)
    kc = _chunk_sizes(Skv, kv_chunk)
    nq, nk = Sq // qc, Skv // kc
    scale = 1.0 / math.sqrt(D) if scale is None else scale

    # (nq, B, qc, Hkv, G, D)
    # NOTE: no sharding pins inside the attention loops — constraints here
    # forced a per-tile reshard (measured ~1.3 GiB of all-gather per kv
    # iteration on qwen1.5-110b: 20 TB/step scan-aware); GSPMD propagates
    # the block-level batch/head sharding correctly on its own.
    qs = q.reshape(B, nq, qc, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, kc, Hkv, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, kc, Hkv, Dv).transpose(1, 0, 2, 3, 4)

    q_off = jnp.asarray(q_offset, jnp.int32)
    weff = _window_len(window)

    @jax.checkpoint
    def q_step(_, qi_qblk):
        # checkpointed: persists only qblk per outer step; the inner kv scan's
        # (m, l, acc) carries live transiently during this q-chunk's backward.
        qi, qblk = qi_qblk
        q_pos = q_off + qi * qc + jnp.arange(qc, dtype=jnp.int32)  # (qc,)

        m0 = jnp.full((B, Hkv, G, qc), _NEG, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qc, Dv), jnp.float32)

        @jax.checkpoint
        def kv_step(carry, ki_kv):
            # checkpointed: the backward pass recomputes each (qc x kc)
            # score/prob tile instead of saving all nq*nk tiles — the
            # flash-attention backward structure (68 GiB -> MBs at 32k).
            m, l, acc = carry
            ki, kblk, vblk = ki_kv
            k_pos = ki * kc + jnp.arange(kc, dtype=jnp.int32)  # (kc,)
            # (B, Hkv, G, qc, kc)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qblk, kblk,
                preferred_element_type=jnp.float32,
            ) * scale
            if logits_soft_cap:
                s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
            ok = jnp.ones((qc, kc), bool)
            if causal:
                ok &= q_pos[:, None] >= k_pos[None, :]
            if weff is not None:
                ok &= (q_pos[:, None] - k_pos[None, :]) < weff
            s = jnp.where(ok, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l, acc), None

        (m, l, acc), _ = lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk, dtype=jnp.int32), ks, vs),
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B,Hkv,G,qc,D)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, qc, Hq, Dv)
        return None, out.astype(q.dtype)

    _, outs = lax.scan(q_step, None, (jnp.arange(nq, dtype=jnp.int32), qs))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, Hq, Dv)


def naive_attention(q, k, v, *, causal=True, window=None, q_offset=0):
    """Materialized-scores oracle used by tests."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    qr = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    q_pos = jnp.asarray(q_offset, jnp.int32) + jnp.arange(Sq)
    k_pos = jnp.arange(Skv)
    ok = jnp.ones((Sq, Skv), bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    weff = _window_len(window)
    if weff is not None:
        ok &= (q_pos[:, None] - k_pos[None, :]) < weff
    s = jnp.where(ok, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cur_pos, *, window=None,
                     scale=None):
    """Single-token attention against a (possibly longer) cache.

    q: (B, 1, Hq, D); caches: (B, S, Hkv, D); cur_pos: () or (B,) int32 —
    0-indexed position of each slot's current token (cache entries
    [0, cur_pos[b]] are valid; a vector gives every slot its own context
    length, the masked-attention half of per-slot continuous batching).
    The cache is read as S blocks of one position each.  ``v_cache`` may
    be ``(B, S, Hkv, Dv)``, and ``scale`` replaces 1/sqrt(D).
    """
    return block_attention(q, k_cache[:, :, :, None], v_cache[:, :, :, None],
                           cur_pos, window=window, scale=scale)


def block_attention(q, k_blocks, v_blocks, cur_pos, *, window=None,
                    block_keep=None, scale=None):
    """Single-token attention over gathered head-major KV blocks, read in
    the order they are stored, without flattening the blocks into one
    sequence axis.

    q: (B, 1, Hq, D); blocks: (B, T, Hkv, bs, D), block ``t`` holding
    positions [t*bs, (t+1)*bs); cur_pos: () or (B,) int32.  The softmax runs
    over all T*bs positions.  ``block_keep`` (optional, (B, Hkv, T) bool)
    masks whole blocks per kv-head on top of the causal/window mask (the
    blockwise-sparse path).  Value rows may be narrower than key rows
    (``v_blocks`` (B, T, Hkv, bs, Dv)); ``scale`` replaces 1/sqrt(D).
    """
    B, _, Hq, D = q.shape
    _, T, Hkv, bs, _ = k_blocks.shape
    G = Hq // Hkv
    qr = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bthsd->bhgts", qr, k_blocks,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(D) if scale is None else s * scale
    pos = jnp.arange(T * bs, dtype=jnp.int32).reshape(T, bs)
    cur = jnp.asarray(cur_pos, jnp.int32).reshape(-1, 1, 1)   # (B|1, 1, 1)
    ok = pos[None] <= cur                                     # (B, T, bs)
    weff = _window_len(window)
    if weff is not None:
        ok &= pos[None] > (cur - weff)
    mask = ok[:, None, None]                                  # (B,1,1,T,bs)
    if block_keep is not None:
        mask = mask & block_keep[:, :, None, :, None]
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=(-2, -1))
    o = jnp.einsum("bhgts,bthsd->bhgd", p.astype(v_blocks.dtype), v_blocks,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, v_blocks.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (GQA, rotary, optional bias, KV cache)
# ---------------------------------------------------------------------------


def init_attention(key, cfg, dtype):
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def attention_qkv(p, cfg, x, positions):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.rope_theta > 0:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block(p, cfg, x, *, window=None, positions=None):
    """Full-sequence (train / prefill) attention. Returns (out, (k, v))."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    q, k, v = attention_qkv(p, cfg, x, positions)
    o = flash_attention(
        q, k, v, causal=True, window=window,
        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
    )
    return o.reshape(B, S, -1) @ p["wo"], (k, v)


def _slot_positions(cur_pos, B):
    """Normalize a scalar-or-(B,) write position to per-slot (B, 1)."""
    cur = jnp.asarray(cur_pos, jnp.int32)
    if cur.ndim == 0:
        return cur * jnp.ones((B, 1), jnp.int32)
    return cur[:, None]


def _cache_write(cache_kv, new_kv, cur_pos):
    """Write each slot's (1, Hkv, D) row at its own position.

    cache_kv: (B, S, Hkv, D); new_kv: (B, 1, Hkv, D); cur_pos () or (B,).
    Scalar positions keep the single contiguous DUS; per-slot positions
    vmap the DUS over the batch (lowered as a scatter)."""
    cur = jnp.asarray(cur_pos, jnp.int32)
    if cur.ndim == 0:
        return lax.dynamic_update_slice_in_dim(cache_kv, new_kv, cur, axis=1)
    return jax.vmap(
        lambda c, u, s: lax.dynamic_update_slice_in_dim(c, u, s, axis=0)
    )(cache_kv, new_kv, cur)


def attention_decode(p, cfg, x, cache, cur_pos, *, window=None):
    """x: (B, 1, d); cache: dict(k=(B,S,Hkv,D), v=...); cur_pos: () or (B,)
    int32 0-indexed position to write/attend per slot. Returns out, new
    cache."""
    B = x.shape[0]
    positions = _slot_positions(cur_pos, B)
    q, k, v = attention_qkv(p, cfg, x, positions)
    kc = _cache_write(cache["k"], k, cur_pos)
    vc = _cache_write(cache["v"], v, cur_pos)
    o = decode_attention(q, kc, vc, cur_pos, window=window)
    return o.reshape(B, 1, -1) @ p["wo"], {"k": kc, "v": vc}


def attention_decode_slice(p, cfg, x, cache, cur_pos, *, window=None):
    """Like attention_decode but returns the new (k, v) SLICES instead of
    updated full caches, so a scan over layers emits O(B*Hkv*D) per layer
    and the caller applies one in-place cache update outside the loop.

    No sharding pin on the cache here: GSPMD picks a factored (H x D)
    model-axis layout PartitionSpec cannot express; pinning D 16-ways
    forced a full cache rematerialization per layer (~15 GiB/step)."""
    B = x.shape[0]
    positions = _slot_positions(cur_pos, B)
    q, k, v = attention_qkv(p, cfg, x, positions)
    kc = _cache_write(cache["k"], k, cur_pos)
    vc = _cache_write(cache["v"], v, cur_pos)
    o = decode_attention(q, kc, vc, cur_pos, window=window)
    return o.reshape(B, 1, -1) @ p["wo"], (k, v)


def attention_decode_paged(p, q, k_pages, v_pages, layer, tables, cur_pos,
                           *, window=None, k_scales=None, v_scales=None,
                           sparse_threshold=0.0, scale=None, w_uv=None):
    """Decode attention of the new token against layer ``layer`` of the
    paged pool.

    q: (B, 1, Hq, D) projected queries; pages: (L, N, Hkv, bs, D) head-major
    blocks that already hold each slot's new row (the caller appends it
    first, ``transformer.decode_step_paged``); tables: (B, T) int32 block
    ids (null-padded); cur_pos: (B,) int32 per-slot position.  Each slot's
    block chain is gathered straight from the layer-stacked pool to
    (B, T, Hkv, bs, D) and attended in that order (``block_attention``), so
    neither the pool, nor the layer's slice of it, nor the gathered view is
    copied into another layout (the Pallas kernel in
    ``repro.kernels.paged_attention`` streams blocks instead of
    gathering).  Returns the attention output projected by ``wo``.

    ``k_scales``/``v_scales`` ((L, N, Hkv) f32) mark a quantized pool
    layout: packed int8/fp8 pages are dequantized on the gather.  A
    positive ``sparse_threshold`` (static) drops whole KV blocks whose
    estimated attention mass falls below it — selection comes from the
    kernel oracle's ``block_keep_mask`` so model path and kernel agree.

    A latent pool (multi-head latent attention, absorbed form) holds rows
    (L, N, bs, W) that are read as one KV "head" shared by every query
    head: ``v_pages`` is None, the values are the first ``w_uv.shape[0]``
    lanes of each gathered row, and the attention output is mapped back to
    each head's values by ``w_uv`` (R, Hq, Dv) before ``wo``; ``scale``
    replaces 1/sqrt(D).
    """
    B = q.shape[0]
    cur = jnp.asarray(cur_pos, jnp.int32)
    # the named scopes let a device trace's per-operation times be read
    # back by part (they change HLO metadata only, not the program)
    with jax.named_scope("kv_gather"):
        kg = k_pages[layer, tables]
        if v_pages is None:
            kg = kg[:, :, None]                      # (B, T, 1, bs, W)
            vg = kg[..., :w_uv.shape[0]]
        else:
            vg = v_pages[layer, tables]
        if k_scales is not None:
            kg = (kg.astype(jnp.float32)
                  * k_scales[layer, tables][..., None, None]).astype(q.dtype)
            vg = (vg.astype(jnp.float32)
                  * v_scales[layer, tables][..., None, None]).astype(q.dtype)
    with jax.named_scope("attention"):
        keep = None
        if sparse_threshold:
            from repro.kernels.paged_attention.ref import block_keep_mask
            keep = block_keep_mask(
                q[:, 0], k_pages[layer], tables, cur,
                threshold=sparse_threshold, window=window,
                k_scales=None if k_scales is None else k_scales[layer])
        o = block_attention(q, kg, vg, cur, window=window, block_keep=keep,
                            scale=scale)
    with jax.named_scope("attn_proj"):
        if w_uv is not None:
            o = mla_values(o, w_uv)
        return o.reshape(B, 1, -1) @ p["wo"]


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------
#
# Per token: q = x @ wq split per head into q_nope and q_pe (no q LoRA);
# [c, k_pe] = x @ wkv_a, c normed by kv_norm; [k_nope, v] = c @ wkv_b per
# head; q_pe and k_pe (one for all heads) rotate by interleaved pairs at
# YaRN frequencies.  The cache holds the latent row [c, k_pe] only.
# Prefill attends in the decompressed form (per-head k = [k_nope, k_pe]);
# decode in the absorbed form, where wkv_b's key half is folded into the
# query (q_nope @ W_UK^T) and its value half applied after the attention
# (W_UV), so that the cached rows are read as they are.


def init_mla(key, cfg, dtype):
    d, H = cfg.d_model, cfg.n_heads
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    R = cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, H * (nope + rope), dtype),
        "wkv_a": dense_init(ks[1], d, R + rope, dtype),
        "kv_norm": jnp.ones((R,), dtype),
        "wkv_b": dense_init(ks[2], R, H * (nope + dv), dtype),
        "wo": dense_init(ks[3], H * dv, d, dtype),
    }


def mla_rope(cfg):
    """(inv_freq, cos/sin scale) of the rotary half of each head: YaRN's
    (``cfg.rope_scaling``)."""
    sc = dict(cfg.rope_scaling)
    f = float(sc["factor"])
    return (yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, sc),
            yarn_get_mscale(f, sc.get("mscale", 1.0))
            / yarn_get_mscale(f, sc.get("mscale_all_dim", 1.0)))


def mla_softmax_scale(cfg) -> float:
    """1/sqrt(qk head size), times YaRN's mscale squared as DeepSeek's own
    modeling code has it (the transformers port leaves the square out)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    sc = dict(cfg.rope_scaling)
    if sc.get("mscale_all_dim"):
        scale *= yarn_get_mscale(float(sc["factor"]),
                                 float(sc["mscale_all_dim"])) ** 2
    return scale


def mla_project(p, cfg, x, positions):
    """q_nope (B, S, H, nope), rotated q_pe (B, S, H, rope) and the latent
    rows (B, S, ``cfg.latent_width``): normed c, the rotated k_pe, then
    zero lanes."""
    B, S, _ = x.shape
    H, nope, R = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    inv, msc = mla_rope(cfg)
    q = (x @ p["wq"]).reshape(B, S, H, -1)
    q_pe = apply_rope_pairs(q[..., nope:], positions, inv, msc)
    kv = x @ p["wkv_a"]
    c = rms_norm(kv[..., :R], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope_pairs(kv[:, :, None, R:], positions, inv, msc)
    return q[..., :nope], q_pe, _lanes(jnp.concatenate([c, k_pe[:, :, 0]],
                                                      -1), cfg)


def _lanes(x, cfg):
    """``x`` (..., R + rope) with zero lanes up to ``cfg.latent_width``."""
    pad = cfg.latent_width - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def mla_block(p, cfg, x, *, positions=None):
    """Full-sequence (train / prefill) latent attention in the decompressed
    form.  Returns (out, latent rows (B, S, ``cfg.latent_width``))."""
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
    B, S, _ = x.shape
    H, nope, R = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("attn_proj"):
        q_nope, q_pe, rows = mla_project(p, cfg, x, positions)
        kv = (rows[..., :R] @ p["wkv_b"]).reshape(B, S, H, -1)
        k_pe = jnp.broadcast_to(
            rows[:, :, None, R:R + cfg.qk_rope_head_dim],
            (B, S, H, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    with jax.named_scope("attention"):
        o = flash_attention(q, k, kv[..., nope:], causal=True,
                            q_chunk=cfg.attn_q_chunk,
                            kv_chunk=cfg.attn_kv_chunk,
                            scale=mla_softmax_scale(cfg))
    with jax.named_scope("attn_proj"):
        return o.reshape(B, S, -1) @ p["wo"], rows


def mla_absorb(p, cfg, x, positions):
    """The absorbed decode query and the new latent rows, each
    ``cfg.latent_width`` wide ((B, S, H, W), (B, S, W)), and W_UV
    (R, H, Dv)."""
    H, nope, R = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla_absorb"):
        q_nope, q_pe, rows = mla_project(p, cfg, x, positions)
        w = p["wkv_b"].reshape(R, H, -1)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w[..., :nope])
        return (_lanes(jnp.concatenate([q_lat, q_pe], -1), cfg), rows,
                w[..., nope:])


def mla_values(o_lat, w_uv):
    """Each head's attention over latent rows (B, S, H, R) -> its values
    (B, S, H, Dv)."""
    with jax.named_scope("mla_absorb"):
        return jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)


# ---------------------------------------------------------------------------
# MLP (SwiGLU or GELU)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model, d_ff, act, dtype):
    ks = jax.random.split(key, 3)
    p = {"w1": dense_init(ks[0], d_model, d_ff, dtype),
         "w2": dense_init(ks[1], d_ff, d_model, dtype)}
    if act == "silu":  # SwiGLU gate
        p["w3"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp_block(p, x, act: str):
    with jax.named_scope("mlp"):
        if act == "silu":
            h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
        else:
            h = jax.nn.gelu(x @ p["w1"])
        return h @ p["w2"]
