"""Decoder-only LM covering the dense / MoE / hybrid / SSM / VLM families.

One parameter pytree per model; per-layer parameters are stacked on a leading
``L`` axis and consumed with ``lax.scan`` so the lowered HLO is O(1) in depth
(critical for 80-layer configs compiled on a single CPU core, and the natural
form for FSDP weight gathering inside the loop).

Public entry points
-------------------
init_lm(key, cfg)                         -> params
forward_lm(params, cfg, batch)            -> (logits_f32, aux)
loss_fn(params, cfg, batch)               -> (loss, metrics)
init_cache(cfg, batch, max_len, dtype)    -> cache pytree
prefill(params, cfg, batch)               -> (logits_last, cache)
decode_step(params, cfg, token, cache)    -> (logits, cache)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from . import layers as L
from . import moe as MOE
from . import ssm as SSM
from .pspec import pbatch, presidual

# ---------------------------------------------------------------------------
# per-layer structure helpers
# ---------------------------------------------------------------------------


def has_attn(cfg) -> bool:
    return cfg.family != "ssm"


def has_ssm(cfg) -> bool:
    return cfg.family in ("ssm", "hybrid")


def has_mlp(cfg) -> bool:
    return cfg.family not in ("ssm",) and cfg.n_experts == 0


def layer_windows(cfg) -> np.ndarray:
    """(L,) int32; 0 => full attention, >0 => sliding window."""
    w = np.zeros((cfg.n_layers,), np.int32)
    if cfg.attn_window > 0:
        w[:] = cfg.attn_window
        for i in cfg.global_layers:
            w[i % cfg.n_layers] = 0
    return w


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_block(key, cfg, dense=False):
    """One layer's parameters; ``dense`` makes one of the leading dense
    layers (``cfg.n_dense_layers``, a SwiGLU of ``cfg.dense_d_ff``)."""
    dt = _dtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p = {"ln1": jnp.ones((d,), dt)}
    if has_attn(cfg):
        p["attn"] = (L.init_mla(ks[0], cfg, dt) if cfg.mla
                     else L.init_attention(ks[0], cfg, dt))
    if has_ssm(cfg):
        p["ssm"] = SSM.init_ssm(ks[1], cfg, dt)
    if dense:
        p["ln2"] = jnp.ones((d,), dt)
        p["mlp"] = L.init_mlp(ks[3], d, cfg.dense_d_ff, cfg.act, dt)
    elif cfg.n_experts:
        p["ln2"] = jnp.ones((d,), dt)
        p["moe"] = MOE.init_moe(ks[2], cfg, dt)
    elif has_mlp(cfg) and cfg.d_ff > 0:
        p["ln2"] = jnp.ones((d,), dt)
        p["mlp"] = L.init_mlp(ks[3], d, cfg.d_ff, cfg.act, dt)
    return p


def init_lm(key, cfg):
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    nd = cfg.n_dense_layers
    params = {
        "embed": L.embed_init(ks[1], cfg.vocab, cfg.d_model, dt),
        "blocks": jax.vmap(lambda k: init_block(k, cfg))(layer_keys[nd:]),
        "ln_f": jnp.ones((cfg.d_model,), dt),
    }
    if nd:
        params["dense_blocks"] = jax.vmap(
            lambda k: init_block(k, cfg, dense=True))(layer_keys[:nd])
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[2], cfg.d_model, cfg.vocab, dt)
    if cfg.n_meta_tokens:
        params["meta"] = (jax.random.normal(
            ks[3], (cfg.n_meta_tokens, cfg.d_model), jnp.float32) * 0.02).astype(dt)
    return params


def count_params(params) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(params)))


def moe_layer(p, cfg, h):
    """An expert layer: (out, aux loss, held experts reached).  An expert
    share (``cfg.expert_share``) runs dropless and reports what it reached;
    the other configurations keep the capacity layer (reached None)."""
    if cfg.expert_share:
        with jax.named_scope("mlp"):
            out, reached = MOE.moe_share(p, cfg, h)
        return out, jnp.zeros((), jnp.float32), reached
    out, aux = MOE.moe_block(p, cfg, h)
    return out, aux, None


def _run_stacks(params, cfg, body, carry, per_layer):
    """``lax.scan`` of ``body`` over each run of layers of one kind in turn
    (the leading dense layers, ``params["dense_blocks"]``, then the rest,
    ``params["blocks"]``: the only run of most configurations), with
    ``per_layer`` ((L, ...) arrays, cut to each run) beside the stacked
    parameters.  Returns (carry, the per-layer outputs over all layers)."""
    nd = cfg.n_dense_layers
    runs = ([(params["dense_blocks"], 0, nd)] if nd else []) + \
        [(params["blocks"], nd, cfg.n_layers)]
    ys = []
    for bps, lo, hi in runs:
        xs = per_layer if len(runs) == 1 else \
            jax.tree.map(lambda a: a[lo:hi], per_layer)
        carry, y = lax.scan(body, carry, (bps, *xs))
        ys.append(y)
    if len(ys) == 1:
        return carry, ys[0]
    return carry, jax.tree.map(lambda *a: jnp.concatenate(a), *ys)


# ---------------------------------------------------------------------------
# block application (full sequence)
# ---------------------------------------------------------------------------


def apply_block(bp, cfg, x, window, positions):
    """One transformer block on a full sequence. Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
    delta = 0.0
    if has_attn(cfg) and cfg.mla:
        a_out, _ = L.mla_block(bp["attn"], cfg, h, positions=positions)
        delta = delta + a_out
    elif has_attn(cfg):
        a_out, _ = L.attention_block(bp["attn"], cfg, h, window=window,
                                     positions=positions)
        delta = delta + a_out
    if has_ssm(cfg):
        s_out, _ = SSM.ssm_block(bp["ssm"], cfg, h)
        if has_attn(cfg):  # hybrid: mean-fuse the two parallel paths
            delta = (delta + s_out) * 0.5
        else:
            delta = delta + s_out
    x = x + delta
    if "moe" in bp:
        h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        m_out, aux, _ = moe_layer(bp["moe"], cfg, h)
        x = x + m_out
    elif "mlp" in bp:
        h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        x = x + L.mlp_block(bp["mlp"], h, cfg.act)
    return x, aux


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _embed_input(params, cfg, batch):
    """Assemble the input embedding sequence (meta/vision prefixes included).

    batch: dict with "tokens" (B, S_text); VLM adds "img_embeds"
    (B, n_img_tokens, d).  Returns (x (B, S_total, d), n_prefix).
    """
    tokens = batch["tokens"]
    B = tokens.shape[0]
    x = params["embed"][tokens]
    n_prefix = 0
    if cfg.n_img_tokens:
        img = batch["img_embeds"].astype(x.dtype)
        x = jnp.concatenate([img, x], axis=1)
        n_prefix += cfg.n_img_tokens
    if cfg.n_meta_tokens:
        meta = jnp.broadcast_to(params["meta"][None], (B,) + params["meta"].shape)
        x = jnp.concatenate([meta, x], axis=1)
        n_prefix += cfg.n_meta_tokens
    return x, n_prefix


def forward_hidden(params, cfg, batch):
    """Full-sequence forward up to the final norm.

    Returns (hidden (B, S_text, d), aux)."""
    x, n_prefix = _embed_input(params, cfg, batch)
    x = presidual(x)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    windows = jnp.asarray(layer_windows(cfg))

    def body(carry, xs):
        x, aux = carry
        bp, win = xs
        x, a = apply_block(bp, cfg, x, win, positions)
        return (presidual(x), aux + a), None

    body = _remat(cfg, body)
    (x, aux), _ = _run_stacks(params, cfg, body,
                              (x, jnp.zeros((), jnp.float32)), (windows,))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]
    return x, aux / cfg.n_layers


def forward_lm(params, cfg, batch):
    """Full-sequence forward. Returns (logits (B, S_text, V) f32, aux)."""
    x, aux = forward_hidden(params, cfg, batch)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head).astype(jnp.float32)
    return logits, aux


def chunked_ce(x, head, labels, mask, chunk: int = 512):
    """Cross entropy without materializing (B, S, V) logits: scan over
    sequence chunks, recomputing each chunk's logits in the backward pass
    (checkpointed scan body).  Essential at 150k vocab x 1M tokens."""
    B, S, d = x.shape
    c = min(chunk, S)
    while S % c:
        c -= 1
    n = S // c
    xs = x.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, c).transpose(1, 0, 2)
    ms = mask.reshape(B, n, c).transpose(1, 0, 2)

    @jax.checkpoint
    def body(carry, inp):
        xc, lc, mc = inp
        logits = pbatch((xc @ head).astype(jnp.float32))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + ((logz - gold) * mc).sum(), None

    xs, ls, ms = pbatch(xs, 1), pbatch(ls, 1), pbatch(ms, 1)
    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls, ms))
    return total


def loss_fn(params, cfg, batch, *, loss_chunk: int = 256):
    """Causal-LM cross entropy (+ MoE aux). batch: tokens, labels[, mask]."""
    x, aux = forward_hidden(params, cfg, batch)
    labels = batch["labels"]
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    nll = chunked_ce(x, head, labels, mask, loss_chunk)
    loss = nll / jnp.maximum(mask.sum(), 1.0)
    total = loss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "aux": aux}


# ---------------------------------------------------------------------------
# KV / SSM cache, prefill, decode
# ---------------------------------------------------------------------------


def init_cache(cfg, batch, max_len, dtype=None):
    """Cache pytree stacked over layers; max_len includes any prefix tokens.

    ``len`` is a per-slot (B,) vector: every sequence in the batch carries
    its own context length, so ragged prompts and per-slot refill share one
    cache (a scalar is still accepted by ``decode_step`` for compat).  A
    latent-attention configuration caches one latent row per position and
    layer under ``k``, ``(L, B, max_len, latent_width)``, and no ``v``."""
    dt = dtype or _dtype(cfg)
    Lc = cfg.n_layers
    c = {"len": jnp.zeros((batch,), jnp.int32)}
    if has_attn(cfg) and cfg.mla:
        c["k"] = jnp.zeros((Lc, batch, max_len, cfg.latent_width), dt)
    elif has_attn(cfg):
        hd = cfg.head_dim
        c["k"] = jnp.zeros((Lc, batch, max_len, cfg.n_kv_heads, hd), dt)
        c["v"] = jnp.zeros((Lc, batch, max_len, cfg.n_kv_heads, hd), dt)
    if has_ssm(cfg):
        d_inner, conv_dim = SSM.ssm_dims(cfg)
        c["ssm_state"] = jnp.zeros(
            (Lc, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), dt)
        c["ssm_conv"] = jnp.zeros((Lc, batch, cfg.ssm_conv - 1, conv_dim), dt)
    return c


def decode_step(params, cfg, token, cache):
    """token: (B, 1) int32. Returns (logits (B, 1, V) f32, new cache).

    ``cache["len"]`` may be a scalar (legacy shared position) or a (B,)
    vector of per-slot write positions — the vector form is what lets one
    decode batch mix sequences of different context lengths (ragged
    prompts, per-slot continuous-batching refill)."""
    x = pbatch(params["embed"][token])  # (B,1,d)
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(cache["len"], jnp.int32), (B,))
    windows = jnp.asarray(layer_windows(cfg))

    def body(carry, xs):
        # cache-as-carry with in-place DUS per layer: the classic JAX KV
        # cache idiom — while-loop carries get in-place dynamic updates,
        # where cache-as-scan-xs/ys double-buffers (measured +16 GiB/dev).
        x, kc_all, vc_all = carry
        bp, win, li, st, cv = xs
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        delta = 0.0
        new_st, new_cv = st, cv
        if has_attn(cfg) and cfg.mla:
            # absorbed latent attention over the cached latent rows
            q, row, w_uv = L.mla_absorb(bp["attn"], cfg, h, pos[:, None])
            b_idx = jnp.arange(B)
            kc_all = kc_all.at[li, b_idx, pos].set(row[:, 0])
            kc = lax.dynamic_index_in_dim(kc_all, li, 0,
                                          keepdims=False)[:, :, None]
            o = L.decode_attention(q, kc, kc[..., :cfg.kv_lora_rank], pos,
                                   scale=L.mla_softmax_scale(cfg))
            delta = delta + L.mla_values(o, w_uv).reshape(B, 1, -1) \
                @ bp["attn"]["wo"]
        elif has_attn(cfg):
            kc = lax.dynamic_index_in_dim(kc_all, li, 0, keepdims=False)
            vc = lax.dynamic_index_in_dim(vc_all, li, 0, keepdims=False)
            a_out, kv = L.attention_decode_slice(
                bp["attn"], cfg, h, {"k": kc, "v": vc}, pos, window=win)
            k_new, v_new = kv  # (B, 1, Hkv, D)
            # write only each slot's new row into the carry (a per-slot
            # scatter, not a full-slab copy — the slab rematerialization
            # attention_decode_slice exists to avoid)
            b_idx = jnp.arange(k_new.shape[0])
            kc_all = kc_all.at[li, b_idx, pos].set(k_new[:, 0])
            vc_all = vc_all.at[li, b_idx, pos].set(v_new[:, 0])
            delta = delta + a_out
        if has_ssm(cfg):
            s_out, sc = SSM.ssm_decode(bp["ssm"], cfg, h,
                                       {"state": st, "conv": cv})
            new_st, new_cv = sc["state"], sc["conv"]
            if has_attn(cfg):
                delta = (delta + s_out) * 0.5
            else:
                delta = delta + s_out
        x = x + delta
        if "moe" in bp:
            h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
            m_out, _, _ = moe_layer(bp["moe"], cfg, h)
            x = x + m_out
        elif "mlp" in bp:
            h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
            x = x + L.mlp_block(bp["mlp"], h, cfg.act)
        return (x, kc_all, vc_all), (new_st, new_cv)

    Lc = cfg.n_layers
    dummy = jnp.zeros((Lc, 0), _dtype(cfg))
    dummy2 = jnp.zeros((0,), _dtype(cfg))
    kc = cache.get("k", dummy2)
    vc = cache.get("v", dummy2)
    st = cache.get("ssm_state", dummy)
    cv = cache.get("ssm_conv", dummy)
    lidx = jnp.arange(Lc, dtype=jnp.int32)

    (x, nk, nv), (nst, ncv) = _run_stacks(
        params, cfg, body, (x, kc, vc), (windows, lidx, st, cv))

    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x @ head).astype(jnp.float32)

    new_cache = dict(cache)
    if has_attn(cfg):
        new_cache["k"] = nk
        if "v" in cache:
            new_cache["v"] = nv
    if has_ssm(cfg):
        new_cache["ssm_state"], new_cache["ssm_conv"] = nst, ncv
    new_cache["len"] = cache["len"] + 1
    return logits, new_cache


def _append_block(pages, scales, li, blk, at_row, new, kv_name):
    """Put each slot's new row into its current block and write the whole
    block back: ``pages`` (L, N, Hkv, bs, D) at layer ``li``, ``blk`` (B,)
    block ids, ``at_row`` (B, 1, bs, 1) marking each slot's row, ``new``
    (B, 1, Hkv, D).  A latent pool has ``pages`` (L, N, bs, W) and ``new``
    (B, 1, W).  A quantized pool (``scales`` (L, N, Hkv) given)
    requantizes the block with a fresh scale.  Returns (pages, scales)."""
    cur = pages[li, blk]                                  # (B, Hkv, bs, D)
    if pages.ndim == 4:
        return pages.at[li, blk].set(
            jnp.where(at_row[:, 0], new.astype(pages.dtype), cur)), None
    row = new[:, 0, :, None, :]                           # (B, Hkv, 1, D)
    if scales is None:
        return pages.at[li, blk].set(
            jnp.where(at_row, row.astype(pages.dtype), cur)), None
    # lazy: serving imports models, so models must not import serving at
    # module scope
    from repro.serving.kv_pool import dequantize_kv, quantize_kv
    full = dequantize_kv(cur, scales[li, blk])
    packed, s = quantize_kv(
        jnp.where(at_row, row.astype(full.dtype), full), kv_name)
    return pages.at[li, blk].set(packed), scales.at[li, blk].set(s)


def decode_step_paged(params, cfg, token, pcache, *, sparse_threshold=0.0):
    """One decode step against a paged (block-table) KV pool.

    token: (B, 1) int32.  pcache:
      k_pages/v_pages : (L, N, Hkv, bs, D) shared pool of head-major blocks
                        (a latent-attention configuration: k_pages
                        (L, N, bs, latent_width) of latent rows, no
                        v_pages)
      tables          : (B, T) int32 per-slot block chains (null-padded)
      lens            : (B,) int32 per-slot write positions
      k_scales/v_scales (quantized pools): (L, N, Hkv) f32 per-(block,
        kv-head) scales; their presence marks packed int8/fp8 pages
      ssm_state/ssm_conv (families with SSM): per-slot as in the dense cache
    Same math as ``decode_step`` on the dense gather of each slot's chain —
    the equivalence the engine test suite pins down.  Each layer first
    appends: it reads each slot's current block, puts the new row in, and
    writes the whole block back (``_append_block``); attention then reads
    the slot's chain, new row included, in the order the pool stores it.
    On a quantized pool the append requantizes the whole block with a
    fresh scale per (bs, D) tile (per-step re-rounding error stays bounded
    by ``scale / 2`` per element; see docs/kv_quantization.md).  A positive
    ``sparse_threshold`` (static) makes attention skip low-mass KV blocks.
    Returns (logits (B, 1, V) f32, new pcache) with every ``lens`` advanced
    by one (the engine overrides lengths for inactive slots from host
    bookkeeping).  An expert share (``cfg.expert_share``) also returns
    ``experts_reached`` in the new pcache: (L,) int32, the held experts
    the step's tokens reached in each layer (0 in a dense layer).
    """
    x = pbatch(params["embed"][token])  # (B,1,d)
    pos = jnp.asarray(pcache["lens"], jnp.int32)
    tables = jnp.asarray(pcache["tables"], jnp.int32)
    windows = jnp.asarray(layer_windows(cfg))
    quant = "k_scales" in pcache
    kv_name = None
    if quant:
        kv_name = "int8" if pcache["k_pages"].dtype == jnp.int8 else "fp8"
    if has_attn(cfg):
        bs = pcache["k_pages"].shape[2 if cfg.mla else 3]
        # each slot's current block, and its row there
        blk = jnp.take_along_axis(
            tables, jnp.clip(pos // bs, 0, tables.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        at_row = (jnp.arange(bs) == (pos % bs)[:, None])[:, None, :, None]
    scale = L.mla_softmax_scale(cfg) if cfg.mla else None

    def body(carry, xs):
        x, kp_all, vp_all, ks_all, vs_all = carry
        bp, win, li, st, cv = xs
        with jax.named_scope("attn_proj"):
            h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        delta = 0.0
        new_st, new_cv = st, cv
        if has_attn(cfg):
            w_uv = None
            with jax.named_scope("attn_proj"):
                if cfg.mla:
                    q, k_new, w_uv = L.mla_absorb(bp["attn"], cfg, h,
                                                  pos[:, None])
                else:
                    q, k_new, v_new = L.attention_qkv(bp["attn"], cfg, h,
                                                      pos[:, None])
            with jax.named_scope("kv_append"):
                kp_all, ks_all = _append_block(kp_all, ks_all, li, blk,
                                               at_row, k_new, kv_name)
                if vp_all is not None:
                    vp_all, vs_all = _append_block(vp_all, vs_all, li, blk,
                                                   at_row, v_new, kv_name)
            delta = delta + L.attention_decode_paged(
                bp["attn"], q, kp_all, vp_all, li, tables, pos, window=win,
                k_scales=ks_all, v_scales=vs_all,
                sparse_threshold=sparse_threshold, scale=scale, w_uv=w_uv)
        if has_ssm(cfg):
            s_out, sc = SSM.ssm_decode(bp["ssm"], cfg, h,
                                       {"state": st, "conv": cv})
            new_st, new_cv = sc["state"], sc["conv"]
            if has_attn(cfg):
                delta = (delta + s_out) * 0.5
            else:
                delta = delta + s_out
        x = x + delta
        reached = None
        if "moe" in bp:
            with jax.named_scope("mlp"):
                h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
            m_out, _, reached = moe_layer(bp["moe"], cfg, h)
            x = x + m_out
        elif "mlp" in bp:
            with jax.named_scope("mlp"):
                h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
            x = x + L.mlp_block(bp["mlp"], h, cfg.act)
        ys = (new_st, new_cv)
        if cfg.expert_share:
            ys += (jnp.zeros((), jnp.int32) if reached is None else reached,)
        return (x, kp_all, vp_all, ks_all, vs_all), ys

    Lc = cfg.n_layers
    dummy = jnp.zeros((Lc, 0), _dtype(cfg))
    kp = pcache.get("k_pages")
    vp = pcache.get("v_pages")
    ks = pcache.get("k_scales")
    vs = pcache.get("v_scales")
    st = pcache.get("ssm_state", dummy)
    cv = pcache.get("ssm_conv", dummy)
    lidx = jnp.arange(Lc, dtype=jnp.int32)

    (x, nkp, nvp, nks, nvs), ys = _run_stacks(
        params, cfg, body, (x, kp, vp, ks, vs), (windows, lidx, st, cv))

    with jax.named_scope("lm_head"):
        x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head).astype(jnp.float32)

    new_pcache = dict(pcache)
    if has_attn(cfg):
        new_pcache["k_pages"] = nkp
        if vp is not None:
            new_pcache["v_pages"] = nvp
        if quant:
            new_pcache["k_scales"], new_pcache["v_scales"] = nks, nvs
    if has_ssm(cfg):
        new_pcache["ssm_state"], new_pcache["ssm_conv"] = ys[0], ys[1]
    if cfg.expert_share:
        new_pcache["experts_reached"] = ys[2]
    new_pcache["lens"] = pos + 1
    return logits, new_pcache


def prefill(params, cfg, batch, max_len=None, lens=None):
    """Run the prompt through the model, building a decode cache.

    ``lens`` (optional, (B,) int32): per-slot valid text-token counts for a
    ragged wave — prompts shorter than the padded batch width take their
    "last-position" logits at their own final token (causal masking makes
    the pad tokens after a slot's length invisible to it), and the cache
    ``len`` vector records each slot's true context.  Without ``lens`` every
    slot uses the full width.  A latent-attention configuration writes each
    layer's latent rows into one cache slab carried through the layers
    (in place), so that its runs of layers build one cache without a
    second copy of it.

    Returns (last-position logits (B, V) f32, cache).
    """
    x, n_prefix = _embed_input(params, cfg, batch)
    x = presidual(x)
    B, S, _ = x.shape
    max_len = max_len or S
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    windows = jnp.asarray(layer_windows(cfg))

    def layer(x, bp, win):
        x = presidual(x)
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        delta = 0.0
        kv = st = None
        outs = {}
        if has_attn(cfg) and cfg.mla:
            a_out, outs["k"] = L.mla_block(bp["attn"], cfg, h,
                                           positions=positions)
            delta = delta + a_out
        elif has_attn(cfg):
            a_out, kv = L.attention_block(bp["attn"], cfg, h, window=win,
                                          positions=positions)
            delta = delta + a_out
        if has_ssm(cfg):
            s_out, st = SSM.ssm_block(bp["ssm"], cfg, h)
            delta = (delta + s_out) * 0.5 if has_attn(cfg) else delta + s_out
        x = x + delta
        if "moe" in bp:
            with jax.named_scope("mlp"):
                hh = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
            m_out, _, _ = moe_layer(bp["moe"], cfg, hh)
            x = x + m_out
        elif "mlp" in bp:
            hh = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
            x = x + L.mlp_block(bp["mlp"], hh, cfg.act)
        if kv is not None:
            k, v = kv
            pad = [(0, 0), (0, max_len - S), (0, 0), (0, 0)]
            # pin the emitted cache slices to the batch axes: prefill's scan
            # ys ARE the returned KV cache; without the pin XLA replicated
            # them across the model axis on large cells.
            outs["k"] = pbatch(jnp.pad(k, pad))
            outs["v"] = pbatch(jnp.pad(v, pad))
        if st is not None:
            outs["ssm_state"] = st["state"]
            outs["ssm_conv"] = st["conv"]
        return x, outs

    if cfg.mla:
        def body(carry, xs):
            x, slab = carry
            bp, win, li = xs
            x, outs = layer(x, bp, win)
            # row-major rows: the slab then keeps the output's layout, where
            # the layout the projection's weights suggest would make XLA
            # relayout the whole slab after the last layer
            rows = with_layout_constraint(outs["k"].astype(slab.dtype),
                                          Layout(major_to_minor=(0, 1, 2)))
            slab = lax.dynamic_update_slice(slab, rows[None], (li, 0, 0, 0))
            return (x, slab), None

        slab = jnp.zeros((cfg.n_layers, B, max_len, cfg.latent_width),
                         _dtype(cfg))
        (x, slab), _ = _run_stacks(
            params, cfg, body, (x, slab),
            (windows, jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        caches = {"k": slab}
    else:
        def body(x, xs):
            bp, win = xs
            return layer(x, bp, win)

        x, caches = _run_stacks(params, cfg, body, x, (windows,))
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if lens is None:
        logits = (x[:, -1] @ head).astype(jnp.float32)
        len_vec = jnp.full((B,), S, jnp.int32)
    else:
        lens = jnp.asarray(lens, jnp.int32)
        idx = jnp.clip(n_prefix + lens - 1, 0, S - 1)       # (B,)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        logits = (last @ head).astype(jnp.float32)
        len_vec = n_prefix + lens

    cache = init_cache(cfg, B, max_len)
    for key in ("k", "v", "ssm_state", "ssm_conv"):
        if key in caches:
            cache[key] = caches[key].astype(cache[key].dtype)
    cache["len"] = len_vec
    return logits, cache
