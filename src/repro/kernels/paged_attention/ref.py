"""Pure-jnp oracles for paged decode attention: one query token per slot
against block-table-indexed KV pages with per-slot context lengths.

Three variants share one attention body:

  * ``paged_decode_attention``        — dense fp pages (the historical ref);
  * ``paged_decode_attention_quant``  — int8/fp8 packed pages with
    per-(block, kv-head) f32 scales, dequantized on the dense gather;
  * ``paged_decode_attention_sparse`` — blockwise-sparse: whole KV blocks
    whose estimated attention mass falls below a threshold are skipped.
    ``block_keep_mask`` is the single source of truth for *which* blocks
    survive — the Pallas kernel consumes the same mask, so ref and kernel
    can only disagree on arithmetic, never on selection.
"""
import math

import jax
import jax.numpy as jnp

_NEG = -1e30


def _attend(q, kd, vd, cur_pos, window: int, head_keep=None):
    """Masked decode attention over a dense (B, S, Hkv, D) view.
    ``head_keep`` (optional, (B, Hkv, S) bool) masks positions per kv-head
    on top of the causal/window mask."""
    B, Hq, D = q.shape
    S, Hkv = kd.shape[1], kd.shape[2]
    G = Hq // Hkv
    qr = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qr, kd,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    pos = jnp.arange(S, dtype=jnp.int32)
    ok = pos[None, :] <= cur_pos[:, None]          # (B, S)
    if window:
        ok &= pos[None, :] > (cur_pos[:, None] - window)
    mask = ok[:, None, None, :]
    if head_keep is not None:
        mask = mask & head_keep[:, :, None, :]
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(vd.dtype), vd,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, D).astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, tables, cur_pos, *,
                           window: int = 0):
    """q: (B, Hq, D); pages: (N, Hkv, bs, D) head-major blocks; tables:
    (B, T) int32 block ids into the pool; cur_pos: (B,) int32 — logical
    positions [0, cur_pos[b]] of slot b are valid (block t of slot b covers
    positions [t*bs, (t+1)*bs)).  Returns (B, Hq, D)."""
    # dense per-slot view via the block table (the gather the kernel avoids)
    return _attend(q, _dense(k_pages[tables]), _dense(v_pages[tables]),
                   cur_pos, window)


def _dense(blocks):
    """(B, T, Hkv, bs, D) gathered blocks -> the (B, T*bs, Hkv, D) sequence
    they hold."""
    B, T, Hkv, bs, D = blocks.shape
    return blocks.swapaxes(2, 3).reshape(B, T * bs, Hkv, D)


def _dequant_gather(pages, scales, tables, dtype):
    """(B, T*bs, Hkv, D) float view of packed pages through the table."""
    x = pages[tables].astype(jnp.float32) * scales[tables][..., None, None]
    return _dense(x).astype(dtype)


def paged_decode_attention_quant(q, k_pages, v_pages, k_scales, v_scales,
                                 tables, cur_pos, *, window: int = 0):
    """Quantized-layout oracle: pages (N, Hkv, bs, D) int8/fp8 packed,
    scales (N, Hkv) f32 per (block, kv-head).  Dequantizes the dense
    gather (``x * scale``) and runs the dense ref's math — the kernel does
    the same multiply in VMEM instead."""
    kd = _dequant_gather(k_pages, k_scales, tables, jnp.float32)
    vd = _dequant_gather(v_pages, v_scales, tables, jnp.float32)
    return _attend(q, kd, vd, cur_pos, window)


def block_keep_mask(q, k_pages, tables, cur_pos, *, threshold: float,
                    window: int = 0, k_scales=None):
    """(B, Hkv, T) bool: which KV blocks each (slot, kv-head) reads.

    Per-block attention mass is *estimated* from the block's mean key: the
    max over the GQA group of ``q . mean_k / sqrt(D)``, softmaxed over the
    slot's valid blocks.  Blocks whose estimated mass falls below
    ``threshold`` are dropped whole; the block holding ``cur_pos`` is
    always kept (the new token's own row lives there), and blocks wholly
    outside the causal/window range never count.  ``threshold == 0`` keeps
    every valid block, which makes the sparse path coincide with dense.

    ``window`` may be a python int or a traced int32 scalar where <= 0
    means "no window" (the model path scans over layers with per-layer
    windows).  ``k_scales`` ((N, Hkv) f32) dequantizes packed pages before
    the mean-key estimate — the scale is constant over a block so
    ``mean(q * scale) == scale * mean(q)``.
    """
    B, Hq, D = q.shape
    _, Hkv, bs, _ = k_pages.shape
    T = tables.shape[1]
    G = Hq // Hkv
    cur = jnp.asarray(cur_pos, jnp.int32)
    if k_scales is not None:
        kmean = k_pages.astype(jnp.float32).mean(axis=2) \
            * k_scales[..., None]                     # (N, Hkv, D)
    else:
        kmean = k_pages.mean(axis=2)                  # (N, Hkv, D)
    km = kmean[tables]                                # (B, T, Hkv, D)
    qr = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bthd->bhgt", qr, km.astype(qr.dtype),
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    s = s.max(axis=2)                                 # (B, Hkv, T)
    blk = jnp.arange(T, dtype=jnp.int32)
    valid = blk[None, :] * bs <= cur[:, None]         # block starts in range
    w = jnp.asarray(0 if window is None else window, jnp.int32)
    win_ok = (blk[None, :] + 1) * bs - 1 > (cur[:, None] - w)
    valid &= jnp.where(w > 0, win_ok, True)
    s = jnp.where(valid[:, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    keep = (p >= threshold) & valid[:, None, :]
    keep |= (blk[None, None, :] == (cur[:, None, None] // bs)) \
        & valid[:, None, :]
    return keep


def paged_decode_attention_sparse(q, k_pages, v_pages, tables, cur_pos, *,
                                  threshold: float, window: int = 0):
    """Blockwise-sparse oracle: positions inside dropped blocks are masked
    out wholesale before the softmax.  Selection comes from
    ``block_keep_mask``; at ``threshold == 0`` this is exactly the dense
    ref (every valid block kept)."""
    bs = k_pages.shape[2]
    keep = block_keep_mask(q, k_pages, tables, cur_pos,
                           threshold=threshold, window=window)
    head_keep = jnp.repeat(keep, bs, axis=-1)         # (B, Hkv, S)
    return _attend(q, _dense(k_pages[tables]), _dense(v_pages[tables]),
                   cur_pos, window, head_keep=head_keep)
