"""Paged decode-attention Pallas TPU kernel: block-table KV pages.

Same online-softmax streaming structure as ``repro.kernels.flash_decode``,
but K/V live in a shared pool of fixed-size head-major blocks
``(N, Hkv, bs, D)`` and each slot reads its own chain of blocks through a
block table.  The table (and the per-slot context lengths) ride in as
*scalar-prefetch* operands — ``PrefetchScalarGridSpec`` makes them
available to the BlockSpec index maps, so grid step ``(b, t)`` DMAs
physical block ``tables[b, t]`` straight from HBM without ever
materializing the dense gather.

Grid: ``(B, T)`` with the block axis sequential per slot.  One grid step
moves a whole ``(Hkv, bs, D)`` block — every KV head at once, each head's
``(bs, D)`` tile contiguous — because a TPU block's last two dimensions must
be tile-aligned or span the array, and ``(bs, D)`` spans it at any head
count.  The kernel body loops over the heads; q rows pack each head's GQA
group so one MXU dot serves every query head of that kv head.  Positions
past a slot's ``cur_pos`` (including whole null-padded blocks of short
slots) are masked by absolute logical position, so ragged contexts stream
the same way as full ones.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _online_softmax_step(h, s, v, m_ref, l_ref, acc_ref):
    """Fold one head's masked scores ``s`` (G, bs) and values ``v``
    (bs, D) into its running (max, denominator, accumulator)."""
    m_prev = m_ref[h]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[h] = acc_ref[h] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def _position_mask(cur, t, bs: int, window: int):
    """(1, bs) bool: which positions of logical block ``t`` a query at
    ``cur`` attends to."""
    k_pos = t * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    ok = k_pos <= cur
    if window > 0:
        ok &= k_pos > (cur - window)
    return ok


def _init_and_finish(t, n_t: int, o_ref, m_ref, l_ref, acc_ref, body):
    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    body()

    @pl.when(t == n_t - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _pa_kernel(tables_ref, cur_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref, *, n_t: int, bs: int, n_kv: int,
               window: int):
    b = pl.program_id(0)
    t = pl.program_id(1)

    def body():
        ok = _position_mask(cur_ref[b], t, bs, window)
        for h in range(n_kv):
            q = q_ref[0, h]              # (G, D)
            k = k_ref[0, h]              # (bs, D)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            _online_softmax_step(h, jnp.where(ok, s, _NEG), v_ref[0, h],
                                 m_ref, l_ref, acc_ref)

    _init_and_finish(t, n_t, o_ref, m_ref, l_ref, acc_ref, body)


def _grid_spec(B, T, Hkv, G, D, page_specs, n_prefetch):
    """Shared grid: q/out blocks hold one slot's (Hkv, G, D) query rows."""
    def q_map(b, t, *_):
        return (b, 0, 0, 0)

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, T),
        in_specs=[pl.BlockSpec((1, Hkv, G, D), q_map)] + page_specs,
        out_specs=pl.BlockSpec((1, Hkv, G, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )


def _prep(q, k_pages, tables, cur_pos):
    B, Hq, D = q.shape
    _, Hkv, bs, _ = k_pages.shape
    if Hq % Hkv:
        raise ValueError(f"{Hq} query heads do not group over {Hkv} kv heads")
    G = Hq // Hkv
    qg = (q.reshape(B, Hkv, G, D) / math.sqrt(D)).astype(q.dtype)
    tables = jnp.asarray(tables, jnp.int32)
    cur = jnp.asarray(cur_pos, jnp.int32).reshape(B)
    return qg, tables, cur, (B, Hq, D, bs, Hkv, G, tables.shape[1])


def _page_spec(bs, Hkv, D):
    return pl.BlockSpec((1, Hkv, bs, D),
                        lambda b, t, tbl, *_: (tbl[b, t], 0, 0, 0))


def paged_decode_pallas(q, k_pages, v_pages, tables, cur_pos, *,
                        window: int = 0, interpret: bool = False):
    """q: (B, Hq, D); pages: (N, Hkv, bs, D); tables: (B, T) int32;
    cur_pos: (B,) int32.  Returns (B, Hq, D); 1/sqrt(D) folded into q."""
    qg, tables, cur, (B, Hq, D, bs, Hkv, G, T) = \
        _prep(q, k_pages, tables, cur_pos)
    kernel = functools.partial(_pa_kernel, n_t=T, bs=bs, n_kv=Hkv,
                               window=window)
    page_spec = _page_spec(bs, Hkv, D)
    out = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(B, T, Hkv, G, D, [page_spec, page_spec], 2),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(tables, cur, qg, k_pages, v_pages)
    return out.reshape(B, Hq, D)


def _pa_quant_kernel(tables_ref, cur_ref, q_ref, k_ref, v_ref, ks_ref,
                     vs_ref, o_ref, m_ref, l_ref, acc_ref, *, n_t: int,
                     bs: int, n_kv: int, window: int):
    """Quantized-layout variant: k/v blocks arrive packed (int8/fp8) with
    their per-(block, kv-head) scales in a (1, Hkv) side block; the dequant
    multiply happens here in VMEM, so HBM only ever moves packed bytes."""
    b = pl.program_id(0)
    t = pl.program_id(1)

    def body():
        ok = _position_mask(cur_ref[b], t, bs, window)
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32)                # (G, D)
            k = k_ref[0, h].astype(jnp.float32) * ks_ref[0, :, h:h + 1]
            v = v_ref[0, h].astype(jnp.float32) * vs_ref[0, :, h:h + 1]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            _online_softmax_step(h, jnp.where(ok, s, _NEG), v,
                                 m_ref, l_ref, acc_ref)

    _init_and_finish(t, n_t, o_ref, m_ref, l_ref, acc_ref, body)


def paged_decode_quant_pallas(q, k_pages, v_pages, k_scales, v_scales,
                              tables, cur_pos, *, window: int = 0,
                              interpret: bool = False):
    """Quantized paged decode: pages (N, Hkv, bs, D) packed int8/fp8,
    scales (N, Hkv) f32.  Same grid and streaming structure as the dense
    kernel; each block's scales ride along through the same block-table
    index map, so the gather stays one DMA per (slot, block)."""
    qg, tables, cur, (B, Hq, D, bs, Hkv, G, T) = \
        _prep(q, k_pages, tables, cur_pos)
    N = k_scales.shape[0]
    kernel = functools.partial(_pa_quant_kernel, n_t=T, bs=bs, n_kv=Hkv,
                               window=window)
    page_spec = _page_spec(bs, Hkv, D)
    # (N, 1, Hkv) view: a (1, Hkv) block then spans its array's last dims
    scale_spec = pl.BlockSpec((1, 1, Hkv),
                              lambda b, t, tbl, *_: (tbl[b, t], 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(B, T, Hkv, G, D,
                             [page_spec, page_spec, scale_spec, scale_spec],
                             2),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(tables, cur, qg, k_pages, v_pages,
      jnp.asarray(k_scales, jnp.float32).reshape(N, 1, Hkv),
      jnp.asarray(v_scales, jnp.float32).reshape(N, 1, Hkv))
    return out.reshape(B, Hq, D)


def _pa_sparse_kernel(tables_ref, cur_ref, keep_ref, read_ref, q_ref, k_ref,
                      v_ref, o_ref, m_ref, l_ref, acc_ref, *, n_t: int,
                      bs: int, n_kv: int, window: int):
    """Blockwise-sparse variant: ``keep`` (B, Hkv, T) rides in as a scalar-
    prefetch operand and masks each head's dropped blocks here, so they
    contribute no probability mass."""
    b = pl.program_id(0)
    t = pl.program_id(1)

    def body():
        ok = _position_mask(cur_ref[b], t, bs, window)
        for h in range(n_kv):
            q = q_ref[0, h]
            k = k_ref[0, h]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            s = jnp.where(ok & (keep_ref[b, h, t] > 0), s, _NEG)
            _online_softmax_step(h, s, v_ref[0, h],
                                 m_ref, l_ref, acc_ref)

    _init_and_finish(t, n_t, o_ref, m_ref, l_ref, acc_ref, body)


def paged_decode_sparse_pallas(q, k_pages, v_pages, tables, cur_pos, keep, *,
                               window: int = 0, interpret: bool = False):
    """Blockwise-sparse paged decode.  ``keep``: (B, Hkv, T) bool/int mask
    from ``ref.block_keep_mask`` — the selection is computed once outside
    (ref and kernel share it).  A block moves all kv heads at once, so its
    read is skipped when every head drops it; a block some heads keep is
    read and masked per head."""
    qg, tables, cur, (B, Hq, D, bs, Hkv, G, T) = \
        _prep(q, k_pages, tables, cur_pos)
    keep = jnp.asarray(keep, jnp.int32)
    read = keep.max(axis=1)                            # (B, T)
    kernel = functools.partial(_pa_sparse_kernel, n_t=T, bs=bs, n_kv=Hkv,
                               window=window)
    # a block no head keeps reads the null block (id 0): tiny, cache-hot,
    # masked out
    page_spec = pl.BlockSpec(
        (1, Hkv, bs, D),
        lambda b, t, tbl, cur, kp, rd: (tbl[b, t] * rd[b, t], 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(B, T, Hkv, G, D, [page_spec, page_spec], 4),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(tables, cur, keep, read, qg, k_pages, v_pages)
    return out.reshape(B, Hq, D)
