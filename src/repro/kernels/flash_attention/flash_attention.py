"""Flash attention Pallas TPU kernel (blockwise online softmax).

Grid: (B * Hkv, Sq/bq, Skv/bk) — kv as the minor sequential axis.  VMEM
scratch carries (m, l, acc) across kv steps; the kv->q GQA group dim G is
folded into the q block so one kernel instance serves all query heads of a
kv head (q block = (bq*G, D) — MXU-aligned when bq*G is a multiple of 128).

Causal + sliding-window masks are computed from absolute positions via
``pl.program_id``; fully-masked kv blocks still execute (grid is static) but
contribute zero — the XLA-level chunked fallback in repro.models.layers has
identical semantics, and this kernel is the TPU-optimized drop-in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               n_kv: int, bq: int, bk: int, G: int, causal: bool,
               window: int, scale: float):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]  # (bq*G, D)
    k = k_ref[0]  # (bk, D)
    v = v_ref[0]  # (bk, D)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bqG, bk)

    # row r of the block is query position q_i*bq + r//G.  The masks compare
    # r against G*(distance) instead of dividing r, since Mosaic cannot
    # reshape a (bq, G) iota into rows when G is not tile-aligned:
    # r//G >= n  <=>  r >= G*n,  and  r//G < n  <=>  r < G*n.
    row = jax.lax.broadcasted_iota(jnp.int32, (bq * G, bk), 0)
    k_off = (kv_i * bk - pl.program_id(1) * bq
             + jax.lax.broadcasted_iota(jnp.int32, (bq * G, bk), 1))
    ok = jnp.ones((bq * G, bk), bool)
    if causal:
        ok &= row >= G * k_off
    if window > 0:
        ok &= row < G * (k_off + window)
    s = jnp.where(ok, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_i == n_kv - 1)
    def _done():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = 128, bk: int = 512,
                           interpret: bool = False):
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D). Returns (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"{Hq} query heads do not group over {Hkv} kv heads")
    G = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    if Sq % bq or Skv % bk:
        raise ValueError(f"blocks ({bq}, {bk}) do not tile the sequence "
                         f"lengths ({Sq}, {Skv})")
    scale = 1.0 / math.sqrt(D)

    # layout: (B*Hkv, Sq*G, D) with q rows grouped [q_pos-major, G-minor]
    qg = (q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B * Hkv, Sq * G, D))
    kg = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vg = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)

    kernel = functools.partial(
        _fa_kernel, n_kv=Skv // bk, bq=bq, bk=bk, G=G,
        causal=causal, window=window, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hkv, Sq // bq, Skv // bk),
        in_specs=[
            pl.BlockSpec((1, bq * G, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq * G, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, Sq * G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, 1), jnp.float32),
            pltpu.VMEM((bq * G, D), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kg, vg)

    return (out.reshape(B, Hkv, Sq, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, Sq, Hq, D))
