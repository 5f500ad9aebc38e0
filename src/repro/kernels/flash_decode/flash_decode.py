"""Flash-decode Pallas TPU kernel: one query token against a long KV cache.

Decode attention is memory-bound (the roofline shows decode cells dominated
by cache/weight movement), so the kernel's job is to stream K/V blocks
through VMEM exactly once with the online-softmax carried in scratch —
the split-K/FlashDecoding structure, tiled as (B*Hkv) x (S/bk) with the kv
axis sequential.  Positions beyond ``cur_pos`` (and outside the sliding
window, if any) are masked via absolute block indices, so partially-filled
and windowed caches stream the same way.

q rows pack the GQA group (G = Hq/Hkv) so each kernel instance serves all
query heads of its kv head with one MXU dot per block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _fd_kernel(cur_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               n_kv: int, bk: int, window: int):
    kv_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur = cur_ref[0]
    q = q_ref[0]          # (G, D)
    k = k_ref[0]          # (bk, D)
    v = v_ref[0]          # (bk, D)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (G, bk)
    k_pos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)[0]
    ok = k_pos <= cur
    if window > 0:
        ok &= k_pos > (cur - window)
    s = jnp.where(ok[None, :], s, _NEG)

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
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_pallas(q, k_cache, v_cache, cur_pos, *, window: int = 0,
                        bk: int = 512, interpret: bool = False):
    """q: (B, Hq, D); caches: (B, S, Hkv, D); cur_pos () int32.
    Returns (B, Hq, D).  The scale 1/sqrt(D) is folded into q."""
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    if Hq % Hkv:
        raise ValueError(f"{Hq} query heads do not group over {Hkv} kv heads")
    G = Hq // Hkv
    bk = min(bk, S)
    if S % bk:
        raise ValueError(f"block {bk} does not tile the cache length {S}")

    qg = (q.reshape(B, Hkv, G, D) / math.sqrt(D)).astype(q.dtype)
    qg = qg.reshape(B * Hkv, G, D)
    kg = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vg = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    cur = jnp.broadcast_to(jnp.asarray(cur_pos, jnp.int32), (1,))

    kernel = functools.partial(_fd_kernel, n_kv=S // bk, bk=bk,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid=(B * Hkv, S // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(cur, qg, kg, vg)
    return out.reshape(B, Hq, D)
