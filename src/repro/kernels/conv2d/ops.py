"""Jit'd public wrapper for the conv2d kernel: SAME/VALID padding and a
VMEM budget check.  ``interpret`` is the caller's choice: True runs the
Pallas interpreter on any backend, False compiles for the TPU, where a
shape whose working set overflows VMEM is refused here, before Mosaic."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .conv2d import conv2d_pallas, output_tile

VMEM_BUDGET = 16 * 2**20  # the TPU's default scoped VMEM limit per kernel


def _tile(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_bytes(H, W, C, kh, kw, K, stride, tk, itemsize) -> int:
    """VMEM one grid step holds for a padded (H, W, C) input: the
    double-buffered phase-plane, weight and output blocks plus the f32
    accumulator, each padded to the (sublane, 128-lane) tile."""
    sub = 32 // itemsize                       # 8 rows f32, 16 rows bf16
    Hs, Ws = -(-H // stride), -(-W // stride)
    Ho, Wo = (H - kh) // stride + 1, (W - kw) // stride + 1
    tk = output_tile(K, tk)
    x = stride * stride * Hs * _tile(Ws, sub) * _tile(C, 128)
    w = kh * kw * _tile(C, sub) * _tile(tk, 128)
    o = Ho * _tile(Wo, sub) * _tile(tk, 128)
    return 2 * (x + w + o) * itemsize + _tile(Ho * Wo, 8) * _tile(tk, 128) * 4


@partial(jax.jit, static_argnames=("stride", "padding", "tk", "interpret"))
def conv2d(x, w, *, stride: int = 1, padding: str = "SAME", tk: int = 128,
           interpret: bool):
    """x: (N, H, W, C); w: (kh, kw, C, K) -> (N, Ho, Wo, K)."""
    kh, kw = w.shape[:2]
    if padding == "SAME":
        N, H, W, C = x.shape
        Ho = -(-H // stride)
        Wo = -(-W // stride)
        ph = max((Ho - 1) * stride + kh - H, 0)
        pw = max((Wo - 1) * stride + kw - W, 0)
        x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                        (pw // 2, pw - pw // 2), (0, 0)))
    if not interpret:
        need = vmem_bytes(*x.shape[1:], kh, kw, w.shape[3], stride, tk,
                          x.dtype.itemsize)
        if need > VMEM_BUDGET:
            raise ValueError(
                f"conv2d of a {x.shape} input with {w.shape} weights needs "
                f"{need} bytes of VMEM per grid step, over the "
                f"{VMEM_BUDGET}-byte budget; use the XLA convolution")
    return conv2d_pallas(x, w, stride=stride, tk=tk, interpret=interpret)
