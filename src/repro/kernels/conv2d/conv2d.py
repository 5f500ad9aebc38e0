"""Implicit-GEMM conv2d Pallas TPU kernel (NHWC x HWIO).

TPU adaptation of the paper's conv hot-spot: instead of a CPU im2col +
GEMM (which materializes the k^2-amplified patch matrix in memory — the
very traffic the paper measures), the input H x W x C panel is staged in
VMEM once per image and the kh*kw reduction is unrolled into MXU dots over
unit-stride in-register slices: the im2col never touches HBM.

A stride-s conv reads its input as s*s phase planes, ``phase[p*s + q] =
x[p::s, q::s]``, split apart by XLA before the kernel; tap (i, j) is then the
unit-stride window of plane ``(i % s, j % s)`` at offset ``(i//s, j//s)``.
Mosaic lowers no strided value slice, so this is how stride > 1 compiles.

Grid: (N, K/tk).  VMEM working set = H*W*C*in_bytes + kh*kw*C*tk*in_bytes
+ Ho*Wo*tk*4 (f32 acc).  Input is pre-padded in ops.py so the kernel
computes a VALID conv.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _conv_kernel(x_ref, w_ref, o_ref, *, kh: int, kw: int, stride: int,
                 Ho: int, Wo: int):
    C = x_ref.shape[-1]
    tk = w_ref.shape[-1]
    acc = jnp.zeros((Ho * Wo, tk), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            a, b = i // stride, j // stride
            x = x_ref[0, (i % stride) * stride + j % stride]  # (Hs, Ws, C)
            xs = x[a:a + Ho, b:b + Wo, :]
            acc += jnp.dot(xs.reshape(Ho * Wo, C), w_ref[i, j],
                           preferred_element_type=jnp.float32)
    o_ref[0] = acc.reshape(Ho, Wo, tk).astype(o_ref.dtype)


def output_tile(K: int, tk: int) -> int:
    """Output-channel tile: the largest multiple of 128 lanes up to ``tk``
    that divides K, else all of K (a block must be lane-aligned or span)."""
    return next((t for t in range(min(tk, K) // 128 * 128, 0, -128)
                 if K % t == 0), K)


def conv2d_pallas(x, w, *, stride: int = 1, tk: int = 128,
                  interpret: bool = False):
    """x: (N, H, W, C) — already padded (VALID conv); w: (kh, kw, C, K)."""
    N, H, W, C = x.shape
    kh, kw, C2, K = w.shape
    if C != C2:
        raise ValueError(f"input channels {C} != weight channels {C2}")
    Ho = (H - kh) // stride + 1
    Wo = (W - kw) // stride + 1
    tk = output_tile(K, tk)

    # phase planes: (N, s*s, Hs, Ws, C) with plane p*s+q = x[p::s, q::s]
    s = stride
    Hs, Ws = -(-H // s), -(-W // s)
    x = jnp.pad(x, ((0, 0), (0, Hs * s - H), (0, Ws * s - W), (0, 0)))
    x = (x.reshape(N, Hs, s, Ws, s, C).transpose(0, 2, 4, 1, 3, 5)
         .reshape(N, s * s, Hs, Ws, C))

    kernel = functools.partial(_conv_kernel, kh=kh, kw=kw, stride=stride,
                               Ho=Ho, Wo=Wo)
    return pl.pallas_call(
        kernel,
        grid=(N, K // tk),
        in_specs=[
            pl.BlockSpec((1, s * s, Hs, Ws, C),
                         lambda n, k: (n, 0, 0, 0, 0)),
            pl.BlockSpec((kh, kw, C, tk), lambda n, k: (0, 0, 0, k)),
        ],
        out_specs=pl.BlockSpec((1, Ho, Wo, tk), lambda n, k: (n, 0, 0, k)),
        out_shape=jax.ShapeDtypeStruct((N, Ho, Wo, K), x.dtype),
        interpret=interpret,
    )(x, w)
