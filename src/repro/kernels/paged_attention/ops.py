"""Jit'd public wrappers for the paged decode-attention kernels.

Three variants, one convention: dense (``paged_decode``), quantized-layout
(``paged_decode_quant``: int8/fp8 packed pages + per-(block, kv-head)
scales), and blockwise-sparse (``paged_decode_sparse``: whole blocks below
an estimated-attention-mass threshold are skipped; the keep mask comes
from ``ref.block_keep_mask`` so the kernel and the oracle always agree on
selection).  ``interpret`` is the caller's choice: True runs the Pallas
interpreter on any backend, False compiles for the TPU.
"""
from __future__ import annotations

from functools import partial

import jax

from .paged_attention import (paged_decode_pallas, paged_decode_quant_pallas,
                              paged_decode_sparse_pallas)
from .ref import block_keep_mask


@partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode(q, k_pages, v_pages, tables, cur_pos, *, window: int = 0,
                 interpret: bool):
    return paged_decode_pallas(q, k_pages, v_pages, tables, cur_pos,
                               window=window, interpret=interpret)


@partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_quant(q, k_pages, v_pages, k_scales, v_scales, tables,
                       cur_pos, *, window: int = 0, interpret: bool):
    return paged_decode_quant_pallas(q, k_pages, v_pages, k_scales, v_scales,
                                     tables, cur_pos, window=window,
                                     interpret=interpret)


@partial(jax.jit, static_argnames=("threshold", "window", "interpret"))
def paged_decode_sparse(q, k_pages, v_pages, tables, cur_pos, *,
                        threshold: float, window: int = 0, interpret: bool):
    keep = block_keep_mask(q, k_pages, tables, cur_pos,
                           threshold=threshold, window=window)
    return paged_decode_sparse_pallas(q, k_pages, v_pages, tables, cur_pos,
                                      keep, window=window,
                                      interpret=interpret)
