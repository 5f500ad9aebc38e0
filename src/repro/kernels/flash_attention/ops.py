"""Jit'd public wrapper for the flash-attention kernel.  ``interpret`` is
the caller's choice: True runs the Pallas interpreter on any backend, False
compiles for the TPU."""
from __future__ import annotations

from functools import partial

import jax

from .flash_attention import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 512, interpret: bool):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  bq=bq, bk=bk, interpret=interpret)
