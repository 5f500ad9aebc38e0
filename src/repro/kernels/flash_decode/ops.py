"""Jit'd public wrapper for the flash-decode kernel.  ``interpret`` is the
caller's choice: True runs the Pallas interpreter on any backend, False
compiles for the TPU."""
from __future__ import annotations

from functools import partial

import jax

from .flash_decode import flash_decode_pallas


@partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def flash_decode(q, k_cache, v_cache, cur_pos, *, window: int = 0,
                 bk: int = 512, interpret: bool):
    return flash_decode_pallas(q, k_cache, v_cache, cur_pos, window=window,
                               bk=bk, interpret=interpret)
