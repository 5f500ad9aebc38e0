"""Jit'd public wrapper for the blocked matmul kernel."""
from __future__ import annotations

from functools import partial

import jax

from .matmul import matmul_pallas


@partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def matmul(a, b, *, bm: int = 256, bk: int = 512, bn: int = 256,
           interpret: bool):
    """Blocked matmul.  ``interpret`` is the caller's choice: True runs the
    Pallas interpreter on any backend, False compiles for the TPU."""
    return matmul_pallas(a, b, bm=bm, bk=bk, bn=bn, interpret=interpret)
