"""The plain float32 reference of a served model, and its controls.

``score`` runs one sequence (prompt followed by the tokens that were served
for it) through the forward of the model's architecture
(``arch.<model_type>.hidden`` and ``head``, written from the published
architecture, importing nothing of the program and taking only the weights
the benchmark drew) in float32 at ``HIGHEST`` precision, and returns at
every scored position the reference's best logit and its logit for the
served token.  With a ``control`` it also runs the same forward with every
matmul operand (weights, activations, q, k, v and the attention
probabilities) rounded to a lower precision with one scale per tensor
(``_low``, ``_mm``): ``"fp8"`` (float8 e4m3) or ``"int8"`` (symmetric, 127
steps), the steps below bfloat16 that a later change could be tempted to
take.  It returns the token that this forward puts first, its own logit
for it, and the reference's logit for it: the control put in the
program's place.  The rounding is shared, so that a control means the same
for every architecture.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import arch

HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0   # largest finite float8 e4m3fn
INT8_MAX = 127.0
CONTROLS = ("fp8", "int8")
PAD = 128         # sequences are padded to a multiple of this (causal mask)
ROWS = 256        # logits are formed this many positions at a time


def _low(x, low):
    """``x`` rounded to the control's precision ``low`` with one scale for
    the whole tensor; unchanged when ``low`` is None."""
    if low is None:
        return x
    top = FP8_MAX if low == "fp8" else INT8_MAX
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    if low == "fp8":
        q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        q = jnp.clip(jnp.round(x / s), -INT8_MAX, INT8_MAX)
    return q * s


def _mm(x, w, low):
    return jnp.matmul(_low(x, low), _low(w, low), precision=HIGHEST)


def _rms(x, g, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * g


def score(params, m, prompt, served, *, length: int,
          control: str | None = None) -> dict:
    """Reference readings at each position that produced a served token.

    Returns numpy arrays over the ``len(served)`` positions: ``best`` (the
    reference's highest logit) and ``served`` (its logit for the token that
    was served); with a ``control``, also ``control_tok`` (the token the
    control's forward puts first), ``control_own`` (the control's logit for
    it) and ``control`` (the reference's logit for it).  Every sequence is
    padded to ``length`` (rounded up to ``PAD``), so that one program
    serves all."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control must be one of {CONTROLS}")
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    S = -(-max(length, len(seq)) // PAD) * PAD
    tokens = np.zeros(S, np.int32)
    tokens[:len(seq)] = seq
    first = len(prompt) - 1
    n = len(served)
    out = {k: [] for k in ("best", "served", "control_tok", "control_own",
                           "control")}
    forward = arch.of(m)
    ref = forward.hidden(params, jnp.asarray(tokens), m, None)
    low = (forward.hidden(params, jnp.asarray(tokens), m, control)
           if control else None)
    for a in range(first, first + n, ROWS):
        rows = np.arange(a, a + ROWS)
        valid = rows < first + n
        rows = np.where(valid, rows, first)
        lg = np.asarray(forward.head(params, ref[rows], None))[valid]
        idx = np.arange(len(lg))
        tok = served[rows[valid] - first]
        out["best"].append(lg.max(-1))
        out["served"].append(lg[idx, tok])
        if control:
            lo = np.asarray(forward.head(params, low[rows], control))[valid]
            ct = lo.argmax(-1)
            out["control_tok"].append(ct)
            out["control_own"].append(lo[idx, ct])
            out["control"].append(lg[idx, ct])
    return {k: np.concatenate(v) for k, v in out.items() if v}
