"""Architectures, looked up by a configuration file's ``model_type``.

``arch_for(doc)`` imports ``chipbench/arch/<model_type>.py``, once per
process, so that a new architecture is a new file and no shared module
changes; ``of(m)`` is the module whose ``dims`` made ``m``.  An unknown
``model_type`` is an error that names the file looked for.

An architecture module provides:

``Dims``
    A frozen (hashable) dataclass of the sizes the harness counts with,
    with the fields ``arch`` (the ``model_type`` that made it, so that
    ``of(m)`` finds its module) and ``vocab`` (token ids the traffic
    draws).  Every other field is the module's own: the sizes this chip
    holds beside the published ones, per-layer kinds, whatever its counts
    need.  The reference's jitted forward takes it as a static argument.
``dims(doc)``
    The ``Dims`` of a configuration file.
``program_config(doc)``
    The program's ``ModelConfig`` for it: the mapping onto the program,
    which refuses what it cannot map.
``param_shapes(m)``
    The program's parameter layout of the leaves this chip holds, as
    nested dicts of shape tuples (an expert share stacks only the experts
    it holds).  ``model.make_params`` draws the leaves in flatten order,
    one split of the seed's key each, and ``model.build_engine`` refuses a
    layout that differs from the program's ``api.init``.
``leaf_scale(path, shape)``
    (mean, half-width) of the uniform draw of the leaf at ``path``.
``hidden(params, tokens, m, low)``
    The plain float32 forward of one padded sequence to its final-normed
    hidden states ``(S, d)``, written from the published architecture and
    importing nothing of the program.  Every matmul operand passes through
    ``reference._low`` (``reference._mm`` does both) with ``low``, so that
    a control means the same for every architecture.
``head(params, hidden, low)``
    The logits of rows of ``hidden``.
``decode_step(m, ctx)``, ``prefill(m, lens)``
    The ``work.Work`` of one decode step over slots reading ``ctx``
    positions each, and of one prefill call over prompts of ``lens``
    tokens, counted as ``work.py`` says, per layer kind: a windowed layer
    reads ``min(ctx, window)`` positions, a latent cache has its own bytes
    per token, a routed layer reads the experts its tokens reach.
"""
from __future__ import annotations

import functools
import importlib
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent


@functools.cache
def load(model_type: str):
    """The module ``arch/<model_type>.py``, imported once per process."""
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", str(model_type)):
        raise ValueError(f"model_type {model_type!r} cannot name a module")
    path = HERE / f"{model_type}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no architecture for model_type "
                                f"{model_type!r}: {path} does not exist")
    return importlib.import_module(f"{__name__}.{model_type}")


def arch_for(doc: dict):
    """The architecture module of a configuration file."""
    return load(doc["model_type"])


def of(m):
    """The architecture module of a ``Dims``."""
    return load(m.arch)
