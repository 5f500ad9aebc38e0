"""A configuration file, its weights, and the engine that serves them.

``load_config`` reads ``configs/<name>.json`` (the published ``config.json``
keys plus the serving sizes); its ``model_type`` names the architecture
module (``arch/<model_type>.py``) that says its sizes, its parameter layout
and its mapping onto the program's ``ModelConfig``.  ``make_params`` draws
the weights from the seed in one jitted call on the device, in the
program's parameter layout and in the dtype they are served in; the
benchmark makes them, not the program, so that the reference can read them
without reading anything the program made.  ``build_engine`` mirrors
``repro.launch.serve.build_fleet`` with its defaults (one
``PartitionEngine`` with the paged pool, the ``uniform`` policy on the
event clock) for a configuration that the CLI's registry does not hold.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from chipbench import arch

HERE = Path(__file__).resolve().parent


def load_config(name: str) -> dict:
    path = HERE / "configs" / f"{name}.json"
    with open(path) as f:
        doc = json.load(f)
    doc["_name"] = name
    return doc


def seed_key(seed: int):
    """A JAX key from any whole number, also one past 2**31."""
    import jax

    lo, hi = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(lo) & 0x7FFFFFFF), int(hi))


def make_params(m, seed: int, dtype="bfloat16"):
    """Every weight of ``m``'s architecture drawn from ``seed`` in one
    jitted call on the default device: uniform draws of the scale it
    states, in the served dtype."""
    import jax
    import jax.numpy as jnp

    mod = arch.of(m)
    shapes = mod.param_shapes(m)
    flat, tree = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    specs = [("/".join(str(getattr(k, "key", k)) for k in path), shape)
             for path, shape in flat]
    dt = jnp.dtype(dtype)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(specs))
        out = []
        for k, (path, shape) in zip(keys, specs):
            mean, half = mod.leaf_scale(path, shape)
            out.append(jax.random.uniform(k, shape, dt, mean - half,
                                          mean + half))
        return jax.tree.unflatten(tree, out)

    return draw(seed_key(seed))


@dataclass
class Served:
    """What one cell serves with: the program's engine, queue and
    scheduler around the benchmark's weights."""
    cfg: object
    engine: object
    queue: object
    sched: object


def build_engine(doc: dict, params) -> Served:
    """One ``PartitionEngine`` (P=1, paged pool) under
    ``make_scheduler(policy="uniform", clock="event")``, given named jitted
    phase functions so that the device trace names its executables."""
    import jax

    from repro.models import api as mapi
    from repro.serving import PartitionEngine, RequestQueue, make_scheduler

    cfg = arch.arch_for(doc).program_config(doc)
    api = mapi.build(cfg)
    want = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the benchmark's parameter layout differs from the "
                         "program's api.init")
    srv = doc["serving"]
    slots, max_len = int(srv["slots"]), int(srv["max_len"])

    def prefill_wave(p, b, lens):
        return api.prefill(p, b, max_len=max_len, lens=lens)

    def prefill_refill(p, b, ml):
        return api.prefill(p, b, max_len=ml)

    def decode_paged(p, token, pcache):
        return api.decode_paged(p, token, pcache)

    engine = PartitionEngine(
        cfg, api, params, slots=slots, max_len=max_len, pid=0, paged=True,
        block_size=int(srv["block_size"]),
        kv_dtype=srv.get("kv_dtype", "fp32"),
        decode_fn=jax.jit(decode_paged, donate_argnums=(2,)),
        prefill_fn=jax.jit(prefill_wave),
        prefill_uniform_fn=jax.jit(prefill_refill, static_argnames=("ml",)))
    queue = RequestQueue()
    sched = make_scheduler([engine], queue, policy="uniform", clock="event")
    return Served(cfg, engine, queue, sched)
