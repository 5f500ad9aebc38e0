"""Mixture-of-Experts FFN with capacity-based top-k routing.

Gather-formulated dispatch: the only scatters touch int32 index arrays (cheap
under SPMD); all wide data movement is expressed as gathers + dense einsums so
GSPMD lowers it to all-to-all / all-gather rather than replicated scatter.

  tokens (T, d) --top-k--> (T, k) expert ids
  sort expert ids -> slot assignment with per-expert capacity C (drop overflow)
  buffer (E, C, d) = tokens[buffer_token_idx]           # gather
  expert FFN on buffer (einsum over E)                  # MXU-dense, E shardable
  out (T, d) = sum_k gate * buffer_out[inv_slot]        # gather + weighted sum

Auxiliary load-balance loss follows Switch/GShard: E * sum_e f_e * p_e.

An expert share (``cfg.expert_share``, ``moe_share``) is the layer of one
chip under expert parallelism: it holds ``n_experts`` of the router's
``n_router_experts`` (from ``expert_offset`` on), routes every token over
all of them with the published router, and computes the held experts'
part of the result, dropless: each (token, held expert) pair is computed,
however many tokens reach one expert.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .layers import dense_init
from .pspec import pbatch, pmodel


def capacity(n_tokens: int, n_experts: int, top_k: int, cf: float) -> int:
    c = int(-(-top_k * n_tokens * cf // n_experts))  # ceil
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8


def init_moe(key, cfg, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        # a share's router keeps its published width, in the served dtype
        "router": (dense_init(ks[0], d, cfg.n_router_experts, dtype)
                   if cfg.expert_share else dense_init(ks[0], d, e,
                                                       jnp.float32)),
        "w1": dense_init(ks[1], d, f, dtype).reshape(1, d, f).repeat(e, 0),
        "w2": dense_init(ks[2], f, d, dtype).reshape(1, f, d).repeat(e, 0),
        "w3": dense_init(ks[3], d, f, dtype).reshape(1, d, f).repeat(e, 0),
    }
    # re-randomize experts independently
    p["w1"] = jax.random.normal(ks[1], p["w1"].shape, jnp.float32).astype(dtype) * (d ** -0.5)
    p["w2"] = jax.random.normal(ks[2], p["w2"].shape, jnp.float32).astype(dtype) * (f ** -0.5)
    p["w3"] = jax.random.normal(ks[3], p["w3"].shape, jnp.float32).astype(dtype) * (d ** -0.5)
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["ws1"] = dense_init(ks[4], d, fs, dtype)
        p["ws3"] = dense_init(jax.random.fold_in(ks[4], 1), d, fs, dtype)
        p["ws2"] = dense_init(jax.random.fold_in(ks[4], 2), fs, d, dtype)
    return p


def moe_block(p, cfg, x, group_tokens: int = 32768):
    """x: (B, S, d) -> (out (B, S, d), aux f32).

    GShard-style grouping: tokens are processed in sequential groups of
    ~``group_tokens`` (capacity applies per group), so dispatch buffers are
    O(group) not O(batch*seq) — the difference between 55 GiB and <1 GiB
    per device on dbrx at 32k prefill.  One group == classic dropping MoE.
    """
    B, S, d = x.shape
    T = B * S
    n_groups = max(1, -(-T // group_tokens))
    while T % n_groups:
        n_groups += 1
    if n_groups == 1:
        out, aux = _moe_group(p, cfg, x.reshape(1, T, d))
        return out.reshape(B, S, d), aux
    xg = x.reshape(n_groups, T // n_groups, d)

    @jax.checkpoint
    def body(carry, xc):
        # checkpointed: expert intermediates (E, C_g, d_ff) are recomputed
        # per group in the backward instead of persisting across all groups
        # (measured ~28 GiB/device on dbrx-132b train without this).
        out, aux = _moe_group(p, cfg, xc[None])
        return carry + aux, out[0]

    aux, outs = lax.scan(body, jnp.zeros((), jnp.float32), xg)
    return outs.reshape(B, S, d), aux / n_groups


def _moe_group(p, cfg, x):
    """One capacity group. x: (1, T, d) -> ((1, T, d), aux)."""
    _, T, d = x.shape
    B, S = 1, T
    E, K = cfg.n_experts, cfg.top_k
    C = capacity(T, E, K, cfg.capacity_factor)

    xf = pbatch(x.reshape(T, d))
    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eid = lax.top_k(probs, K)  # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- slot assignment (int-only scatters) ----
    e_flat = eid.reshape(-1)  # (T*K,)
    order = jnp.argsort(e_flat, stable=True)  # token*K ids grouped by expert
    e_sorted = e_flat[order]
    start = jnp.searchsorted(e_sorted, jnp.arange(E, dtype=e_sorted.dtype))
    pos = jnp.arange(T * K, dtype=jnp.int32) - start[e_sorted].astype(jnp.int32)
    keep = pos < C
    slot = e_sorted.astype(jnp.int32) * C + jnp.clip(pos, 0, C - 1)  # (T*K,)

    # buffer slot -> source token row (sentinel T => zero row)
    buf_tok = jnp.full((E * C,), T, jnp.int32)
    buf_tok = buf_tok.at[slot].set(
        jnp.where(keep, (order // K).astype(jnp.int32), T), mode="drop")
    # token copy -> buffer slot (sentinel E*C => zero row)
    inv_slot = jnp.full((T * K,), E * C, jnp.int32)
    inv_slot = inv_slot.at[order].set(jnp.where(keep, slot, E * C))

    # ---- dispatch (gather) ----
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    buf = pmodel(x_pad[buf_tok].reshape(E, C, d))

    # ---- expert FFN (dense einsum over experts) ----
    h = pmodel(jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w1"])))
    h = h * pmodel(jnp.einsum("ecd,edf->ecf", buf, p["w3"]))
    y = pmodel(jnp.einsum("ecf,efd->ecd", h, p["w2"]))  # (E, C, d)

    # ---- combine (gather back) ----
    y_pad = jnp.concatenate([y.reshape(E * C, d), jnp.zeros((1, d), y.dtype)], 0)
    yk = pbatch(y_pad[inv_slot].reshape(T, K, d))
    out = jnp.einsum("tkd,tk->td", yk.astype(jnp.float32),
                     gate.astype(jnp.float32)).astype(x.dtype)

    if cfg.n_shared_experts:
        hs = jax.nn.silu(xf @ p["ws1"]) * (xf @ p["ws3"])
        out = out + (hs @ p["ws2"]).astype(out.dtype)

    # ---- aux load-balance loss (Switch) ----
    me = probs.mean(axis=0)  # avg router prob per expert
    one_hot_top1 = jax.nn.one_hot(eid[:, 0], E, dtype=jnp.float32)
    fe = one_hot_top1.mean(axis=0)  # fraction routed (top-1)
    aux = E * jnp.sum(me * fe)

    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# expert share, dropless
# ---------------------------------------------------------------------------


def route(p, cfg, x):
    """Router over all ``n_router_experts`` in float32 (the matmul at
    ``HIGHEST``: a TPU's default precision for float32 operands rounds them
    to bfloat16, which flips near-tied choices): softmax, greedy top-k,
    the weights renormalised only under ``norm_topk_prob``, times
    ``routed_scaling_factor``.  x: (T, d).  Returns the weights (T, K) f32
    and each choice's held-expert index (T, K), ``n_experts`` where the
    chosen expert is not held here."""
    with jax.named_scope("moe_router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
        if cfg.norm_topk_prob:
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        w = w * cfg.routed_scaling_factor
        local = idx - cfg.expert_offset
        held = (local >= 0) & (local < cfg.n_experts)
        return w, jnp.where(held, local, cfg.n_experts).astype(jnp.int32)


def _held_experts(p, cfg, x, w, eid):
    """The held experts' weighted sum for one group of tokens: the (token,
    choice) pairs sorted by expert, one grouped matmul per projection over
    the held experts' rows (``lax.ragged_dot``; pairs of experts held
    elsewhere sort last and fall outside every group), then back to token
    order.  x: (T, d); w, eid: (T, K)."""
    T, K = eid.shape
    E = cfg.n_experts
    with jax.named_scope("moe_experts"):
        flat = eid.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=E + 1)[:E].astype(jnp.int32)
        rows = x[order // K]
        h = jax.nn.silu(lax.ragged_dot(rows, p["w1"], sizes)) \
            * lax.ragged_dot(rows, p["w3"], sizes)
        y = lax.ragged_dot(h, p["w2"], sizes)
        back = jnp.zeros((T * K,), jnp.int32).at[order].set(
            jnp.arange(T * K, dtype=jnp.int32))
        y = y[back].reshape(T, K, -1).astype(jnp.float32)
        held = eid < E
        y = jnp.where(held[..., None], y, 0.0)
        return jnp.einsum("tkd,tk->td", y, jnp.where(held, w, 0.0))


def moe_share(p, cfg, x, group_tokens: int = 4096):
    """The expert share's layer.  x: (B, S, d) -> (out (B, S, d), the number
    of held experts the tokens reached (int32 scalar)).  Tokens are taken
    ``group_tokens`` at a time, which bounds the sorted copies and adds no
    capacity: every pair of every group is computed."""
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    w, eid = route(p, cfg, xf)
    reached = jnp.sum(jnp.bincount(eid.reshape(-1), length=cfg.n_experts + 1)
                      [:cfg.n_experts] > 0).astype(jnp.int32)
    G = min(T, group_tokens)
    n = -(-T // G)
    if n == 1:
        out = _held_experts(p, cfg, xf, w, eid)
    else:
        pad = n * G - T
        xg = jnp.pad(xf, ((0, pad), (0, 0))).reshape(n, G, d)
        wg = jnp.pad(w, ((0, pad), (0, 0))).reshape(n, G, -1)
        eg = jnp.pad(eid, ((0, pad), (0, 0)),
                     constant_values=cfg.n_experts).reshape(n, G, -1)
        out = lax.map(lambda a: _held_experts(p, cfg, *a),
                      (xg, wg, eg)).reshape(n * G, d)[:T]
    with jax.named_scope("moe_shared"):
        if cfg.n_shared_experts:
            hs = jax.nn.silu(xf @ p["ws1"]) * (xf @ p["ws3"])
            out = out + (hs @ p["ws2"]).astype(jnp.float32)
    return out.astype(x.dtype).reshape(B, S, d), reached
