"""Per-partition inference engine: params, paged KV pool, prefill/decode.

An engine is one traffic-shaping partition of the serving fleet.  It owns
``slots`` concurrent sequences backed by a paged KV-cache pool
(``repro.serving.kv_pool``), and exposes exactly two steppable phases to
the scheduler:

  * ``prefill_wave()`` — compute-bound: run the (possibly ragged) prompt
    batch through the model, writing each slot's prefix into its own
    freshly allocated blocks and emitting each request's first token;
  * ``decode_step()``  — bandwidth-bound: one token for every active slot
    (each slot's block chain streams from HBM per step).

Continuous batching is *per-slot*: every slot carries its own context
length and block table, so a prefill wave may mix prompt lengths freely,
and when a slot's request completes mid-wave its blocks return to the pool
and the next backlog request prefills its OWN prompt into fresh blocks —
no shared-prefix boundary, no wave-chain cap.  A slot that is already
empty (a partial wave's) is seated the same way at the next op's commit,
so requests that arrive while the engine is busy join its decode batch.
The refill prefill is priced and billed into the tick that triggered it,
so a refilled request's TTFT reflects its own slot prefill rather than
the wave boundary.  Refill is FIFO and gated only by pool capacity
(``PoolExhausted`` is a hard report, never a silent truncation).  The
dense per-wave layout survives behind ``paged=False`` — per-slot cache
lengths with masked attention give it the same ragged/refill semantics
inside one ``(L, slots, max_len)`` slab — and is the oracle the paged
engine is equivalence-tested against.

Phase costs (FLOPs / bytes / duration / bandwidth demand) come from the
engine's ``CostModel`` (``repro.profiling.cost_model``).  The default
``AnalyticCostModel`` prices from the analytic LM traces in
``repro.core.traffic`` — the same per-layer (FLOPs, bytes) decomposition
the paper's simulator consumes; a ``MeasuredCostModel`` replaces the
durations with on-device timings (the engine feeds its ``PhaseTimer`` by
wall-clocking each issued op, blocking on the device via
``jax.block_until_ready`` before reading the clock).  Decode pricing sums
each active slot's own context (``CostModel.decode`` takes a per-slot ctx
vector), so the scheduler's ``demand`` policy sees the true ragged KV
read, consistent with ``core.traffic``.  ``PhaseCost`` and the analytic
pricing functions are re-exported here for back-compat (they lived in
this module before ``repro.profiling`` existed).
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from functools import partial as _partial
from typing import List, Optional, Tuple

import numpy as np

from repro.configs.base import ModelConfig
from repro.core import hw
from repro.obs.tracer import NO_SPAN, span
# PhaseCost + the analytic pricing functions moved to repro.profiling;
# re-exported here because the rest of the stack (and downstream users)
# import them from repro.serving.engine.
from repro.profiling.cost_model import (AnalyticCostModel,  # noqa: F401
                                        CostModel, PhaseCost, decode_cost,
                                        prefill_cost, prefill_cost_ragged)
from repro.profiling.timer import shape_key
from repro.serving.kv_pool import (KV_DTYPES, NULL_BLOCK, BlockPool,
                                   ChainAlloc, PoolExhausted,
                                   write_latent_pages, write_prefix_pages)
from repro.serving.queue import Request

# model families whose per-sequence state does not live (only) in KV blocks:
# SSM/hybrid recurrent state is a per-slot array (not content-addressable by
# token prefix) and enc-dec has no paged cache at all, so block-level prefix
# sharing cannot represent a cached prefix for them
_NO_PREFIX_CACHE_FAMILIES = ("ssm", "hybrid", "encdec")

# families the bandwidth-reduction KV layouts (quantized pages, blockwise-
# sparse reads) cannot serve: SSM/hybrid recurrent state is not KV blocks
# (quantizing only the attention half would misprice the hybrid mix) and
# enc-dec has no paged cache at all
_NO_KV_QUANT_FAMILIES = ("ssm", "hybrid", "encdec")


@dataclass
class PendingOp:
    """An issued-but-uncommitted phase op.

    Device execution is eager (it happened at issue), but the virtual-time
    effects — first-token stamps, retirement, slot refill — wait for the
    clock to decide when the op actually ends.  The lockstep clock commits
    immediately at ``issue_time + cost.duration``; the event clock commits
    at the contention-stretched completion event."""
    kind: str                 # "prefill" | "decode"
    cost: PhaseCost
    stamp_first: List[Request] = field(default_factory=list)
    # requests whose first token was emitted by this op (stamped at commit)


# ---------------------------------------------------------------------------
# engine base: slot/backlog/pool state machine (model-execution agnostic)
# ---------------------------------------------------------------------------


class EngineBase:
    """Slot, backlog, and block-pool bookkeeping shared by the real and the
    simulated engine.

    Scheduler-facing surface:
      assign(requests)   — extend this partition's FIFO backlog
      wants_prefill      — drained of active work but has backlog
      busy               — at least one active slot
      issue_prefill()    -> PendingOp   (only when wants_prefill)
      issue_decode()     -> PendingOp   (only when busy)
      commit_op(op, t)   -> Optional[PhaseCost]  (refill cost, if any)
      prefill_wave(now)  -> PhaseCost   (issue+commit at now+duration)
      decode_step(now)   -> PhaseCost   (issue+commit at now+duration)

    ``issue_*`` runs the model and mutates slot state eagerly (the next op
    cannot be issued before the previous one commits, so ordering is safe);
    ``commit_op`` applies the time-dependent effects at the clock-chosen
    end instant and returns the cost of the refill prefills it ran.  A
    drained engine admits a wave; a busy one admits at each commit, where
    the backlog is seated FIFO, one batch-1 refill each, into every free
    slot: those the op's finished requests freed and those left empty
    before (a partial wave's), as far as the pool holds them.  So requests
    assigned while the engine is busy join it at its next commit instead
    of waiting for it to drain.  ``wave_only`` engines seat only at waves.
    The one-shot ``prefill_wave``/``decode_step`` wrappers preserve the
    original lockstep semantics exactly.

    Per-slot state: ``slot_lens[i]`` is slot i's context length (cache
    write position, prefix tokens included) and ``slot_tables[i]`` its
    block chain.  Both are host-side source of truth; the device arrays the
    real engine feeds the model are rebuilt from them every step.
    """

    def __init__(self, cfg: ModelConfig, *, slots: int, max_len: int,
                 pid: int = 0, peak_flops: float = hw.TPU_PEAK_FLOPS,
                 block_size: int = 16, pool_blocks: Optional[int] = None,
                 wave_only: bool = False,
                 cost_model: Optional[CostModel] = None,
                 prefix_cache: bool = False, kv_dtype: str = "fp32",
                 sparse_threshold: float = 0.0):
        if prefix_cache and cfg.family in _NO_PREFIX_CACHE_FAMILIES:
            raise ValueError(
                f"prefix caching is not supported for the {cfg.family!r} "
                "family: its per-sequence state is not (only) KV blocks, so "
                "a shared block chain cannot stand in for a cached prefix")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}: expected one "
                             f"of {KV_DTYPES}")
        if not 0.0 <= sparse_threshold < 1.0:
            raise ValueError("sparse_threshold must be in [0, 1) — it is a "
                             "per-block attention-mass cutoff, and >= 1 "
                             f"would drop every block (got {sparse_threshold})")
        if cfg.mla and (kv_dtype != "fp32" or sparse_threshold > 0.0
                        or prefix_cache):
            raise ValueError(
                "a latent (MLA) pool is served at the model's dtype, read "
                "whole and unshared: packed kv_dtype pages, blockwise-sparse "
                "reads and prefix caching are not implemented for it "
                f"(kv_dtype={kv_dtype!r}, sparse_threshold="
                f"{sparse_threshold}, prefix_cache={prefix_cache})")
        if (kv_dtype != "fp32" or sparse_threshold > 0.0) \
                and cfg.family in _NO_KV_QUANT_FAMILIES:
            raise ValueError(
                f"quantized / blockwise-sparse KV is not supported for the "
                f"{cfg.family!r} family: its per-sequence state is not "
                "(only) attention KV blocks, so packed pages or block "
                "skipping cannot represent its cache traffic")
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.pid = pid
        self.peak_flops = peak_flops
        self.block_size = block_size
        self.prefix_cache = bool(prefix_cache)
        self.kv_dtype = kv_dtype
        self.sparse_threshold = float(sparse_threshold)
        # phase pricing: analytic by default (bit-for-bit the historical
        # behaviour for fp32/keep-all; quantized or sparse layouts reprice
        # the KV-traffic term); a MeasuredCostModel swaps in on-device
        # durations and its live timer (if any) is fed by _run_timed below
        self.cost_model = cost_model if cost_model is not None \
            else AnalyticCostModel(cfg, peak_flops, kv_dtype=kv_dtype,
                                   sparse_keep=1.0 - self.sparse_threshold)
        # shape buckets whose compile-tainted first sample was discarded
        self._timed_warm: set = set()
        # wave-only batching: freed slots wait for the engine to drain and
        # the next *policy-granted* prefill wave instead of refilling
        # immediately (the enc-dec behaviour, also the load shape of the
        # paper's Fig. 5 — every wave start passes through the stagger
        # policy, so phase shaping binds for the whole run, not just at
        # startup)
        self.wave_only = wave_only
        # default pool: every slot can hold a full max_len chain (+ null)
        n_blocks = pool_blocks or \
            1 + slots * int(math.ceil(max_len / block_size))
        self.pool = BlockPool(n_blocks, block_size,
                              prefix_cache=self.prefix_cache)
        self.table_width = self.pool.blocks_for(max_len)
        self.backlog: List[Request] = []
        self.active: List[Optional[Request]] = [None] * slots
        self.slot_lens: List[int] = [0] * slots
        self.slot_tables: List[List[int]] = [[] for _ in range(slots)]
        # leading reference-shared blocks per slot (prefix-cache hits): the
        # real engine masks exactly these entries out of its page scatters,
        # so shared content is written once by its original owner
        self.slot_shared: List[int] = [0] * slots
        self.assign_order: List[int] = []  # rids in service order (tests)
        self.slot_tokens: List[List[int]] = [[] for _ in range(slots)]
        self.n_prefills = 0
        self.n_refills = 0
        # refills into a slot that no request of the same commit freed
        self.n_free_seats = 0
        self.n_decode_steps = 0
        self.n_exports = 0
        self.n_imports = 0
        self.n_prefix_hits = 0     # seatings that reused cached content
        self.n_cached_tokens = 0   # cache positions served from the index
        self.completed: List[Request] = []
        # an expert share's last paged decode step: (L,) int32 on the
        # device, the held experts its tokens reached in each layer
        self.experts_reached = None
        self._prefix = (getattr(cfg, "n_meta_tokens", 0) or 0) + \
                       (getattr(cfg, "n_img_tokens", 0) or 0)
        # opt-in observability (repro.obs): request lifecycle hops at
        # seat/first-token/retire/handoff, and on a wall-clock tracer host
        # spans around each phase and its parts (``obs.tracer.span``).  Every
        # emission site is guarded by ``if self.tracer is not None`` so the
        # off path (the default) executes no tracing code on the hot
        # issue/commit path — pinned by the zero-allocation guard in
        # tests/test_obs.py.  In cluster mode worker engines keep
        # tracer=None; the controller records the same transitions from
        # the protocol messages instead.
        self.tracer = None

    def kv_blocks_read(self) -> Tuple[int, int]:
        """(addressed, live) KV blocks of the next decode step: the paged
        decode gathers every slot's whole block table, inactive rows too
        (``slots * table_width``), while live context fills
        ``ceil((len + 1) / block_size)`` blocks of each active slot."""
        bs = self.block_size
        live = sum(-(-(n + 1) // bs)
                   for r, n in zip(self.active, self.slot_lens)
                   if r is not None)
        return self.slots * self.table_width, live

    def metrics_snapshot(self):
        """Flat ((name, value), ...) metrics view — computed on demand
        from counters the engine maintains anyway (zero steady-state
        overhead).  Workers piggyback this on every ``WorkerStatus`` so
        the controller can aggregate fleet-wide; the in-process CLI folds
        the same tuples via ``repro.obs.registry.merge_snapshots``."""
        return (
            ("engine.backlog", float(len(self.backlog))),
            ("engine.decode_steps", float(self.n_decode_steps)),
            ("engine.exports", float(self.n_exports)),
            ("engine.free_slot_seats", float(self.n_free_seats)),
            ("engine.imports", float(self.n_imports)),
            ("engine.prefills", float(self.n_prefills)),
            ("engine.refills", float(self.n_refills)),
            ("engine.slots_in_use",
             float(sum(1 for r in self.active if r is not None))),
            ("pool.cached_blocks", float(self.pool.n_cached)),
            ("pool.cow", float(self.pool.n_cow)),
            ("pool.evicted", float(self.pool.n_evicted)),
            ("pool.free_blocks", float(self.pool.n_free)),
            ("prefix.cached_tokens", float(self.n_cached_tokens)),
            ("prefix.hits", float(self.n_prefix_hits)),
        )

    # -- scheduler predicates ------------------------------------------------
    @property
    def busy(self) -> bool:
        return any(r is not None for r in self.active)

    @property
    def wants_prefill(self) -> bool:
        return (not self.busy) and bool(self.backlog)

    @property
    def idle(self) -> bool:
        return not self.busy and not self.backlog

    def assign(self, requests: List[Request]) -> None:
        self.backlog.extend(requests)

    def _ctx_budget(self, req: Request) -> int:
        """Cache positions this request needs end-to-end."""
        return self._prefix + req.prompt_len + req.max_new_tokens

    # -- prefix caching ------------------------------------------------------
    def _prefix_key(self, req: Request) -> list:
        """Content key for the prefix index: one sentinel per meta/img
        position (their embeddings are request-independent in the current
        frontends, so every request shares them) followed by the prompt
        token ids."""
        return [("pfx", j) for j in range(self._prefix)] + \
            [int(t) for t in np.asarray(req.prompt).reshape(-1)]

    def peek_cached(self, req: Request) -> int:
        """Prompt tokens of ``req`` the prefix cache would serve right now
        (0 when caching is off).  Pure peek — admission-control probes and
        the demand policy's cost estimates price from this without
        touching pool state."""
        if not self.prefix_cache:
            return 0
        hit = self.pool.peek_cached_tokens(self._prefix_key(req))
        return max(hit - self._prefix, 0)

    def _alloc_blocks(self, req: Request) -> ChainAlloc:
        """Allocate ``req``'s full-budget block chain, reusing cached
        prefix blocks when caching is on (all-or-nothing either way)."""
        need = self._ctx_budget(req)
        if not self.prefix_cache:
            return ChainAlloc(self.pool.alloc_for_tokens(need))
        return self.pool.alloc_chain(self._prefix_key(req), need)

    def _seat_blocks(self, i: int, req: Request, ca: ChainAlloc) -> None:
        """Install an allocated chain into slot ``i``'s bookkeeping and
        stamp the request's actual hit length (prompt-token units)."""
        self.slot_tables[i] = ca.table
        self.slot_shared[i] = ca.shared_blocks
        req.cached_len = max(ca.cached_tokens - self._prefix, 0)
        if ca.cached_tokens:
            self.n_prefix_hits += 1
            self.n_cached_tokens += ca.cached_tokens

    def _register_prefix(self, i: int, req: Request) -> None:
        """Publish slot ``i``'s prompt-content blocks in the prefix index
        (generated tokens are never shared, so registration stops at the
        end of the prompt)."""
        if self.prefix_cache:
            self.pool.register_chain(self._prefix_key(req),
                                     self.slot_tables[i],
                                     self._prefix + req.prompt_len)

    # -- KV handoff (prefill/decode disaggregation) --------------------------
    def export_kv(self, rid: int):
        """Remove active request ``rid`` from this engine, returning
        ``(request, state)`` — the request object plus everything a peer
        engine needs to continue decoding it: ``state['len']`` is the
        slot's context length, ``state['kv_bytes']`` the modeled transfer
        size (the per-slot cache bytes one decode step streams, in this
        engine's cost-model dtype), ``state['pages']`` the device arrays
        gathered by ``_export_slot_state`` (empty for the simulated
        engine).  The slot and its blocks are freed immediately — the
        prefill-pool worker can start its next wave while the payload is
        still in flight."""
        from repro.core.traffic import decode_kv_bytes

        for i, req in enumerate(self.active):
            if req is not None and req.rid == rid:
                break
        else:
            raise KeyError(f"request {rid} is not active on engine "
                           f"{self.pid}")
        from repro.profiling.cost_model import KV_PRICE_BYTES

        dtype_bytes = int(getattr(self.cost_model, "dtype_bytes", 2))
        state = {
            "len": int(self.slot_lens[i]),
            # a quantized pool ships packed pages, so the handoff payload is
            # priced at the pool's bytes-per-element, not the model dtype's
            "kv_bytes": float(decode_kv_bytes(
                self.cfg, self.slot_lens[i], dtype_bytes,
                kv_dtype_bytes=KV_PRICE_BYTES.get(self.kv_dtype))),
            "kv_dtype": self.kv_dtype,
            "pages": self._export_slot_state(i),
        }
        self.active[i] = None
        # a decref, not a destroy: blocks shared with other chains (or
        # published in the prefix index) survive the donor's departure
        self.pool.free(self.slot_tables[i])
        self.slot_tables[i] = []
        self.slot_shared[i] = 0
        self.slot_lens[i] = 0
        self.n_exports += 1
        if self.tracer is not None:
            self.tracer.lifecycle.event(req.rid, "handoff_export",
                                        self.tracer.vnow, pid=self.pid,
                                        kv_bytes=state["kv_bytes"])
        return req, state

    def import_kv(self, req: Request, state: dict) -> int:
        """Seat a handed-off request in a free slot and restore its KV
        state; returns the slot index.  All-or-nothing: every capacity
        check runs BEFORE any state mutates, so a ``PoolExhausted`` (no
        free slot, or not enough blocks for the request's full context
        budget) leaves the engine untouched and the caller free to defer
        the import to another worker or a later time."""
        free = [i for i, r in enumerate(self.active) if r is None]
        if not free:
            raise PoolExhausted(
                f"engine {self.pid}: no free slot for imported request "
                f"{req.rid} ({self.slots} slots active)")
        need = self._ctx_budget(req)
        if need > self.max_len:
            raise ValueError(
                f"request {req.rid} needs {need} cache positions > "
                f"per-slot budget max_len={self.max_len}")
        if int(state["len"]) > need:
            raise ValueError(
                f"request {req.rid} imports len={state['len']} beyond its "
                f"context budget {need}")
        if not self.pool.can_fit(need):
            raise PoolExhausted(
                f"engine {self.pid}: request {req.rid} needs "
                f"{self.pool.blocks_for(need)} blocks; pool has "
                f"{self.pool.n_free} of {self.pool.n_blocks}")
        i = free[0]
        # re-match the prompt against the recipient's own prefix index: a
        # shared system prompt already resident here is reference-shared
        # instead of re-stored, and the handoff scatter masks those blocks
        # out (their content is already authoritative on this engine)
        self._seat_blocks(i, req, self._alloc_blocks(req))
        self.active[i] = req
        self.slot_lens[i] = int(state["len"])
        self.assign_order.append(req.rid)
        self._import_slot_state(i, state.get("pages") or {}, req)
        self._register_prefix(i, req)
        self.n_imports += 1
        if self.tracer is not None:
            self.tracer.lifecycle.event(req.rid, "handoff_import",
                                        self.tracer.vnow, pid=self.pid)
        return i

    # -- cost estimates (used by the demand policy) --------------------------
    def prefill_cost_est(self) -> PhaseCost:
        n = min(self.slots, max(len(self.backlog), 1))
        plen = self.backlog[0].prompt_len if self.backlog else self.max_len // 2
        # price the NEXT wave as it would actually run: a resident shared
        # prefix makes it cheaper, and the demand policy must space from
        # the post-hit cost, not the cold one
        cached = self.peek_cached(self.backlog[0]) if self.backlog else 0
        return self.cost_model.prefill(n, plen, cached)

    def decode_cost_est(self) -> PhaseCost:
        ctxs = [max(l, 1) for r, l in zip(self.active, self.slot_lens)
                if r is not None]
        if not ctxs:
            plen = (self.backlog[0].prompt_len if self.backlog
                    else self.max_len // 2)
            ctxs = [max(self._prefix + plen, 1)] * self.slots
        return self.cost_model.decode(ctxs)

    # -- on-device timing: feed the cost model's live PhaseTimer -------------
    def _run_timed(self, phase: str, batch: int, tokens: int, fn):
        """Run a model-execution hook, wall-clocking it into the cost
        model's timer when one is attached.

        Both edges must block on the device (``_sync_device``): JAX
        dispatch is asynchronous, so work queued by a PREVIOUS op would
        otherwise bill into this measurement, and the return of ``fn``
        alone does not mean this op ran.  The first sample per shape
        bucket is discarded — the first execution of a jitted fn at a new
        shape includes XLA compilation (seconds against microseconds of
        steady-state run time), and an EMA never fully forgets a sample
        that large."""
        timer = self.cost_model.timer
        if timer is None:
            return fn()
        self._sync_device()
        t0 = time.perf_counter()
        out = fn()
        self._sync_device()
        dt = time.perf_counter() - t0
        key = shape_key(phase, batch, tokens)
        if key in self._timed_warm:
            timer.observe(key, dt)
        else:
            self._timed_warm.add(key)   # compile-tainted: discard
        return out

    # -- phase execution: issue (eager) / commit (clock-timed) ---------------
    def issue_prefill(self) -> PendingOp:
        assert self.wants_prefill, "issue_prefill() on a busy/idle engine"
        with span(self.tracer, "engine.prefill", self.pid):
            # validate the whole candidate wave BEFORE allocating anything, so
            # a contract violation cannot leak earlier members' blocks
            for req in self.backlog[:self.slots]:
                if self._ctx_budget(req) > self.max_len:
                    raise ValueError(
                        f"request {req.rid} needs {self._ctx_budget(req)} "
                        f"cache positions > per-slot budget "
                        f"max_len={self.max_len}")
            wave: List[Request] = []
            for req in self.backlog[:self.slots]:
                if not self.pool.can_fit(self._ctx_budget(req)):
                    break  # pool exhausted: the rest stays queued (FIFO)
                wave.append(req)
                self._seat_blocks(len(wave) - 1, req, self._alloc_blocks(req))
                # intra-wave sharing
                self._register_prefix(len(wave) - 1, req)
            if not wave:
                need = self.pool.blocks_for(self._ctx_budget(self.backlog[0]))
                raise PoolExhausted(
                    f"request {self.backlog[0].rid} needs {need} blocks; "
                    f"pool has {self.pool.n_free} of {self.pool.n_blocks}")
            self.backlog = self.backlog[len(wave):]
            lens = [r.prompt_len for r in wave]
            cost = self.cost_model.prefill_ragged(
                lens,
                [r.cached_len for r in wave] if self.prefix_cache else None)
            if self.tracer is not None:
                t = self.tracer.vnow
                for req in wave:
                    self.tracer.lifecycle.event(req.rid, "prefill", t,
                                                pid=self.pid,
                                                cached_len=req.cached_len)
            first = self._run_timed("prefill", len(wave), max(lens),
                                    lambda: self._run_prefill(wave))
            for i, req in enumerate(wave):
                self.active[i] = req
                self.slot_lens[i] = self._prefix + req.prompt_len
                self.assign_order.append(req.rid)
                if first is not None:  # prefill emits the first token
                    req.tokens.append(int(first[i]))
                    self.slot_tokens[i].append(int(first[i]))
            for i in range(len(wave), self.slots):
                self.active[i] = None
                self.slot_lens[i] = 0
            self.n_prefills += 1
            return PendingOp("prefill", cost,
                             list(wave) if first is not None else [])

    def issue_decode(self) -> PendingOp:
        assert self.busy, "issue_decode() on an engine with no active slots"
        step = NO_SPAN
        if self.tracer is not None and self.tracer.wall:
            addressed, live = self.kv_blocks_read()
            step = self.tracer.span("engine.decode", tid=self.pid,
                                    kv_blocks_addressed=addressed,
                                    kv_blocks_live=live)
        with step:
            with span(self.tracer, "engine.decode.price", self.pid):
                ctxs = [max(l, 1) for r, l in zip(self.active, self.slot_lens)
                        if r is not None]
                cost = self.cost_model.decode(ctxs)
            toks = self._run_timed("decode", len(ctxs), sum(ctxs),
                                   self._run_decode)
            if self.experts_reached is not None and self.tracer is not None \
                    and self.tracer.wall:
                # a host copy of (L,) ints, made only under a wall tracer
                self.tracer.counter(
                    "host", self.pid, "experts_reached", None,
                    experts_reached=int(np.asarray(
                        self.experts_reached).sum()))
            with span(self.tracer, "engine.decode.bookkeeping", self.pid):
                firsts: List[Request] = []
                for i, req in enumerate(self.active):
                    if req is None:
                        continue
                    self.slot_lens[i] += 1
                    req.tokens.append(int(toks[i]))
                    self.slot_tokens[i].append(int(toks[i]))
                    if req.t_first_token is None:
                        firsts.append(req)
                self.n_decode_steps += 1
            return PendingOp("decode", cost, firsts)

    def commit_op(self, pending: PendingOp,
                  t_end: float) -> Optional[PhaseCost]:
        """Apply the op's time-dependent effects at its end instant: stamp
        first tokens, retire completed requests, seat the backlog into
        every free slot.
        Returns the combined cost of any refill prefills (the caller bills
        them into its tick or schedules them as a follow-on span)."""
        with span(self.tracer, "engine.commit", self.pid):
            for req in pending.stamp_first:
                if req.t_first_token is None:
                    req.t_first_token = t_end
                    if self.tracer is not None:
                        self.tracer.lifecycle.event(req.rid, "first_token",
                                                    t_end, pid=self.pid)
            return self._finish_done(t_end)

    # -- one-shot wrappers (lockstep clock + direct use in tests) ------------
    def prefill_wave(self, now: float) -> PhaseCost:
        pend = self.issue_prefill()
        return pend.cost.merge(self.commit_op(pend, now + pend.cost.duration))

    def decode_step(self, now: float) -> PhaseCost:
        pend = self.issue_decode()
        return pend.cost.merge(self.commit_op(pend, now + pend.cost.duration))

    def _retire(self, i: int, req: Request, t: float) -> None:
        req.t_done = t
        self.completed.append(req)
        self.active[i] = None
        self.pool.free(self.slot_tables[i])
        self.slot_tables[i] = []
        self.slot_shared[i] = 0
        self.slot_lens[i] = 0
        if self.tracer is not None:
            self.tracer.lifecycle.event(req.rid, "retire", t, pid=self.pid,
                                        tokens=len(req.tokens))

    def _finish_done(self, t_end: float) -> Optional[PhaseCost]:
        """Retire finished requests, then seat the backlog FIFO into every
        free slot: the slots this commit freed and those already empty (a
        partial wave's, or one the pool could not fill at an earlier
        commit).  Each newcomer's OWN prompt is prefilled into freshly
        allocated blocks, gated only by pool capacity: a request the pool
        cannot hold waits at the head, and everything behind it waits too.
        Seatings into a slot that no request of this commit freed count in
        ``n_free_seats``.  Returns the combined cost of the refill prefills
        so the caller can bill them into its tick."""
        extra: Optional[PhaseCost] = None
        t_cursor = t_end
        refill = self._supports_slot_refill()
        for i, req in enumerate(self.active):
            freed = req is not None and req.done
            if freed:
                self._retire(i, req, t_end)
            elif req is not None:
                continue
            # chained refill: a newcomer whose prefill-emitted first token
            # already exhausts its budget retires immediately and frees the
            # slot for the next backlog request within the same tick
            while refill and self.backlog:
                nxt = self.backlog[0]
                if (self._ctx_budget(nxt) > self.max_len
                        or not self.pool.can_fit(self._ctx_budget(nxt))):
                    # exhausted now (retried at the next free slot or
                    # commit); over-budget requests surface as ValueError
                    # at the wave
                    break
                with span(self.tracer, "engine.refill", self.pid):
                    self.backlog.pop(0)
                    with span(self.tracer, "engine.refill.seat", self.pid):
                        self._seat_blocks(i, nxt, self._alloc_blocks(nxt))
                        self._register_prefix(i, nxt)
                        c = self.cost_model.prefill(1, nxt.prompt_len,
                                                    nxt.cached_len)
                    if self.tracer is not None:
                        self.tracer.lifecycle.event(
                            nxt.rid, "prefill", t_cursor, pid=self.pid,
                            refill=True, cached_len=nxt.cached_len)
                    tok = self._run_timed("prefill", 1, nxt.prompt_len,
                                          lambda: self._refill_slot(i, nxt))
                    self.active[i] = nxt
                    self.slot_lens[i] = self._prefix + nxt.prompt_len
                    self.assign_order.append(nxt.rid)
                    self.n_refills += 1
                    t_cursor += c.duration  # a tick's refills run in turn
                    extra = c if extra is None else extra.merge(c)
                    if tok is not None:
                        nxt.tokens.append(int(tok))
                        self.slot_tokens[i].append(int(tok))
                        nxt.t_first_token = t_cursor
                        if self.tracer is not None:
                            self.tracer.lifecycle.event(
                                nxt.rid, "first_token", t_cursor,
                                pid=self.pid)
                if not freed:
                    # a chained newcomer's retire frees the slot in this
                    # commit, so only the first seating here counts
                    freed = True
                    self.n_free_seats += 1
                    if self.tracer is not None and self.tracer.wall:
                        self.tracer.counter(
                            "host", self.pid, "free_slot_seats", None,
                            free_slot_seats=self.n_free_seats)
                if not nxt.done:
                    break
                self._retire(i, nxt, t_cursor)
        return extra

    # -- model-execution hooks ----------------------------------------------
    def _supports_slot_refill(self) -> bool:
        return not self.wave_only

    def _sync_device(self) -> None:
        """Block until the engine's device state is materialized (the stop
        edge of a phase-op wall-clock measurement).  The base/simulated
        engine has no device; the real engine overrides this with
        ``jax.block_until_ready`` over its cache/pages/logits."""

    def _run_prefill(self, wave: List[Request]):
        """Seat ``wave`` in slots [0, len(wave)); returns per-slot first
        tokens (len(wave),) or None."""
        raise NotImplementedError

    def _run_decode(self):
        """Returns per-slot next tokens (slots,)."""
        raise NotImplementedError

    def _refill_slot(self, i: int, req: Request):
        """Prefill ``req``'s own prompt into slot ``i`` (blocks already
        allocated).  Returns the request's first token, or None."""
        raise NotImplementedError

    def _export_slot_state(self, i: int) -> dict:
        """Gather slot ``i``'s device state as host numpy arrays (keyed by
        name).  The base engine has no device state — the simulated engine
        hands off an empty payload and migration is pure bookkeeping."""
        return {}

    def _import_slot_state(self, i: int, pages: dict,
                           req: Request) -> None:
        """Install an exported payload into slot ``i`` (tables already
        allocated, request already seated).  Base engine: nothing to do."""


# ---------------------------------------------------------------------------
# real engine (jax, via models.api) and the execution-free simulated engine
# ---------------------------------------------------------------------------


def _scatter_prefix(pages, k, v, src, tables):
    """Scatter batch rows ``src`` of dense K/V ``(L, B, S, Hkv, D)`` (a
    latent pool: latent rows, ``v`` None) into the pool blocks ``tables``
    names."""
    if v is None:
        return {"k_pages": write_latent_pages(pages["k_pages"], k, tables,
                                               src)}
    return write_prefix_pages(pages, k[:, src], v[:, src], tables)


class PartitionEngine(EngineBase):
    """Runs the actual model.  ``params`` may be shared across engines
    in-process (they are read-only during serving); on hardware each
    partition holds its own replica — the paper's reuse-vs-shaping tradeoff,
    priced by ``core.partitioning.weight_replica_bytes``.

    ``paged=True`` (default for decoder-only families) stores KV in the
    block pool and decodes through ``models.transformer.decode_step_paged``;
    ``paged=False`` keeps the dense ``(L, slots, max_len)`` slab with
    per-slot lengths — same serving semantics, used as the equivalence
    oracle.  Enc-dec models keep the dense scalar-len cache and wave-only
    batching (their decoder cache is rebuilt from the encoder per wave).
    """

    def __init__(self, cfg: ModelConfig, api, params, *, slots: int,
                 max_len: int, pid: int = 0,
                 peak_flops: float = hw.TPU_PEAK_FLOPS, seed: int = 0,
                 decode_fn=None, prefill_fn=None, prefill_uniform_fn=None,
                 paged: Optional[bool] = None,
                 block_size: int = 16, pool_blocks: Optional[int] = None,
                 wave_only: bool = False,
                 cost_model: Optional[CostModel] = None,
                 prefix_cache: bool = False, kv_dtype: str = "fp32",
                 sparse_threshold: float = 0.0, device=None):
        super().__init__(cfg, slots=slots, max_len=max_len, pid=pid,
                         peak_flops=peak_flops, block_size=block_size,
                         pool_blocks=pool_blocks, wave_only=wave_only,
                         cost_model=cost_model, prefix_cache=prefix_cache,
                         kv_dtype=kv_dtype, sparse_threshold=sparse_threshold)
        import jax

        self.api = api
        self.params = params
        self.paged = (cfg.family != "encdec") if paged is None else paged
        if self.paged and cfg.family == "encdec":
            raise ValueError("paged KV is not supported for enc-dec models")
        if self.prefix_cache and not self.paged:
            raise ValueError("prefix caching shares KV *blocks* and needs "
                             "the paged pool (paged=True); the dense "
                             "per-wave slab has no blocks to share")
        if (self.kv_dtype != "fp32" or self.sparse_threshold > 0.0) \
                and not self.paged:
            raise ValueError("kv quantization / blockwise-sparse attention "
                             "live in the paged block pool (paged=True); "
                             "the dense per-wave slab has neither packed "
                             "pages nor block granularity to skip")
        # engines may share jitted phase fns (same shapes -> one executable)
        if self.paged:
            pg = api.decode_paged
            if self.sparse_threshold > 0.0:
                pg = _partial(api.decode_paged,
                              sparse_threshold=self.sparse_threshold)
            self._decode_fn = decode_fn or jax.jit(pg, donate_argnums=(2,))
        else:
            self._decode_fn = decode_fn or jax.jit(api.decode,
                                                   donate_argnums=(2,))
        if cfg.family == "encdec":
            self._prefill_fn = prefill_fn or (
                lambda p, b, lens=None: api.prefill(p, b, max_len=max_len))
        else:
            self._prefill_fn = prefill_fn or jax.jit(
                lambda p, b, lens: api.prefill(p, b, max_len=max_len,
                                               lens=lens))
        # per-length executables (batch-1 slot refills, uniform SSM groups);
        # shareable across engines like decode_fn so a fleet compiles each
        # distinct prompt length once, not once per partition
        self._prefill_uniform_fn = prefill_uniform_fn or jax.jit(
            lambda p, b, ml: api.prefill(p, b, max_len=ml),
            static_argnames=("ml",))
        # one jitted scatter per engine: donating the pool updates it in
        # place instead of holding two copies of it at every install
        self._write_pages = jax.jit(_scatter_prefix, donate_argnums=(0,))
        # where this engine's arrays live (None: JAX's default device); the
        # caller places ``params`` there, the engine its lazily built pool
        self.device = device
        self.cache = None          # dense mode / encdec
        self.pages = None          # paged mode: k_pages/v_pages/ssm arrays
        self._last_tok = None
        self.last_logits = None    # (slots, V) np, for equivalence tests
        self._rng = np.random.default_rng(seed + pid)

    # -- batch assembly ------------------------------------------------------
    def _make_batch(self, prompts: List[np.ndarray]) -> dict:
        import jax.numpy as jnp

        cfg = self.cfg
        stack = np.stack([np.asarray(p, np.int32) for p in prompts])
        b = {"tokens": jnp.asarray(stack)}
        if cfg.n_img_tokens:
            b["img_embeds"] = jnp.zeros(
                (len(prompts), cfg.n_img_tokens, cfg.d_model), jnp.float32)
        if cfg.family == "encdec":
            b["enc_embeds"] = jnp.asarray(self._rng.standard_normal(
                (len(prompts), cfg.enc_seq, cfg.d_model), dtype=np.float32))
        return b

    def _has_ssm(self) -> bool:
        return self.cfg.family in ("ssm", "hybrid")

    # -- prefill paths -------------------------------------------------------
    def _wave_prefill_cache(self, wave: List[Request]):
        """Run the wave's prompts, returning (first_logits, dense cache)
        covering slots [0, len(wave)) with a per-slot ``len`` vector.

        Attention-only families fuse the ragged wave into ONE padded batch
        (stable shapes -> one executable) — causal masking keeps each
        slot's last-token logits and cache prefix exact.  SSM-bearing
        families run one fused batch per distinct length instead: their
        recurrent state integrates every input position, so in-row padding
        would corrupt short slots' states.
        """
        import jax.numpy as jnp

        lens = np.array([r.prompt_len for r in wave], np.int32)
        if not self._has_ssm():
            width = max(int(lens.max()), 1)
            padded = np.zeros((self.slots, width), np.int32)
            for i, r in enumerate(wave):
                padded[i, :r.prompt_len] = np.asarray(r.prompt, np.int32)
            lens_full = np.concatenate(
                [lens, np.ones(self.slots - len(wave), np.int32)])
            logits, cache = self._prefill_fn(
                self.params, self._make_batch(list(padded)),
                jnp.asarray(lens_full))
            return logits, cache
        # uniform groups (rows padded to full slot width, never in-row)
        cache = self.api.init_cache(self.slots, self.max_len)
        logits_out = [None] * len(wave)
        by_len = {}
        for i, r in enumerate(wave):
            by_len.setdefault(r.prompt_len, []).append(i)
        for plen, idxs in by_len.items():
            prompts = [np.asarray(wave[i].prompt, np.int32) for i in idxs]
            while len(prompts) < self.slots:
                prompts.append(np.zeros(plen, np.int32))
            lg, cg = self._prefill_uniform_fn(
                self.params, self._make_batch(prompts), self.max_len)
            rows = jnp.asarray(idxs, jnp.int32)
            src = jnp.arange(len(idxs), dtype=jnp.int32)
            for key in ("k", "v", "ssm_state", "ssm_conv"):
                if key in cache:
                    cache[key] = cache[key].at[:, rows].set(cg[key][:, src])
            cache["len"] = cache["len"].at[rows].set(cg["len"][src])
            for j, i in enumerate(idxs):
                logits_out[i] = lg[j]
        logits = jnp.stack([l for l in logits_out])
        return logits, cache

    def _ensure_pages(self) -> None:
        """Lazily initialise the paged pool arrays (first prefill OR first
        KV import on a fresh decode-pool engine)."""
        import jax

        from repro.serving import kv_pool as KV

        if self.pages is None:
            with jax.default_device(self.device):
                self.pages = KV.init_pages(self.cfg, self.pool.n_blocks,
                                           self.block_size,
                                           kv_dtype=self.kv_dtype)
                if self._has_ssm():
                    st = self.api.init_cache(self.slots, 1)
                    self.pages["ssm_state"] = st["ssm_state"]
                    self.pages["ssm_conv"] = st["ssm_conv"]

    def _install_paged(self, cache, rows: List[int],
                       src_rows: Optional[List[int]] = None) -> None:
        """Move batch rows ``src_rows`` (default: ``rows`` themselves) of a
        dense cache into slots ``rows``: K/V prefixes into the block pool,
        SSM state into the per-slot arrays.  One scatter per pool array
        regardless of how many slots are installed."""
        import jax.numpy as jnp

        self._ensure_pages()
        src = list(src_rows if src_rows is not None else rows)
        if "k" in cache:
            tables = np.zeros((len(rows), self.table_width), np.int32)
            for j, i in enumerate(rows):
                tables[j, :len(self.slot_tables[i])] = self.slot_tables[i]
                # prefix-cache hit: the leading shared blocks already hold
                # this content (written once by their original owner) —
                # divert their rewrite to the null block so a reference-
                # shared block is never written
                tables[j, :self.slot_shared[i]] = NULL_BLOCK
            sub = {k: self.pages[k] for k in
                   ("k_pages", "v_pages", "k_scales", "v_scales")
                   if k in self.pages}
            self.pages.update(self._write_pages(
                sub, cache["k"], cache.get("v"), jnp.asarray(src, jnp.int32),
                jnp.asarray(tables)))
        if self._has_ssm():
            rows_a = jnp.asarray(rows, jnp.int32)
            src_a = jnp.asarray(src, jnp.int32)
            self.pages["ssm_state"] = self.pages["ssm_state"].at[
                :, rows_a].set(cache["ssm_state"][:, src_a])
            self.pages["ssm_conv"] = self.pages["ssm_conv"].at[
                :, rows_a].set(cache["ssm_conv"][:, src_a])

    def _run_prefill(self, wave: List[Request]):
        import jax.numpy as jnp

        if self.cfg.family == "encdec":
            # decoder cache is built from the encoder output; prompts are
            # not consumed (stub frontend) and batching stays wave-only
            prompts = [np.asarray(r.prompt, np.int32) for r in wave]
            width = max(len(p) for p in prompts)
            prompts = [np.pad(p, (0, width - len(p))) for p in prompts]
            while len(prompts) < self.slots:
                prompts.append(np.zeros(width, np.int32))
            _, self.cache = self._prefill_fn(self.params,
                                             self._make_batch(prompts))
            self._last_tok = jnp.ones((self.slots, 1), jnp.int32)
            return None

        # seat lens/tables before installing storage (base sets them after
        # _run_prefill returns, so mirror the assignment here first)
        for i, req in enumerate(wave):
            self.slot_lens[i] = self._prefix + req.prompt_len
        with span(self.tracer, "engine.prefill.launch", self.pid):
            logits, cache = self._wave_prefill_cache(wave)
        with span(self.tracer, "engine.prefill.write_pages", self.pid):
            if self.paged:
                self._install_paged(cache, list(range(len(wave))))
                self.cache = None
            else:
                self.cache = cache
        with span(self.tracer, "engine.prefill.first_tokens", self.pid):
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B,)
            last = np.ones((self.slots, 1), np.int32)
            last[:first.shape[0], 0] = \
                np.asarray(first).reshape(-1)[:self.slots]
            self._last_tok = jnp.asarray(last)
            return np.asarray(first).reshape(-1)[:len(wave)]

    def _refill_slot(self, i: int, req: Request):
        import jax.numpy as jnp

        prompt = np.asarray(req.prompt, np.int32)
        with span(self.tracer, "engine.refill.prefill", self.pid):
            lg, c1 = self._prefill_uniform_fn(
                self.params, self._make_batch([prompt]),
                self.max_len if not self.paged
                else self._prefix + req.prompt_len)
        self.slot_lens[i] = self._prefix + req.prompt_len
        with span(self.tracer, "engine.refill.write_pages", self.pid):
            if self.paged:
                self._install_paged(c1, [i], src_rows=[0])
            else:
                for key in ("k", "v", "ssm_state", "ssm_conv"):
                    if key in self.cache:
                        self.cache[key] = self.cache[key].at[:, i].set(
                            c1[key][:, 0])
                self.cache["len"] = self.cache["len"].at[i].set(c1["len"][0])
        with span(self.tracer, "engine.refill.first_token", self.pid):
            tok = int(np.asarray(jnp.argmax(lg, axis=-1)).reshape(-1)[0])
            last = np.asarray(self._last_tok).copy()
            last[i, 0] = tok
            self._last_tok = jnp.asarray(last)
        return tok

    # -- KV handoff device-state movers --------------------------------------
    def _export_slot_state(self, i: int) -> dict:
        """Gather slot ``i``'s cache to host numpy, in table order (paged)
        or as the slot's dense rows.  The last generated token is not
        shipped — it is ``req.tokens[-1]`` and the importer rebuilds the
        ``_last_tok`` row from it."""
        if self.cfg.family == "encdec":
            raise ValueError("KV handoff is not supported for enc-dec "
                             "models (wave-shared decoder cache)")
        out: dict = {}
        if self.paged:
            if self.pages is not None and "k_pages" in self.pages:
                tbl = np.asarray(self.slot_tables[i], np.int32)
                out["k"] = np.asarray(self.pages["k_pages"][:, tbl])
                if "v_pages" in self.pages:
                    out["v"] = np.asarray(self.pages["v_pages"][:, tbl])
                if "k_scales" in self.pages:
                    # packed pages travel as-is; ship their scales so the
                    # importer can rebuild the quantized layout exactly
                    out["k_scales"] = np.asarray(
                        self.pages["k_scales"][:, tbl])
                    out["v_scales"] = np.asarray(
                        self.pages["v_scales"][:, tbl])
            if self._has_ssm() and self.pages is not None:
                out["ssm_state"] = np.asarray(self.pages["ssm_state"][:, i])
                out["ssm_conv"] = np.asarray(self.pages["ssm_conv"][:, i])
        elif self.cache is not None:
            for key in ("k", "v", "ssm_state", "ssm_conv"):
                if key in self.cache:
                    out[key] = np.asarray(self.cache[key][:, i])
        return out

    def _import_slot_state(self, i: int, pages: dict,
                           req: Request) -> None:
        import jax.numpy as jnp

        if self.cfg.family == "encdec":
            raise ValueError("KV handoff is not supported for enc-dec "
                             "models (wave-shared decoder cache)")
        if not req.tokens:
            raise ValueError(f"request {req.rid} imported before prefill "
                             "(no generated tokens to resume from)")
        if self.paged:
            self._ensure_pages()
            if "k" in pages:
                n_blk = len(self.slot_tables[i])
                if pages["k"].shape[1] != n_blk:
                    raise ValueError(
                        f"handoff carries {pages['k'].shape[1]} blocks but "
                        f"slot {i} allocated {n_blk} (block_size mismatch "
                        "across the fleet?)")
                if ("k_scales" in pages) != ("k_scales" in self.pages):
                    raise ValueError(
                        "KV handoff layout mismatch: donor and receiver "
                        "must use the same kv_dtype (packed pages carry "
                        "per-block scales a float pool cannot hold, and "
                        "float pages cannot be scattered into a packed "
                        "pool without requantizing)")
                tbl_np = np.asarray(self.slot_tables[i], np.int32).copy()
                # blocks re-matched from this engine's own prefix index
                # already hold the donor's prefix content — mask them out
                # of the scatter (shared blocks are never written)
                tbl_np[:self.slot_shared[i]] = NULL_BLOCK
                tbl = jnp.asarray(tbl_np)
                kd = self.pages["k_pages"].dtype
                self.pages["k_pages"] = self.pages["k_pages"].at[:, tbl].set(
                    jnp.asarray(pages["k"]).astype(kd))
                if "v" in pages:
                    self.pages["v_pages"] = self.pages["v_pages"].at[
                        :, tbl].set(jnp.asarray(pages["v"]).astype(kd))
                if "k_scales" in pages:
                    sd = self.pages["k_scales"].dtype
                    self.pages["k_scales"] = \
                        self.pages["k_scales"].at[:, tbl].set(
                            jnp.asarray(pages["k_scales"]).astype(sd))
                    self.pages["v_scales"] = \
                        self.pages["v_scales"].at[:, tbl].set(
                            jnp.asarray(pages["v_scales"]).astype(sd))
            if self._has_ssm():
                for key in ("ssm_state", "ssm_conv"):
                    self.pages[key] = self.pages[key].at[:, i].set(
                        jnp.asarray(pages[key]).astype(self.pages[key].dtype))
        else:
            if self.cache is None:
                self.cache = self.api.init_cache(self.slots, self.max_len)
            for key in ("k", "v", "ssm_state", "ssm_conv"):
                if key in self.cache and key in pages:
                    self.cache[key] = self.cache[key].at[:, i].set(
                        jnp.asarray(pages[key]).astype(
                            self.cache[key].dtype))
        last = (np.asarray(self._last_tok).copy()
                if self._last_tok is not None
                else np.ones((self.slots, 1), np.int32))
        last[i, 0] = int(req.tokens[-1])
        self._last_tok = jnp.asarray(last, jnp.int32)

    # -- decode --------------------------------------------------------------
    def _device_lens(self) -> np.ndarray:
        return np.array([l if r is not None else 0
                         for r, l in zip(self.active, self.slot_lens)],
                        np.int32)

    def _run_decode(self):
        import jax.numpy as jnp

        if self.paged:
            with span(self.tracer, "engine.decode.tables", self.pid):
                tables = np.zeros((self.slots, self.table_width), np.int32)
                for i, tbl in enumerate(self.slot_tables):
                    if self.active[i] is not None:
                        tables[i, :len(tbl)] = tbl
                pcache = dict(self.pages)
                pcache["tables"] = jnp.asarray(tables)
                pcache["lens"] = jnp.asarray(self._device_lens())
        with span(self.tracer, "engine.decode.launch", self.pid):
            if self.cfg.family == "encdec":
                logits, self.cache = self._decode_fn(
                    self.params, self._last_tok, self.cache)
            elif self.paged:
                logits, pcache = self._decode_fn(self.params, self._last_tok,
                                                 pcache)
                self.experts_reached = pcache.pop("experts_reached", None)
                self.pages = {k: v for k, v in pcache.items()
                              if k not in ("tables", "lens")}
            else:
                cache = dict(self.cache)
                cache["len"] = jnp.asarray(self._device_lens())
                logits, self.cache = self._decode_fn(
                    self.params, self._last_tok, cache)
            self._last_tok = jnp.argmax(logits, axis=-1).astype(
                jnp.int32).reshape(self.slots, 1)
        with span(self.tracer, "engine.decode.logits_to_host", self.pid):
            self.last_logits = np.asarray(logits, np.float32).reshape(
                self.slots, -1)
        with span(self.tracer, "engine.decode.tokens_to_host", self.pid):
            return np.asarray(self._last_tok)[:, 0]

    def _supports_slot_refill(self) -> bool:
        return self.cfg.family != "encdec" and not self.wave_only

    def _sync_device(self) -> None:
        """Wait for the issued op's device work: block on whichever arrays
        the last phase touched (next-token buffer + dense cache or paged
        pool).  ``block_until_ready`` walks pytrees, so the dict states are
        passed whole."""
        import jax

        for obj in (self._last_tok, self.cache, self.pages):
            if obj is not None:
                jax.block_until_ready(obj)


class SimulatedEngine(EngineBase):
    """Same slot/backlog/pool/phase state machine, no model execution:
    tokens are synthetic.  Used by scheduler unit tests and the partitions
    x policy benchmark sweep, where only phase timing, pool accounting, and
    bandwidth demand matter."""

    def _run_prefill(self, wave):
        return np.arange(len(wave)) + 1

    def _run_decode(self):
        return np.full(self.slots, 1 + (self.n_decode_steps % 7))

    def _refill_slot(self, i, req):
        return 1 + (self.n_refills % 7)
