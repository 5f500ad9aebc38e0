"""Partition worker: one engine behind the cluster message protocol.

``WorkerRuntime`` is the protocol adapter — it owns exactly one
``EngineBase`` and maps each controller message onto the engine's
issue/commit surface, replying with a fresh ``WorkerStatus`` snapshot.
The SAME runtime class serves both transports: the loopback transport
calls ``handle`` in-process, ``worker_main`` runs it as a subprocess
recv/handle/send loop over a multiprocessing pipe.

``WorkerSpec`` is the picklable recipe a worker process builds its engine
from (the controller never ships live objects across the boundary).  A
real engine for worker ``wid`` holds its params and KV pages on local
device ``wid % n`` for its whole run, so one process can serve one replica
per chip of its host.
"""
from __future__ import annotations

import traceback
from dataclasses import dataclass
from typing import Optional

from repro.serving.cluster import protocol as P
from repro.serving.engine import EngineBase, PendingOp


class WorkerRuntime:
    """Protocol adapter around one engine (any ``EngineBase``)."""

    def __init__(self, engine: EngineBase):
        self.engine = engine
        self._pending: Optional[PendingOp] = None
        # fleet-virtual clock as last exported by the controller (every
        # CommitOp.t_end and Ping.t_virtual); the worker never advances it
        self.vnow = 0.0

    # -- status snapshot -----------------------------------------------------
    def status(self) -> P.WorkerStatus:
        e = self.engine
        pre_dur = wave_dur = 0.0
        head_arrival = 0.0
        if e.backlog:
            head = e.backlog[0]
            head_arrival = float(head.arrival)
            if e.wants_prefill:
                # the demand-spacing ingredients, priced engine-side by the
                # worker's own cost model (analytic by default; measured
                # on-device timings under --cost-model measured) — the same
                # estimators the in-process policy uses
                pre = e.prefill_cost_est()
                pre_dur = pre.duration
                wave_dur = pre.duration + head.max_new_tokens * \
                    e.decode_cost_est().duration
        return P.WorkerStatus(
            busy=e.busy, wants_prefill=e.wants_prefill,
            backlog_len=len(e.backlog),
            n_active=sum(1 for r in e.active if r is not None),
            head_arrival=head_arrival, pre_dur=pre_dur, wave_dur=wave_dur,
            cost_source=e.cost_model.kind,
            active_rids=tuple(r.rid for r in e.active if r is not None),
            # flat metrics snapshot, piggybacked on every reply so the
            # controller's fleet view is as fresh as its worker mirror
            metrics=e.metrics_snapshot())

    def hello(self) -> P.Hello:
        return P.Hello(wid=self.engine.pid, slots=self.engine.slots,
                       max_len=self.engine.max_len, status=self.status())

    # -- message dispatch ----------------------------------------------------
    def handle(self, msg):
        try:
            return self._handle(msg)
        except Exception as e:  # noqa: BLE001 — shipped to the controller
            return P.WorkerError(error=f"{type(e).__name__}: {e}",
                                 traceback=traceback.format_exc())

    def _handle(self, msg):
        if isinstance(msg, P.Assign):
            self.engine.assign([wr.to_request() for wr in msg.requests])
            return P.AssignAck(status=self.status())
        if isinstance(msg, P.IssueOp):
            assert self._pending is None, "issue before previous commit"
            if msg.op == "prefill":
                self._pending = self.engine.issue_prefill()
            elif msg.op == "decode":
                self._pending = self.engine.issue_decode()
            else:
                raise ValueError(f"unknown op {msg.op!r}")
            return P.OpIssued(op=msg.op,
                              cost=P.WireCost.from_cost(self._pending.cost),
                              status=self.status())
        if isinstance(msg, P.CommitOp):
            assert self._pending is not None, "commit with no issued op"
            self.vnow = max(self.vnow, msg.t_end)
            pend, self._pending = self._pending, None
            extra = self.engine.commit_op(pend, msg.t_end)
            retired = tuple(
                P.RetiredRequest(rid=r.rid, tokens=tuple(r.tokens),
                                 t_first_token=r.t_first_token,
                                 t_done=r.t_done)
                for r in self._drain_completed())
            refill = P.WireCost.from_cost(extra) if extra is not None else None
            return P.OpCommitted(op=pend.kind, retired=retired,
                                 refill=refill, status=self.status())
        if isinstance(msg, P.ExportKv):
            from repro.serving.pd import handoff as H
            handoffs = tuple(H.export_handoff(self.engine, rid)
                             for rid in msg.rids)
            return P.KvExported(handoffs=handoffs, status=self.status())
        if isinstance(msg, P.ImportKv):
            from repro.serving.kv_pool import PoolExhausted
            from repro.serving.pd import handoff as H
            try:
                H.apply_handoff(self.engine, msg.handoff)
            except PoolExhausted as e:
                # capacity, not failure: all-or-nothing import left the
                # engine untouched; the controller defers and retries
                return P.KvImported(ok=False, reason=str(e),
                                    status=self.status())
            return P.KvImported(ok=True, reason="", status=self.status())
        if isinstance(msg, P.Ping):
            self.vnow = max(self.vnow, msg.t_virtual)
            return P.Pong(t_wall=msg.t_wall, status=self.status(),
                          t_virtual=self.vnow)
        if isinstance(msg, P.Shutdown):
            return P.Bye(n_prefills=self.engine.n_prefills,
                         n_refills=self.engine.n_refills,
                         n_decode_steps=self.engine.n_decode_steps)
        raise ValueError(f"unknown message {type(msg).__name__}")

    def _drain_completed(self):
        out, self.engine.completed = self.engine.completed, []
        return out


# ---------------------------------------------------------------------------
# engine construction from a picklable spec (subprocess + loopback share it)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class WorkerSpec:
    """Everything a worker process needs to build its engine.

    ``cost_model`` picks the phase-pricing source ("analytic" |
    "measured"); ``profile`` is an optional path to a saved calibration
    profile — with ``cost_model="measured"`` an existing profile is loaded
    as a FROZEN replay model (deterministic across the fleet), a missing
    one means each worker calibrates live with its own ``PhaseTimer``.
    """
    wid: int
    arch: str
    smoke: bool
    slots: int
    max_len: int
    peak_flops: float
    engine: str = "sim"          # "sim" | "real"
    wave_only: bool = False
    block_size: int = 16
    paged: Optional[bool] = None
    seed: int = 0
    cost_model: str = "analytic"  # phase pricing: "analytic" | "measured"
    profile: Optional[str] = None  # saved calibration profile (replay)
    prefix_cache: bool = False   # per-worker KV-pool prefix index (COW)
    kv_dtype: str = "fp32"       # KV pool element layout: fp32 | int8 | fp8
    sparse_threshold: float = 0.0  # blockwise-sparse attention cutoff


def replica_device(wid: int):
    """The local device worker ``wid``'s real engine lives on."""
    import jax

    devices = jax.local_devices()
    return devices[wid % len(devices)]


def build_engine(spec: WorkerSpec) -> EngineBase:
    """Build the engine a spec describes (used by subprocess workers and by
    the loopback transport, so both paths serve identical engines)."""
    from repro.configs import get_config
    from repro.profiling import make_cost_model
    from repro.serving.engine import SimulatedEngine

    cfg = get_config(spec.arch, smoke=spec.smoke)
    cost_model = make_cost_model(
        spec.cost_model, cfg, spec.peak_flops, profile=spec.profile,
        kv_dtype=spec.kv_dtype, sparse_keep=1.0 - spec.sparse_threshold)
    if spec.engine == "sim" and cost_model.timer is not None:
        # a live timer on a SimulatedEngine would fold the Python wall
        # time of synthetic token generation — not device time — into the
        # EMAs and silently wreck the spacing rule; measured pricing on
        # sim engines is replay-only
        raise ValueError(
            "cost_model='measured' on a simulated engine requires a "
            "calibration profile (the sim has no device to time); "
            "calibrate with the real in-process fleet first: "
            "python -m repro.launch.serve --cost-model measured "
            "--profile PATH ...")
    kw = dict(slots=spec.slots, max_len=spec.max_len, pid=spec.wid,
              peak_flops=spec.peak_flops, wave_only=spec.wave_only,
              block_size=spec.block_size, cost_model=cost_model,
              prefix_cache=spec.prefix_cache, kv_dtype=spec.kv_dtype,
              sparse_threshold=spec.sparse_threshold)
    if spec.engine == "sim":
        return SimulatedEngine(cfg, **kw)
    if spec.engine != "real":
        raise ValueError(f"unknown engine kind {spec.engine!r}")
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro.models import api as mapi
    from repro.serving.engine import PartitionEngine

    device = replica_device(spec.wid)
    api = mapi.build(cfg)
    # jitted: eager init would materialize each stacked leaf in float32
    # before the cast; out_shardings builds the params on the device
    params = jax.jit(api.init, out_shardings=SingleDeviceSharding(device))(
        jax.random.PRNGKey(spec.seed))
    return PartitionEngine(cfg, api, params, paged=spec.paged,
                           seed=spec.seed, device=device, **kw)


def worker_main(conn, spec: WorkerSpec) -> None:
    """Subprocess entry: build the engine, say Hello, then serve the
    request/reply loop until Shutdown (or the pipe closes)."""
    rt = WorkerRuntime(build_engine(spec))
    conn.send(P.encode(rt.hello()))
    while True:
        try:
            msg = P.decode(conn.recv())
        except (EOFError, OSError):
            break  # controller went away
        reply = rt.handle(msg)
        conn.send(P.encode(reply))
        if isinstance(reply, P.Bye):
            break
    conn.close()
