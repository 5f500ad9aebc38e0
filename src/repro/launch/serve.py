"""Serving CLI: thin driver over the ``repro.serving`` engine.

P partition engines (the paper's compute-unit partitions, applied to one
serving device) run phase-staggered continuous batching under the
traffic-shaping scheduler; each partition gets 1/P of the compute while all
share one HBM pipe.  ``--clock`` picks the virtual clock: the event-driven
contention timeline (default; op overlap is fluid-model exact) or the
legacy lockstep tick (the regression oracle).  Prints throughput, latency
percentiles, the aggregate bandwidth-demand std, and the fluid-simulation
validation of the shaping claim (P staggered vs P=1 synchronous on the
identical request load).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
      --partitions 4 --stagger demand --clock event

``--cluster N`` runs the same load as a controller + N partition-worker
cluster instead (see ``repro.launch.cluster`` for the routing/failover
semantics and the transports).  ``build_fleet`` is the in-process fleet
``main`` runs; ``chip_smoke.py`` drives the same one on a TPU.

``--cost-model measured`` prices the demand-shaping rule from on-device
wall-clock timings instead of the analytic decomposition; with
``--profile PATH`` the run loads an existing calibration profile (frozen
deterministic replay) or, when the file does not exist yet, calibrates
live and writes it at exit — see ``docs/cost_models.md``.

``--prefix-cache`` turns on automatic prefix caching in every engine's KV
pool: shared prompt prefixes reference-share resident blocks, only the
divergent tail is priced as prefill, and admission control probes the
fleet's caches so deadline feasibility reflects the post-hit service time
— see ``docs/prefix_caching.md``.

``--kv-dtype int8`` (or ``fp8``) packs the paged KV pool with per-(block,
kv-head) scales and ``--sparse-threshold T`` skips KV blocks below an
estimated attention-mass cutoff; both shrink the decode KV stream and flow
through the cost model so the demand-shaping rule prices the reduced
traffic — see ``docs/kv_quantization.md``.  Both require the paged pool
(incompatible with ``--dense``).
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass

import jax
import numpy as np

from repro.configs import get_config
from repro.core import hw
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api as mapi
from repro.obs import (Tracer, format_summary, observe_phase_durations,
                       registry_from_engines, write_chrome)
from repro.profiling import make_cost_model, save_profile
from repro.serving import (CLOCKS, EventScheduler, PartitionEngine,
                           RequestQueue, decode_cost, make_scheduler,
                           prefill_cost, serving_trace_report)
from repro.serving.trace_sim import phase_balanced_bandwidth


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and validate the serving CLI's flags (``ap.error`` on a bad
    combination, before any model or device work)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots per partition")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    # in-process fleet axes use None sentinels so an explicit value can be
    # rejected (not silently dropped) when combined with --cluster
    ap.add_argument("--partitions", type=int, default=None)
    ap.add_argument("--stagger", default=None,
                    choices=["none", "uniform", "demand"])
    ap.add_argument("--clock", default=None, choices=list(CLOCKS),
                    help="virtual clock: 'event' overlaps partition ops on "
                         "the contention timeline (fluid-model-accurate "
                         "timing; the default), 'lockstep' advances the "
                         "fleet tick-by-tick (a long prefill stretches the "
                         "tick for every partition — quantized, but the "
                         "pre-event-clock regression oracle)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV pool block size (tokens)")
    ap.add_argument("--dense", action="store_true",
                    help="use the dense per-wave KV layout instead of the "
                         "paged pool (the equivalence oracle)")
    ap.add_argument("--cluster", type=int, default=None, metavar="N",
                    help="run as a controller + N partition-worker cluster "
                         "instead of the in-process fleet (see "
                         "repro.launch.cluster; --router/--transport pick "
                         "the routing policy and worker transport)")
    ap.add_argument("--simulated", action="store_true",
                    help="with --cluster: SimulatedEngine workers")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission control: max queued requests")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request completion deadline (virtual s)")
    ap.add_argument("--no-sim", action="store_true",
                    help="skip the serving-trace shaping validation")
    from repro.launch.cluster import build_cluster_args
    build_cluster_args(ap)
    args = ap.parse_args(argv)

    # validate the fleet shape BEFORE any model/config work so a bad flag
    # fails with a clear message instead of a downstream crash
    if args.partitions is not None and args.partitions < 1:
        ap.error(f"--partitions must be >= 1 (got {args.partitions}): the "
                 "fleet needs at least one partition engine")
    if args.batch < 1:
        ap.error(f"--batch must be >= 1 (got {args.batch}): each partition "
                 "needs at least one decode slot")
    if args.requests < 1:
        ap.error(f"--requests must be >= 1 (got {args.requests})")
    if args.cluster is not None and args.cluster < 1:
        ap.error(f"--cluster must be >= 1 (got {args.cluster})")
    from repro.launch.cluster import validate_cluster_args
    validate_cluster_args(ap, args)
    if args.trace is not None and args.clock == "lockstep":
        ap.error("--trace records the event-driven contention clock; the "
                 "lockstep oracle has no span timeline to trace")
    if args.cluster is None and args.router == "pd":
        ap.error("--router pd needs --cluster N: prefill/decode "
                 "disaggregation routes between cluster workers")
    if args.pd_split is not None and args.cluster is not None \
            and sum(args.pd_split) != args.cluster:
        ap.error(f"--pd-split {args.pd_split[0]}:{args.pd_split[1]} does "
                 f"not cover the {args.cluster}-worker fleet")

    if args.cluster is not None:
        # controller + N worker-process cluster (repro.launch.cluster).
        # The in-process-only axes have no cluster meaning: reject them
        # loudly rather than run a configuration the user did not ask for.
        for flag, val, hint in [
                ("--partitions", args.partitions, "--cluster N IS the "
                 "partition count"),
                ("--stagger", args.stagger, "use --router (round_robin ~ "
                 "none, shaping ~ demand)"),
                ("--clock", args.clock, "the cluster always runs the "
                 "event-driven contention clock")]:
            if val is not None:
                ap.error(f"{flag} applies to the in-process fleet and is "
                         f"ignored by --cluster; {hint}")
    return args


@dataclass
class Fleet:
    """The in-process serving fleet ``main`` runs: P engines sharing one
    set of params and one request queue, driven by ``sched``."""
    cfg: object
    api: object
    params: object
    engines: list
    queue: RequestQueue
    sched: object
    bandwidth: float
    cost_model: object
    tracer: object


def build_fleet(args: argparse.Namespace) -> Fleet:
    """Build the engines, queue the request load and assemble the
    scheduler for parsed serving ``args`` (not ``--cluster``)."""
    cfg = get_config(args.arch, smoke=args.smoke)
    P = args.partitions if args.partitions is not None else 1
    args.stagger = args.stagger if args.stagger is not None else "uniform"
    args.clock = args.clock if args.clock is not None else "event"
    slots = args.batch
    peak_per_part = hw.TPU_PEAK_FLOPS / P  # partitions split one device

    # --- phase pricing: one cost model shared by the whole fleet (same
    # shapes, same device -> shared EMA buckets warm P times faster).
    # measured + existing profile = frozen deterministic replay; measured
    # without one = live calibration (saved to --profile at exit, if set).
    cost_model = None  # None -> engines default to AnalyticCostModel
    if args.cost_model == "measured":
        cost_model = make_cost_model(
            "measured", cfg, peak_per_part, profile=args.profile,
            kv_dtype=args.kv_dtype,
            sparse_keep=1.0 - args.sparse_threshold)
    max_len = args.prompt_len + 4 * args.gen + (cfg.n_meta_tokens or 0) + \
        (cfg.n_img_tokens or 0)

    # --- engines: in-process the (read-only) params are aliased; real
    # deployments replicate per partition (core.partitioning prices that).
    # Built BEFORE the request load so admission control can probe the
    # fleet's prefix caches (a hit-eligible request is priced post-hit).
    api = mapi.build(cfg)
    # jitted: eager init would materialize each stacked leaf in float32
    # before the cast
    params = jax.jit(api.init)(jax.random.PRNGKey(0))
    paged = (cfg.family != "encdec") and not args.dense
    # one shared jitted fn per phase: same shapes across engines -> one
    # compiled executable for the whole fleet
    if paged:
        pg = api.decode_paged
        if args.sparse_threshold > 0.0:
            from functools import partial
            pg = partial(api.decode_paged,
                         sparse_threshold=args.sparse_threshold)
        decode_fn = jax.jit(pg, donate_argnums=(2,))
    else:
        decode_fn = jax.jit(api.decode, donate_argnums=(2,))
    if cfg.family == "encdec":
        prefill_fn = jax.jit(lambda p, b: api.prefill(p, b, max_len=max_len))
    else:
        prefill_fn = jax.jit(
            lambda p, b, lens: api.prefill(p, b, max_len=max_len, lens=lens))
    prefill_uniform_fn = jax.jit(
        lambda p, b, ml: api.prefill(p, b, max_len=ml),
        static_argnames=("ml",))
    engines = [PartitionEngine(cfg, api, params, slots=slots,
                               max_len=max_len, pid=p,
                               peak_flops=peak_per_part, paged=paged,
                               block_size=args.block_size,
                               decode_fn=decode_fn, prefill_fn=prefill_fn,
                               prefill_uniform_fn=prefill_uniform_fn,
                               cost_model=cost_model,
                               prefix_cache=args.prefix_cache,
                               kv_dtype=args.kv_dtype,
                               sparse_threshold=args.sparse_threshold)
               for p in range(P)]

    # --- request load + admission control ---
    from repro.profiling.cost_model import KV_PRICE_BYTES
    kv_price = KV_PRICE_BYTES.get(args.kv_dtype)
    kv_keep = 1.0 - args.sparse_threshold

    def estimate(req):
        pre = prefill_cost(cfg, slots, req.prompt_len, peak_per_part,
                           cached=req.cached_len, kv_dtype_bytes=kv_price)
        dec = decode_cost(cfg, slots, req.prompt_len + args.gen // 2,
                          peak_per_part, kv_dtype_bytes=kv_price,
                          kv_keep=kv_keep)
        return pre.duration + req.max_new_tokens * dec.duration

    # the probe answers "how much of this prompt is already resident
    # SOMEWHERE in the fleet" — optimistic across engines (the scheduler
    # is free to seat the request on the engine that holds the prefix)
    probe = (lambda req: max(e.peek_cached(req) for e in engines)) \
        if args.prefix_cache else None
    queue = RequestQueue(max_depth=args.max_queue, service_estimate=estimate,
                         prefix_probe=probe)
    # the tracer must watch the queue BEFORE the load goes in, so the
    # admission instants and lifecycle 'submit' records are captured
    tracer = None
    if args.trace is not None:
        tracer = Tracer()
        queue.tracer = tracer
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        queue.submit(rng.integers(1, cfg.vocab, size=(args.prompt_len,))
                     .astype(np.int32), args.gen, arrival=0.0,
                     deadline=args.deadline)

    # pipe sized inside the load's phase dynamic range (see trace_sim);
    # smoke-scale models put both phases past the physical HBM number
    bandwidth = phase_balanced_bandwidth(
        cfg, total_slots=P * slots, prompt_len=args.prompt_len, gen=args.gen)
    sched = make_scheduler(engines, queue, policy=args.stagger,
                           bandwidth=bandwidth, clock=args.clock)
    if tracer is not None:
        sched.attach_tracer(tracer)
    return Fleet(cfg, api, params, engines, queue, sched, bandwidth,
                 cost_model, tracer)


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    if args.cluster is not None:
        from repro.launch.cluster import run_cluster
        ctl, _ = run_cluster(
            arch=args.arch, smoke=args.smoke, workers=args.cluster,
            slots=args.batch, prompt_len=args.prompt_len, gen=args.gen,
            n_requests=args.requests, router=args.router,
            transport=args.transport, simulated=args.simulated,
            block_size=args.block_size, dense=args.dense,
            heartbeat_timeout=args.heartbeat_timeout,
            max_queue=args.max_queue, deadline=args.deadline,
            cost_model=args.cost_model, profile=args.profile,
            pd_split=args.pd_split, prefix_cache=args.prefix_cache,
            kv_dtype=args.kv_dtype, sparse_threshold=args.sparse_threshold,
            trace=args.trace)
        return [r.tokens for r in ctl.queue.completed]

    f = build_fleet(args)
    cfg, engines, queue, sched = f.cfg, f.engines, f.queue, f.sched
    cost_model, tracer, bandwidth = f.cost_model, f.tracer, f.bandwidth
    P, slots = len(engines), args.batch
    m = sched.run()
    s = m.summary()
    print(f"serve: {cfg.name} P={P} stagger={args.stagger} "
          f"clock={args.clock} cost_model={args.cost_model} "
          f"kv={args.kv_dtype} sparse={args.sparse_threshold:g} "
          f"slots={P}x{slots} completed={s['requests_completed']}"
          f"/{queue.n_submitted} rejected={queue.n_rejected}")
    if cost_model is not None:
        mode = "replay" if cost_model.timer is None else "calibrating"
        print(f"  cost model: measured ({mode}) "
              f"warm_buckets={cost_model.n_warm} "
              f"observations={cost_model.n_observations}")
        if cost_model.timer is not None and args.profile is not None:
            out = save_profile(cost_model, args.profile)
            print(f"  cost model: calibration profile written to {out}")
    # the shared summary formatter (repro.obs.format_summary) — one
    # registry-backed report for both CLIs, so the in-process and cluster
    # runs stay line-compatible
    reg = registry_from_engines(engines, queue=queue)
    observe_phase_durations(reg, getattr(sched, "trace", ()))
    achieved = sched.achieved_bw_stats() \
        if isinstance(sched, EventScheduler) else None
    lifecycle = tracer.lifecycle.format_exit_line() \
        if tracer is not None else None
    for line in format_summary(s, reg, bandwidth=bandwidth,
                               achieved=achieved,
                               prefix_cache=args.prefix_cache,
                               lifecycle=lifecycle):
        print(line)
    if tracer is not None:
        doc = write_chrome(tracer, args.trace)
        print(f"  trace: {len(doc['traceEvents'])} events -> {args.trace}")

    if not args.no_sim:
        rep = serving_trace_report(
            cfg, partitions=P, policy=args.stagger, total_slots=P * slots,
            n_requests=max(args.requests, P), prompt_len=args.prompt_len,
            gen=args.gen, bandwidth=bandwidth)
        print(f"  sim: P={P} {args.stagger} bw_std={rep['bw_std']/1e9:.2f} "
              f"GB/s vs P=1 sync {rep['base_bw_std']/1e9:.2f} GB/s "
              f"(x{rep['std_rel']:.2f}, bw_mean x{rep['mean_rel']:.2f}, "
              f"perf x{rep['perf_rel']:.2f})")

    # per-slot token streams across all partitions (driver contract)
    outs = [toks for eng in engines for toks in eng.slot_tokens]
    return outs


if __name__ == "__main__":
    main()
