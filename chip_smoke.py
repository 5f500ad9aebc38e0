"""Chip smoke test: serve full-width Qwen1.5-4B on a TPU through the normal
serving entry points, and check what comes out.

  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # four one-chip replicas behind the router

One chip: ``repro.launch.serve``'s in-process fleet — 2 partition engines
x 4 slots with the paged KV pool, the demand-staggered scheduler on the
event clock — serves 16 requests (prompt 512, 64 new tokens) of
``qwen1.5-4b`` at its published config with random weights from a seed.
Then engine 0 takes two fresh requests through one prefill and one decode
step, and its logits for them must match a cacheless ``api.forward`` over
the same tokens within ``LOGITS_RTOL`` of the reference's largest logit.

Four chips (``--chips 4``): four one-chip replicas, one per device, behind
the cluster controller (loopback transport, shaping router) serve the same
16 requests that one replica on device 0 served before them; every
replica must hold its params and KV pages on its own device, and each
request's tokens must equal the one-replica run's.  Both runs seat prompts
in whole waves (``wave_only``), so every request meets the same compiled
programs in both and greedy tokens agree exactly.

The script exits non-zero and prints no result when JAX finds no TPU or a
check fails.  Timings come from the host clock around work that ends in
``block_until_ready``; compile seconds are the durations JAX reports for
tracing, lowering and compiling.  The last line of a passing run is one
JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ARCH = "qwen1.5-4b"
SLOTS = 4
PARTITIONS = 2
REQUESTS = 16
PROMPT_LEN = 512
GEN = 64
SEED = 0
# served-vs-reference logits tolerance, relative to the reference's largest
# |logit|: bf16 weights and activations through 40 layers, with the cached
# decode and the cacheless forward ordering their sums differently
LOGITS_RTOL = 0.05

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Sums the compile durations JAX reports while it is registered."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration


def _prompts(vocab: int, n: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=(PROMPT_LEN,)).astype(np.int32)
            for _ in range(n)]


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_logits(fleet, *, seed: int = SEED + 1) -> float:
    """Two fresh requests through engine 0's prefill and first decode step
    against a cacheless forward over prompt + first token.  Returns the max
    |served - reference| over the reference's max |logit|."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.queue import Request

    eng = fleet.engines[0]
    reqs = [Request(rid=10_000 + i, prompt=p, max_new_tokens=GEN)
            for i, p in enumerate(_prompts(fleet.cfg.vocab, 2, seed))]
    eng.assign(reqs)
    eng.prefill_wave(0.0)
    eng.decode_step(0.0)
    served = eng.last_logits[:len(reqs)]
    tokens = np.stack([np.append(r.prompt, r.tokens[0]) for r in reqs])
    logits, _ = jax.jit(fleet.api.forward)(
        fleet.params, {"tokens": jnp.asarray(tokens)})
    ref = np.asarray(logits[:, -1], np.float32)
    return float(np.abs(served - ref).max() / np.abs(ref).max())


def one_chip(smoke: bool = False) -> dict:
    """Serve the load through ``serve.build_fleet`` and check its logits."""
    import jax

    from repro.launch import serve

    clock = CompileClock()
    t0 = time.perf_counter()
    args = serve.parse_args([
        "--arch", ARCH, *(["--smoke"] if smoke else []),
        "--partitions", str(PARTITIONS), "--batch", str(SLOTS),
        "--stagger", "demand", "--requests", str(REQUESTS),
        "--prompt-len", str(PROMPT_LEN), "--gen", str(GEN), "--no-sim"])
    fleet = serve.build_fleet(args)
    jax.block_until_ready(fleet.params)
    setup_s = time.perf_counter() - t0
    c0, t1 = clock.seconds, time.perf_counter()
    fleet.sched.run()
    for eng in fleet.engines:
        eng._sync_device()
    serve_s = time.perf_counter() - t1
    done = fleet.queue.completed
    ok_tokens = all(len(r.tokens) == GEN for r in done)
    err = check_logits(fleet)
    return {
        "setup_s": setup_s, "serve_wall_s": serve_s,
        "serve_compile_s": clock.seconds - c0,
        "total_compile_s": clock.seconds,
        "completed": len(done), "submitted": fleet.queue.n_submitted,
        "all_tokens": ok_tokens, "logits_rel_err": err,
        "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
        "ok": (len(done) == fleet.queue.n_submitted == REQUESTS
               and ok_tokens and err <= LOGITS_RTOL),
    }


def _serve_replicas(n: int, smoke: bool):
    """Serve the load on ``n`` one-device replicas behind the cluster
    controller.  Returns ({rid: tokens}, {wid: device ids}, seconds)."""
    import jax

    from repro.configs import get_config
    from repro.serving import RequestQueue
    from repro.serving.cluster import make_cluster, make_worker_specs

    cfg = get_config(ARCH, smoke=smoke)
    queue = RequestQueue()
    for p in _prompts(cfg.vocab, REQUESTS, SEED):
        queue.submit(p, GEN, arrival=0.0)
    specs = make_worker_specs(ARCH, n, smoke=smoke, slots=SLOTS,
                              max_len=PROMPT_LEN + 4 * GEN, engine="real",
                              wave_only=True, seed=SEED)
    t0 = time.perf_counter()
    ctl = make_cluster(specs, queue, transport="loopback", router="shaping")
    ctl.run()
    seconds = time.perf_counter() - t0
    placement = {}
    for wid, rt in ctl.transport.runtimes.items():
        eng = rt.engine
        eng._sync_device()
        arrays = jax.tree.leaves((eng.params, eng.pages))
        placement[wid] = sorted({d.id for a in arrays for d in a.devices()})
    tokens = {r.rid: list(r.tokens) for r in queue.completed}
    ctl.transport.runtimes.clear()
    return tokens, placement, seconds


def four_chips(smoke: bool = False) -> dict:
    """Four one-chip replicas against one replica on device 0."""
    import jax

    ref, ref_place, ref_s = _serve_replicas(1, smoke)
    gc.collect()
    got, place, fleet_s = _serve_replicas(4, smoke)
    same = sum(got.get(rid) == toks for rid, toks in ref.items())
    ids = [d.id for d in jax.local_devices()]
    return {
        "reference_s": ref_s, "fleet_s": fleet_s,
        "reference_devices": ref_place, "replica_devices": place,
        "requests_identical": same, "requests": len(ref),
        "peak_bytes_in_use": [_peak_bytes(d) for d in jax.devices()],
        "ok": (len(ref) == len(got) == REQUESTS and same == REQUESTS
               and ref_place == {0: [ids[0]]}
               and place == {w: [ids[w % len(ids)]] for w in range(4)}),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the serving fleet on one chip; 4: four "
                         "one-chip replicas against one replica")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are missing ({e})",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.core import hw

    peaks = hw.chip_peaks(dev.device_kind)
    print(f"device_kind: {dev.device_kind}  count: {len(devices)}  "
          f"hbm_bytes: {peaks.hbm_bytes:.0f} ({peaks.source})")
    res = (one_chip() if args.chips == 1 else four_chips())
    for k, v in res.items():
        print(f"{k}: {v}")
    if not res["ok"]:
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
