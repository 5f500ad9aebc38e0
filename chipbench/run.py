"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout: its configuration (``chipbench/configs/<config>.json``), its
traffic (``chipbench/traffic/<traffic>.json``), its end-to-end metrics and
the per-layer metrics whose readers (``chipbench/metrics/<name>.py``) the
run calls.  A new cell needs data files and a ``workloads`` entry only.

One run: draw the weights from the seed on the device, build the program's
engine, warm up every shape the traffic reaches (all of this is set-up),
then offer the traffic for ``--seconds`` while the program's scheduler
serves it, stamping every token by the wall clock.  ``--trace 1`` records
the window's first ``TRACE_SECONDS`` with the JAX profiler and reports the
per-layer metrics over them instead of the end-to-end ones.  After the window the float32 reference checks a
sample of what was served (``check.py``) against the limits in the
configuration's ``check``.  The last line of standard output is one JSON
object; the numbers compared, each with its limit, are the last lines of
standard error and the last key of that object.

It exits non-zero and prints no result without a TPU, or with fewer chips
than the cell asks for, or when the program's sources are not beside it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE = ROOT / ".chipbench_cache"
# A traced run traces the first TRACE_SECONDS of its window: over longer
# windows the profiler drops device events (a 50 s trace of q4b.decode.
# offline held 733 of its 994 decode steps; 20 s traces held them all).
TRACE_SECONDS = 20.0
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def load_spec(workload: str, bench_file: Path = ROOT / "BENCHMARK.json"):
    """The cell, its configuration and traffic files, and the metrics it
    reports, all by name from ``BENCHMARK.json``; the configuration's
    architecture module (``arch/<model_type>.py``) must exist."""
    from chipbench.arch import arch_for
    from chipbench.model import load_config
    from chipbench.traffic import load_traffic

    with open(bench_file) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_file}")
    cell = cells[workload]

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    config = load_config(cell["config"])
    arch_for(config)
    return {
        "cell": cell,
        "config": config,
        "traffic": load_traffic(cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


class CompileClock:
    """Counts and sums the compile work JAX reports (copied from
    ``chip_smoke.py``: tracing, lowering and backend compiles), and the
    persistent cache's hits and misses: a warm run misses nothing."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.events = 0
        self.backend = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1
            self.backend += event.endswith("backend_compile_duration")

    def _on_event(self, event, **_):
        for k in self.cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                self.cache[k] += 1

    def mark(self):
        return (self.seconds, self.events, self.backend, dict(self.cache))


def enable_compile_cache() -> None:
    """JAX's persistent cache at one fixed path inside the checkout, for
    every program however small."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metric(value, unit):
    return {"value": value, "unit": unit}


def run_cell(spec: dict, *, seed: int, seconds: float, trace: bool,
             controls: tuple = (), require_chip: bool = True,
             tamper=None, log=print, t_start: float | None = None,
             keep=None) -> dict:
    """One run of one cell; returns the result object (``checks`` last).

    ``controls`` (``calibrate.py``) also reads those lower-precision
    controls, and puts the first of them in the program's place: its
    numbers are compared against the limits, so ``correct`` must come out
    false.  ``tamper(served)`` may break the timed path (the harness's own
    tests use it); ``require_chip=False`` lets those tests run on the CPU.
    ``keep(xplane_path, rec, window)`` sees a traced run's raw record
    before it is deleted (the trace fixture is recorded so)."""
    import jax
    import numpy as np

    from chipbench import arch, check, driver, model, stats, traffic
    from chipbench import peaks as peaks_mod
    from chipbench import trace as trace_mod
    from chipbench.view import RunView

    t_start = time.perf_counter() if t_start is None else t_start
    cell, doc, tdoc = spec["cell"], spec["config"], spec["traffic"]
    devices = (check_devices(int(cell.get("chips", 1))) if require_chip
               else jax.devices())
    dev = devices[0]
    pk = (peaks_mod.peaks(dev.device_kind) if require_chip
          else peaks_mod.PEAKS["TPU v5 lite"])
    clock = CompileClock()
    m = arch.arch_for(doc).dims(doc)
    limits = doc["check"]

    # --- set-up: weights, engine, warm-up of every shape the traffic uses
    params = model.make_params(m, seed, doc["torch_dtype"])
    jax.block_until_ready(params)
    served = model.build_engine(doc, params)
    gen = traffic.Generator(tdoc, m.vocab, seed)
    if gen.max_len() > served.engine.max_len:
        raise ValueError(f"traffic {tdoc['_name']} needs {gen.max_len()} "
                         f"positions, the configuration serves "
                         f"{served.engine.max_len}")
    if tdoc["kind"] == "open" and not tdoc.get("rate"):
        raise ValueError(f"traffic {tdoc['_name']} has no rate")
    if tamper is not None:
        tamper(served)
    driver.warm_up(served, gen)
    jax.block_until_ready(served.engine.pages)
    rec = driver.Recorder(served.engine).install()
    c_setup = clock.mark()

    trace_dir = CACHE / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    lead_in = float(tdoc.get("lead_in_s", 0))
    win = driver.run_window(
        served, gen, rec, seconds=seconds, lead_in=lead_in,
        span_seconds=min(seconds, TRACE_SECONDS) if trace else None)
    c_window = clock.mark()
    if trace:
        jax.profiler.stop_trace()
    setup_s = win.t_open - t_start
    # requests sent in the window get their first token, however late
    wants_ttft = any(x["name"].startswith("ttft") for x in spec["end_to_end"])
    if wants_ttft or any(x["name"].startswith("queue_wait")
                         for x in spec["per_layer"]):
        eng, t_wait = served.engine, driver.clock() + 60.0
        while (any(not rec.stamps.get(r) for r in win.sent)
               and (eng.busy or eng.backlog or len(served.queue))
               and driver.clock() < t_wait):
            served.sched.run(max_spans=1)
    stats_dev = dev.memory_stats() or {}
    peak = stats_dev.get("peak_bytes_in_use")

    # --- what the window measured
    w0, w1 = win.t_open, win.t_close
    e2e = {
        "tokens_per_s": stats.tokens_per_s(rec.stamps, w0, w1),
        "ttft_p90_ms": stats.percentile_ms(
            stats.ttft(win.sent, rec.due, rec.stamps), 0.9),
        "tbt_p99_ms": stats.percentile_ms(
            stats.gaps_in_window(rec.stamps, w0, w1), 0.99),
        "setup_s": setup_s,
    }
    red = None
    if trace:
        xplane = trace_mod.find_xplane(str(trace_dir))
        red = trace_mod.reduce(trace_mod.load(xplane))
        if keep is not None:
            keep(xplane, rec, win)
        shutil.rmtree(trace_dir, ignore_errors=True)
    # a traced run's per-layer metrics cover the traced part of the window
    traced = dataclasses.replace(
        win, t_close=win.t_span_close,
        sent=[r for r in win.sent if rec.due[r] < win.t_span_close])
    view = RunView(m=m, peaks=pk, slots=served.engine.slots, rec=rec,
                   window=traced if trace else win, trace=red)
    if trace:
        log("trace_match: " + json.dumps(view.match_counts()))
        metrics = {}
        for x in spec["per_layer"]:
            v = reader(x["name"])(view)
            if v is not None:
                metrics[x["name"]] = _metric(v, x["unit"])
    else:
        metrics = {x["name"]: _metric(e2e[x["name"]], x["unit"])
                   for x in spec["end_to_end"]
                   if e2e.get(x["name"]) is not None}

    sent, fin = len(win.sent), sum(
        1 for r in win.sent if len(rec.stamps.get(r, ())) >=
        rec.requests[r].max_new_tokens)
    lag = np.asarray(win.lag) if win.lag else np.zeros(1)
    log(f"requests: sent_in_window {sent} finished {fin} unfinished "
        f"{sent - fin} sent_in_lead_in {len(win.sent_before)}")
    log(f"tokens_in_window: {stats.tokens_in_window(rec.stamps, w0, w1)}")
    log(f"generator_lag_s: p50 {float(np.median(lag)):.6f} "
        f"p99 {float(np.quantile(lag, 0.99)):.6f} max {float(lag.max()):.6f}")
    log(f"setup: setup_s {setup_s} compile_s {c_setup[0]:.3f} "
        f"compile_events {c_setup[1]} backend_compiles {c_setup[2]} "
        f"cache_hits {c_setup[3]['hits']} cache_misses "
        f"{c_setup[3]['misses']}")
    log(f"compiles_in_window: events {c_window[1] - c_setup[1]} backend "
        f"{c_window[2] - c_setup[2]} seconds {c_window[0] - c_setup[0]:.3f}")
    log(f"peak_bytes_in_use: {peak}")
    log("e2e: " + json.dumps(e2e))
    if red is not None:
        log(f"trace: busy_s {red.busy_s} window_s {red.window_s} "
            f"modules {json.dumps(red.modules)}")

    # --- correctness, after the program's state is freed
    pick = check.sample(rec, seed)
    reqs = [rec.requests[r] for r in pick]
    max_len = served.engine.max_len
    served.engine.pages = None
    served.engine._last_tok = None
    rec.engine = None
    del served, view
    gc.collect()
    t_ref = time.perf_counter()
    rd = check.readings(params, m, reqs, rec.logits, length=max_len,
                        controls=tuple(controls))
    ref_s = time.perf_counter() - t_ref
    log(f"reference: requests {len(reqs)} tokens {rd['tokens']} "
        f"seconds {ref_s:.3f} paths "
        f"{sorted({rec.path.get(r, '?') for r in pick})}")
    log("readings: " + json.dumps(rd))
    judged = rd["control_" + controls[0]] if controls else rd
    checks = {k: {"value": judged[k], "limit": lim}
              for k, lim in limits.items()}
    checks["sampled_tokens"] = {"value": judged["tokens"], "limit": 1}
    checks["sampled_decode_tokens"] = {"value": judged["err_tokens"],
                                       "limit": 1}
    ok = (judged["tokens"] >= 1 and judged["err_tokens"] >= 1
          and all(c["value"] is not None and c["value"] <= c["limit"]
                  for k, c in checks.items() if k in limits))
    result = {
        "correct": bool(ok), "attempted": sent,
        "failed": 0 if ok else max(len(reqs), 1),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if red is not None:
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = trace_mod.breakdown(red)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_spec(args.workload)
    except (OSError, KeyError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        importlib.import_module("repro.serving")
    except ImportError as e:
        print(f"chipbench: the program's sources are missing ({e})",
              file=sys.stderr)
        return 2
    os.makedirs(CACHE, exist_ok=True)
    enable_compile_cache()
    try:
        result = run_cell(spec, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
