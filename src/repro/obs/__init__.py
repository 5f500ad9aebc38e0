"""Observability: span tracing, metrics registry, request lifecycles.

The paper's argument is made by looking at traffic over time (Fig. 1/5/6);
this package makes the live stack emit that view.  ``Tracer`` collects
structured events (span begin/end, instants, counters, flows) from every
layer — the contention timeline, engines, schedulers, the queue, the
cluster controller, and the PD router.  By default every event is
stamped on the shared *virtual* clock, so traces are deterministic and
CI-assertable.  ``Tracer(clock=WallClock())`` stamps on the host's clock
instead and adds host spans inside the program (``engine.decode``,
``engine.decode.logits_to_host``, ``scheduler.step``, ...), each also a
``jax.profiler.TraceAnnotation``: a profiler trace then shows them on its
host plane, on the same clock as the device's executables.
``export.to_chrome`` renders them as Chrome-trace / Perfetto JSON
(partitions and workers as tracks, phases as slices, the aggregate
bw-demand curve as a counter track).  ``MetricsRegistry`` holds
counters/gauges/histograms with deterministic snapshots that workers
piggyback on ``WorkerStatus`` for fleet-wide aggregation.
``LifecycleLog`` records per-request hop timestamps
(arrival→admit→prefill→[handoff]→decode→retire).

Tracing is strictly opt-in and zero-overhead when off: every hot call
site is guarded by ``if <owner>.tracer is not None`` on a plain attribute
that defaults to ``None``, so the off path executes no observability code
and allocates nothing (pinned by ``tests/test_obs.py``).
"""
from repro.obs.export import (to_chrome, trace_bw_segments, validate_chrome,
                              write_chrome)
from repro.obs.lifecycle import LifecycleLog
from repro.obs.registry import MetricsRegistry, merge_snapshots
from repro.obs.summary import (format_summary, observe_phase_durations,
                               registry_from_engines)
from repro.obs.tracer import Tracer, WallClock

__all__ = [
    "LifecycleLog", "MetricsRegistry", "Tracer", "WallClock",
    "format_summary", "merge_snapshots", "observe_phase_durations",
    "registry_from_engines", "to_chrome", "trace_bw_segments",
    "validate_chrome", "write_chrome",
]
