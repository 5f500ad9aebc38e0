"""Scheduler and queue: 90th percentile of the program's own ``submit`` to
``prefill`` (seated) lifecycle stamps, over the requests sent in the
traced window; one never seated ranks above all.  Needs the program's
wall-clock tracer."""
from chipbench import stats


def read(view):
    tracer = getattr(view, "tracer", None)
    if tracer is None or not view.window.sent:
        return None
    first = {}
    for rid in view.window.sent:
        for stage, t, _ in tracer.lifecycle.timeline(rid):
            first.setdefault((rid, stage), t)
    return stats.percentile_ms(
        [first[r, "prefill"] - first[r, "submit"]
         if (r, "prefill") in first else stats.INF
         for r in view.window.sent if (r, "submit") in first], 0.9)
