"""Engine, host side of a decode step: device idle time that overlaps the
program's ``engine.decode`` spans (by overlap), over the decode steps whose
span opened in the traced window.  Needs the program's wall-clock tracer
(``program_trace.py``)."""
from chipbench import trace


def read(view):
    host, tr = getattr(view, "host", None), view.trace
    if not host or tr is None or not tr.calls:
        return None
    lo, hi = tr.window
    spans = [(s, e) for n, s, e in host
             if n == "engine.decode" and lo <= s < hi]
    if not spans:
        return None
    runs = [(k, s, e) for k, v in tr.calls.items() for s, e in v]
    idle = trace.idle_gaps(trace.busy_intervals(runs, lo, hi), lo, hi)
    overlap = sum(max(0.0, min(b, e) - max(a, s))
                  for a, b in idle for s, e in spans)
    return 1e3 * overlap / len(spans)
