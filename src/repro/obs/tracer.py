"""The event collector: spans, instants, counters, flows, on one clock.

A ``Tracer`` is a list of plain event dicts plus a clock binding.  Every
producer (timeline, engine, scheduler, queue, controller, PD router)
holds a ``tracer`` attribute that defaults to ``None`` and only emits
under an ``if self.tracer is not None`` guard — the off path runs no
observability code at all.

Event shape (one dict per event, kept close to the Chrome trace format
so ``export.to_chrome`` is a projection, not a transformation):

  {"ph": "B"|"E"|"i"|"C"|"s"|"f", "group": str, "tid": int|str,
   "name": str, "t": float_seconds, "args": {...}}

Flow events ("s"/"f") additionally carry ``"id"`` — allocate one with
``flow_id()`` and use it for both ends (the PD handoff export→import
arrow).

Two clocks:

* virtual (the default) — ``attach_tracer`` binds the tracer to the
  shared ``ContentionTimeline`` and every event carries the virtual
  instant its producer passes, so identical runs produce identical event
  lists (pinned byte-for-byte by ``tests/test_obs.py``).  Host work takes
  no virtual time, so ``span`` records nothing here.
* wall — ``Tracer(clock=WallClock())`` keeps its clock through every
  ``attach_tracer``.  Each event, lifecycle stage included, is stamped
  with ``time.perf_counter()`` when it is emitted; the virtual instant a
  producer passes is ignored.  ``span`` then times host work: it records
  B/E events and enters a ``jax.profiler.TraceAnnotation`` of the same
  name, so a profiler trace shows the span on its host plane, on the
  device trace's own clock.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

from repro.obs.lifecycle import LifecycleLog

# the span of a virtual tracer, and of producers with no tracer
NO_SPAN = contextlib.nullcontext()


class WallClock:
    """The host's monotonic clock (``time.perf_counter``)."""

    @property
    def now(self) -> float:
        return time.perf_counter()


class Tracer:
    """Collects structured events in emission order."""

    def __init__(self, clock: Optional[object] = None):
        self.events: List[Dict[str, Any]] = []
        self.clock = clock          # object with a ``.now`` attribute
        self.wall = isinstance(clock, WallClock)
        self.lifecycle = LifecycleLog(clock if self.wall else None)
        # names of the host spans open now, outermost first (wall only)
        self.open_spans: List[str] = []
        self._flow_seq = 0
        if self.wall:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation

    # -- clock ---------------------------------------------------------------
    @property
    def vnow(self) -> float:
        """Current time of the bound clock (0.0 when unbound) — lets
        producers that do not own a clock (engines) stamp events."""
        c = self.clock
        return 0.0 if c is None else float(c.now)

    # -- emission ------------------------------------------------------------
    def _emit(self, ph: str, group: str, tid, name: str, t: float,
              args: dict) -> Dict[str, Any]:
        ev = {"ph": ph, "group": group, "tid": tid, "name": name,
              "t": self.clock.now if self.wall else t, "args": args}
        self.events.append(ev)
        return ev

    def begin(self, group: str, tid, name: str, t: float, **args) -> None:
        """Open a slice on track (group, tid)."""
        self._emit("B", group, tid, name, t, args)

    def end(self, group: str, tid, name: str, t: float, **args) -> None:
        """Close the innermost open slice on track (group, tid)."""
        self._emit("E", group, tid, name, t, args)

    def instant(self, group: str, tid, name: str, t: float, **args) -> None:
        """A zero-duration marker (admissions, holds, failovers, ...)."""
        self._emit("i", group, tid, name, t, args)

    def counter(self, group: str, tid, name: str, t: float,
                **values) -> None:
        """One sample of a (multi-series) counter track; ``values`` maps
        series name -> number (the aggregate bw-demand curve)."""
        self._emit("C", group, tid, name, t, values)

    def flow_id(self) -> int:
        """A fresh id linking a flow's start and finish events."""
        self._flow_seq += 1
        return self._flow_seq

    def flow_start(self, group: str, tid, name: str, t: float, fid: int,
                   **args) -> None:
        """Flow arrow tail (e.g. KV export on the source worker track)."""
        self._emit("s", group, tid, name, t, args)["id"] = fid

    def flow_end(self, group: str, tid, name: str, t: float, fid: int,
                 **args) -> None:
        """Flow arrow head (e.g. KV import on the destination track)."""
        self._emit("f", group, tid, name, t, args)["id"] = fid

    def span(self, name: str, group: str = "host", tid=0, **args):
        """Context manager timing host work as a slice on (group, tid).

        Wall clock only: a virtual tracer returns a shared no-op context,
        since host work takes no virtual time.  ``args`` ride on the B
        event."""
        if not self.wall:
            return NO_SPAN
        return _HostSpan(self, name, group, tid, args)


def span(tracer: Optional[Tracer], name: str, tid=0):
    """``tracer.span(name, tid=tid)``, or the shared no-op ``NO_SPAN``
    where no tracer is attached: the ``tracer is None`` guard of every
    host-span site in the engines and the scheduler."""
    if tracer is None:
        return NO_SPAN
    return tracer.span(name, tid=tid)


class _HostSpan:
    """One host span of a wall-clock tracer (see ``Tracer.span``)."""
    __slots__ = ("tracer", "name", "group", "tid", "args", "ann")

    def __init__(self, tracer: Tracer, name: str, group: str, tid,
                 args: dict):
        self.tracer, self.name, self.group, self.tid, self.args = \
            tracer, name, group, tid, args

    def __enter__(self):
        tr = self.tracer
        tr.open_spans.append(self.name)
        tr._emit("B", self.group, self.tid, self.name, None, self.args)
        self.ann = tr._annotation(self.name)
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        tr = self.tracer
        tr._emit("E", self.group, self.tid, self.name, None, {})
        tr.open_spans.pop()
        return False
