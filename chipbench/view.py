"""What a per-layer metric reader may read about one run.

Each reader in ``metrics/<name>.py`` defines ``read(view)`` and returns a
number, or None when the run has nothing for it to read (the harness then
leaves the metric out of the line).  Readers see the harness's stamps and
counts (``ops``, ``rec``), the window, the model's sizes, the chip's peaks
and, in a traced run, the reduced device trace.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass

DECODE = "jit_decode_paged"
PREFILL = ("jit_prefill_wave", "jit_prefill_refill")
MODULE_OF = {"decode": DECODE, "wave": PREFILL[0], "refill": PREFILL[1]}


@dataclass
class RunView:
    m: object            # the architecture's Dims (arch/)
    peaks: object        # peaks.Peaks
    slots: int
    rec: object          # driver.Recorder
    window: object       # driver.Window
    trace: object = None  # trace.Reduced, traced runs only

    def ops(self, *kinds):
        """Harness-observed executions of these kinds that ended inside
        the window."""
        w = self.window
        return [op for op in self.rec.ops
                if op.kind in kinds and w.t_open <= op.t1 < w.t_close]

    def device_seconds(self, op) -> float | None:
        """Device time of the run of ``op``'s executable that ``op``
        launched, or None when there is no such run (``_matches``)."""
        return self._matches(op.kind).get(id(op))

    def _matches(self, kind: str) -> dict:
        """The window's ops of one kind, in order, each paired with the run
        of its executable whose start lies nearest the op's start (plus
        the offset between the clocks), at most half of the op's host time
        away.  The device's clock in the trace can sit off the host's and
        drift over a window, so the offset is first estimated from the
        ops and runs in order (``_offset``), then taken anew from each
        pair matched."""
        cache = self.__dict__.setdefault("_match_cache", {})
        if kind in cache:
            return cache[kind]
        tr, out = self.trace, {}
        runs = tr.calls.get(MODULE_OF[kind], []) if tr is not None else []
        ops = sorted(self.ops(kind), key=lambda o: o.t0)
        off, used = self._offset(ops, runs), -1
        for op in ops:
            lo = op.t0 + off
            i = bisect.bisect_left(runs, (lo,))
            near = [j for j in (i - 1, i) if used < j < len(runs)]
            if not near:
                continue
            j = min(near, key=lambda j: abs(runs[j][0] - lo))
            if abs(runs[j][0] - lo) > 0.5 * (op.t1 - op.t0):
                continue
            out[id(op)] = runs[j][1] - runs[j][0]
            off, used = runs[j][0] - op.t0, j
        cache[kind] = out
        return out

    def _offset(self, ops, runs, head: int = 64, shifts: int = 3) -> float:
        """Trace time minus host time at the window's start.  Each op
        launched one run, so the first ops and the first runs correspond
        in order, up to a few at the window's edge: of the pairings
        shifted by up to ``shifts`` places, the one whose start differences
        agree best gives their median.  Without ops or runs, the window
        span's offset."""
        off = (self.trace.window[0] - self.window.t_open
               if self.trace is not None else 0.0)
        best = None
        for s in range(-shifts, shifts + 1):
            d = sorted(runs[k + s][0] - op.t0
                       for k, op in enumerate(ops[:head])
                       if 0 <= k + s < len(runs))
            if len(d) < 2:
                continue
            # ties (ops evenly spaced) go to the offset nearest the span's
            key = (round(d[3 * len(d) // 4] - d[len(d) // 4], 6),
                   abs(d[len(d) // 2] - off))
            if best is None or key < best[0]:
                best = (key, d[len(d) // 2])
        return off if best is None else best[1]

    def match_counts(self) -> dict:
        """kind -> (ops in the window, ops matched to a device run)."""
        return {k: (len(self.ops(k)), len(self._matches(k)))
                for k in MODULE_OF}

    def roofline(self, kinds, work_of) -> float | None:
        """Least time over device time, in %, over the window's runs of
        these kinds; None without a trace, without runs, or when under
        nine in ten runs can be matched to a device execution."""
        if self.trace is None:
            return None
        ops = self.ops(*kinds)
        pairs = [(op, self.device_seconds(op)) for op in ops]
        pairs = [(op, t) for op, t in pairs if t is not None]
        if not ops or len(pairs) < 0.9 * len(ops):
            return None
        p = self.peaks
        least = sum(work_of(op).seconds(p.bf16_flops, p.hbm_bytes_per_s)
                    for op, _ in pairs)
        return 100.0 * least / sum(t for _, t in pairs)
