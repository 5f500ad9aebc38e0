"""A traced run that also reads the program's own tracer and the decode
executable's operations.

    python3 chipbench/program_trace.py --workload <name> --seconds <s> \
        --runs on:<seed>,off:<seed>,...

Each run is ``run.run_cell(trace=True)``.  In an ``on`` run a wall-clock
``repro.obs.Tracer`` is attached to the program's scheduler before the
warm-up, so the program's host spans (``engine.decode.*``,
``engine.refill.*``, ``scheduler.step``, ...) land in the profiler trace
beside the harness's, the program stamps each request's lifecycle on the
host clock, and each decode step's ``engine.decode`` span carries the KV
blocks it addresses and the blocks that hold live context.  After the
window, outside the timed part, the decode executable's compiled HLO text
names the ``jax.named_scope`` of each of its instructions, and the device
trace's ``XLA Ops`` line gives each instruction's device time.

Beside the cell's own per-layer metrics the run reads ``PROGRAM_METRICS``
(``metrics/<name>.py``) from a ``ProgramView``, and prints
``compiles_by_span``: the compile events inside the window, each charged to
the innermost program span open when it fired.  The result also holds
the decode executable's device seconds by scope (``decode_scopes``), its
costliest instructions (``decode_top_ops``), and the device's idle time
per decode step by the innermost host span it overlaps
(``idle_ms_per_decode_step``).  An ``off`` run attaches no tracer: the
pair gives the tracer's cost.  Each run prints one JSON object on a line
of standard output.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import re
import sys
import time
from collections import Counter, defaultdict
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.view import DECODE, RunView  # noqa: E402

PROGRAM_METRICS = (("decode_host_ms", "ms"), ("decode_gather_pct", "%"),
                   ("kv_read_live_pct", "%"), ("seat_wait_ms_p90", "ms"))
# the jax.named_scope names inside the decode step (models/transformer.py,
# models/layers.py)
SCOPES = ("kv_gather", "attention", "kv_append", "mlp", "lm_head",
          "attn_proj")
OPS_LINE = "XLA Ops"
OUTSIDE = "outside program spans"

_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_BODY = re.compile(r"body=%([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
_REF = re.compile(r"%([\w.\-]+)")
_ARRAY = re.compile(r"^[a-z]\w*\[([\d,]*)\]")
# operations that only move or reshape data: an unscoped one takes the
# scope of the data it moves (see ``scope_map``)
_MOVES = {"copy", "bitcast", "reshape", "transpose", "slice",
          "dynamic-slice", "copy-start", "copy-done", "fusion", "convert",
          "broadcast", "pad"}


@dataclass
class ProgramView(RunView):
    """A ``RunView`` that also holds what the program recorded: its
    wall-clock tracer, the host spans of the profiler trace, and the decode
    executable's operations with the scope of each."""
    tracer: object = None      # repro.obs.Tracer on the wall clock
    host: list = None          # [(span, start_s, end_s)] on the trace clock
    decode_ops: list = None    # [(instruction, start_s, end_s)]
    scope_of: dict = None      # instruction -> scope ("" for none)


def instruction(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclass
class _Hlo:
    """An HLO module's instructions, each with its computation, opcode,
    operands, op_name (None without metadata), size and text; its users;
    each computation's instructions, parameters, root and caller."""
    op_name: dict
    where: dict
    opcode: dict
    operands: dict
    index: dict
    elements: dict
    text: dict
    users: dict
    members: dict
    params: dict
    root: dict
    caller: dict
    fused: dict
    body: dict


def _split(rest: str):
    """``<shape> <opcode>(<operands>)<attributes>`` -> (shape, opcode,
    operands, attributes); a tuple shape is parenthesised."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    shape_end = rest.index(" ", i)
    shape, tail = rest[:shape_end], rest[shape_end + 1:]
    op_end = tail.index("(")
    depth, j = 0, op_end
    for j in range(op_end, len(tail)):
        depth += (tail[j] == "(") - (tail[j] == ")")
        if depth == 0:
            break
    return shape, tail[:op_end], tail[op_end + 1:j], tail[j + 1:]


def _parse_hlo(hlo_text: str) -> _Hlo:
    h = _Hlo(*({} for _ in range(14)))
    h.users, h.members = defaultdict(list), defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(1)
        shape, opcode, args, attrs = _split(line[m.end():])
        op = _OP_NAME.search(attrs)
        h.op_name[name] = op.group(1) if op else None
        h.where[name], h.opcode[name], h.text[name] = comp, opcode, \
            line.strip()
        h.operands[name] = _REF.findall(args)
        a = _ARRAY.match(shape)
        h.elements[name] = math.prod(
            int(d) for d in a.group(1).split(",") if d) if a else 0
        h.members[comp].append(name)
        if opcode == "parameter":
            h.params.setdefault(comp, {})[int(args)] = name
        if line.lstrip().startswith("ROOT "):
            h.root[comp] = name
        k = _INDEX.search(attrs)
        if opcode == "get-tuple-element" and k is not None:
            h.index[name] = int(k.group(1))
        for c in _CALLED.findall(attrs):
            h.caller.setdefault(c, name)
        cl = _CALLS.search(attrs)
        if cl is not None:
            h.fused[name] = cl.group(1)
        b = _BODY.search(attrs)
        if opcode == "while" and b is not None:
            h.body[name] = b.group(1)
    for name, ops in h.operands.items():
        for pos, o in enumerate(ops):
            h.users[o].append((name, pos))
    return h


def scope_map(hlo_text: str, scopes=SCOPES) -> dict:
    """Instruction name -> the part of the decode step it works for: one
    of ``scopes``, several joined by "+" where the data it moves serves
    several, or "" where none is found.

    An instruction takes the innermost of ``scopes`` in its op_name
    metadata.  A fusion whose op_name names none takes the scope most of
    its fused instructions carry.  An instruction with no metadata, such
    as the body of a loop XLA makes from a scatter, takes the scope of the
    instruction that calls its computation.  Failing these, an operation
    that only moves data (a copy XLA inserts to change a layout, a slice,
    a bitcast, ...) takes the scope of the operations that produced the
    data it moves (its largest operand), and failing that, of the
    operations that use its result; both walks pass through other such
    moves, tuples, and the carried values of ``while`` loops.  So the copy
    of a gathered view is ``kv_gather``'s, and a copy of the whole KV pool
    into the layer loop's layout is shared by the gather and the append
    that use it there (``kv_append+kv_gather``)."""
    h = _parse_hlo(hlo_text)
    inner, out = {}, {}

    def own(name: str) -> str:
        if name not in inner:
            inner[name] = ""           # a cycle reads as no scope
            parts = (h.op_name[name] or "").split("/")
            s = next((p for p in reversed(parts) if p in scopes), "")
            if not s and name in h.fused:
                votes = Counter(own(n) for n in h.members[h.fused[name]])
                votes.pop("", None)
                s = votes.most_common(1)[0][0] if votes else ""
            inner[name] = s
        return inner[name]

    def tagged(name: str) -> str:
        """``own``, or for no metadata, the calling instruction's."""
        s = own(name)
        up = h.caller.get(h.where[name])
        if not s and h.op_name[name] is None and up is not None:
            s = tagged(up)
        return s

    def source(name: str, k, seen) -> set:
        """Scopes of the instructions that produced value ``name`` (its
        element ``k`` where it is a tuple)."""
        if (name, k) in seen or name not in h.opcode:
            return set()
        seen.add((name, k))
        op, args = h.opcode[name], h.operands[name]
        if op == "get-tuple-element":
            return source(args[0], h.index.get(name), seen)
        if op == "tuple":
            return source(args[k], None, seen) if k is not None \
                and k < len(args) else set()
        if op == "while":
            r = h.root.get(h.body.get(name))
            return source(r, k, seen) if r else set()
        if op == "parameter":
            loop = h.caller.get(h.where[name])
            if loop is None or h.opcode[loop] != "while":
                return set()
            return (source(h.operands[loop][0], k, seen)
                    | source(h.root[h.where[name]], k, seen))
        s = tagged(name)
        if s:
            return {s}
        if op not in _MOVES or not args:
            return set()
        big = max(args, key=lambda a: h.elements.get(a, 0))
        return source(big, None, seen)

    def sink(name: str, k, seen) -> set:
        """Scopes of the instructions that use value ``name`` (its
        element ``k`` where it is a tuple)."""
        if (name, k) in seen:
            return set()
        seen.add((name, k))
        got = set()
        for user, pos in h.users.get(name, ()):
            op = h.opcode[user]
            if op == "get-tuple-element":
                if k is None or h.index.get(user) == k:
                    got |= sink(user, None, seen)
            elif op == "tuple":
                got |= sink(user, pos, seen)
            elif op == "while":
                p = h.params.get(h.body.get(user), {}).get(0)
                if p is not None:
                    got |= sink(p, k, seen)
            else:
                s = tagged(user)
                if s:
                    got.add(s)
                elif op in _MOVES:
                    got |= sink(user, None, seen)
        comp = h.where.get(name)
        if h.root.get(comp) == name:          # a loop body's result
            loop = h.caller.get(comp)
            if loop is not None and h.opcode[loop] == "while":
                got |= sink(loop, k, seen)
        return got

    def resolve(name: str) -> str:
        s = tagged(name)
        if s or h.opcode[name] not in _MOVES:
            return s
        got = source(name, None, set()) - {""}
        if not got:
            got = sink(name, None, set()) - {""}
        return "+".join(sorted(got))

    for name in h.op_name:
        out[name] = resolve(name)
    return out


def scoped(scope: str, scopes=SCOPES) -> bool:
    """Whether ``scope`` (as ``scope_map`` gives it) names parts of
    ``scopes`` only."""
    return bool(scope) and all(p in scopes for p in scope.split("+"))


def top_ops(events, hlo_text: str, steps: int, n: int = 12) -> list:
    """The ``n`` instructions with the most device time, each as
    [name, ms per step, scope, op_name (its own or its caller's), HLO
    text]: what a scope's time is made of."""
    h = _parse_hlo(hlo_text)
    scope_of = scope_map(hlo_text)
    secs = defaultdict(float)
    for name, sec in self_seconds(events):
        secs[name] += sec
    out = []
    for name, sec in sorted(secs.items(), key=lambda kv: -kv[1])[:n]:
        up, op = name, h.op_name.get(name)
        while op is None and up in h.where and h.where[up] in h.caller:
            up = h.caller[h.where[up]]
            op = h.op_name.get(up)
        out.append([name, 1e3 * sec / max(steps, 1),
                    scope_of.get(name), op, h.text.get(name, "")[:160]])
    return out


def self_seconds(events) -> list:
    """[(instruction, seconds)]: each event's time less the time of the
    events nested in it (a ``while`` holds its body's operations)."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    own = [e - s for _, s, e in evs]
    stack = []
    for k, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(k)
    return [(evs[k][0], own[k]) for k in range(len(evs))]


def scope_seconds(events, scope_of: dict):
    """({scope: device seconds}, share of the seconds whose instruction
    the map puts under the decode step's parts, ``SCOPES``)."""
    out, known, total = defaultdict(float), 0.0, 0.0
    for name, sec in self_seconds(events):
        total += sec
        s = scope_of.get(name, "")
        out[s] += sec
        if scoped(s):
            known += sec
    return dict(out), (known / total if total > 0 else 0.0)


def idle_by_span(gaps, spans) -> dict:
    """Seconds of the idle ``gaps`` by the innermost host span open over
    each part of each gap (by overlap: a gap that spans several spans is
    split between them, where ``trace.attribute`` gives it all to the
    span open at its middle)."""
    from chipbench.trace import NO_SPAN

    marks = [(s, 1, n) for n, s, _ in spans] + \
        [(e, 0, n) for n, _, e in spans] + \
        [(a, 2, None) for a, _ in gaps] + [(b, -1, None) for _, b in gaps]
    marks.sort(key=lambda x: (x[0], x[1]))
    out, stack, idle, t = defaultdict(float), [], False, None
    for at, kind, name in marks:
        if idle and t is not None and at > t:
            out[stack[-1] if stack else NO_SPAN] += at - t
        t = at
        if kind == 1:
            stack.append(name)
        elif kind == 0:
            for k in range(len(stack) - 1, -1, -1):
                if stack[k] == name:
                    del stack[k]
                    break
        else:
            idle = kind == 2
    return dict(out)


def load_ops(path: str, module: str, window: tuple) -> list:
    """The ``XLA Ops`` events of the first TPU device that lie inside runs
    of ``module`` and inside ``window`` (trace clock, seconds)."""
    from jax.profiler import ProfileData

    from chipbench.trace import MODULES_LINE, module_name

    lo, hi = window
    for plane in ProfileData.from_file(path).planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        runs = [(e.start_ns, e.start_ns + e.duration_ns)
                for e in lines.get(MODULES_LINE, ())
                if module_name(e.name) == module
                and lo <= e.start_ns * 1e-9 < hi]
        if not runs:
            continue
        runs.sort()
        out, j = [], 0
        for e in sorted(lines.get(OPS_LINE, ()), key=lambda e: e.start_ns):
            while j < len(runs) and runs[j][1] <= e.start_ns:
                j += 1
            if j < len(runs) and runs[j][0] <= e.start_ns:
                out.append((instruction(e.name), e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9))
        return out
    return []


def decode_hlo(eng) -> str:
    """The compiled HLO text of the engine's decode executable, lowered at
    the shapes it serves (a compile-cache hit after the window)."""
    import jax.numpy as jnp

    pcache = dict(eng.pages,
                  tables=jnp.zeros((eng.slots, eng.table_width), jnp.int32),
                  lens=jnp.zeros((eng.slots,), jnp.int32))
    return eng._decode_fn.lower(eng.params, eng._last_tok,
                                pcache).compile().as_text()


class CompilesBySpan:
    """A ``jax.monitoring`` listener that notes each compile event with the
    host time and the innermost program span open when it fired."""

    def __init__(self, tracer):
        import jax

        from chipbench.run import _COMPILE_EVENTS

        self.tracer, self.names, self.seen = tracer, _COMPILE_EVENTS, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.names:
            spans = self.tracer.open_spans
            self.seen.append((time.perf_counter(),
                              spans[-1] if spans else OUTSIDE))

    def counts(self, lo: float, hi: float) -> dict:
        return dict(Counter(s for t, s in self.seen if lo <= t < hi))

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def host_spans_per_step(tracer, lo: float, hi: float) -> float | None:
    """Program host spans opened inside [lo, hi) per decode step there."""
    opened = [ev["name"] for ev in tracer.events
              if ev["ph"] == "B" and ev["group"] == "host"
              and lo <= ev["t"] < hi]
    steps = opened.count("engine.decode")
    return len(opened) / steps if steps else None


def profile_cell(spec: dict, *, seed: int, seconds: float,
                 program_tracer: bool = True, require_chip: bool = True,
                 log=print, keep=None):
    """One traced run of a cell (``run.run_cell``), with the program's
    wall-clock tracer attached when ``program_tracer``.  Returns the
    result, which holds the cell's metrics and those of
    ``PROGRAM_METRICS`` that found something to read and ``e2e`` the run's
    end-to-end numbers, and the ``ProgramView`` they were read from.
    ``keep(xplane_path, hlo_text)`` sees the raw trace before it is
    deleted (the trace fixture is recorded so)."""
    from chipbench import arch, run
    from chipbench import trace as trace_mod
    from repro.obs import Tracer, WallClock

    box, lines = {}, []

    def tamper(served):
        if program_tracer:
            tracer = Tracer(clock=WallClock())
            served.sched.attach_tracer(tracer)
            box["tracer"] = tracer
            box["compiles"] = CompilesBySpan(tracer)

    def collect(xplane, rec, win):
        tr = trace_mod.load(xplane)
        box.update(trace=tr, rec=rec, win=win,
                   ops=load_ops(xplane, DECODE, tr.window))
        box["hlo"] = decode_hlo(rec.engine) if box["ops"] else ""
        if keep is not None:
            keep(xplane, box["hlo"])

    def log_both(line):
        lines.append(line)
        log(line)

    res = run.run_cell(spec, seed=seed, seconds=seconds, trace=True,
                       require_chip=require_chip, tamper=tamper,
                       keep=collect, log=log_both)
    rec, win, tracer = box["rec"], box["win"], box.get("tracer")
    traced = dataclasses.replace(
        win, t_close=win.t_span_close,
        sent=[r for r in win.sent if rec.due[r] < win.t_span_close])
    view = ProgramView(
        m=arch.arch_for(spec["config"]).dims(spec["config"]), peaks=None,
        slots=int(spec["config"]["serving"]["slots"]), rec=rec,
        window=traced, trace=trace_mod.reduce(box["trace"]), tracer=tracer,
        host=box["trace"].host, decode_ops=box["ops"],
        scope_of=scope_map(box["hlo"]))
    for name, unit in PROGRAM_METRICS:
        v = run.reader(name)(view)
        if v is not None:
            res["metrics"][name] = {"value": v, "unit": unit}
    e2e = next(json.loads(x[5:]) for x in lines if x.startswith("e2e: "))
    res["e2e"] = e2e
    res["program_tracer"] = bool(program_tracer)
    steps = len(view.ops("decode"))
    if view.decode_ops:
        by_scope, cover = scope_seconds(view.decode_ops, view.scope_of)
        res["decode_scopes"] = {"seconds": by_scope, "covered": cover}
        res["decode_top_ops"] = top_ops(view.decode_ops, box["hlo"], steps)
    red = view.trace
    if red.calls and steps:
        lo, hi = red.window
        runs = [(k, a, b) for k, v in red.calls.items() for a, b in v]
        gaps = trace_mod.idle_gaps(trace_mod.busy_intervals(runs, lo, hi),
                                   lo, hi)
        spans = [x for x in view.host if x[0] != trace_mod.WINDOW_SPAN]
        res["idle_ms_per_decode_step"] = {
            k: 1e3 * v / steps for k, v in sorted(
                idle_by_span(gaps, spans).items(), key=lambda kv: -kv[1])}
    if tracer is not None:
        lo, hi = traced.t_open, traced.t_close
        res["compiles_by_span"] = box["compiles"].counts(lo, hi)
        box["compiles"].close()
        res["host_spans_per_decode_step"] = host_spans_per_step(tracer, lo,
                                                                hi)
        log_both("compiles_by_span: "
                 + json.dumps(res["compiles_by_span"]))
    return res, view


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", required=True,
                    help="comma-separated on:<seed> or off:<seed>")
    args = ap.parse_args(argv)
    from chipbench import run

    spec = run.load_spec(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    run.enable_compile_cache()
    for item in args.runs.split(","):
        mode, seed = item.split(":")
        if mode not in ("on", "off"):
            raise SystemExit(f"chipbench: a run is on:<seed> or off:<seed>, "
                             f"not {item!r}")
        try:
            res, _ = profile_cell(spec, seed=int(seed), seconds=args.seconds,
                                  program_tracer=mode == "on",
                                  log=lambda x: print(x, file=sys.stderr))
        except run.NoChip as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 1
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
