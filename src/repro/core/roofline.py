"""Three-term roofline analysis from compiled XLA artifacts.

  compute    = HLO_FLOPs / peak_FLOP/s          (per chip)
  memory     = HLO_bytes / HBM_bw               (per chip)
  collective = collective_bytes / link_bw       (per chip)

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes; collective bytes are
parsed from the post-SPMD HLO text (shapes there are per-device shards).

IMPORTANT caveat (measured, see scratch probes): XLA cost analysis counts a
``while`` (lax.scan) body ONCE, not trip-count times.  All steps here scan
over layers, so per-cell roofline terms are assembled as

  total = full_program_terms + (n_layers - 1) * layer_program_terms

with the single-layer program compiled under the same mesh/shardings.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

from . import hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LINE_RE = re.compile(
    r"=\s*(\(?[^)=]*?\)?)\s*(" + "|".join(COLLECTIVE_OPS) + r")(-start|-done)?\(")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op (per-device, post-SPMD).

    ``-done`` ops are skipped so async (start/done) pairs count once.
    """
    out = {op: {"count": 0, "bytes": 0} for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        if m.group(3) == "-done":
            continue
        op = m.group(2)
        out[op]["count"] += 1
        out[op]["bytes"] += _shape_bytes(m.group(1))
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


# header like: %name (p0: type, ...) -> ret_type {   — params may nest parens
_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{\s*$",
                      re.M)
_WHILE_RE = re.compile(r"body=%([\w\.\-]+)")
_TRIP_RE = re.compile(r"\"known_trip_count\":\{\"n\":\"(\d+)\"\}")
_COND_RE = re.compile(r"condition=%([\w\.\-]+)")
_CALL_RE = re.compile(r"\bto_apply=%?([\w\.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _split_computations(hlo_text: str) -> dict:
    """computation name -> body text (post-SPMD module)."""
    names, starts = [], []
    for m in _COMP_RE.finditer(hlo_text):
        names.append(m.group(1))
        starts.append(m.end())
    out = {}
    for i, (n, s) in enumerate(zip(names, starts)):
        e = hlo_text.index("\n}", s) if "\n}" in hlo_text[s:] else len(hlo_text)
        e = hlo_text.find("\n}", s)
        out[n] = hlo_text[s:e if e > 0 else len(hlo_text)]
    return out


def scan_aware_collectives(hlo_text: str) -> dict:
    """Collective bytes with while-loop bodies multiplied by their
    ``known_trip_count`` (XLA cost_analysis counts loop bodies once — this
    walker recovers the true per-step totals).  Returns
    {"total_bytes": ..., "by_op": {...}, "flat_bytes": plain-parse total}.
    """
    comps = _split_computations(hlo_text)
    entry = None
    m = re.search(r"^ENTRY %?([\w\.\-]+)", hlo_text, re.M)
    if m:
        entry = m.group(1)
    if entry is None or entry not in comps:
        entry = max(comps, key=lambda n: len(comps[n])) if comps else None
    memo: dict = {}

    def visit(name, stack=()):
        if name in memo:
            return memo[name]
        if name not in comps or name in stack:
            return {}
        body = comps[name]
        tot: dict = {}

        def add(d, scale=1):
            for k, v in d.items():
                tot[k] = tot.get(k, 0) + v * scale

        for line in body.splitlines():
            lm = _LINE_RE.search(line)
            if lm and lm.group(3) != "-done":
                add({lm.group(2): _shape_bytes(lm.group(1))})
            wm = _WHILE_RE.search(line)
            if wm:
                tm = _TRIP_RE.search(line)
                trip = int(tm.group(1)) if tm else 1
                add(visit(wm.group(1), stack + (name,)), trip)
                continue
            if " call(" in line or " conditional(" in line:
                cm = _CALL_RE.search(line)
                if cm:
                    add(visit(cm.group(1), stack + (name,)))
                bm = _BRANCH_RE.search(line)
                if bm:
                    branches = [visit(b.strip().lstrip("%"),
                                      stack + (name,))
                                for b in bm.group(1).split(",")]
                    if branches:
                        # conditional: take the heaviest branch
                        best = max(branches,
                                   key=lambda d: sum(d.values()) if d else 0)
                        add(best)
        memo[name] = tot
        return tot

    by_op = visit(entry) if entry else {}
    flat = parse_collectives(hlo_text)["total_bytes"]
    return {"total_bytes": sum(by_op.values()), "by_op": by_op,
            "flat_bytes": flat}


@dataclass
class RooflineTerms:
    flops: float = 0.0            # per device
    bytes_hbm: float = 0.0        # per device
    bytes_coll: float = 0.0       # per device

    def times(self):
        return {
            "compute_s": self.flops / hw.TPU_PEAK_FLOPS,
            "memory_s": self.bytes_hbm / hw.TPU_HBM_BW,
            "collective_s": self.bytes_coll / hw.TPU_ICI_BW,
        }

    def dominant(self):
        t = self.times()
        return max(t, key=t.get).replace("_s", "")

    def bound_time(self):
        return max(self.times().values())

    def add(self, other, scale: float = 1.0):
        self.flops += other.flops * scale
        self.bytes_hbm += other.bytes_hbm * scale
        self.bytes_coll += other.bytes_coll * scale
        return self


def terms_from_compiled(compiled) -> RooflineTerms:
    ca = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text())
    return RooflineTerms(
        flops=float(ca.get("flops", 0.0)),
        bytes_hbm=float(ca.get("bytes accessed", 0.0)),
        bytes_coll=float(coll["total_bytes"]),
    )


def model_flops(cfg, n_params: int, n_active: int, shape) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (prefill) / 2*N*B (decode step)."""
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # one decode step
