"""The work each executable needs, counted from shapes.

These count what the algorithm needs, whatever implements it, so that a
roofline share is a share of the least time the chip could take: the
weights and the KV a call must read and write once each, the FLOPs of the
real tokens only, no padding.  A padded wave or a gather of the whole
block table therefore shows as a low share, and a kernel that reads only
live blocks raises it without changing the count.  What a call of each
kind needs depends on the architecture, whose module counts it
(``arch.<model_type>.decode_step`` and ``prefill``; ``arch/qwen2.py``
says what a dense decoder counts).
"""
from __future__ import annotations

from dataclasses import dataclass

from chipbench import arch

BF16 = 2
F32 = 4


@dataclass(frozen=True)
class Work:
    flops: float
    byts: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.byts + other.byts)

    def seconds(self, peak_flops: float, peak_bytes_per_s: float) -> float:
        """The least time: the larger of the compute and memory bounds."""
        return max(self.flops / peak_flops, self.byts / peak_bytes_per_s)


def decode_step(m, ctx: list) -> Work:
    """One decode step; ``ctx`` holds each active slot's context read,
    the new position included."""
    return arch.of(m).decode_step(m, ctx)


def prefill(m, lens: list) -> Work:
    """One prefill call over prompts of ``lens`` real tokens each."""
    return arch.of(m).prefill(m, lens)
