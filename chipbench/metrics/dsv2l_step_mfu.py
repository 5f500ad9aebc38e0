"""Whole step: ``step_mfu`` for the dsv2l cells: the model FLOPs of the real
prompt and output tokens served in the window (``work.prefill`` and
``work.decode_step``, the latent forms and the held experts of
``arch/deepseek_v2.py``), over the window's length times the chip's bf16
peak."""
from chipbench import work


def read(view):
    w, m = view.window, view.m
    flops = sum(work.prefill(m, op.lens).flops
                for op in view.ops("wave", "refill"))
    flops += sum(work.decode_step(m, op.lens).flops
                 for op in view.ops("decode"))
    if flops <= 0:
        return None
    return 100.0 * flops / ((w.t_close - w.t_open) * view.peaks.bf16_flops)
