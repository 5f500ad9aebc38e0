"""Kernels: ``prefill_roofline`` for the dsv2l cells: the prefill
executables' (wave and batch-1 refill) share of their roofline, from the
real prompt tokens (``work.prefill``, the decompressed latent form of
``arch/deepseek_v2.py``) over their device time."""
from chipbench import work


def read(view):
    return view.roofline(("wave", "refill"), lambda op: work.prefill(
        view.m, op.lens))
