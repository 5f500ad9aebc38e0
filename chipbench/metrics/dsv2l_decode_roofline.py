"""Kernels: ``decode_roofline`` for the dsv2l cells, whose end-to-end
metric that list of cells does not name: the decode executable's share of
its roofline, from the work each step needs (``work.decode_step``, the
absorbed latent form of ``arch/deepseek_v2.py``) over its device time."""
from chipbench import work


def read(view):
    return view.roofline(("decode",), lambda op: work.decode_step(
        view.m, op.lens))
