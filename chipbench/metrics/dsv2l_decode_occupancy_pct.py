"""Engine and KV pool: ``decode_occupancy_pct`` for the dsv2l cells: active
slots over all slots, averaged over the window's decode steps."""


def read(view):
    steps = view.ops("decode")
    if not steps:
        return None
    return 100.0 * sum(len(op.lens) for op in steps) / (len(steps) * view.slots)
