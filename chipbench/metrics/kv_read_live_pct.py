"""Engine and KV pool: KV blocks that hold live context over the blocks
the decode steps' tables address, summed over the steps whose
``engine.decode`` span opened in the traced window (the program's own
counts, carried on the span).  Needs the program's wall-clock tracer."""


def read(view):
    tracer = getattr(view, "tracer", None)
    if tracer is None:
        return None
    w = view.window
    addressed = live = 0
    for ev in tracer.events:
        if (ev["ph"] == "B" and ev["name"] == "engine.decode"
                and w.t_open <= ev["t"] < w.t_close):
            addressed += ev["args"]["kv_blocks_addressed"]
            live += ev["args"]["kv_blocks_live"]
    return 100.0 * live / addressed if addressed else None
