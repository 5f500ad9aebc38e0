"""Device: ``idle_pct`` for the dsv2l cells: the share of the traced window
in which no executable ran."""


def read(view):
    tr = view.trace
    if tr is None:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
