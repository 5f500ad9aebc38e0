"""Model step: ``decode_step_ms`` for the dsv2l cells: mean device time of
one paged decode step, from the trace."""
from chipbench.view import DECODE


def read(view):
    if view.trace is None or DECODE not in view.trace.modules:
        return None
    seconds, runs = view.trace.modules[DECODE]
    return 1e3 * seconds / runs
