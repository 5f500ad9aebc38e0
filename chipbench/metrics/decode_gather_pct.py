"""Kernels: device time of the decode executable's operations under the
``kv_gather`` scope, over that executable's device time in the traced
window.  None where the scope map knows under 90% of the operations'
time.  Needs the device trace's ``XLA Ops`` line and the executable's HLO
(``program_trace.py``)."""
from chipbench.view import DECODE


def read(view):
    from chipbench.program_trace import scope_seconds

    ops, scope_of = getattr(view, "decode_ops", None), \
        getattr(view, "scope_of", None)
    tr = view.trace
    if not ops or not scope_of or tr is None or DECODE not in tr.modules:
        return None
    by_scope, covered = scope_seconds(ops, scope_of)
    if covered < 0.9:
        return None
    return 100.0 * by_scope.get("kv_gather", 0.0) / tr.modules[DECODE][0]
