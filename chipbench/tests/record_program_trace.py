"""Record the scoped trace fixture of ``test_chipbench_program.py`` on the
chip.

    python3 chipbench/tests/record_program_trace.py

Serves the tiny configuration of ``tiny.py`` on the TPU for a third of a
second with the profiler and the program's wall-clock tracer on, and
writes beside this file ``data/tiny_chip_scopes.xplane.pb`` (the raw
trace, with the program's host spans and the decode executable's
operations) and ``data/tiny_chip_scopes.hlo.txt`` (that executable's
compiled HLO text, which names the scope of each operation), each with
the checkout's path in source locations replaced by a placeholder.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]


def scrub(data: bytes) -> bytes:
    """The checkout's absolute path in source locations, replaced by a
    placeholder of the same length (the trace's strings keep their
    lengths, so the file stays valid)."""
    root = str(HERE.parents[1]).encode()
    mark = (b"<checkout>" + b"_" * len(root))[:len(root)]
    return data.replace(root, mark)


def main() -> int:
    from chipbench import program_trace, run
    from chipbench.tests import tiny

    run.enable_compile_cache()
    out = HERE / "data"
    out.mkdir(exist_ok=True)

    def keep(xplane, hlo):
        with open(xplane, "rb") as f:
            (out / "tiny_chip_scopes.xplane.pb").write_bytes(scrub(f.read()))
        (out / "tiny_chip_scopes.hlo.txt").write_bytes(
            scrub(hlo.encode()))

    res, _ = program_trace.profile_cell(tiny.spec("closed"), seed=3,
                                        seconds=0.3, keep=keep)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
