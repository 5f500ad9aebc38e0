"""The program's own tracer read by the harness: host spans inside the
harness's, wall-clock lifecycles, the decode executable's operations by
named scope, the compile counter, and the readers of ``PROGRAM_METRICS``
on hand-made views; a tiny run on the CPU and a trace recorded on the chip
(``record_program_trace.py``)."""
from pathlib import Path

import pytest

from chipbench import program_trace as pt
from chipbench import run, trace
from chipbench.driver import Recorder, Window
from chipbench.view import DECODE, RunView

DATA = Path(__file__).resolve().parent / "data"
XPLANE = DATA / "tiny_chip_scopes.xplane.pb"
HLO = DATA / "tiny_chip_scopes.hlo.txt"

DECODE_PARTS = {"engine.decode.price", "engine.decode.tables",
                "engine.decode.launch", "engine.decode.logits_to_host",
                "engine.decode.tokens_to_host", "engine.decode.bookkeeping"}


def _inside(spans, name, outer):
    """Every ``name`` span lies inside some ``outer`` span."""
    outs = [(s, e) for n, s, e in spans if n == outer]
    inner = [(s, e) for n, s, e in spans if n == name]
    return bool(inner) and all(any(a <= s and e <= b for a, b in outs)
                               for s, e in inner)


@pytest.fixture(scope="module")
def cpu_run(tmp_path_factory):
    """One traced tiny run on the CPU with the program's tracer on, with a
    trace directory of its own (other test files' traced runs may run
    beside it, under pytest-xdist)."""
    from chipbench.tests import tiny

    got = {}

    def keep(xplane, hlo):
        got["host"] = trace.load(xplane).host

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "CACHE", tmp_path_factory.mktemp("chipbench_cache"))
        res, view = pt.profile_cell(tiny.spec("closed"), seed=2**33 + 5,
                                    seconds=0.6, require_chip=False,
                                    log=lambda *a: None, keep=keep)
    return res, view, got["host"]


def test_program_spans_nest_inside_the_harness_spans(cpu_run):
    """On the profiler's host plane, the engine's sub-spans sit inside its
    phase spans, and those inside the harness's wrappers."""
    _, _, host = cpu_run
    for part in DECODE_PARTS:
        assert _inside(host, part, "engine.decode"), part
    assert _inside(host, "engine.decode", "engine.issue_decode")
    for part in ("engine.refill.prefill", "engine.refill.write_pages",
                 "engine.refill.first_token"):
        assert _inside(host, part, "engine.refill"), part
    assert _inside(host, "engine.commit", "engine.commit_op")
    assert _inside(host, "scheduler.step", "scheduler.run")


def test_wall_clock_lifecycles_are_ordered(cpu_run):
    _, view, _ = cpu_run
    lc = view.tracer.lifecycle
    done = [r for r in view.window.sent
            if any(s == "retire" for s, _, _ in lc.timeline(r))]
    assert done
    for rid in done:
        t = {s: t for s, t, _ in reversed(lc.timeline(rid))}
        assert t["submit"] <= t["prefill"] <= t["first_token"] \
            <= t["retire"]
        # the program seats a request after the harness submitted it
        assert view.rec.due[rid] <= t["prefill"]


def test_program_metrics_read_the_cpu_run(cpu_run):
    """The counters and stamps are read; the device metrics are not (a CPU
    run has no device operations to read)."""
    res, view, _ = cpu_run
    m = res["metrics"]
    assert 0 < m["kv_read_live_pct"]["value"] <= 100
    assert m["seat_wait_ms_p90"]["value"] > 0
    assert "decode_host_ms" not in m and "decode_gather_pct" not in m
    assert res["compiles_by_span"] == {}
    assert res["host_spans_per_decode_step"] >= 7


def test_compiles_are_charged_to_the_innermost_span():
    import time

    import jax
    import jax.numpy as jnp

    from repro.obs import Tracer, WallClock

    tracer = Tracer(clock=WallClock())
    counter = pt.CompilesBySpan(tracer)
    try:
        t0 = time.perf_counter()
        with tracer.span("engine.decode"):
            with tracer.span("engine.decode.launch"):
                jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0))
        jax.jit(lambda x: x - 5)(jnp.arange(9.0))
        got = counter.counts(t0, time.perf_counter())
    finally:
        counter.close()
    assert got["engine.decode.launch"] >= 1
    assert got[pt.OUTSIDE] >= 1 and "engine.decode" not in got


HLO_TEXT = """\
HloModule jit_decode_paged, is_scheduled=true

%fused_gather (param_0: bf16[8]) -> bf16[8] {
  %param_0 = bf16[8]{0} parameter(0)
  %gather.1 = bf16[8]{0} gather(%param_0), metadata={op_name="jit(decode_paged)/while/body/kv_gather/gather"}
  ROOT %convert.2 = bf16[8]{0} convert(%gather.1), metadata={op_name="jit(decode_paged)/while/body/kv_gather/convert"}
}

%outer (param_0: bf16[8]) -> bf16[8] {
  %param_0.1 = bf16[8]{0} parameter(0)
  ROOT %fusion.7 = bf16[8]{0} fusion(%param_0.1), kind=kLoop, calls=%fused_gather
}

%scatter_body (q: bf16[8]) -> bf16[8] {
  %q = bf16[8]{0} parameter(0)
  ROOT %dynamic-update-slice.12 = bf16[8]{0} dynamic-update-slice(%q, %q)
}

%body (p: (bf16[8], bf16[64])) -> (bf16[8], bf16[64]) {
  %p = (bf16[8]{0}, bf16[64]{0}) parameter(0)
  %get-tuple-element.20 = bf16[8]{0} get-tuple-element(%p), index=0
  %get-tuple-element.21 = bf16[64]{0} get-tuple-element(%p), index=1
  %dynamic-slice.22 = bf16[8]{0} dynamic-slice(%get-tuple-element.21), metadata={op_name="jit(decode_paged)/while/body/kv_gather/dynamic_slice"}
  %while.11 = bf16[8]{0} while(%dynamic-slice.22), body=%scatter_body, metadata={op_name="jit(decode_paged)/while/body/kv_gather/vmap(vmap())/scatter"}
  %fusion.3 = bf16[8]{0} fusion(%while.11), kind=kLoop, calls=%outer
  %copy.13 = bf16[8]{0} copy(%fusion.3)
  %dot.4 = bf16[8]{0} dot(%copy.13, %get-tuple-element.20), metadata={op_name="jit(decode_paged)/while/body/attention/dot_general"}
  %add.5 = bf16[8]{0} add(%dot.4, %get-tuple-element.20), metadata={op_name="jit(decode_paged)/while/body/mlp/add"}
  %scatter.23 = bf16[64]{0} scatter(%get-tuple-element.21, %add.5), metadata={op_name="jit(decode_paged)/while/body/kv_append/scatter"}
  ROOT %tuple.24 = (bf16[8]{0}, bf16[64]{0}) tuple(%add.5, %scatter.23)
}

ENTRY %main (a: bf16[8], pool: bf16[64]) -> (bf16[8], bf16[64], bf16[8]) {
  %a = bf16[8]{0} parameter(0)
  %pool = bf16[64]{0} parameter(1)
  %copy.30 = bf16[64]{0} copy(%pool)
  %tuple.31 = (bf16[8]{0}, bf16[64]{0}) tuple(%a, %copy.30)
  %while.9 = (bf16[8]{0}, bf16[64]{0}) while(%tuple.31), body=%body, metadata={op_name="jit(decode_paged)/while"}
  %get-tuple-element.32 = bf16[8]{0} get-tuple-element(%while.9), index=0
  %get-tuple-element.33 = bf16[64]{0} get-tuple-element(%while.9), index=1
  %copy.34 = bf16[64]{0} copy(%get-tuple-element.33)
  %dot.10 = bf16[8]{0} dot(%get-tuple-element.32, %a), metadata={op_name="jit(decode_paged)/lm_head/dot_general"}
  %copy.35 = bf16[8]{0} copy(%a)
  ROOT %tuple.36 = (bf16[8]{0}, bf16[64]{0}, bf16[8]{0}) tuple(%dot.10, %copy.34, %copy.35)
}
"""


def test_scope_map_reads_a_hand_made_hlo_text():
    got = pt.scope_map(HLO_TEXT)
    # a fusion with no metadata takes its fused instructions' scope, also
    # through a fusion nested in it
    assert got["fusion.3"] == got["fusion.7"] == "kv_gather"
    assert got["dot.4"] == "attention" and got["add.5"] == "mlp"
    assert got["while.9"] == "" and got["dot.10"] == "lm_head"
    # no metadata: the scope of the instruction calling the computation
    # (the loop XLA makes of a scatter)
    assert got["dynamic-update-slice.12"] == "kv_gather"
    assert pt.instruction("%fusion.3 = bf16[8]{0} fusion(%p)") == "fusion.3"


def test_scope_map_follows_the_data_a_copy_moves():
    """A copy with no scope of its own takes that of what produced its
    data, else of what uses it, through tuples and a loop's carry."""
    got = pt.scope_map(HLO_TEXT)
    # the gathered view's layout copy, from the gather
    assert got["copy.13"] == "kv_gather"
    # the pool copied into the loop's layout: sliced for the gather and
    # scattered into by the append there
    assert got["copy.30"] == "kv_append+kv_gather"
    # and copied back out of the loop, from the append
    assert got["copy.34"] == "kv_append"
    # a copy of a parameter straight to the output serves no part
    assert got["copy.35"] == ""
    assert pt.scoped("kv_append+kv_gather") and pt.scoped("mlp")
    assert not pt.scoped("") and not pt.scoped("kv_gather+other")


def test_scope_seconds_counts_each_operation_once():
    """A ``while`` event holds its body's operations: only its own time
    counts; time the map puts under no part, or an instruction the map does
    not know, lowers the coverage; time shared by parts counts as
    covered."""
    ops = [("while.9", 0.0, 10.0), ("fusion.3", 1.0, 4.0),
           ("dot.4", 4.0, 6.0), ("add.5", 6.0, 7.0),
           ("fusion.3", 7.0, 9.0), ("dot.10", 10.0, 11.0),
           ("copy.99", 11.0, 12.0), ("copy.30", 12.0, 13.0)]
    by_scope, covered = pt.scope_seconds(ops, pt.scope_map(HLO_TEXT))
    assert by_scope == pytest.approx({"": 3.0, "kv_gather": 5.0,
                                      "attention": 2.0, "mlp": 1.0,
                                      "lm_head": 1.0,
                                      "kv_append+kv_gather": 1.0})
    assert covered == pytest.approx(10.0 / 13.0)


def test_top_ops_name_each_instruction_and_its_origin():
    ops = [("while.9", 0.0, 10.0), ("fusion.3", 1.0, 4.5),
           ("dynamic-update-slice.12", 4.5, 8.5), ("dot.10", 10.0, 11.0)]
    rows = pt.top_ops(ops, HLO_TEXT, steps=2, n=2)
    assert [r[0] for r in rows] == ["dynamic-update-slice.12", "fusion.3"]
    name, ms, scope, op_name, text = rows[0]
    assert ms == pytest.approx(2000.0) and scope == "kv_gather"
    assert op_name.endswith("kv_gather/vmap(vmap())/scatter")
    assert text.startswith("ROOT %dynamic-update-slice.12 = ")


def test_idle_is_split_between_the_spans_it_overlaps():
    """A gap that runs from one host span into the next is split between
    them; the part outside every span has its own name."""
    spans = [("engine.decode", 0.0, 4.0),
             ("engine.decode.logits_to_host", 1.0, 2.0),
             ("engine.decode.tokens_to_host", 2.0, 3.0),
             ("scheduler.step", 5.0, 6.0)]
    got = pt.idle_by_span([(1.5, 2.5), (3.5, 5.5), (7.0, 8.0)], spans)
    assert got == pytest.approx({
        "engine.decode.logits_to_host": 0.5,
        "engine.decode.tokens_to_host": 0.5, "engine.decode": 0.5,
        trace.NO_SPAN: 2.0, "scheduler.step": 0.5})


def _view(**program):
    """A hand-made view of a 10 s window: two decode runs on the device,
    two decode steps of the program, two requests."""
    from repro.obs import Tracer

    tracer = Tracer()
    tracer.events = [
        {"ph": "B", "group": "host", "tid": 0, "name": "engine.decode",
         "t": t, "args": {"kv_blocks_addressed": 40, "kv_blocks_live": n}}
        for t, n in ((1.0, 10), (5.0, 20), (12.0, 40))]
    for rid, (sub, seat) in {7: (0.5, 1.5), 8: (2.0, 2.25)}.items():
        tracer.lifecycle.records[rid] = [("submit", sub, {}),
                                         ("prefill", seat, {})]
    red = trace.Reduced(window=(100.0, 110.0), window_s=10.0, busy_s=6.0,
                        modules={DECODE: [6.0, 2]},
                        calls={DECODE: [(101.0, 104.0), (105.0, 108.0)]})
    host = [("engine.issue_decode", 100.5, 101.2),
            ("engine.decode", 100.5, 101.2),
            ("engine.decode", 104.0, 105.5)]
    ops = [("fusion.3", 101.0, 102.5), ("dot.4", 102.5, 104.0),
           ("fusion.3", 105.0, 106.5), ("add.5", 106.5, 108.0)]
    fields = dict(tracer=tracer, host=host, decode_ops=ops,
                  scope_of=pt.scope_map(HLO_TEXT))
    fields.update(program)
    return pt.ProgramView(
        m=None, peaks=None, slots=4, rec=Recorder(engine=None),
        window=Window(0.0, 10.0, [7, 8], [], []), trace=red, **fields)


@pytest.mark.parametrize("name, want", [
    # idle [100, 101) and [104, 105) overlap the spans by 0.5 + 1.0 s
    ("decode_host_ms", 750.0),
    # 3 of 6 device seconds under kv_gather
    ("decode_gather_pct", 50.0),
    ("kv_read_live_pct", 100.0 * 30 / 80),
    ("seat_wait_ms_p90", 1000.0),
])
def test_program_metric_readers(name, want):
    read = run.reader(name)
    assert read(_view()) == pytest.approx(want)
    plain = RunView(m=None, peaks=None, slots=4, rec=Recorder(engine=None),
                    window=Window(0.0, 10.0, [7, 8], [], []),
                    trace=_view().trace)
    assert read(plain) is None


def test_decode_gather_pct_needs_the_scope_map_to_cover_the_ops():
    view = _view(decode_ops=[("fusion.3", 101.0, 102.0),
                             ("unknown.1", 102.0, 104.0)])
    assert run.reader("decode_gather_pct")(view) is None


def test_decode_gather_pct_is_silent_under_a_large_unscoped_copy():
    """An instruction the map knows but puts under no part (a copy of a
    parameter straight to the output) is not covered: a tenth of the time
    or more in it silences the reader."""
    ops = [("fusion.3", 101.0, 102.0), ("dot.4", 102.0, 103.0),
           ("add.5", 103.0, 104.0), ("copy.35", 104.0, 104.5)]
    assert run.reader("decode_gather_pct")(_view(decode_ops=ops)) is None
    ops[-1] = ("copy.35", 104.0, 104.3)          # 0.3 of 3.3 s: 9%
    assert run.reader("decode_gather_pct")(_view(decode_ops=ops)) \
        == pytest.approx(100.0 / 6.0)


@pytest.fixture(scope="module")
def chip():
    if not (XPLANE.exists() and HLO.exists()):
        pytest.fail(f"the recorded trace {XPLANE} is missing")
    tr = trace.load(str(XPLANE))
    return tr, pt.load_ops(str(XPLANE), DECODE, tr.window), HLO.read_text()


def test_recorded_program_spans_nest_on_the_chip(chip):
    tr, _, _ = chip
    for part in ("engine.decode.launch", "engine.decode.logits_to_host"):
        assert _inside(tr.host, part, "engine.decode")
    assert _inside(tr.host, "engine.decode", "engine.issue_decode")
    red = trace.reduce(tr)
    assert any(k.startswith("engine.decode.") for k in red.idle_by_span)


def test_recorded_scope_map_covers_the_decode_operations(chip):
    """The decode executable's recorded operations, by the scopes its HLO
    names: the map knows nine tenths of their time or more, and each of
    the decode step's scopes takes some of it."""
    tr, ops, hlo = chip
    assert ops
    by_scope, covered = pt.scope_seconds(ops, pt.scope_map(hlo))
    assert covered >= 0.9
    assert all(by_scope.get(s, 0) > 0 for s in pt.SCOPES)
    view = pt.ProgramView(m=None, peaks=None, slots=4,
                          rec=Recorder(engine=None),
                          window=Window(0.0, 1.0, [], [], []),
                          trace=trace.reduce(tr), decode_ops=ops,
                          scope_of=pt.scope_map(hlo))
    assert 0 < run.reader("decode_gather_pct")(view) < 100
