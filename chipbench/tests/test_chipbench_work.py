"""Work counts of the roofline and MFU readers against hand counts."""
import numpy as np
import pytest

from chipbench import model, peaks, work
from chipbench.arch import qwen2
from chipbench.driver import Op, Recorder, Window
from chipbench.trace import Reduced
from chipbench.view import RunView

Q4B = qwen2.dims(model.load_config("qwen1.5-4b"))
Q7B = qwen2.dims(model.load_config("qwen2-7b.pp4"))


def test_parameter_counts():
    # Qwen1.5-4B: per layer q,k,v,o 4 x 2560^2, q/k/v biases 3 x 2560,
    # SwiGLU 3 x 2560 x 6912, two norms; untied embedding and head
    layer = 4 * 2560 ** 2 + 3 * 2560 + 3 * 2560 * 6912 + 2 * 2560
    assert Q4B.params == 40 * layer + 2 * 151936 * 2560 + 2560
    assert Q4B.params == 3_950_369_280
    # Qwen2-7B, 7 of 28 layers: q,o 3584^2 each, k,v 3584 x 512 each
    layer = (2 * 3584 ** 2 + 2 * 3584 * 512 + 3584 + 2 * 512
             + 3 * 3584 * 18944 + 2 * 3584)
    assert Q7B.params == 7 * layer + 2 * 152064 * 3584 + 3584
    assert Q7B.params == 2_721_402_880
    assert Q7B.kv_bytes_per_token == 7 * 4 * 128 * 2 * 2 == 14336
    assert Q4B.kv_bytes_per_token == 40 * 20 * 128 * 2 * 2 == 409600


@pytest.mark.parametrize("m", [Q4B, Q7B], ids=["qwen1.5-4b", "qwen2-7b.pp4"])
def test_decode_step_work_by_hand(m):
    ctx = [10, 20]
    proj = m.d * m.heads * m.head_dim * 2 + m.d * m.kv_heads * m.head_dim * 2
    matmul = m.layers * (proj + 3 * m.d * m.d_ff) + m.d * m.vocab
    flops = 2 * matmul * 2 + 4 * m.layers * m.heads * m.head_dim * 30
    weights = 2 * (m.params - m.vocab * m.d)   # all but the embedding
    byts = weights + 2 * 2 * m.d + 30 * m.kv_bytes_per_token \
        + 4 * 2 * m.vocab
    w = work.decode_step(m, ctx)
    assert w.flops == pytest.approx(flops, rel=1e-12)
    assert w.byts == pytest.approx(byts, rel=1e-12)


@pytest.mark.parametrize("m", [Q4B, Q7B], ids=["qwen1.5-4b", "qwen2-7b.pp4"])
def test_prefill_work_by_hand(m):
    lens = [3, 5]
    proj = m.d * m.heads * m.head_dim * 2 + m.d * m.kv_heads * m.head_dim * 2
    per_token = m.layers * (proj + 3 * m.d * m.d_ff)
    pairs = 3 * 4 / 2 + 5 * 6 / 2
    flops = 2 * per_token * 8 + 2 * m.d * m.vocab * 2 \
        + 4 * m.layers * m.heads * m.head_dim * pairs
    byts = 2 * (m.params - m.vocab * m.d) + 2 * 8 * m.d \
        + 8 * m.kv_bytes_per_token + 4 * 2 * m.vocab
    w = work.prefill(m, lens)
    assert w.flops == pytest.approx(flops, rel=1e-12)
    assert w.byts == pytest.approx(byts, rel=1e-12)


def _view(ops, calls=None):
    rec = Recorder(engine=None)
    rec.ops = ops
    win = Window(t_open=100.0, t_close=102.0, sent=[], sent_before=[],
                 lag=[])
    red = None
    if calls is not None:
        red = Reduced(window=(5.0, 7.0), window_s=2.0, busy_s=1.0,
                      calls=calls)
    return RunView(m=Q4B, peaks=peaks.peaks("TPU v5 lite"), slots=8,
                   rec=rec, window=win, trace=red)


def test_step_mfu_counts_the_window_only():
    from chipbench.run import reader

    ops = [Op("decode", 100.1, 100.2, [10, 20]),
           Op("refill", 100.3, 100.4, [7]),
           Op("decode", 99.0, 99.5, [5]),     # before the window
           Op("decode", 101.9, 102.5, [5])]   # ends after it
    flops = work.decode_step(Q4B, [10, 20]).flops + \
        work.prefill(Q4B, [7]).flops
    got = reader("step_mfu")(_view(ops))
    assert got == pytest.approx(100 * flops / (2.0 * 197e12))


def test_decode_roofline_matches_each_step_to_its_device_run():
    from chipbench.run import reader

    # host window opens at 100.0, the trace's at 5.0: offset -95
    ops = [Op("decode", 100.1, 100.2, [10, 20]),
           Op("decode", 100.3, 100.4, [11, 21])]
    # the device's clock may sit a little off the host's: 5.095 < 5.1
    calls = {"jit_decode_paged": [(5.095, 5.135), (5.31, 5.36)]}
    p = peaks.peaks("TPU v5 lite")
    least = sum(work.decode_step(Q4B, op.lens).seconds(
        p.bf16_flops, p.hbm_bytes_per_s) for op in ops)
    got = reader("decode_roofline")(_view(ops, calls))
    assert got == pytest.approx(100 * least / (0.04 + 0.05))
    # a step whose device run cannot be found leaves the metric silent
    assert reader("decode_roofline")(_view(ops, {"jit_decode_paged": [
        (5.11, 5.15), (5.9, 5.95)]})) is None
    assert reader("decode_roofline")(_view(ops)) is None


def test_decode_roofline_follows_a_drifting_device_clock():
    """The trace's clock sits 40 ms off what the window span says, more
    than a step's tolerance, and drifts by 4 ms a step: every step is
    still matched to its own run."""
    from chipbench.run import reader

    gaps = 0.1 + np.random.default_rng(0).uniform(0.0, 0.1, 11)
    t0 = 100.0 + np.cumsum([0.0, *gaps])
    ops = [Op("decode", t, t + 0.05, [10, 20]) for t in t0]
    runs = [(t - 95.0 + 0.04 + 0.004 * k, t - 95.0 + 0.07 + 0.004 * k
             + 0.0005 * k) for k, t in enumerate(t0)]
    view = _view(ops, {"jit_decode_paged": runs})
    assert [view.device_seconds(op) for op in ops] == pytest.approx(
        [e - s for s, e in runs])
    assert reader("decode_roofline")(view) is not None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
