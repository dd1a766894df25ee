"""The trace reduction, on a small trace recorded on a TPU v5e: three
rounds of one BGMV call and one matmul, each inside the benchmark's host
spans (``bench/tests/data/probe.xplane.pb``)."""
from pathlib import Path

import pytest

from bench import readers
from bench import trace as T

DATA = Path(__file__).parent / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return T.load(str(DATA))


def test_planes_and_spans(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    assert len(tr.devices["/device:TPU:0"]) == 15
    assert [s[0] for s in tr.spans] == [
        "bench.submit", "engine.step_begin", "engine.step_finish"] * 3


def test_busy_is_the_union_of_op_intervals(tr):
    lo, hi = T.window_of(tr)
    # by hand from the event list: the 15 ops never overlap; per round
    # copy 462 + bgmv 5906/5911/6120 + copy-start 13 + copy-done 434/436/..
    # + fusion 3502/3403/.. ns, 30,930 ns in all
    assert T.busy_ns(tr.devices["/device:TPU:0"], lo, hi) == pytest.approx(30930, abs=2)
    s = T.summarize(tr, lo, hi)
    assert s["busy_s"] == pytest.approx(30930e-9, abs=2e-9)
    assert s["window_s"] == pytest.approx((67053352 - 43278131) * 1e-9, abs=1e-9)
    idle = sum(v for _, v in s["idle_gaps"])
    assert idle == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-9)


def test_kernel_time_and_shapes(tr):
    lo, hi = T.window_of(tr)
    evs = T.kernel_events(tr, "qrlora_bgmv", lo, hi)
    assert [e.end - e.start for e in evs] == [5906, 5911, 6120]
    assert T.operand_shapes(evs[0].name) == [
        ("bf16", (64, 576)), ("bf16", (64, 576)), ("bf16", (576, 576)),
        ("bf16", (576, 128)), ("bf16", (128, 576)), ("f32", (4097, 128)),
        ("s32", (64, 1))]
    ops = dict(T.summarize(tr, lo, hi)["device_ops"])
    assert ops["qrlora_bgmv"] == pytest.approx(17937e-9, abs=1e-12)


def test_idle_attributed_to_the_innermost_span():
    gaps = [(0, 10), (20, 40)]
    spans = [("engine.step_finish", 0, 30), ("bench.submit", 25, 35)]
    got = T.attribute(gaps, spans)
    assert got == {"engine.step_finish": 15, "bench.submit": 10, "untraced": 5}


def test_op_kind_strips_suffixes():
    assert T.op_kind("%fusion.12 = bf16[2]{0} fusion(...)") == "fusion"
    assert T.op_kind("%copy-start.3 = (bf16[2]) copy-start(...)") == "copy-start"


def test_bgmv_roofline_on_the_recorded_calls(tr):
    lo, hi = T.window_of(tr)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"trace": tr, "lo": lo, "hi": hi, "peaks": peaks, "log": lambda m: None}
    got = readers.bgmv_roofline(ctx)
    # each call needs 1,138,944 B (memory-bound: 1.3907 µs at 819 GB/s
    # against 0.3114 µs of operations); 3 calls took 17,937 ns
    assert got == pytest.approx(100 * 3 * 1138944 / 819e9 / 17937e-9, rel=1e-9)
    assert 0 < got < 100
