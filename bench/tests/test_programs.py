"""The readers of the program's own names, on a trace recorded on a TPU v5e
by ``record_probe_spans.py``: three engine steps of smollm-135m cut to two
layers, with a whole prefill, three chunks, a block append and two lane
resets (``bench/tests/data/probe_spans.xplane.pb``)."""
from pathlib import Path

import pytest

from bench import programs as P
from bench import trace as T

DATA = Path(__file__).parent / "data" / "probe_spans.xplane.pb"


@pytest.fixture(scope="module")
def probe():
    tr = T.load(str(DATA))
    lo, hi = T.window_of(tr)
    return tr, P.program_spans(str(DATA)), {"trace": tr, "lo": lo, "hi": hi}


def test_programs_are_found_by_their_stable_names(probe):
    tr, _, ctx = probe
    runs = P.module_runs(tr, ctx["lo"], ctx["hi"])
    assert {k: c for k, (c, _) in runs.items() if k.startswith("jit_serve_")} == {
        "jit_serve_prefill_paged": 1, "jit_serve_prefill_chunk": 2,
        "jit_serve_prefill_chunk_final": 1, "jit_serve_decode": 3,
        "jit_serve_append_block": 1, "jit_serve_reset_lane": 2}


def test_decode_ms(probe):
    # the three decode runs took 310,291 + 310,257 + 310,094 ns
    assert P.decode_ms(probe[2]) == pytest.approx((310291 + 310257 + 310094) / 3e6, abs=1e-9)


def test_prefill_ms_per_step(probe):
    # prefill_paged 200,263, chunks 127,628 and 127,644, final chunk 205,762 ns
    assert P.prefill_ms_per_step(probe[2]) == pytest.approx(
        (200263 + 127628 + 127644 + 205762) / 3e6, abs=1e-9)


def test_pool_upkeep_ms(probe):
    # one append_block of 6,284 ns and two reset_lane of 5,245 and 5,246 ns
    assert P.pool_upkeep_ms(probe[2]) == pytest.approx((6284 + 5245 + 5246) / 3e6, abs=1e-9)


def test_readers_are_silent_without_the_decode_program(probe):
    tr, _, ctx = probe
    bare = T.Trace(devices=tr.devices, spans=tr.spans,
                   modules={d: [o for o in ms if "serve_decode" not in o.name]
                            for d, ms in tr.modules.items()})
    ctx = {**ctx, "trace": bare}
    assert P.decode_ms(ctx) is None
    assert P.prefill_ms_per_step(ctx) is None
    assert P.pool_upkeep_ms(ctx) is None


def test_spans_carry_their_request_and_step_stats(probe):
    _, spans, _ = probe
    named = [(n, a) for n, _, _, a in spans]
    assert ("serve.prefill", {"uid": 2, "prompt": 30}) in named
    assert [a["start"] for n, a in named if n == "serve.prefill_chunk" and a] == [0, 32, 64]
    assert [(a["lanes"], a["attend_blocks"]) for n, a in named if n == "serve.dispatch"] == [
        (1, 4), (1, 4), (2, 8)]
    assert [a["admitted"] for n, a in named if n == "serve.admit"] == [2, 0, 0]


def test_host_idle_ms_per_step(probe):
    tr, spans, ctx = probe
    lo, hi = ctx["lo"], ctx["hi"]
    per_step, split = P.host_idle_ms_per_step(tr, spans, lo, hi)
    # by hand from the idle attribution: 4,243,382 (admit) + 4,122,204
    # (prefill) + 7,057,478 (prefill_chunk) + 1,177,121 (grow) + 2,722,512
    # (dispatch) + 4,027,153 (sync) + 1,604,255 (emit) ns over 3 syncs
    assert per_step == pytest.approx(24954105 / 3e6, abs=1e-6)
    assert split["serve.prefill_chunk"] == pytest.approx(7057478 / 3e6, abs=1e-6)
    # the serve spans sit inside the harness's step spans and hold 98.7%
    # of the idle the harness gives them
    harness = P.idle_by_span(tr, tr.spans, lo, hi)
    step_idle = harness["engine.step_begin"] + harness["engine.step_finish"]
    assert per_step * 3e-3 / step_idle == pytest.approx(0.98723, abs=1e-5)


def test_host_idle_is_none_without_a_sync(probe):
    tr, spans, ctx = probe
    no_sync = [s for s in spans if s[0] != "serve.sync"]
    assert P.host_idle_ms_per_step(tr, no_sync, ctx["lo"], ctx["hi"]) == (None, {})


def test_op_time_by_program_adds_up_to_the_op_time(probe):
    tr, _, ctx = probe
    by = P.op_seconds_by_program(tr, ctx["lo"], ctx["hi"])
    assert "none" not in by  # every op of the probe ran inside a program
    ops = T.op_seconds(tr.devices["/device:TPU:0"], ctx["lo"], ctx["hi"])
    for kind, s in ops.items():
        assert sum(d.get(kind, 0.0) for d in by.values()) == pytest.approx(s, abs=1e-12)
    assert by["jit_serve_decode"]["copy"] == pytest.approx(577161e-9, abs=1e-12)


def test_step_gaps_split_by_what_the_step_did():
    # (end s, decoding lanes, blocks appended, prompt work) per step
    steps = [(0.0, 4, 0, 0), (0.2, 4, 0, 0), (0.42, 4, 1, 0), (0.62, 4, 0, 0),
             (0.92, 2, 0, 1), (1.12, 4, 0, 0)]
    got = P.step_gaps(steps)
    assert got["steps"] == 5
    assert got["plain"] == {"steps": 3, "median_ms": pytest.approx(200.0)}
    assert got["append"] == {"steps": 1, "median_ms": pytest.approx(220.0)}
    assert got["prompt"] == {"steps": 1, "median_ms": pytest.approx(300.0)}
    # gaps 200 ×4 lanes ×3, 220 ×4, 300 ×2: the lane-weighted median is 200
    assert got["tbt_like_p50_ms"] == pytest.approx(200.0)
    assert P.step_gaps(steps[:1]) == {}
