"""The comparison that decides ``correct`` fails a broken timed path.

Each test drives a whole run of a cell cut to CPU size through the
harness (past its look for a chip), with the served path broken
underneath, and sees ``correct`` come out false; the sound run beside
them comes out true."""
import numpy as np
import pytest

from bench.tests.tiny import run_tiny, tiny_cell
from repro.serving.engine import MultiTenantEngine


def test_sound_run_is_correct(monkeypatch):
    res = run_tiny(monkeypatch, tiny_cell())
    assert res["correct"] is True
    assert res["checks"]["tokens_compared"]["value"] > 0


def test_a_token_altered_where_it_is_produced(monkeypatch):
    emit = MultiTenantEngine._emit

    def altered(self, req, row, finished):
        if len(req.tokens) == 2:  # the third token of every request
            row = np.asarray(row).copy()
            row[np.argmin(row)] = row.max() + 1.0
        return emit(self, req, row, finished)

    monkeypatch.setattr(MultiTenantEngine, "_emit", altered)
    res = run_tiny(monkeypatch, tiny_cell())
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > res["checks"]["widest_gap"]["limit"]


def test_no_result_without_a_tpu(capsys):
    from bench import run as R

    rc = R.main(["--workload", "smollm-135m.chat-zipf", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "needs a TPU" in out.err


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite"])
def test_no_result_on_a_device_missing_from_peaks(monkeypatch, capsys, kind):
    from bench import run as R

    class Dev:
        platform, device_kind = "tpu", kind

    monkeypatch.setattr(R.jax, "devices", lambda: [Dev()])
    rc = R.main(["--workload", "smollm-135m.chat-zipf", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "peaks.json" in out.err
