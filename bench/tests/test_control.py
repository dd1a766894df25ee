"""The control: the reference in the precision below the configuration's
(float8_e4m3 operands for a bfloat16 model), put in the program's place
and judged by the same comparison, comes out not correct where the program
comes out correct.  The chip-size readings are in PERF.md; this keeps the
control's path working at a size the CPU holds."""
import pytest

from bench import run as R
from bench.tests.tiny import run_tiny, tiny_cell


@pytest.mark.parametrize("seed", [2**31 + 11, 5, 977])
def test_control_reads_wider_than_the_program(monkeypatch, seed):
    import argparse

    real = R.run

    def with_control(args, cell, devices, peaks):
        a = argparse.Namespace(**{**vars(args), "control": "fp8"})
        return real(a, cell, devices, peaks)

    monkeypatch.setattr(R, "run", with_control)
    res = run_tiny(monkeypatch, tiny_cell(), seed=seed, seconds=3.0)
    program = res["checks"]["widest_gap"]["value"]
    control = res["control"]["widest_gap"]
    print(f"seed {seed}: program {program} control {control}")
    assert res["correct"] is True
    assert res["control"]["correct"] is False
    assert control > 3 * program
