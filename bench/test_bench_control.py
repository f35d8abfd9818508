"""The control of ``correct`` at a size a test run holds: the program
reads under every limit, the reference with its emission rates stored
in bfloat16 (the control) reads over one, and so does the reference
choosing each job's second-best cell (``runner_up``)."""
import pytest

from bench import check, control, harness, rehearsal


@pytest.mark.parametrize("cell", ["ftn_overlay.replan",
                                  "metro_fanout_200.served"])
def test_control_fails_and_program_passes(cell):
    limits = harness.resolve(cell)["config"]["limits"]
    (row,) = control.readings(cell, [rehearsal.SEED], 0.5,
                              overrides=rehearsal.TINY[cell],
                              require_accelerator=False, log=lambda s: None)
    assert check.judge(row["program"], limits)[0], row
    assert not check.judge(row["control"], limits)[0], row
    assert row["runner_up"]["regret"] > limits["regret"], row
    assert row["runner_up"]["emis_err"] == 0.0, row
