"""The rule that sets a cell's limits from its readings, and the verdict
that holds a run to them (CPU only, no program)."""
from __future__ import annotations

import pytest

from bench import calibrate, check

READINGS = {
    "program": {"loss_gap": 1e-3, "grad_gap": 1e-2, "change_gap": 4e-3,
                "routing_overflow": 0.0},
    "control_fp8": {"loss_gap": 4e-2, "grad_gap": 2.0, "change_gap": 0.7,
                    "routing_overflow": 0.0},
    "fault_half_batch": {"loss_gap": 5e-2, "grad_gap": 0.4,
                         "change_gap": 0.2, "routing_overflow": 0.0},
    "fault_rows_altered": {"loss_gap": 1e-2, "grad_gap": 3e-2,
                           "change_gap": 8e-2, "routing_overflow": 0.0},
}


def test_limits_lie_between_the_readings():
    limits, not_compared, why = calibrate.limits_from(READINGS)
    assert not_compared == []
    assert limits["routing_overflow"] == 0.0
    for k in ("loss_gap", "grad_gap", "change_gap"):
        lower, upper = why[k]["lower"], min(why[k]["uppers"].values())
        assert 2 * lower < limits[k] < upper
        # more room above the lower reading than below the upper
        assert limits[k] / lower > upper / limits[k]
    # rows altered reads under 10x the program's grad_gap: not an upper
    assert "fault_rows_altered" not in why["grad_gap"]["uppers"]
    assert why["grad_gap"]["uppers"]["fault_state_unchanged"] == 1.0


def test_every_control_and_fault_fails_a_number():
    limits, _, _ = calibrate.limits_from(READINGS)
    failed = calibrate.failed_by(READINGS, limits)
    assert set(failed) == {"control_fp8", "fault_half_batch",
                           "fault_rows_altered", "fault_state_unchanged"}
    assert all(failed.values()), failed


def test_a_number_with_no_upper_reading_is_not_compared():
    flat = {kind: dict(r, loss_gap=1e-3) for kind, r in READINGS.items()}
    limits, not_compared, _ = calibrate.limits_from(flat)
    assert not_compared == ["loss_gap"]
    assert "loss_gap" not in limits


@pytest.mark.parametrize("loss_gap,not_compared,ok", [
    (1e-3, (), True),
    (1.0, (), False),
    (float("nan"), (), False),
    (1.0, ("loss_gap",), True),
])
def test_verdict(loss_gap, not_compared, ok):
    limits = {"loss_gap": 1e-2, "grad_gap": 0.1, "change_gap": 0.1,
              "routing_overflow": 0.0}
    values = {"loss_gap": loss_gap, "grad_gap": 0.01, "change_gap": 0.01,
              "routing_overflow": 0.0}
    got, checks = check.verdict(values, limits, not_compared)
    assert got is ok
    assert list(checks) == list(check.NUMBERS)


def test_an_unset_limit_fails():
    values = {k: 0.0 for k in check.NUMBERS}
    assert not check.verdict(values, {})[0]
