"""The two end-to-end numbers of a window, worked by hand on made-up stamps."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
import measure


def _result(stamps, steps_per_unit=1, group=0, committed=True):
    """Units that end at ``stamps`` (each starts where the last one ended), window 10..20."""
    units = [
        {"t_start": a, "t_end": b, "steps": [{"loss": 1.0, "committed": committed}] * steps_per_unit}
        for a, b in zip([stamps[0] - 1.0] + stamps, stamps)
    ]
    return {"group": group, "window": {"open": 10.0, "close": 20.0}, "units": units, "tokens_per_step": 100}


def test_rate_and_median_step_agree_on_even_steps():
    # commits at 9 (before the window), 11, 13, 15, 17, 19 and 21 (after it): four intervals of 2 s
    r = _result([9.0, 11.0, 13.0, 15.0, 17.0, 19.0, 21.0])
    assert measure.tokens_per_s([r]) == pytest.approx(4 * 100 / 8.0)
    assert measure.step_p50_s([r]) == pytest.approx(2.0)


def test_a_stall_is_all_in_the_rate_and_hardly_in_the_median():
    # one of five intervals is 3 s longer: the rate pays all of it, the median none
    r = _result([10.5, 11.5, 12.5, 16.5, 17.5, 18.5])
    assert measure.tokens_per_s([r]) == pytest.approx(5 * 100 / 8.0)
    assert measure.step_p50_s([r]) == pytest.approx(1.0)
    # a step that is slower every time moves both in full
    slow = _result([10.5, 12.0, 13.5, 15.0, 16.5, 18.0])
    assert measure.step_p50_s([slow]) == pytest.approx(1.5)
    assert measure.tokens_per_s([slow]) == pytest.approx(100 / 1.5)


def test_blocks_of_steps_and_several_groups():
    # a unit of 5 fused steps: the interval is shared out over its steps; groups: mean of medians, sum of rates
    a = _result([10.0, 12.5, 15.0, 17.5, 20.0], steps_per_unit=5)
    b = _result([10.0, 13.0, 16.0, 19.0], steps_per_unit=5, group=1)
    assert measure.step_p50_s([a]) == pytest.approx(0.5)
    assert measure.step_p50_s([a, b]) == pytest.approx((0.5 + 0.6) / 2)
    assert measure.tokens_per_s([a, b]) == pytest.approx(500 / 2.5 + 500 / 3.0)
    # a step that did not commit still took its time, and adds no tokens
    lost = _result([10.0, 12.5, 15.0, 17.5, 20.0], steps_per_unit=5, committed=False)
    assert measure.step_p50_s([lost]) == pytest.approx(0.5)
    assert measure.tokens_per_s([lost]) == 0.0


def test_fewer_than_two_intervals_is_no_number():
    r = _result([9.0, 12.0, 17.0, 22.0])
    with pytest.raises(measure.NotEnoughCommits):
        measure.step_p50_s([r])
    with pytest.raises(measure.NotEnoughCommits):
        measure.tokens_per_s([r])
