"""The span arithmetic on made-up completion times."""

import pytest
from lib.span import measured_span

T0 = 1000.0


def test_closes_at_the_last_completion_inside_the_window():
    span = measured_span([T0 + 15, T0 + 30, T0 + 45, T0 + 60], T0, 51)
    assert (span.start, span.end, span.counted, span.stalled) == (T0, T0 + 45, [0, 1, 2], False)
    assert span.seconds == 45


def test_order_of_the_times_does_not_matter():
    span = measured_span([T0 + 45, T0 + 15, T0 + 30], T0, 51)
    assert span.counted == [1, 2, 0] and span.end == T0 + 45


def test_a_trailing_stall_closes_the_span_at_the_deadline():
    # the wait from the last completion (20 s) is longer than any gap inside
    span = measured_span([T0 + 10, T0 + 20, T0 + 31, T0 + 70], T0, 51)
    assert span.stalled and span.end == T0 + 51 and span.counted == [0, 1, 2]


def test_a_wait_as_long_as_the_longest_gap_is_no_stall():
    span = measured_span([T0 + 17, T0 + 34], T0, 51)
    assert not span.stalled and span.end == T0 + 34


@pytest.mark.parametrize("times", [[], [T0 + 20], [T0 + 20, T0 + 52], [T0 - 1, T0, T0 + 3]])
def test_fewer_than_two_completions_after_t0_is_no_rate(times):
    assert measured_span(times, T0, 51) is None


def test_a_completion_on_the_deadline_counts_and_one_never_landed_does_not():
    span = measured_span([T0 + 25, T0 + 51, float("inf")], T0, 51)
    assert span.counted == [0, 1] and span.end == T0 + 51 and not span.stalled
