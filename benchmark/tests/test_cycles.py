"""The whole-cycle arithmetic, on synthetic event lists."""

import pytest

from benchmark.lib import cycles


def test_no_cycle_yet():
    assert cycles.whole_cycles([], 50) is None
    assert cycles.whole_cycles([100.0], 50) is None
    assert not cycles.closed([100.0], 50, now=1000.0)


def test_window_cut_at_both_ends():
    # saves end every 38 s; the window opens at the first and may last 51 s:
    # one whole cycle fits, the second is cut and does not count
    ends = [100.0, 138.0, 176.0, 214.0]
    w = cycles.whole_cycles(ends, 51)
    assert (w.t0, w.t1, w.cycles, w.overran) == (100.0, 138.0, 1, False)
    # epochs before the opening (warm-up) and after the close are out
    epochs = [(95.0, 10), (105.0, 10), (110.0, 10), (137.0, 10), (150.0, 10)]
    assert cycles.tokens_in(epochs, w.t0, w.t1) == 30
    # 51 s is up only after t0 + 51: until then a cycle may still complete
    assert not cycles.closed(ends[:2], 51, now=150.0)
    assert cycles.closed(ends[:2], 51, now=151.5)


def test_two_cycles_fit():
    w = cycles.whole_cycles([10.0, 32.0, 55.0, 80.0], 51)
    assert (w.t1, w.cycles, w.overran) == (55.0, 2, False)


def test_no_cycle_in_time_runs_on_to_the_first():
    w = cycles.whole_cycles([10.0, 75.0], 51)
    assert (w.t0, w.t1, w.cycles, w.overran) == (10.0, 75.0, 1, True)
    # time is up but nothing has completed: not closed, the run goes on
    assert not cycles.closed([10.0], 51, now=70.0)
    assert cycles.closed([10.0, 75.0], 51, now=75.1)


def test_phase_of_the_window_does_not_move_the_rate():
    """The fault this definition cures: the same rhythm, seen through a
    window that opens at another phase, reads the same rate."""
    def rate(shift):
        ends = [shift + 38.0 * k for k in range(4)]
        epochs = [(e - 28.0 - 1.5 * j, 100) for e in ends for j in range(7)]
        w = cycles.whole_cycles(ends, 51)
        return cycles.tokens_in(epochs, w.t0, w.t1) / (w.t1 - w.t0)
    assert rate(0.0) == pytest.approx(rate(17.3))
    assert rate(0.0) == pytest.approx(700 / 38.0)


def test_a_stats_refresh_inside_a_cycle_counts_as_stall():
    # second cycle's save takes 20 s more (an instrumented pass rode on it)
    saves = [(90.0, 100.0), (112.0, 122.0), (134.0, 164.0)]
    ends = [b for _, b in saves]
    w = cycles.whole_cycles(ends, 70)
    assert w.cycles == 2
    assert cycles.stall_seconds(saves, w.t0, w.t1) == pytest.approx(40.0)
    # the opening save itself is warm-up: not in the window's stall
    assert cycles.stall_seconds(saves[:1], w.t0, w.t1) == 0.0


def test_steady_steps_leave_out_the_gap_a_save_fills():
    epoch_ends = [101.5, 103.0, 104.5, 116.0, 117.5]
    saves = [(104.6, 114.5)]
    steps = cycles.steady_steps(epoch_ends, saves, 100.0, 120.0)
    assert steps == pytest.approx([1.5, 1.5, 1.5])
    # steps outside the window are out
    assert cycles.steady_steps(epoch_ends, saves, 102.0, 120.0) == \
        pytest.approx([1.5, 1.5])


def test_rate_between_saves_ignores_how_long_a_save_took():
    """What ``train_tokens_per_s`` is until the save repeats: tokens of the
    whole cycles over their wall time less the time inside the saves."""
    def rates(slow_save):
        saves = [(90.0, 95.0), (107.0, 112.0),
                 (124.0, 129.0 + slow_save)]
        ends = [b for _, b in saves]
        epochs = [(95.0 + 1.3 * k, 100) for k in range(1, 10)] + \
                 [(112.0 + 1.3 * k, 100) for k in range(1, 10)]
        w = cycles.whole_cycles(ends, 51)
        tokens = cycles.tokens_in(epochs, w.t0, w.t1)
        stall = cycles.stall_seconds(saves, w.t0, w.t1)
        return tokens / (w.t1 - w.t0), tokens / (w.t1 - w.t0 - stall)
    whole_fast, between_fast = rates(0.0)
    whole_slow, between_slow = rates(2.3)
    assert whole_slow < 0.95 * whole_fast            # the two-valued rate
    assert between_slow == pytest.approx(between_fast)
    assert between_fast == pytest.approx(1800 / 24.0)
