"""The whole-cycle arithmetic, on synthetic event lists."""

import pytest

from benchmark.lib import cycles, kernel_costs, peaks
from benchmark.run import metric_reader


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
    """``train_tokens_per_s``: tokens of the whole cycles over their wall
    time less the time inside the saves."""
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


# -- the rate between saves, split into its two readers (PR 37) ---------------

TOKENS = 147456          # one optimizer step of gpt2s-train-1chip
STEP = 0.99              # its steady step, seconds
FLOPS = kernel_costs.model_flops_per_token(
    kernel_costs.gpt2_matmul_params(768, 12, 50304), 12, 768, 1024)
V5E = peaks.peaks_for("TPU v5 lite")


def artifact(cycle_specs):
    """What ``kinds/train.py`` hands the readers, for a made-up run.  The
    warm-up's save ends at 100 s; each cycle is ``steps`` optimizer steps of
    ``STEP`` seconds (step ``i`` longer by ``slow[i]``), ``before`` seconds
    of bookkeeping and a ``save``."""
    t, ends, saves = 100.0, [], [(95.0, 100.0)]
    for spec in cycle_specs:
        for i in range(spec.get("steps", 11)):
            t += STEP + spec.get("slow", {}).get(i, 0.0)
            ends.append(t)
        start = t + spec.get("before", 0.0)
        t = start + spec.get("save", 5.0)
        saves.append((start, t))
    return {"kind": "train", "epochs": [(e, TOKENS) for e in ends],
            "saves": saves, "peaks": V5E,
            "device": {"count": 1}, "flops_per_token": FLOPS,
            "window": cycles.Window(100.0, t, len(cycle_specs), False)}


def readings(art):
    w = art["window"]
    tokens = cycles.tokens_in(art["epochs"], w.t0, w.t1)
    stall = cycles.stall_seconds(art["saves"], w.t0, w.t1)
    out = {name: metric_reader(name)(art) for name in (
        "train_step_ms", "train_mfu_pct", "save_edge_ms", "ckpt_stall_pct")}
    # ``train_tokens_per_s`` as kinds/train.py computes it
    out["rate"] = tokens / (w.t1 - w.t0 - stall)
    return out


PLAIN = [{}, {}, {}]
STEADY = ("train_step_ms", "train_mfu_pct")


@pytest.mark.parametrize("case, specs, moved, unmoved", [
    ("a step that waits for the save's flush (the second of a cycle, on "
     "the chip) is in the rate and in the save's edge, not in the median "
     "step",
     [{"slow": {1: 0.36}}] * 3,
     {"save_edge_ms": 360.0, "rate": TOKENS * 11 / (11 * STEP + 0.36)},
     STEADY),
    ("so is a slow first step after a save, which no back-to-back step "
     "measures",
     [{"slow": {0: 0.3}}] * 3,
     {"save_edge_ms": 300.0, "rate": TOKENS * 11 / (11 * STEP + 0.3)},
     STEADY),
    ("and the bookkeeping before a save",
     [{"before": 0.12}] * 3,
     {"save_edge_ms": 120.0, "rate": TOKENS * 11 / (11 * STEP + 0.12)},
     STEADY),
    ("the edge is the median over the window's cycles; the rate holds "
     "every cycle's",
     [{"slow": {0: 0.1}}, {"slow": {1: 0.45}, "before": 0.05},
      {"slow": {0: 0.2}}],
     {"save_edge_ms": 200.0, "rate": TOKENS * 33 / (33 * STEP + 0.8)},
     STEADY),
    ("one stalled step in thirty-three: the rate counts all the time it "
     "took; the median step and the median cycle's edge do not see it",
     [{}, {"slow": {5: 1.8}}, {}],
     {"rate": TOKENS * 33 / (33 * STEP + 1.8)},
     STEADY + ("save_edge_ms",)),
    ("a slower save is the stall's alone",
     [{}, {"save": 7.3}, {}],
     {"ckpt_stall_pct": 100 * 17.3 / (33 * STEP + 17.3)},
     STEADY + ("rate", "save_edge_ms")),
    ("a step more or less to a cycle changes the stall's share only",
     [{"steps": 10}, {}, {"steps": 12}], {},
     STEADY + ("rate", "save_edge_ms", "ckpt_stall_pct")),
])
def test_what_moves_the_rate_the_median_step_and_the_edge(case, specs, moved,
                                                          unmoved):
    plain, got = readings(artifact(PLAIN)), readings(artifact(specs))
    assert plain["rate"] == pytest.approx(TOKENS / STEP, rel=1e-12)
    assert plain["save_edge_ms"] == pytest.approx(0.0, abs=1e-6)
    for name, value in moved.items():
        assert got[name] == pytest.approx(value, rel=1e-9, abs=1e-6), case
        assert got[name] != pytest.approx(plain[name], rel=1e-4), case
    for name in unmoved:
        assert got[name] == pytest.approx(plain[name], rel=1e-9,
                                          abs=1e-6), (case, name)


def test_mfu_is_the_median_step_in_another_unit():
    """``train_mfu_pct`` = tokens of a step / ``train_step_ms`` x FLOPs a
    token / peak, to the last digit: both read one sample
    (``cycles.steady_steps``), whatever the other steps did."""
    for specs in (PLAIN, [{}, {"slow": {1: 0.4, 5: 1.8}}, {"before": 0.1}]):
        got = readings(artifact(specs))
        assert got["train_mfu_pct"] == pytest.approx(
            100.0 * TOKENS / (got["train_step_ms"] / 1e3) * FLOPS / 197e12,
            rel=1e-12)
        assert got["train_step_ms"] == pytest.approx(1e3 * STEP, rel=1e-12)
        assert 64.5 < got["train_mfu_pct"] < 64.7     # the cell's, PR 32 on


def test_the_median_step_and_the_edges_account_for_the_rate():
    """Time between saves = each cycle's steps x the steady step + its
    edge, exactly, whatever ``step`` is: the two per-layer readers split
    ``train_tokens_per_s`` and leave nothing out."""
    art = artifact([{"slow": {0: 0.25}, "before": 0.1, "save": 4.7},
                    {"steps": 12, "slow": {1: 0.2, 3: 0.5}}])
    w = art["window"]
    ends = [t for t, _ in art["epochs"]]
    one, two = cycles.anatomy(ends, art["saves"], w.t0, w.t1)
    assert (len(one.steps), len(two.steps)) == (11, 12)
    assert one.steps[0] == pytest.approx(STEP + 0.25)   # taken from the save
    assert one.before_save == pytest.approx(0.1)
    assert one.stall == pytest.approx(4.7)
    assert two.t0 == one.t1 and max(two.steps) == pytest.approx(STEP + 0.5)
    for c in (one, two):
        assert sum(c.steps) + c.before_save + c.stall == pytest.approx(
            c.t1 - c.t0)
    step = metric_reader("train_step_ms")(art) / 1e3
    between = sum(len(c.steps) * step + cycles.save_edge(c, step)
                  for c in (one, two))
    assert readings(art)["rate"] == pytest.approx(23 * TOKENS / between,
                                                  rel=1e-12)
    assert cycles.save_edge(one, step) == pytest.approx(0.35)
    assert cycles.save_edge(two, step) == pytest.approx(0.7)
