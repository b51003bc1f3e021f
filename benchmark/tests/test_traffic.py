"""The serving generator: the same work from every seed, in another order."""

import numpy as np

from benchmark.lib import stats, traffic

PARAMS = {"rate_per_s": 0.6, "lead_in_s": 20,
          "prompt_tokens": {"median": 192, "sigma": 0.6, "min": 32,
                            "max": 640},
          "output_tokens": {"median": 64, "sigma": 0.6, "min": 16,
                            "max": 192}}


def _work(reqs):
    counted = [r for r in reqs if r.counted]
    return (sorted(len(r.prompt) for r in counted),
            sorted(r.max_new for r in counted), len(counted))


def test_every_seed_offers_the_same_work():
    a = traffic.schedule(PARAMS, 1, 51, 50257, 1024)
    b = traffic.schedule(PARAMS, 2**31 + 12345, 51, 50257, 1024)
    assert _work(a) == _work(b)
    assert [r.due for r in a] != [r.due for r in b]
    assert _work(a)[2] == round(0.6 * 51)


def test_same_seed_same_requests():
    a = traffic.schedule(PARAMS, 77, 51, 50257, 1024)
    b = traffic.schedule(PARAMS, 77, 51, 50257, 1024)
    assert [(r.due, r.prompt, r.max_new) for r in a] == \
           [(r.due, r.prompt, r.max_new) for r in b]


def test_requests_fit_and_share_no_first_page():
    reqs = traffic.schedule(PARAMS, 5, 51, 50257, 1024)
    assert all(len(r.prompt) + r.max_new <= 1024 for r in reqs)
    assert all(32 <= len(r.prompt) <= 640 and 16 <= r.max_new <= 192
               for r in reqs)
    firsts = [r.prompt[0] for r in reqs]
    assert len(set(firsts)) == len(firsts)
    assert all(0 <= r.due < 51 for r in reqs if r.counted)


def test_arrivals_are_the_same_gaps_in_another_order_and_periodic():
    a = traffic.schedule(PARAMS, 1, 51, 50257, 1024)
    b = traffic.schedule(PARAMS, 2, 51, 50257, 1024)

    def gaps(reqs):
        due = [r.due for r in reqs if r.counted]
        wrap = due[0] + 51 - due[-1]
        return sorted(np.round(np.diff(due).tolist() + [wrap], 9))
    assert gaps(a) == gaps(b)               # same near-collisions, every seed
    assert abs(sum(gaps(a)) - 51) < 1e-6
    # mean gap 1/rate; exponential: the shortest is far under the mean
    assert gaps(a)[0] < 0.1 / 0.6 < 1 / 0.6 < gaps(a)[-1]
    # the lead-in is the end of the same cycle, one period earlier
    window = [r for r in a if r.counted]
    lead = [r for r in a if not r.counted]
    tail = [r for r in window if r.due >= 51 - 20]
    assert len(lead) == len(tail) and all(-20 <= r.due < 0 for r in lead)
    assert [(round(r.due + 51, 9), len(r.prompt), r.max_new) for r in lead] \
        == [(round(r.due, 9), len(r.prompt), r.max_new) for r in tail]
    assert all(x.prompt != y.prompt for x, y in zip(lead, tail))


def test_midpoints_are_the_stated_distribution():
    v = traffic.lognormal_midpoints(1001, PARAMS["prompt_tokens"])
    assert int(np.median(v)) == 192 and v.min() == 32 and v.max() == 640


def test_histogram_window_delta_and_quantile():
    before = {"buckets": [10, 20, 50], "counts": [5, 0, 0], "sum": 25.0,
              "count": 5, "max": 9.0}
    after = {"buckets": [10, 20, 50], "counts": [5, 8, 2], "sum": 200.0,
             "count": 15, "max": 40.0}
    d = stats.hist_delta(after, before)
    assert d["counts"] == [0, 8, 2] and d["count"] == 10
    # the 5 ticks before the window (compiles among them) are out: the
    # median falls in the (10, 20] bucket, 5/8 of the way
    assert stats.hist_quantile(d, 0.5) == 10 + 10 * 5 / 8
    assert stats.hist_quantile(d, 0.99) <= 40.0
    assert stats.hist_quantile({"buckets": [1], "counts": [0], "sum": 0,
                                "count": 0, "max": None}, 0.5) is None


def test_nothing_is_offered_at_or_after_the_windows_close():
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in sorted(os.listdir(os.path.join(here, "traffic"))):
        mix = json.load(open(os.path.join(here, "traffic", name)))
        if mix["kind"] != "serve_open":
            continue
        for seed in (3, 2**31 + 99):
            reqs = traffic.schedule(mix, seed, 51, 50257, 1024)
            assert all(r.due < 51 for r in reqs), name
            assert all(0 <= r.due for r in reqs if r.counted), name
            assert all(r.due < 0 for r in reqs if not r.counted), name
