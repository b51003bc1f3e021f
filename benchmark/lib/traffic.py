"""One general generator of open-loop serving traffic, driven by a data file.

A traffic file of kind ``serve_open`` gives the arrival rate, the length
distributions and the warm-up waves; this module turns it and a seed into
requests.  Every seed offers the *same work and the same arrivals in another
order*: the N gaps between arrivals are the N quantile midpoints of the
exponential distribution of a Poisson process at the stated rate (scaled to
fill the window exactly), prompt and reply lengths are the N quantile
midpoints of the stated lognormals, and the seed only shuffles the three
lists against each other and picks where in the cycle the window starts.
So runs with different seeds differ by which lengths meet which gaps, never
by the amount of work or the number of near-collisions.  The schedule is
periodic with the window's length: the lead-in replays the end of the same
cycle just before the window opens, so what the lead-in carries into the
window is what the window's last requests carry out of it.  Token ids are
random per request, with the first token made distinct, so no two requests
share a prefix page.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    due: float                  # seconds from the window's opening (may be < 0)
    prompt: list
    max_new: int
    counted: bool = True        # False: lead-in or warm-up, not a sample
    # filled in by the load generator, absolute monotonic seconds
    due_at: float = 0.0
    sent_at: float = 0.0
    status: int = 0
    error: str = ""
    tokens: list = field(default_factory=list)
    token_at: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.status == 200 and len(self.tokens) == self.max_new


def lognormal_midpoints(n: int, dist: dict) -> np.ndarray:
    """The ``n`` quantile midpoints ((i + ½)/n) of a lognormal with the
    given ``median`` and ``sigma``, clipped to [``min``, ``max``], as whole
    numbers."""
    if n <= 0:
        return np.zeros(0, np.int64)
    normal = NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    values = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(values), dist["min"], dist["max"]).astype(np.int64)


def exponential_midpoints(n: int) -> np.ndarray:
    """The ``n`` quantile midpoints of the unit exponential distribution,
    scaled so that they sum to ``n`` (mean 1)."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    return gaps * n / gaps.sum()


def _tokens(rng, n: int, vocab: int, first_id: int) -> list:
    tokens = rng.integers(0, vocab, n)
    tokens[0] = first_id % vocab            # no shared first page
    return [int(t) for t in tokens]


def schedule(params: dict, seed: int, seconds: float, vocab: int,
             block: int, rate: float | None = None) -> list:
    """Lead-in requests (due before 0, not counted) then the window's, in
    order of their due times."""
    rate = float(params["rate_per_s"] if rate is None else rate)
    rng = np.random.default_rng([int(seed), 20240924])
    n = max(1, int(round(rate * seconds)))
    gaps = rng.permutation(exponential_midpoints(n)) * (seconds / n)
    due = np.sort((rng.uniform(0.0, seconds) + np.cumsum(gaps)) % seconds)
    prompts = rng.permutation(lognormal_midpoints(n, params["prompt_tokens"]))
    outputs = rng.permutation(lognormal_midpoints(n, params["output_tokens"]))
    first = int(rng.integers(0, vocab))
    window = []
    for i in range(n):
        p = int(min(prompts[i], block - 1))
        o = int(min(outputs[i], block - p))
        window.append(Request(float(due[i]), _tokens(rng, p, vocab,
                                                     first + i), o, True))
    # the lead-in: the cycle's own end, one period earlier (fresh token ids,
    # the same lengths), as many periods back as the lead-in is long
    lead, lead_in = float(params.get("lead_in_s", 0.0)), []
    k = 1
    while lead > 0 and (k - 1) * seconds < lead:
        for r in window:
            t = r.due - k * seconds
            if -lead <= t < 0:
                lead_in.append(Request(
                    t, _tokens(rng, len(r.prompt), vocab,
                               first + n + len(lead_in)), r.max_new, False))
        k += 1
    return sorted(lead_in, key=lambda r: r.due) + window


def wave_requests(spec: list, rng, vocab: int, first_id: int) -> list:
    """Warm-up requests from ``[[prompt_tokens, new_tokens], ...]``."""
    return [Request(0.0, _tokens(rng, int(p), vocab, first_id + i), int(o),
                    False) for i, (p, o) in enumerate(spec)]
