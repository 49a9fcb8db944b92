"""Open-loop Poisson arrivals at ``rate_per_s``::

    "arrivals": {"kind": "poisson", "rate_per_s": 0.8}

``round(rate * seconds)`` requests (at least one): the stratified
quantiles of the exponential gap, in an order drawn once from the mix's
own stream, the first from the window's opening, scaled so that the last
arrival falls one n-th of the window before it closes.
"""

from __future__ import annotations

import numpy as np

from bench.traffic.generator import SCHEDULE_SEED, Schedule, draw_prompts, lengths, quantiles, seed_rng


def schedule(mix, seed: int, seconds: float, vocab: int) -> Schedule:
    rate = mix.arrivals["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    plen = lengths(mix.prompt_len, n, seed_rng(SCHEDULE_SEED, 1))
    olen = lengths(mix.output_len, n, seed_rng(SCHEDULE_SEED, 2))
    gaps = seed_rng(SCHEDULE_SEED, 3).permutation(-np.log1p(-quantiles(n)) / rate)
    due = np.cumsum(gaps) * (seconds * n / (n + 1) / gaps.sum())
    return Schedule(due, plen, olen, draw_prompts(plen, seed, vocab))
