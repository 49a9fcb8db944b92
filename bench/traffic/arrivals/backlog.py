"""An offline backlog: ``count`` requests, every one due when the window
opens::

    "arrivals": {"kind": "backlog", "count": 128}

The first ``max_slots`` of them are submitted during set-up, and the
pool admits as many as it can hold there, so their prefill is set-up;
the tokens delivered before the window opens count toward no metric.
The rest are submitted as the window opens and wait for slots and
pages. Lengths are the mix's stratified quantiles in one order for every
seed; ``count`` is large enough that the queue never empties in the
window, so the window measures a full engine.
"""

from __future__ import annotations

import numpy as np

from bench.traffic.generator import SCHEDULE_SEED, Schedule, draw_prompts, lengths, seed_rng


def schedule(mix, seed: int, seconds: float, vocab: int) -> Schedule:
    n = int(mix.arrivals["count"])
    plen = lengths(mix.prompt_len, n, seed_rng(SCHEDULE_SEED, 1))
    olen = lengths(mix.output_len, n, seed_rng(SCHEDULE_SEED, 2))
    return Schedule(
        np.zeros(n), plen, olen, draw_prompts(plen, seed, vocab),
        in_setup=min(n, mix.engine["max_slots"]),
    )
