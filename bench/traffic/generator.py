"""Open-loop request schedules drawn from a traffic mix file and a seed.

A mix file (``bench/traffic/<mix>.json``) holds only parameters::

    {"arrivals": {"kind": "poisson", "rate_per_s": 6.0},
     "prompt_len": {"median": 1024, "sigma": 0.8, "min": 128, "max": 4096},
     "output_len": {"median": 192, "sigma": 0.7, "min": 16, "max": 1024},
     "engine": {"max_slots": 32, "max_len": 5120, "max_prefill_batch": 4}}

Lengths are lognormal (``median`` and log-space ``sigma``) clipped to
``[min, max]``: the ``n`` stratified quantiles of each distribution.
Arrivals are Poisson at ``rate_per_s``: the ``n`` stratified quantiles
of the exponential gap. Every seed replays one schedule: the order of
the gaps and which request gets which lengths are drawn once from a
stream of the mix's own, so two seeds offer the same work at the same
moments and differ in their token ids (drawn from the seed), as do the
weights. In a window that opens on an empty engine, the work that fits
depends on when the long requests arrive; a seed that reordered them
would move the output rate by some 15 % from seed to seed.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

__all__ = ["Mix", "Schedule", "load_mix", "schedule", "lengths", "seed_rng"]

TRAFFIC_DIR = Path(__file__).resolve().parent
# the stream the schedule's order is drawn from, the same for every run
SCHEDULE_SEED = 0


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    arrivals: dict
    prompt_len: dict
    output_len: dict
    engine: dict


@dataclasses.dataclass
class Schedule:
    """Requests in due order: ``due_s`` seconds after the window opens."""

    due_s: np.ndarray  # (n,) float64, non-decreasing
    prompt_len: np.ndarray  # (n,) int
    output_len: np.ndarray  # (n,) int
    prompts: list[np.ndarray]  # n int32 token-id arrays

    def __len__(self) -> int:
        return int(self.due_s.size)


def load_mix(name: str, root: Path = TRAFFIC_DIR) -> Mix:
    raw = json.loads((root / f"{name}.json").read_text())
    return Mix(
        name=name,
        arrivals=raw["arrivals"],
        prompt_len=raw["prompt_len"],
        output_len=raw["output_len"],
        engine=raw["engine"],
    )


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use (``stream``) of a run's seed; any
    non-negative integer seed, however large."""
    return np.random.default_rng([int(seed), int(stream)])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lognormal lengths: the stratified quantiles, clipped, in an
    order drawn from ``rng``."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(q) for q in _quantiles(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    vals = np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(vals)


def schedule(
    mix: Mix, seed: int, seconds: float, vocab: int, *, rate: float | None = None
) -> Schedule:
    """The run's requests. ``rate`` overrides a poisson mix's rate (the
    knee sweep); the benchmark's runs never pass it."""
    if rate is not None:
        mix = dataclasses.replace(
            mix, arrivals={**mix.arrivals, "rate_per_s": rate}
        )
    if mix.arrivals["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {mix.arrivals['kind']!r}")
    rate = mix.arrivals["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    plen = lengths(mix.prompt_len, n, seed_rng(SCHEDULE_SEED, 1))
    olen = lengths(mix.output_len, n, seed_rng(SCHEDULE_SEED, 2))
    gaps = seed_rng(SCHEDULE_SEED, 3).permutation(-np.log1p(-_quantiles(n)) / rate)
    # the n gaps, the first from the window's opening, scaled so the
    # last arrival falls one n-th of the window before it closes
    due = np.cumsum(gaps) * (seconds * n / (n + 1) / gaps.sum())
    tok_rng = seed_rng(seed, 4)
    prompts = [
        tok_rng.integers(0, vocab, size=int(p), dtype=np.int32) for p in plen
    ]
    return Schedule(due, plen, olen, prompts)
