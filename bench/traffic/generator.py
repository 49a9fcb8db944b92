"""Request schedules drawn from a traffic mix file and a seed.

A mix file (``bench/traffic/<mix>.json``) holds only parameters::

    {"arrivals": {"kind": "poisson", "rate_per_s": 6.0},
     "prompt_len": {"median": 1024, "sigma": 0.8, "min": 128, "max": 4096},
     "output_len": {"median": 192, "sigma": 0.7, "min": 16, "max": 1024},
     "engine": {"max_slots": 32, "max_len": 5120, "max_prefill_batch": 4}}

The arrival kind selects its module, ``bench/traffic/arrivals/<kind>.py``
beside the mix, whose ``schedule(mix, seed, seconds, vocab)`` gives the
run's ``Schedule``; the rest of the ``arrivals`` object is that module's
parameters. Lengths are lognormal (``median`` and log-space ``sigma``)
clipped to ``[min, max]``: the ``n`` stratified quantiles of each
distribution, in an order drawn once from a stream of the mix's own.
Every seed replays one schedule: two seeds offer the same work at the
same moments and differ in their token ids (drawn from the seed), as do
the weights. In a window that opens on an empty engine, the work that
fits depends on when the long requests arrive; a seed that reordered
them would move the output rate by some 15 % from seed to seed.
"""

from __future__ import annotations

import dataclasses
import json
import math
import re
from pathlib import Path
from statistics import NormalDist

import numpy as np

from bench import loader

__all__ = ["Mix", "Schedule", "load_mix", "schedule", "lengths", "quantiles", "seed_rng", "draw_prompts"]

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
    # the directory the mix was read from, which holds ``arrivals/``
    dir: Path = TRAFFIC_DIR


@dataclasses.dataclass
class Schedule:
    """Requests in due order: ``due_s`` seconds after the window opens."""

    due_s: np.ndarray  # (n,) float64, non-decreasing
    prompt_len: np.ndarray  # (n,) int
    output_len: np.ndarray  # (n,) int
    prompts: list[np.ndarray]  # n int32 token-id arrays
    # the first ``in_setup`` requests are submitted during set-up, before
    # the window opens, and admitted as far as the pool allows
    in_setup: int = 0

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
        dir=root,
    )


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use (``stream``) of a run's seed; any
    non-negative integer seed, however large."""
    return np.random.default_rng([int(seed), int(stream)])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lognormal lengths: the stratified quantiles, clipped, in an
    order drawn from ``rng``."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(q) for q in quantiles(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    vals = np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(vals)


def draw_prompts(lens: np.ndarray, seed: int, vocab: int) -> list[np.ndarray]:
    """Token ids of prompts of lengths ``lens``, from the run's seed."""
    tok_rng = seed_rng(seed, 4)
    return [tok_rng.integers(0, vocab, size=int(p), dtype=np.int32) for p in lens]


def schedule(
    mix: Mix, seed: int, seconds: float, vocab: int, *, rate: float | None = None
) -> Schedule:
    """The run's requests, from the mix's arrival kind. ``rate``
    overrides a poisson mix's rate (the knee sweep); the benchmark's
    runs never pass it."""
    if rate is not None:
        mix = dataclasses.replace(
            mix, arrivals={**mix.arrivals, "rate_per_s": rate}
        )
    kind = mix.arrivals["kind"]
    path = Path(mix.dir) / "arrivals" / f"{kind}.py"
    if not re.fullmatch(r"[a-z0-9_]+", kind) or not path.is_file():
        raise ValueError(f"unknown arrival kind {kind!r}: no {path}")
    return loader.load(path).schedule(mix, seed, seconds, vocab)
