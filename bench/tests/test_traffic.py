"""The open-loop generator and the benchmark's mix files."""

import dataclasses
import json
import math

import numpy as np
import pytest

from bench import harness
from bench.traffic import generator

MIXES = sorted(p.stem for p in generator.TRAFFIC_DIR.glob("*.json"))
POISSON = [m for m in MIXES if generator.load_mix(m).arrivals["kind"] == "poisson"]
BACKLOG = [m for m in MIXES if generator.load_mix(m).arrivals["kind"] == "backlog"]
SEEDS = (0, 7, 2**31 + 12345, 2**40 + 3)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    m = generator.load_mix(mix)
    a = generator.schedule(m, 2**33 + 1, 10.0, 151936)
    b = generator.schedule(m, 2**33 + 1, 10.0, 151936)
    np.testing.assert_array_equal(a.due_s, b.due_s)
    np.testing.assert_array_equal(a.prompt_len, b.prompt_len)
    np.testing.assert_array_equal(a.output_len, b.output_len)
    for x, y in zip(a.prompts, b.prompts):
        np.testing.assert_array_equal(x, y)
    # another seed replays the schedule with other token ids
    c = generator.schedule(m, 2**33 + 2, 10.0, 151936)
    np.testing.assert_array_equal(a.due_s, c.due_s)
    np.testing.assert_array_equal(a.prompt_len, c.prompt_len)
    np.testing.assert_array_equal(a.output_len, c.output_len)
    assert a.in_setup == c.in_setup
    assert not all(np.array_equal(x, y) for x, y in zip(a.prompts, c.prompts))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_draws_the_same_work(mix):
    """Every seed offers one set of sizes and gaps."""
    m = generator.load_mix(mix)
    runs = [generator.schedule(m, s, 10.0, 1000) for s in SEEDS]
    for r in runs[1:]:
        np.testing.assert_array_equal(np.sort(r.prompt_len), np.sort(runs[0].prompt_len))
        np.testing.assert_array_equal(np.sort(r.output_len), np.sort(runs[0].output_len))
        gaps = [np.sort(np.diff(x.due_s, prepend=0.0)) for x in (r, runs[0])]
        np.testing.assert_allclose(*gaps, atol=1e-9)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_respect_clips_and_medians(mix):
    m = generator.load_mix(mix)
    s = generator.schedule(m, 3, 40.0, 151936)
    for lens, spec in ((s.prompt_len, m.prompt_len), (s.output_len, m.output_len)):
        assert lens.min() >= spec["min"] and lens.max() <= spec["max"]
        assert abs(np.median(lens) - spec["median"]) <= 0.02 * spec["median"] + 1
    assert all(p.size == n for p, n in zip(s.prompts, s.prompt_len))
    assert all(int(p.min()) >= 0 and int(p.max()) < 151936 for p in s.prompts)


@pytest.mark.parametrize("mix", POISSON)
def test_arrivals(mix):
    m = generator.load_mix(mix)
    s = generator.schedule(m, 11, 30.0, 1000)
    assert np.all(np.diff(s.due_s) >= 0) and s.due_s[0] >= 0
    assert s.due_s[-1] < 30.0
    assert len(s) == round(m.arrivals["rate_per_s"] * 30.0)
    gaps = np.diff(s.due_s, prepend=0.0)
    # exponential gaps: the spread of the gaps is about their mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


@pytest.mark.parametrize("mix", MIXES)
def test_warmed_buckets_cover_every_prompt(mix):
    """Every (N, S) prefill program a request of the mix can land in is
    among the buckets the harness warms."""
    m = generator.load_mix(mix)
    page = 128
    warmed = set(harness.prefill_buckets(m, page))
    per_seq = m.engine["max_len"] // page
    for plen in range(m.prompt_len["min"], m.prompt_len["max"] + 1):
        pages = -(-plen // page)
        s = min(1 << (pages - 1).bit_length(), per_seq) * page
        for n in range(1, m.engine["max_prefill_batch"] + 1):
            chunk = 1 << (n.bit_length() - 1)
            assert (chunk, s) in warmed, (plen, n)
    assert m.prompt_len["max"] + m.output_len["min"] <= m.engine["max_len"]


@pytest.mark.parametrize("mix", BACKLOG)
def test_backlog_is_due_at_the_open(mix):
    """Every request is due when the window opens, the first wave (one
    request a slot) is submitted in set-up, and the count does not
    depend on the window's length."""
    m = generator.load_mix(mix)
    a = generator.schedule(m, 2**31 + 5, 51.0, 151936)
    b = generator.schedule(m, 9, 10.0, 151936)
    assert len(a) == len(b) == m.arrivals["count"] > m.engine["max_slots"]
    assert a.in_setup == m.engine["max_slots"]
    assert not a.due_s.any()
    np.testing.assert_array_equal(a.prompt_len, b.prompt_len)
    np.testing.assert_array_equal(a.output_len, b.output_len)


def test_unknown_arrival_kind_is_refused():
    m = generator.load_mix(POISSON[0])
    m = dataclasses.replace(m, arrivals={"kind": "no-such-kind"})
    with pytest.raises(ValueError, match="no-such-kind"):
        generator.schedule(m, 1, 10.0, 100)


def test_quantiles_are_stratified():
    spec = {"median": 100, "sigma": 1.0, "min": 1, "max": 10**9}
    vals = generator.lengths(spec, 1001, np.random.default_rng(0))
    assert int(np.median(vals)) == 100
    assert math.isclose(np.mean(np.log(vals)), math.log(100), abs_tol=0.01)


def test_mix_files_hold_only_parameters():
    for mix in MIXES:
        raw = json.loads((generator.TRAFFIC_DIR / f"{mix}.json").read_text())
        assert set(raw) == {"arrivals", "prompt_len", "output_len", "engine"}
