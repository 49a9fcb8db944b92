"""The harness's cell loop on the CPU at small widths: the chat cell of
both twins and the pixelfly backlog cell, through set-up, the open-loop
window and the check against the reference, with the profiler off and
on."""

import math
import time

import pytest

from bench import session
from bench.tests import smoke


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("like", ["pixelfly-chat", "dense-chat"])
def test_cell_loop(like, trace):
    c = smoke.cell(like, "chat")
    seed = 2**31 + 99
    result, extra = session.run(c, seed, 3.0, trace, time.perf_counter(), test=smoke.options(c))
    assert result["correct"], extra["lines"]
    assert result["_compiles"] == 0  # every program was warmed in set-up
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    got = result["metrics"]
    if trace:
        # the traced line: the device's busy and window seconds, the
        # breakdown, and only per-layer metrics (no peaks on the CPU, so
        # no roofline or mfu share)
        dev = result["device"]
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert set(got) <= {m["name"] for m in c.per_layer}
        assert {"decode_batch_occupancy", "kv_pool_tokens", "device_idle_share"} <= set(got)
        return
    assert set(got) == {m["name"] for m in c.end_to_end}
    for m in c.end_to_end:
        v = got[m["name"]]["value"]
        assert math.isfinite(v) and v > 0, (m["name"], v)
    win = extra["record"]["window"]
    assert win.steps and all(s.end >= s.start for s in win.steps)
    # a request's tokens: one per step after its first, never more than asked
    for r in win.reqs:
        assert r.count <= r.olen
        if r.finished is not None:
            assert r.count == len(r.finished.tokens) == r.olen
    # host and counter readers read without a trace; trace readers give nothing
    readers = session._readers(c)
    for m in c.per_layer:
        if m["name"] == "mfu":  # needs the chip's peaks
            continue
        v = readers[m["name"]](extra["record"], None)
        if m["source"] == "device_trace":
            assert v is None
        else:
            assert v is not None and v >= 0, m["name"]


def test_backlog_cell_loop():
    """The backlog: its first wave is submitted and admitted in set-up and
    its tokens before the open count toward no metric; the queue is
    never empty at the close; warm-up covered every prefill program."""
    from bench import harness

    c = smoke.cell("pixelfly-longctx", "longctx")
    result, extra = session.run(c, 2**31 + 77, 3.0, False, time.perf_counter(), test=smoke.options(c))
    assert result["correct"], extra["lines"]
    assert result["_compiles"] == 0
    assert set(result["metrics"]) == {"itl_p95_ms", "output_tokens_per_s", "setup_s"}
    win = extra["record"]["window"]
    first = win.reqs[: c.mix.engine["max_slots"]]
    assert [r.idx for r in first] == list(range(len(first)))
    assert all(r.submit < win.open for r in first)
    assert all(r.submit >= win.open for r in win.reqs[len(first):])
    admitted = [r for r in first if r.times and r.times[0] < win.open]
    assert admitted, "no request was admitted in set-up"
    assert win.waiting_at_close > 0
    assert len(win.reqs) == c.mix.arrivals["count"]
    in_window = [t for r in win.reqs for t in r.times if win.open <= t <= win.close]
    assert sum(len(r.times) for r in win.reqs) > len(in_window)
    got = result["metrics"]["output_tokens_per_s"]["value"]
    assert got == pytest.approx(len(in_window) / win.seconds)
    gaps = [b - a for r in win.reqs for a, b in zip(r.times, r.times[1:]) if win.open <= a and b <= win.close]
    assert len(harness.itl_ms(win)) == len(gaps) > 0


def test_wait_readers_leave_out_the_profiler_start():
    """A request whose wait spans the seconds in which starting the
    profiler held the loop is not read by the host wait metrics."""
    from bench import harness

    reqs = [harness.Req(i, due, 8, 2) for i, due in enumerate((0.5, 1.0, 1.5, 4.5))]
    for i, (r, sub) in enumerate(zip(reqs, (0.5, 1.0, 4.0, 4.5))):
        r.submit, r.first_step = sub, i
    steps = [harness.Step(t, t + 0.1) for t in (0.5, 1.0, 4.0, 4.5)]
    win = harness.Window(0.0, 6.0, reqs, steps, 0)
    held = {"window": win, "profiler_start": (1.2, 3.9)}
    free = {"window": win, "profiler_start": None}
    lag = harness.metric_reader("submit_lag_p95_ms")
    wait = harness.metric_reader("queue_wait_p95_ms")
    assert lag(free, None) > 2000 and wait(free, None) > 2000
    assert lag(held, None) == wait(held, None) == 0.0
