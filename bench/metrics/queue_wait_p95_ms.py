"""Admission wait: p95 over the requests due in the window of the time
from when a request was due to the start of the step that admitted it
(the step that delivered its first token). A request not admitted by
the close counts at its wait so far. In a traced run, starting the
profiler holds the loop for seconds; a request whose wait spans that
hold is left out."""

import numpy as np


def read(run, trace):
    win = run["window"]
    held = run.get("profiler_start")
    waits = []
    for r in win.reqs:
        if not win.open <= r.due < win.close:
            continue
        t = min(win.steps[r.first_step].start if r.first_step is not None else win.close, win.close)
        if held and r.due <= held[1] and t >= held[0]:
            continue
        waits.append((t - r.due) * 1e3)
    return float(np.percentile(waits, 95)) if waits else None
