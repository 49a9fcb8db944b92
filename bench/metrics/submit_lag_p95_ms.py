"""How late the load generator submitted: p95 of submit time minus due
time over the requests due in the window (harness clock). In a traced
run, starting the profiler holds the loop for seconds; a request whose
wait spans that hold is left out."""

import numpy as np


def read(run, trace):
    win = run["window"]
    held = run.get("profiler_start")
    lags = [(r.submit - r.due) * 1e3 for r in win.reqs
            if r.submit is not None and win.open <= r.due < win.close
            and not (held and r.due <= held[1] and r.submit >= held[0])]
    return float(np.percentile(lags, 95)) if lags else None
