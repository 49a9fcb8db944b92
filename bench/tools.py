"""Measurements on the chip that set up the benchmark; its own runs use
none of them.

    python bench/tools.py sweep   --workload W --seed N --seconds S --rates 0.6,0.9,1.2
    python bench/tools.py control --workload W --seed N --seconds S --seeds 11,12,13
    python bench/tools.py trace   --workload W --seed N --seconds S --out DIR

``sweep`` offers a chat cell's mix at several fixed rates, one run each,
to find the knee: the highest rate whose queue does not grow through the
window, which shows as a first-token time that rises from one third of
the window to the next. ``control`` runs a cell with the fp8 control in the program's place in
the comparison that decides ``correct``, and prints per seed whether it
came out correct, its widest gap and the program's own widest gap on
the same sample: the readings a limit is set from.
``trace`` keeps the profiler trace of one traced run under ``DIR``.
Each prints one JSON line per run.
"""

import time

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("tool", choices=("sweep", "control", "trace"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from bench import harness, session

    cell = harness.resolve_cell(harness.load_benchmark(ROOT), args.workload, ROOT)
    session.use_compile_cache(ROOT)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("tools: needs a TPU", file=sys.stderr)
        return 3

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    def thirds(win):
        """The 90th percentile of the first-token time of the requests
        due in each third of the window: a queue that grows through the
        window shows as a rise from third to third."""
        out = []
        for k in range(3):
            lo, hi = win.open + k * win.seconds / 3, win.open + (k + 1) * win.seconds / 3
            part = harness.Window(win.open, win.close, [r for r in win.reqs if lo <= r.due < hi], [], 0)
            vals = harness.ttft_ms(part)
            out.append(float(np.percentile(vals, 90)) if vals else None)
        return out

    if args.tool == "sweep":
        for rate in (float(r) for r in args.rates.split(",")):
            t0 = time.perf_counter()
            result, extra = session.run(cell, args.seed, args.seconds, False, t0, rate=rate)
            win = extra["record"]["window"]
            ttft, itl = harness.ttft_ms(win), harness.itl_ms(win)
            emit(
                rate=rate, due=len(win.reqs), waiting_at_close=win.waiting_at_close,
                finished=sum(1 for r in win.reqs if r.finish_time is not None and r.finish_time <= win.close),
                output_tokens_per_s=harness.tokens_in_window(win) / win.seconds,
                ttft_ms={q: float(np.percentile(ttft, q)) for q in (50, 75, 90, 95)},
                ttft_p90_ms_by_third=thirds(win),
                itl_ms={q: float(np.percentile(itl, q)) for q in (50, 95)},
                steps=len(win.steps), setup_s=result["metrics"]["setup_s"]["value"],
                correct=result["correct"], compared=result["compared"], compiles=result["_compiles"],
                memory_peak_bytes=result["device"]["memory_peak_bytes"],
            )
    elif args.tool == "control":
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            result, extra = session.run(cell, seed, args.seconds, False, t0, control=True)
            emit(
                seed=seed, program_gap=extra["program_gap"],
                control_gap=result["compared"]["served_logit_gap_max"]["value"],
                control_correct=result["correct"], lines=extra["lines"],
                seconds=time.perf_counter() - t0,
            )
    else:
        t0 = time.perf_counter()
        result, extra = session.run(cell, args.seed, args.seconds, True, t0, trace_dir=args.out)
        emit(**{k: v for k, v in result.items() if not k.startswith("_")})
    return 0


if __name__ == "__main__":
    sys.exit(main())
