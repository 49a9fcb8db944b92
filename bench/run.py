"""Run one cell of the chip benchmark.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's engine through the program's normal path, warms its
programs (set-up), drives the cell's traffic open loop for ``--seconds``,
then checks a sample of the served tokens against the plain float32
reference. The last line of standard output is one JSON object; the
numbers compared for ``correct`` are also the last lines of standard
error. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run: the program's sources are not under {ROOT / 'src'}", file=sys.stderr)
        return 2

    from bench import harness, session

    bench = harness.load_benchmark(ROOT)
    cell = harness.resolve_cell(bench, args.workload, ROOT)
    session.use_compile_cache(ROOT)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(
            f"run: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform!r} device(s)",
            file=sys.stderr,
        )
        return 3
    result, compared = session.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(f"window compile events: {result.pop('_compiles')}; "
          f"waiting at close: {result.pop('_waiting_at_close')}", flush=True)
    for line in compared["lines"]:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
