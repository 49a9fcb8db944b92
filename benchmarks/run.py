"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; suites with JSON artifacts
(``serve_engine`` -> BENCH_serve.json) write them under ``--json DIR``.

  PYTHONPATH=src python -m benchmarks.run [--only NAME] [--json DIR]
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--json",
        default=None,
        metavar="DIR",
        help="directory for JSON artifacts (e.g. BENCH_serve.json); "
        "suites that emit JSON write there instead of the cwd",
    )
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        block_microbench,
        common,
        flat_vs_product,
        lm_speedup,
        lra_attention,
        ntk_distance,
        roofline_report,
        serve_engine,
        vision_speedup,
    )

    if args.json:
        common.set_json_dir(args.json)

    suites = {
        "flat_vs_product": flat_vs_product.run,      # App. J / Fig 11
        "block_microbench": block_microbench.run,    # App. L.5 / Table 7
        "ntk_distance": ntk_distance.run,            # Fig 4
        "vision_speedup": vision_speedup.run,        # Fig 5 / Table 4
        "lm_speedup": lm_speedup.run,                # Fig 8 / Table 5
        "lra_attention": lra_attention.run,          # Fig 9 (LRA)
        "roofline": roofline_report.run,             # §Roofline
        "serve_engine": serve_engine.run,            # BENCH_serve.json
    }
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in suites.items():
        if args.only and args.only != name:
            continue
        try:
            fn()
        except Exception:  # noqa: BLE001
            failed += 1
            traceback.print_exc()
            print(f"{name},0.0,FAILED")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
