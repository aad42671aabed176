"""Benchmark harness: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only psf,scdl,memory,driver,api,deconv,many,serve]
                                            [--smoke]

Prints ``name,us_per_call,derived`` CSV (see benchmarks/common.py for the
single-core measurement caveats; the derived column is defined per
table).  ``--smoke`` shrinks the driver table to a tiny problem size for
CI.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only",
                    default="psf,scdl,memory,driver,api,deconv,many,serve")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    wanted = set(args.only.split(","))
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    if "psf" in wanted:
        from benchmarks import bench_psf
        _run(bench_psf.run, "psf", failures)
    if "scdl" in wanted:
        from benchmarks import bench_scdl
        _run(lambda: bench_scdl.run(smoke=args.smoke), "scdl", failures)
    if "memory" in wanted:
        from benchmarks import bench_memory
        _run(lambda: bench_memory.run(smoke=args.smoke), "memory",
             failures)
    if "driver" in wanted:
        from benchmarks import bench_driver
        _run(lambda: bench_driver.run(smoke=args.smoke), "driver",
             failures)
    if "api" in wanted:
        from benchmarks import bench_api
        _run(lambda: bench_api.run(smoke=args.smoke), "api", failures)
    if "deconv" in wanted:
        from benchmarks import bench_deconv
        _run(lambda: bench_deconv.run(smoke=args.smoke), "deconv",
             failures)
    if "many" in wanted:
        from benchmarks import bench_many
        _run(lambda: bench_many.run(smoke=args.smoke), "many", failures)
    if "serve" in wanted:
        from benchmarks import bench_serve
        _run(lambda: bench_serve.run(smoke=args.smoke), "serve",
             failures)
    if failures:
        print(f"# FAILED tables: {failures}", file=sys.stderr)
        raise SystemExit(1)


def _run(fn, tag, failures):
    try:
        fn()
    except Exception:
        traceback.print_exc()
        failures.append(tag)


if __name__ == "__main__":
    main()
