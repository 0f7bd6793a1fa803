"""Benchmark harness entry point: one module per paper table/figure, plus
the perf-trajectory suites.

    PYTHONPATH=src python -m benchmarks.run [--only table1,...] [--smoke]

Emits ``name,us_per_call,derived`` CSV rows (stdout); the ``kernel`` and
``serve`` suites additionally write machine-readable ``BENCH_kernels.json``
and ``BENCH_serve.json`` at the repo root — the perf record every future
PR is measured against (ROADMAP.md bench-trajectory convention).

``--smoke`` runs only the JSON-emitting suites at reduced sizes — the CI
bench job (fast, validates schema, uploads artifacts). Smoke output lands
in ``BENCH_*.smoke.json`` so a quick post-run smoke can never overwrite
the committed full-size trajectory; CI fails if a committed BENCH_*.json
ever carries ``smoke: true`` records.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

MODULES = [
    ("table1", "benchmarks.table1_quality"),
    ("table2", "benchmarks.table2_throughput"),
    ("table3", "benchmarks.table3_blocksize"),
    ("theory", "benchmarks.theory_smoothing"),
    ("kernel", "benchmarks.kernel_bench"),
    ("serve", "benchmarks.serve_bench"),
    # decode-attention records are embedded in the kernel/serve suites
    # above (benchmarks/attn_bench.py); running the module here too would
    # measure everything twice. `python -m benchmarks.attn_bench` runs it
    # standalone (CSV only, JSON trajectory untouched).
]
SMOKE_MODULES = ("kernel", "serve")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: "
                         + ",".join(name for name, _ in MODULES))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-size run of the BENCH_*.json suites only")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.smoke and only is None:
        only = set(SMOKE_MODULES)
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()

    print("name,us_per_call,derived")
    failed = []
    for name, modname in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(modname, fromlist=["main"])
            if "smoke" in inspect.signature(mod.main).parameters:
                mod.main(smoke=args.smoke)
            else:
                mod.main()
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:
            failed.append(name)
            print(f"# {name} FAILED:\n{traceback.format_exc()}", file=sys.stderr)
    if failed:
        sys.exit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
