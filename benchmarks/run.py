"""Benchmark aggregator: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (paper mapping in DESIGN.md S8):
  Fig. 6b -> bench_spmm         Fig. 6c -> bench_spmspm
  Tab. 1  -> bench_precision
The stencil of Fig. 6a is measured on the chip by ``bench/run.py``
(cell ``j3d27pt.jacobi``).
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import bench_precision, bench_spmm, bench_spmspm
    sections = [
        ("Fig6b/spmm", bench_spmm),
        ("Fig6c/spmspm", bench_spmspm),
        ("Tab1/precision", bench_precision),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for title, mod in sections:
        print(f"# --- {title} ---")
        try:
            for r in mod.run():
                print(r)
        except Exception:
            failures += 1
            print(f"# SECTION FAILED: {title}", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
