"""Benchmark harness entry point — one section per paper table/figure plus
the framework-integration benches. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig5 fig8  # subset
"""
from __future__ import annotations

import sys

from benchmarks import (bench_caching, bench_opt_time, bench_placement,
                        bench_prefix_cache, bench_scalability)

SECTIONS = {
    "fig5": ("Fig 5: caching strategies x budgets x formats",
             bench_caching.run),
    "fig6": ("Fig 6: 100-query improvement over file_lru",
             bench_scalability.run),
    "fig7": ("Fig 7: optimization time (chunking / evict+place)",
             bench_opt_time.run),
    "fig8": ("Fig 8: placement static vs dynamic", bench_placement.run),
    "prefix": ("KV prefix cache: cost vs LRU", bench_prefix_cache.run),
}


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    wanted = [a for a in sys.argv[1:] if a in SECTIONS] or list(SECTIONS)
    print("name,us_per_call,derived")
    for key in wanted:
        title, fn = SECTIONS[key]
        print(f"# {title}")
        fn()


if __name__ == "__main__":
    main()
