"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Order: paper-figure reproduction (Figs. 2-3, reduced-faithful by default;
--full for the paper's exact N=100/T=500/5-seed scale), microbenchmarks,
then the roofline table assembled from whatever dry-run results exist.
"""
from __future__ import annotations

import sys


def main():
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    full = "--full" in sys.argv
    from benchmarks import micro, paper_figs, roofline_table

    print("=" * 72)
    print("BENCH 1/5: paper Figs. 2-3 reproduction (CA-AFL vs baselines)")
    print("=" * 72)
    checks = paper_figs.main(full=full)
    failed = [k for k, v in checks.items()
              if k.startswith("claim_") and v is False]
    if failed:
        print(f"!! claims not reproduced this run: {failed}")

    print("=" * 72)
    print("BENCH 2/5: microbenchmarks (selection scalability, kernel model)")
    print("=" * 72)
    micro.main()

    print("=" * 72)
    print("BENCH 3/5: roofline table from dry-run artifacts")
    print("=" * 72)
    roofline_table.main()

    print("=" * 72)
    print("BENCH 4/5: beyond-paper ablations (noise robustness, fading)")
    print("=" * 72)
    from benchmarks import ablations
    ablations.main()

    print("=" * 72)
    print("BENCH 5/5: batched sweep-engine smoke (BENCH_sweep.json)")
    print("=" * 72)
    from benchmarks import sweep_smoke
    sweep_smoke.main()


if __name__ == "__main__":
    main()
