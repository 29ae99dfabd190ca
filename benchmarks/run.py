"""Benchmark orchestrator: one module per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            (quick sizes)
    REPRO_BENCH_FULL=1 ... python -m benchmarks.run    (paper-fidelity sizes)
"""
from __future__ import annotations

import sys
import traceback

MODULES = (
    "fig1_toy",      # Fig 1: toy phase diagram + Claim 4.10 boundary
    "fig2_star",     # Fig 2: star graphs (a-d)
    "fig3_grid",     # Fig 3: grid efficiency, MSE vs n, ADMM convergence
    "fig4_large",    # Fig 4: 100-node scale-free + Euclidean
    "comm_cost",     # Sec. 1/3 communication-cost table
    "anytime_stream",  # streaming any-time engine over a lossy network
    "kernels_bench",  # kernel-path comparison rows + HLO rooflines
    "arch_steps",    # assigned-architecture step smoke timings
)


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    failures = []
    for name in MODULES:
        print(f"# === benchmarks.{name} ===", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"# FAILED modules: {failures}")
        sys.exit(1)
    print("# all benchmark modules completed")


if __name__ == "__main__":
    main()
