"""Benchmark harness — one entry per paper table/figure plus the system
benchmarks. Prints ``name,us_per_call,derived`` CSV lines (one per bench)
and writes per-bench row CSVs under reports/bench/.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig1,...]
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback

# One XLA CPU device per core (before any jax import): the sweep runner
# shards the (scenario x seed) work grid across them. The concurrency-
# optimized scheduler measurably helps the scan-heavy sweep programs on
# CPU. An explicit XLA_FLAGS wins.
os.environ.setdefault(
    "XLA_FLAGS",
    f"--xla_force_host_platform_device_count={os.cpu_count() or 1}"
    " --xla_cpu_enable_concurrency_optimized_scheduler=true",
)

from benchmarks import (  # noqa: E402
    fig1_availability, fig2_capacity, fig3_stability, fig4_staleness,
    fig_adversarial, fig_convergence, fig_faults, fig_learning,
    fig_multizone,
)

BENCHES = {
    "fig1": fig1_availability.main,
    "fig2": fig2_capacity.main,
    "fig3": fig3_stability.main,
    "fig4": fig4_staleness.main,
    "fig_adversarial": fig_adversarial.main,
    "fig_convergence": fig_convergence.main,
    "fig_faults": fig_faults.main,
    "fig_learning": fig_learning.main,
    "fig_multizone": fig_multizone.main,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps (CI-sized)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    names = list(BENCHES) if not args.only else args.only.split(",")
    failures = 0
    print("name,us_per_call,derived")
    for n in names:
        try:
            BENCHES[n](quick=args.quick)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{n},FAILED,")
            traceback.print_exc()
    return failures


if __name__ == "__main__":
    sys.exit(main())
