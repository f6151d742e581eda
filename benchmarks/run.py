"""Benchmark harness entry point: one benchmark per paper table/figure plus
the kernel-engine table.  ``python -m benchmarks.run [--fast]``."""
import argparse
import time

from repro import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the slow Credit / traffic-scale workloads")
    args = ap.parse_args()

    from . import (fig6_energy_throughput, fig7_nonidealities, kernel_bench,
                   table4_dcap, table5_tiles, table6_comparison)
    from .common import emit

    t0 = time.time()
    table4_dcap.main()
    if args.fast:
        emit(fig6_energy_throughput.run(
            ["iris", "cancer", "haberman", "car"]), "Fig 6 (fast subset)")
        emit(fig7_nonidealities.run(("cancer",), trials=2),
             "Fig 7 (fast subset)")
    else:
        table5_tiles.main()
        fig6_energy_throughput.main()
        fig7_nonidealities.main()
        table6_comparison.main()
        kernel_bench.main()
    print(f"# total {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
