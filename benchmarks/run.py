"""Benchmark runner: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick | --smoke]

--quick runs the sims at 15k inferences instead of the paper's 150k
(identical code paths, ~10x faster; claim tolerances unchanged).
--smoke is the CI job: tiny sizes, only the benchmarks whose claims are
scale-free (hardware table, continuous batching, mixed backfill).
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny sizes, scale-free claims only")
    args = ap.parse_args(argv)
    n_total = 15_000 if args.quick else 150_000

    from . import (bench_table1_hardware, bench_fig4_scaling_efforts,
                   bench_fig5_table2_task_times, bench_fig6_busy_cluster,
                   bench_fig7_resilience, bench_claims,
                   bench_batch_policy, bench_context_plane,
                   bench_continuous_batching, bench_disagg, bench_elastic,
                   bench_faults, bench_gateway)

    t0 = time.time()
    if args.smoke:
        bench_table1_hardware.main()
        bench_continuous_batching.main(n_requests=120, n_workers=8)
        # asserts plan/executed byte-accounting equality and the
        # budgeted-vs-idle staging-makespan criterion
        bench_context_plane.main(smoke=True)
        # asserts interactive p95 <= 1.2x unloaded under 10x batch
        # overload at equal batch work, token-exact suspend/resume, and
        # zero slot/page accounting leaks
        bench_gateway.main(smoke=True)
        # asserts disaggregated routing >= colocated throughput at equal
        # completed work, shipped-KV decode token-exact,
        # and zero KV byte leaks (planned == moved incl KV_SHIP)
        bench_disagg.main(smoke=True)
        # asserts forecast-driven elastic supply strictly beats the
        # reactive EWMA baseline on goodput under burst-then-storm at
        # equal completed work, with zero slot/byte leaks after storms
        bench_elastic.main(smoke=True)
        # asserts checkpointed resume strictly beats restart-fresh on
        # goodput AND wasted decode tokens under a seeded crash storm at
        # equal completed work, crash detection within one lease, zero
        # slot/page/byte leaks, and token-exact checkpoint/adopt resume
        bench_faults.main(smoke=True)
        print(f"\nsmoke benchmarks done in {time.time()-t0:.1f}s")
        return 0
    bench_table1_hardware.main()
    res4 = bench_fig4_scaling_efforts.run_all(150_000)   # claims need paper scale
    bench_fig4_scaling_efforts.main(res=res4)
    bench_fig5_table2_task_times.main(n_total)
    res6 = bench_fig6_busy_cluster.run_pair(150_000)
    bench_fig6_busy_cluster.main(res=res6)
    bench_fig6_busy_cluster.main_mixed()
    bench_fig7_resilience.main(n_total)
    bench_fig7_resilience.main_storms(n_total)
    bench_claims.main(res=res4, drain=res6)
    bench_batch_policy.main(n_total)
    bench_batch_policy.main_mixed()
    bench_continuous_batching.main()
    bench_context_plane.main()
    bench_gateway.main()
    bench_disagg.main()
    bench_elastic.main()
    bench_faults.main()
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
