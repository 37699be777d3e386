"""Benchmark harness entry point: one benchmark per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick]

  Fig. 12-14  -> bench_kernel_suite   (kernel suite across targets)
  §6.4        -> bench_horizontal     (DCT horizontal parallelization)
  Tables 3/4  -> bench_vml            (vecmathlib vs scalarized libm)
  §3          -> bench_bufalloc       (buffer allocator)
  §Roofline   -> roofline_report      (dry-run derived, if results exist)
  §4.1        -> bench_cache          (compile cache: cold vs hit dispatch)
  §3 runtime  -> bench_events         (event DAG overlap + co-execution)
  §4 pipeline -> bench_compile        (plan sharing across the target sweep)
  §3 memory   -> bench_memory         (map/unmap, pooling, ordered migration)
  §Serving    -> bench_serving        (continuous batching vs fixed-slot)
  §Fusion     -> bench_fusion         (DAG-fused chain vs per-kernel launches)
  §Scoreboard -> bench_scoreboard     (suite x target roofline matrix)
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="results/bench")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    summary = {}

    t0 = time.time()
    print("=" * 72)
    print("[1/15] Kernel suite across execution targets (paper Fig. 12-14)")
    print("=" * 72)
    from . import bench_kernel_suite
    res = bench_kernel_suite.main()
    summary["kernel_suite"] = {k: v for k, v in res.items()}

    print()
    print("=" * 72)
    print("[2/15] DCT horizontal inner-loop parallelization (paper §6.4)")
    print("=" * 72)
    from . import bench_horizontal
    summary["horizontal"] = bench_horizontal.main()

    print()
    print("=" * 72)
    print("[3/15] Vecmathlib vs scalarized libm (paper Tables 3/4)")
    print("=" * 72)
    from . import bench_vml
    res = bench_vml.main()
    summary["vml"] = {f"{k[0]}_{k[1]}": v for k, v in res.items()}

    print()
    print("=" * 72)
    print("[4/15] Bufalloc (paper §3)")
    print("=" * 72)
    from . import bench_bufalloc
    summary["bufalloc"] = bench_bufalloc.main()

    print()
    print("=" * 72)
    print("[5/15] Context-array uniform merging (paper §4.7)")
    print("=" * 72)
    from . import bench_context
    summary["context"] = bench_context.main()

    print()
    print("=" * 72)
    print("[6/15] Compilation cache: cold vs cache-hit dispatch (§4.1)")
    print("=" * 72)
    from . import bench_cache
    summary["cache"] = bench_cache.main()

    print()
    print("=" * 72)
    print("[7/15] Event-DAG runtime: overlap + multi-device co-execution (§3)")
    print("=" * 72)
    from . import bench_events
    summary["events"] = bench_events.main()

    print()
    print("=" * 72)
    print("[8/15] Pass-manager plan sharing: cold autotune compile (§4)")
    print("=" * 72)
    from . import bench_compile
    summary["compile"] = bench_compile.main()

    print()
    print("=" * 72)
    print("[9/15] Hierarchical memory: map/unmap, pool, migration (§3)")
    print("=" * 72)
    from . import bench_memory
    summary["memory"] = bench_memory.main()

    print()
    print("=" * 72)
    print("[10/15] Continuous-batching serving scheduler (vs fixed-slot)")
    print("=" * 72)
    from . import bench_serving
    summary["serving"] = bench_serving.main(ci=args.quick)

    print()
    print("=" * 72)
    print("[11/15] Adaptive N-device co-execution vs static (§Scheduler)")
    print("=" * 72)
    from . import bench_coexec
    summary["coexec"] = bench_coexec.main()

    print()
    print("=" * 72)
    print("[12/15] DAG-level kernel fusion vs per-kernel launches (§Fusion)")
    print("=" * 72)
    from . import bench_fusion
    summary["fusion"] = bench_fusion.main()

    print()
    print("=" * 72)
    print("[13/15] Replicated mesh: kill-one-of-three fault recovery")
    print("=" * 72)
    from . import bench_mesh
    summary["mesh"] = bench_mesh.main(ci=args.quick)

    print()
    print("=" * 72)
    print("[14/15] Performance-portability scoreboard (Figs. 12-14, Rupp)")
    print("=" * 72)
    from . import bench_scoreboard
    summary["scoreboard"] = bench_scoreboard.main(
        ["--ci"] if args.quick else [])["gates"]

    print()
    print("=" * 72)
    print("[15/15] Roofline report (dry-run derived)")
    print("=" * 72)
    from . import roofline_report
    roofline_report.main()

    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=float)
    print(f"\nall benchmarks done in {time.time() - t0:.1f}s; "
          f"summary -> {args.out}/summary.json")


if __name__ == "__main__":
    from repro.backend import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    main()
