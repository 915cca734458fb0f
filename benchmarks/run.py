"""Benchmark runner — one entry per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--full]

Prints ``name,case,us_per_call,derived`` CSV lines per bench.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache

enable_compile_cache()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the CNN training bench (fig3)")
    ap.add_argument("--full", action="store_true",
                    help="full fig3 sweep (3 backbones x 5 settings)")
    args = ap.parse_args()

    t0 = time.time()
    print("# bench_deployment (paper Fig. 2)")
    from . import bench_deployment
    bench_deployment.run()

    print("# bench_uav_energy (paper Table II)")
    from . import bench_uav_energy
    bench_uav_energy.run()

    print("# bench_rounds (paper Eq. 6 / Alg. 2)")
    from . import bench_rounds
    bench_rounds.run()

    print("# bench_resource (paper Table III)")
    from . import bench_resource
    bench_resource.run()

    print("# bench_engine_perf (host-loop vs scanned vs fleet engines; "
          "appends results/engine_perf.json)")
    from . import bench_engine_perf
    bench_engine_perf.run()

    if not args.fast:
        print("# bench_sl_accuracy (paper Fig. 3) — trains CNNs, takes minutes")
        from . import bench_sl_accuracy
        if args.full:
            bench_sl_accuracy.run(
                models=("resnet18", "googlenet", "mobilenetv2"),
                settings=("FL", "SL_75_25", "SL_40_60", "SL_25_75",
                          "SL_15_85"))
        else:
            bench_sl_accuracy.run()

    print("# roofline (dry-run derived; deliverable g)")
    from . import roofline
    roofline.run()

    print(f"# all benches done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
