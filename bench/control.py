"""The readings that set a cell's limits (``bench/limits/<cell>.json``).

    python3 bench/control.py --workload <name> --seeds 11,12,13 [--program]

For each seed it computes the plain reference's first rounds at full
precision, and compares with it, by ``bench/check.py``'s numbers:

* the control: the reference with the operands of its products in float8,
  put in the program's place (the upper readings; the program's products
  take bfloat16 operands at the platform's default precision, so float8
  is the precision below the one the configuration states);
* the reference computed in bfloat16 throughout (a second reading);
* a planted fault: the reference with half of every batch left out, the
  mean taken over the rest;
* with ``--program``, the program's own first rounds (the lower readings;
  the benchmark's runs print the same numbers in their check).

A state left unchanged reads 1 on ``change_gap`` by construction and needs
no run. Prints one JSON line per seed and reading. Needs the chip, like
``run.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, run, workload as wl  # noqa: E402


def readings(workload: str, seed: int, *, program: bool,
             allow_cpu: bool = False, root: str = ROOT) -> list:
    wl.add_program_path(ROOT)
    if not allow_cpu:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
    cell = wl.load_cell(workload, root=root,
                        bench_dir=os.path.join(root, "bench"))
    run.device_info(cell.chips, allow_cpu)
    out = []
    data = wl.make_data(cell, seed)
    if program:
        plan, state, prog, data, _ = run.start_program(cell, seed)
        del plan, state
        gc.collect()
    t = time.perf_counter()
    ref = run.reference_reading(cell, seed, data)
    ref_s = time.perf_counter() - t
    if program:
        out.append({"reading": "program", **check.compare(prog, ref)})
    for p in ("fp8", "bf16"):
        out.append({"reading": f"control_{p}", **check.compare(
            run.reference_reading(cell, seed, data, precision=p), ref)})
    out.append({"reading": "fault_half_batch", **check.compare(
        run.reference_reading(cell, seed, data, batch_part=0.5), ref)})
    for o in out:
        o.update(workload=workload, seed=seed, reference_s=ref_s,
                 device=run.device_info(cell.chips, allow_cpu)[0].device_kind)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    try:
        for s in args.seeds.split(","):
            for r in readings(args.workload, int(s), program=args.program):
                print(json.dumps(r), flush=True)
    except run.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
