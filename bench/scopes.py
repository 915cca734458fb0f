"""What the program's own instrumentation says of a traced run: device time
by named scope, and the set-up spans.

A TPU trace names each device op by its HLO instruction and carries none of
its metadata. With its telemetry on, the program writes beside its events
the compiled round's op map, ``ops/<module>.json``: instruction name ->
``op_name``, whose path holds the ``jax.named_scope``s the op ran under
(``sl/client``, ``sl/link``, ``sl/server``, ``fl/client``, ``flash_bwd``).
Each traced op of the round is charged to the scopes in its ``op_name``; a
fusion carries its root op's. An op with no tier scope, or that the map does
not name, is ``unscoped``.

The run's telemetry lives where ``bench/run.py`` puts it,
``<bench>/.out/runs/<cell>/``; a reader passes its own ``__file__``
(``<bench>/metrics/<name>.py``) to find it. Where the program writes no map
or no such span, or the run has no device trace, the readings are None.
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Optional

from bench import trace as trace_mod

TIERS = ("sl/client", "sl/link", "sl/server", "fl/client")
UNSCOPED = "unscoped"


def run_dir(ctx, reader_file: str) -> str:
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(reader_file)))
    return os.path.join(bench_dir, ".out", "runs", ctx.cell.name)


def setup_spans(ctx, reader_file: str) -> list:
    """The program's span events without a ``round`` (``compile``,
    ``compile/data``, ``compile/params``, ``compile/flops``,
    ``compile/lower``), in the order they closed."""
    path = os.path.join(run_dir(ctx, reader_file), "events.jsonl")
    if not os.path.isfile(path):
        return []
    with open(path) as f:
        evs = [json.loads(line) for line in f if line.strip()]
    return [e for e in evs if e.get("ev") == "span" and "round" not in e]


def op_map(ctx, reader_file: str) -> Optional[dict]:
    """``{instruction: op_name}`` of the program's compiled round(s)."""
    files = sorted(glob.glob(os.path.join(run_dir(ctx, reader_file), "ops",
                                          "*.json")))
    if not files:
        return None
    ops = {}
    for path in files:
        with open(path) as f:
            ops.update(json.load(f)["ops"])
    return ops


def round_ops(ctx) -> list:
    """The traced rounds' op events of the busiest chip, loops and calls
    left out (their bodies' ops are events of their own)."""
    evs = ctx.device_events()
    if not evs or not ctx.traced_rounds or ctx.trace_window is None:
        return []
    lo, hi = ctx.trace_window
    busiest = max(evs, key=lambda d: trace_mod.busy_ns(evs[d], lo, hi))
    return [e for e in evs[busiest] if lo <= e.start_ns <= hi
            and not trace_mod.is_container(e.name)]


def scope_ns_per_round(ctx, scope: str, reader_file: str) -> Optional[float]:
    """Device ns per traced round of the ops whose scope path contains
    ``scope``, on the busiest chip."""
    ops = op_map(ctx, reader_file)
    events = round_ops(ctx)
    if ops is None or not events:
        return None
    ns = sum(e.dur_ns for e in events
             if scope in ops.get(trace_mod.op_name(e.name), ""))
    return ns / ctx.traced_rounds


def breakdown(ctx, reader_file: str, nested=("flash_bwd",),
              top: int = 8) -> Optional[dict]:
    """Seconds per traced round by tier scope (``unscoped``: in none) and
    by each ``nested`` scope (run inside the tiers), of all ops and of the
    busy union; with the ``top`` ops of each by time."""
    ops = op_map(ctx, reader_file)
    events = round_ops(ctx)
    if ops is None or not events:
        return None
    n = ctx.traced_rounds
    lo, hi = ctx.trace_window
    keys = TIERS + (UNSCOPED,) + tuple(nested)
    ns, names = {k: 0.0 for k in keys}, {k: {} for k in keys}
    for e in events:
        op = ops.get(trace_mod.op_name(e.name), "")
        hit = [next((t for t in TIERS if t in op), UNSCOPED)]
        for k in hit + [s for s in nested if s in op]:
            ns[k] += e.dur_ns
            short = trace_mod.short_name(e.name)
            names[k][short] = names[k].get(short, 0.0) + e.dur_ns
    return {
        "scopes_s": {k: 1e-9 * v / n for k, v in ns.items()},
        "ops_s": 1e-9 * sum(e.dur_ns for e in events) / n,
        "busy_s": 1e-9 * trace_mod.busy_ns(events, lo, hi) / n,
        "top": {k: [[name, 1e-9 * v / n] for name, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top]]
            for k, d in names.items()},
    }


def log_breakdown(ctx, reader_file: str) -> None:
    """The breakdown on standard error (the result line is standard
    output's)."""
    b = breakdown(ctx, reader_file)
    if b is not None:
        print(f"[{ctx.cell.name}] scopes per round: {json.dumps(b)}",
              file=sys.stderr, flush=True)
