"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``: a configuration
(``bench/configs/``) under a traffic mix (``bench/traffic/``). The run

1. makes the cell's data from the seed on the device, compiles the job
   through the program's entry point (``compile_experiment``) and drives
   its first rounds through ``Plan.run_round``, as ``Plan.run`` does; these
   rounds compile and warm every program the window uses, and the program's
   state after round 1 and after round 3 is read for the comparison;
2. runs rounds back to back through the same ``Plan.run_round`` for
   ``--seconds`` (closed loop), timing each round on the host clock;
3. with ``--trace 1`` turns on the program's spans and captures a profiler
   trace of the window's first rounds, and reports the per-layer metrics
   instead of the end-to-end ones;
4. frees the program, runs the plain reference (``bench/reference/``)
   over the same rows from the same seed, and compares (``bench/check.py``).

What depends on the model is found by the configuration's ``family``, one
file per family and kind in the cell's bench dir: ``jobs/<family>.py``
(the data and the ``ExperimentSpec``), ``reference/<family>.py`` (the
plain reference) and ``flops/<family>.py`` (the model FLOPs).

Every metric is read by a reader, ``bench/metrics/<name>.py`` (or, for a
name split by the metric it moves, such as ``mfu.tokens``, the
quantity's ``bench/metrics/mfu.py``), from what the run recorded. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``check``: each number compared with
its limit); the last lines of standard error repeat the check.

It exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Callable, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# import the harness as the package ``bench`` (its ``trace`` module must not
# shadow the standard library's for everything else in the process)
if sys.path and os.path.abspath(sys.path[0]) == BENCH:
    sys.path.pop(0)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, workload as wl  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

TRACE_SECONDS = 4.0      # profiler window: the first rounds of this length
#                          (at most a quarter of the window)
FIRST_ROUNDS = 3         # rounds before the window, compared with the reference


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what a metric reader sees
# ---------------------------------------------------------------------------

class Context:
    """What one run recorded, for the readers in ``bench/metrics/``."""

    def __init__(self, cell, *, setup_s, rounds, window_s, chips, peaks):
        self.cell = cell
        self.setup_s = setup_s
        self.rounds = rounds          # [{"dur_s", "samples", "tokens"}]
        self.window_s = window_s
        self.chips = chips
        self.peaks = peaks            # this device's row of peaks.json
        self.spans = []               # program span events of the window
        self.trace = None             # trace.Trace of the traced rounds
        self.trace_window = None      # (lo, hi) ns on the trace clock
        self.traced_rounds = 0
        self.flops_per_round = wl.model_flops_per_round(cell)

    # ---- host clock ----
    def work(self, unit: str) -> float:
        return float(sum(r[unit] for r in self.rounds))

    def round_ms(self) -> list:
        return [1e3 * r["dur_s"] for r in self.rounds]

    # ---- program spans ----
    def host_ms(self) -> Optional[float]:
        """Mean per round of the round span less its fenced device wait."""
        rounds = {e["round"]: e["dur_s"] for e in self.spans
                  if e.get("name") == "round"}
        sync = {e["round"]: e["sync_s"] for e in self.spans
                if e.get("name") == "round/execute"}
        vals = [rounds[r] - sync[r] for r in rounds if r in sync]
        return 1e3 * sum(vals) / len(vals) if vals else None

    # ---- device trace ----
    def device_events(self):
        if self.trace is None:
            return {}
        return {d: self.trace.ops[d] for d in self.trace.devices[:self.chips]}

    def busy_share(self) -> Optional[float]:
        evs = self.device_events()
        if not evs or self.trace_window is None:
            return None
        lo, hi = self.trace_window
        return sum(trace_mod.busy_ns(e, lo, hi) for e in evs.values()) / (
            len(evs) * (hi - lo))

    def kernel_ns_per_round(self, pattern: str) -> Optional[tuple]:
        """(events per round, device ns per round) of the kernel whose
        trace name matches ``pattern``, on the busiest chip."""
        evs = self.device_events()
        if not evs or not self.traced_rounds:
            return None
        lo, hi = self.trace_window
        best = None
        for e in evs.values():
            inside = [x for x in e if lo <= x.start_ns <= hi]
            n, ns = trace_mod.time_by_name(inside, pattern)
            if n and (best is None or ns > best[1]):
                best = (n, ns)
        if best is None:
            return None
        return best[0] / self.traced_rounds, best[1] / self.traced_rounds


def reader_path(name: str, bench_dir: str = BENCH) -> str:
    """``metrics/<name>.py``, else ``metrics/<name up to its first dot>.py``:
    the quantity's one reader serves each of its split names
    (``mfu.tokens``, ``mfu.samples``)."""
    d = os.path.join(bench_dir, "metrics")
    own = os.path.join(d, f"{name}.py")
    return own if os.path.isfile(own) else os.path.join(
        d, name.split(".")[0] + ".py")


def load_reader(name: str, bench_dir: str = BENCH) -> Callable:
    return wl.load_module(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
        reader_path(name, bench_dir)).read


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports in this mode: the end-to-end
    ones (``--trace 0``) or the per-layer ones (``--trace 1``) that list the
    cell, or that list no cells and move a metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(chips: int, allow_cpu: bool):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"JAX found no TPU (first device: {devs[0].platform}); "
                     f"this benchmark reports device metrics only from one")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def peaks_of(kind: str, allow_cpu: bool, bench_dir: str = BENCH) -> dict:
    table = wl.load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if kind not in table:
        if allow_cpu:
            return {}
        raise NoChip(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def start_program(cell, seed: int, *, obs=None, plan_hook=None):
    """Compile the cell's job through the program's entry point and drive
    its first rounds through ``Plan.run_round``; returns ``(plan, state,
    reading, data, last round seconds)``. ``reading`` holds each round's
    loss, the norms of Adam's first moments after round 1 and of each
    leaf's change after the last of the rounds."""
    import jax
    from repro.api import compile_experiment

    from bench.reference.common import change_norms, leaf_norms
    t = time.perf_counter()
    data = wl.make_data(cell, seed)
    t_data = time.perf_counter()
    plan = compile_experiment(wl.make_spec(cell, seed), data=data,
                              mesh=wl.make_mesh(cell), obs=obs)
    t_plan = time.perf_counter()
    if plan_hook is not None:
        plan_hook(plan)
    with_eval = bool(cell.traffic["eval_every_round"])
    views = lambda st: wl.state_views(cell, st.engine_state)  # noqa: E731
    copy = jax.jit(lambda t: jax.tree_util.tree_map(lambda v: v + 0, t))
    state = plan.init()
    p0 = copy(views(state)["params"])
    reading = {"losses": [], "moment1": None}
    for r in range(FIRST_ROUNDS):
        t_round = time.perf_counter()
        state, rec = plan.run_round(state, with_eval=with_eval)
        last_s = time.perf_counter() - t_round
        reading["losses"].append(float(rec.loss))
        if r == 0:
            t_first = time.perf_counter()
            if views(state)["moment"] is not None:
                reading["moment1"] = leaf_norms(views(state)["moment"])
    reading["change"] = change_norms(views(state)["params"], p0)
    log(f"[{cell.name}] set-up phases: data_s={t_data - t!r} "
        f"compile_experiment_s={t_plan - t_data!r} "
        f"first_round_s={t_first - t_plan!r}")
    return plan, state, reading, data, last_s


def reference_reading(cell, seed: int, data, *, precision: str = "highest",
                      batch_part: float = 1.0) -> dict:
    """The plain reference over the same first rounds: the rows the batch
    stream names for them (the first ``batch_part`` of each batch)."""
    ref_mod = wl.family_module(cell, "reference")
    stream = wl.BatchStream(cell, data[1], seed)
    rows = [stream.next_round() for _ in range(FIRST_ROUNDS)]
    keep = max(1, int(round(cell.batch * batch_part)))
    rows = [r[:, :, :keep] for r in rows]
    return ref_mod.Reference(cell, seed, precision=precision).run(
        data[0], data[1], rows)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, allow_cpu: bool = False,
             plan_hook: Optional[Callable] = None, root: str = ROOT) -> dict:
    """One run of one cell; returns the result object. ``plan_hook(plan)``
    lets a test break the timed path underneath the harness."""
    t_start = T_START if t_start is None else t_start
    bench_dir = os.path.join(root, "bench")
    wl.add_program_path(ROOT)
    if not allow_cpu:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
    import jax
    from repro.obs import ObsConfig
    from repro.obs.gauges import global_counter

    bench = wl.benchmark_file(root)
    cell = wl.load_cell(workload, root=root, bench_dir=bench_dir)
    devs = device_info(cell.chips, allow_cpu)
    peaks = peaks_of(devs[0].device_kind, allow_cpu, bench_dir)
    compiles = global_counter().install()
    out_dir = os.path.join(bench_dir, ".out")
    run_dir = os.path.join(out_dir, "runs", workload)
    trace_dir = os.path.join(out_dir, "trace", workload)
    obs = None
    if trace:
        shutil.rmtree(run_dir, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs = ObsConfig(run_root=os.path.dirname(run_dir),
                        run_id=os.path.basename(run_dir), gauge_every=0)

    # ---- set-up: the first rounds compile, warm and are compared ----------
    plan, state, prog, data, last_s = start_program(cell, seed, obs=obs,
                                                    plan_hook=plan_hook)
    with_eval = bool(cell.traffic["eval_every_round"])
    c0 = compiles.count
    traced_s = min(TRACE_SECONDS, seconds / 4)
    n_traced = (max(2, math.ceil(traced_s / max(last_s, 1e-3)))
                if trace else 0)
    if trace:
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    log(f"[{workload}] setup_s={setup_s!r} first_losses={prog['losses']!r} "
        f"last_setup_round_s={last_s!r}")

    # ---- the window: rounds back to back ----------------------------------
    rounds, marks, failed, rec = [], [], 0, None
    per = {"samples": cell.samples_per_round, "tokens": cell.tokens_per_round}
    t0 = t_measured = time.perf_counter()
    n_measured0 = 0
    while True:
        ts = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench/round"):
                state, rec = plan.run_round(state, with_eval=with_eval)
            ok = math.isfinite(rec.loss)
        except Exception as e:   # a round that raises counts as failed
            log(f"[{workload}] round {len(marks) + 1} raised "
                f"{type(e).__name__}: {e}")
            ok = False
        te = time.perf_counter()
        marks.append((ts, te))
        if ok:
            active = rec.active_clients / cell.clients
            rounds.append({"dur_s": te - ts,
                           "samples": per["samples"] * active,
                           "tokens": per["tokens"] * active})
        else:
            failed += 1
        if trace and len(marks) == n_traced:
            jax.profiler.stop_trace()
            t_measured, n_measured0 = time.perf_counter(), len(rounds)
        if te - t0 >= seconds or not ok:
            break
    t_end = time.perf_counter()
    if trace and len(marks) < n_traced:
        jax.profiler.stop_trace()
    peak = memory_peak(devs)
    durs = sorted(e - s for s, e in marks)
    log(f"[{workload}] rounds={len(marks)} failed={failed} "
        f"window_s={t_end - t0!r} compiles_in_window={compiles.count - c0} "
        f"memory_peak_bytes={peak} round_s_min={durs[0]!r} "
        f"round_s_median={durs[len(durs) // 2]!r} round_s_max={durs[-1]!r}")

    # with the profiler on, the host-clock readings of the traced run are
    # taken over the rounds after the traced ones
    ctx = Context(cell, setup_s=setup_s, rounds=rounds[n_measured0:],
                  window_s=t_end - t_measured, chips=cell.chips, peaks=peaks)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        plan.obs.flush()
        spans = read_spans(run_dir, first_round=FIRST_ROUNDS)
        ctx.spans = [e for e in spans
                     if e["round"] >= FIRST_ROUNDS + n_traced]
        breakdown = attach_trace(ctx, trace_dir, marks[:n_traced],
                                 plan.obs.timeline.t0, spans)
        device["busy_s"] = breakdown.pop("busy_s")
        device["window_s"] = breakdown.pop("window_s")
        log(f"[{workload}] breakdown={json.dumps(breakdown)}")

    # ---- free the program, then the reference -----------------------------
    del state, plan, rec
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_reading(cell, seed, data)
    gaps = check.compare(prog, ref)
    log(f"[{workload}] reference_s={time.perf_counter() - t_ref!r} "
        f"ref_losses={ref['losses']!r} gaps={gaps!r}")
    ok, checked = check.verdict(gaps, check.limits_of(bench_dir, workload))

    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        v = load_reader(m["name"], bench_dir)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": bool(ok and failed == 0 and rounds),
              "attempted": len(marks), "failed": failed, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": v, "limit": lim} for k, v, lim in checked}
    return result


def read_spans(run_dir: str, first_round: int) -> list:
    out = []
    path = os.path.join(run_dir, "events.jsonl")
    with open(path) as f:
        for line in f:
            ev = json.loads(line)
            if ev.get("ev") == "span" and ev.get("round", -1) >= first_round:
                out.append(ev)
    return out


def attach_trace(ctx: Context, trace_dir: str, marks: list,
                 tl_t0: float, all_spans: list) -> dict:
    """Read the profiler trace into ``ctx`` and return the breakdown with
    ``busy_s`` and ``window_s`` of the traced rounds."""
    tr = trace_mod.load(trace_mod.latest_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    ann = [m for m in tr.marks if m.name == "bench/round"]
    ctx.trace, ctx.traced_rounds = tr, len(ann)
    if not ann or not tr.ops:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": []}
    lo, hi = ctx.trace_window = trace_mod.window(tr)
    # host perf_counter seconds -> trace ns, from the annotated rounds
    offs = [a.start_ns - 1e9 * m[0] for a, m in zip(ann, marks)]
    off = sorted(offs)[len(offs) // 2]
    spans = [(e["name"], 1e9 * (tl_t0 + e["t"]) + off,
              1e9 * (tl_t0 + e["t"] + e["dur_s"]) + off)
             for e in all_spans if e.get("name", "").startswith("round/")]
    evs = ctx.device_events()
    busiest = max(evs, key=lambda d: trace_mod.busy_ns(evs[d], lo, hi))
    busy = [trace_mod.busy_ns(e, lo, hi) for e in evs.values()]
    inside = [e for e in evs[busiest] if lo <= e.start_ns <= hi]
    log(f"trace: devices={sorted(evs)} rounds={len(ann)} ops={len(inside)} "
        f"by_time={json.dumps(trace_mod.top_ops(inside, 40))}")
    return {"busy_s": 1e-9 * sum(busy) / len(busy), "window_s": 1e-9 * (hi - lo),
            "device_ops": trace_mod.top_ops(inside),
            "idle_gaps": trace_mod.label_gaps(
                trace_mod.gaps(evs[busiest], lo, hi), spans)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        log(f"bench/run.py: {e}")
        return 2
    for name, c in result["check"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    log(f"correct {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
