"""Engine perf: tracked steps/sec log across engine variants and PRs.

Measures steps/sec of one SL global round (Algorithm 3) and one FL round on
the same model, data and optimizer state across the engine generations.
Every compiled variant is now built from the SAME ``ExperimentSpec`` —
only the ``EngineSpec`` field changes; the seed's host loop stays
hand-wired as the historical baseline:

  sl_host_loop : the seed's host loop — one jitted split step per
                 (client, local step), per-step Python dispatch.
  sl_scanned   : spec ``sl/scan`` — ``make_multi_client_round``; whole
                 round one compiled program (nested scan, FedAvg inside).
  sl_fleet     : spec ``sl/vmap`` — parallel split learning, client axis
                 vmapped (shardable over `data`).
  sl_shard_map : spec ``sl/shard_map`` — the explicit-collective variant
                 (in-map ``lax.pmean`` server gradient, ``fedavg_pmean``
                 FedAvg); the sl_shard_map/sl_fleet ratio prices the
                 pinned collective schedule vs GSPMD inference.
  fl_scan      : spec ``fl/scan`` — ``make_fl_round(client_axis='scan')``.
  fl_vmap      : spec ``fl/vmap`` — the fl_vmap/fl_scan ratio is the
                 measured steps/s delta bought by the loosened
                 FLEET_EQUIV_ATOL equivalence bound.
  fl_shard_map : spec ``fl/shard_map`` — explicit ``fedavg_pmean`` FedAvg.
  mc_vmap      : ``repro.sim.run_monte_carlo(mode='vmap')`` — one jitted
                 vmap-over-seeds scenario rollout (stochastic channel +
                 markov availability, 16 seeds).
  mc_loop      : the same rollout dispatched per (seed, round) from Python
                 — the idealized-campaign execution model. The
                 mc_vmap/mc_loop ratio is the vectorization win the
                 acceptance gate holds at >= 3x on XLA:CPU.
  fl_cohort    : spec ``fl/vmap`` with ``ClientSpec.population=M`` — one
                 round trains a sampled cohort of 8 from M registered
                 clients (stateless FL rounds). Logged per M (1e4/1e5/1e6
                 by default) with the engine-state byte size, which must
                 NOT grow with M (the O(cohort) claim).
  sl_cohort    : the same over ``sl/vmap`` — the EPSL shared client tier
                 (one client model broadcast across the cohort axis).

Results append to ``results/engine_perf.json`` as a per-PR log — one row
per (commit, model, case, variant):

    {"commit": "...", "bench": "engine_perf", "model": "tinycnn",
     "case": "c4s4b16", "variant": "sl_fleet", "steps_per_s": 301.2}

and print as the usual ``bench,case,us_per_call,derived`` CSV.
``benchmarks/report.py --check`` reads the log and flags >10% steps/s
regressions between the last two logged commits.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.api import (ClientSpec, CutPolicy, DataSpec, EngineSpec,  # noqa: E402
                       ExperimentSpec, ModelSpec, compile_experiment)
from repro.core.split import SplitStep, apply_stages  # noqa: E402
from repro.models.cnn import CNN_BUILDERS, cross_entropy_loss  # noqa: E402
from repro.obs import (NULL_OBS, Obs, ObsConfig, pytree_bytes,  # noqa: E402
                       time_fenced)
from repro.optim import adamw, apply_updates  # noqa: E402

CACHE = "results/engine_perf.json"


def _commit() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              capture_output=True, text=True,
                              timeout=10).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _base_spec(model: str, clients: int, steps: int, batch: int,
               image: int) -> ExperimentSpec:
    return ExperimentSpec(
        model=ModelSpec(name=model, num_classes=12),
        data=DataSpec(kind="synthetic", image_size=image,
                      classes_per_client=3),
        clients=ClientSpec(num_clients=clients),
        cut_policy=CutPolicy(mode="fraction", fraction=0.25),
        engine=EngineSpec(kind="sl", client_axis="scan"),
        local_steps=steps, batch_size=batch)


def bench_spec_variant(spec: ExperimentSpec, *, rounds: int,
                       obs: Obs = NULL_OBS) -> float:
    """steps/sec of one compiled plan variant (post-warmup). The same
    fixed batch stack drives every round via ``Plan.raw_round`` — rounds
    queue back-to-back with ONE block at the end (``obs.time_fenced``),
    like the legacy bench (``run_round``'s per-round record assembly
    would serialize dispatch)."""
    plan = compile_experiment(spec, obs=obs)
    state = plan.init()
    batches = plan.round_batches(state)
    es = state.engine_state
    # warmup / compile (*_: metrics-bus taps when --obs compiled them in)
    es, losses, *_ = plan.raw_round(es, batches)
    jax.block_until_ready(losses)

    def one_round():
        nonlocal es
        es, losses, *_ = plan.raw_round(es, batches)
        return losses

    wall = time_fenced(one_round, repeats=rounds)
    n = spec.clients.num_clients * spec.local_steps
    return rounds * n / wall


def bench_sl_host_loop(spec: ExperimentSpec, *, rounds: int,
                       obs: Obs = NULL_OBS) -> float:
    """Seed-style per-step dispatch; returns steps/sec (post-warmup)."""
    plan = compile_experiment(spec, obs=obs)
    clients, steps = spec.clients.num_clients, spec.local_steps
    k = plan.cut_of_client[0]
    stages, params = plan.stages, plan.params0
    cs, cp0 = list(stages[:k]), list(params[:k])
    ss, sp = list(stages[k:]), list(params[k:])
    step = SplitStep(
        client_fwd=lambda pc, xx: apply_stages(cs, pc, xx),
        server_loss=lambda ps, sm, yy: (
            cross_entropy_loss(apply_stages(ss, ps, sm), yy), {}),
    )
    batches = plan.round_batches(plan.init())
    bx, by = batches["inputs"], batches["targets"]
    opt_c, opt_s = adamw(spec.lr), adamw(spec.lr)

    @jax.jit
    def split_step(cp, cop, spar, sop, xx, yy):
        loss, _, gc, gs = step.grads(cp, spar, {"inputs": xx, "targets": yy})
        upc, cop = opt_c.update(gc, cop, cp)
        ups, sop = opt_s.update(gs, sop, spar)
        return apply_updates(cp, upc), cop, apply_updates(spar, ups), sop, loss

    cps = [jax.tree_util.tree_map(jnp.copy, cp0) for _ in range(clients)]
    cops = [opt_c.init(cp0) for _ in range(clients)]
    spar, sop = sp, opt_s.init(sp)
    # warmup / compile
    split_step(cps[0], cops[0], spar, sop, bx[0, 0], by[0, 0])

    def one_round():
        nonlocal spar, sop
        loss = None
        for si in range(steps):
            for ci in range(clients):
                cps[ci], cops[ci], spar, sop, loss = split_step(
                    cps[ci], cops[ci], spar, sop, bx[ci, si], by[ci, si])
        return loss

    wall = time_fenced(one_round, repeats=rounds)
    return rounds * steps * clients / wall


def bench_monte_carlo(model: str, *, clients: int = 4, steps: int = 2,
                      batch: int = 8, image: int = 16, seeds: int = 16,
                      mc_rounds: int = 20,
                      obs: Obs = NULL_OBS) -> dict[str, float]:
    """steps/sec of the vectorized vs per-seed-looped Monte-Carlo scenario
    rollout (``repro.sim.run_monte_carlo``) on a stochastic campaign —
    a2g channel + markov availability over a UAV mission. Both modes run
    the identical per-round program; only the dispatch differs."""
    from repro.api import MissionSpec
    from repro.sim import (AvailabilityParams, ChannelParams, ScenarioSpec,
                           run_monte_carlo)
    spec = dataclasses.replace(
        _base_spec(model, clients, steps, batch, image),
        engine=EngineSpec(kind="sl", client_axis="vmap"),
        mission=MissionSpec(farm_acres=100.0),
        scenario=ScenarioSpec(
            channel=ChannelParams(kind="a2g"),
            availability=AvailabilityParams(kind="markov", p_drop=0.3,
                                            p_recover=0.5)))
    plan = compile_experiment(spec, obs=obs)
    total = seeds * mc_rounds * clients * steps
    out = {}
    for mode in ("vmap", "loop"):
        mc = run_monte_carlo(plan, seeds, rounds=mc_rounds, mode=mode)
        out[f"mc_{mode}"] = total / mc.wall_s
    return out


def bench_cohort(model: str, population: int, *, clients: int = 8,
                 steps: int = 2, batch: int = 8, image: int = 16,
                 rounds: int = 10, obs: Obs = NULL_OBS) -> dict[str, dict]:
    """steps/sec + engine-state bytes of one cohort round sampled from a
    ``population``-client fleet (fl/vmap stateless rounds; sl/vmap EPSL
    shared client tier). The byte size (``repro.obs.pytree_bytes`` — the
    same gauge telemetry stamps per round) is the O(cohort) acceptance
    bar: it must not move across populations."""
    out = {}
    for kind in ("fl", "sl"):
        spec = dataclasses.replace(
            _base_spec(model, clients, steps, batch, image),
            clients=ClientSpec(num_clients=clients, population=population),
            engine=EngineSpec(kind, "vmap"))
        plan = compile_experiment(spec, obs=obs)
        state = plan.init()
        es = state.engine_state
        state_bytes = pytree_bytes(es)
        # one representative cohort gather; the compiled round is the same
        # program whichever population ids the rows came from
        batches = plan.round_batches(state,
                                     cohort=plan._round_cohort(state))
        es, losses, *_ = plan.raw_round(es, batches)  # warmup / compile
        jax.block_until_ready(losses)

        def one_round():
            nonlocal es
            es, losses, *_ = plan.raw_round(es, batches)
            return losses

        wall = time_fenced(one_round, repeats=rounds)
        sps = rounds * clients * steps / wall
        out[f"{kind}_cohort"] = {"steps_per_s": sps,
                                 "state_bytes": state_bytes}
    return out


def bench_kernels(*, rounds: int = 20, obs: Obs = NULL_OBS) -> dict:
    """calls/sec of each Pallas hot-path kernel vs its XLA reference.

    Off-accelerator the Pallas side runs in interpret mode — those rows
    price the oracle, not the kernel (the CPU container's numbers are a
    trend pin, not a speedup claim; re-measure where a TPU/GPU backend
    compiles the kernels natively). Cases:

      attn_b2h4s256d64 : flash_attention vs flash_attention_ref (the
                         O(S²) XLA oracle) on a (2,4,256,64) block
      link_m2048d256   : fused one-kernel int8 quant+dequant vs the
                         two-op jnp reference boundary
      link_res_m2048d256 : the fused dequant+residual server epilogue vs
                         the unfused composition
    """
    from repro.kernels.attn.flash import flash_attention
    from repro.kernels.attn.ref import flash_attention_ref
    from repro.kernels.dispatch import pallas_interpret
    from repro.kernels.quant.ops import quant_dequant, quant_dequant_residual

    interpret = pallas_interpret()
    out: dict[tuple[str, str], float] = {}

    def meas(case, variant, fn, *args):
        jax.block_until_ready(fn(*args))          # warmup / compile
        wall = time_fenced(lambda: fn(*args), repeats=rounds)
        out[(case, variant)] = rounds / wall

    b, h, s, d = 2, 4, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    attn_case = f"attn_b{b}h{h}s{s}d{d}"
    meas(attn_case, "pallas", jax.jit(lambda q, k, v: flash_attention(
        q, k, v, block_q=128, block_k=128, interpret=interpret)), q, k, v)
    meas(attn_case, "xla",
         jax.jit(lambda q, k, v: flash_attention_ref(q, k, v)), q, k, v)

    m, dd = 2048, 256
    kx, kr = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (m, dd)) * 4.0
    r = jax.random.normal(kr, (m, dd))
    link_case = f"link_m{m}d{dd}"
    for variant, up in (("fused", True), ("xla", False)):
        meas(link_case, variant,
             lambda xx, up=up: quant_dequant(xx, use_pallas=up,
                                             interpret=interpret), x)
    res_case = f"link_res_m{m}d{dd}"
    for variant, up in (("fused", True), ("xla", False)):
        meas(res_case, variant,
             lambda xx, rr, up=up: quant_dequant_residual(
                 xx, rr, use_pallas=up, interpret=interpret), x, r)
    return out


def run(model: str = "tinycnn", clients: int = 4, steps: int = 4,
        batch: int = 16, image: int = 32, rounds: int = 10,
        print_csv: bool = True, commit: str | None = None,
        mc_seeds: int = 16,
        populations: tuple[int, ...] | None = None,
        kernels: bool = False,
        obs: Obs | ObsConfig | None = None) -> list[dict]:
    obs = Obs.ensure(obs)
    base = _base_spec(model, clients, steps, batch, image)
    commit = commit or _commit()
    case = f"c{clients}s{steps}b{batch}"
    if populations is None:
        populations = (10_000, 100_000, 1_000_000)
    variant_fns = [
        ("sl_host_loop",
         lambda: bench_sl_host_loop(base, rounds=rounds, obs=obs)),
        ("sl_scanned",
         lambda: bench_spec_variant(base, rounds=rounds, obs=obs)),
        ("sl_fleet", lambda: bench_spec_variant(
            dataclasses.replace(base, engine=EngineSpec("sl", "vmap")),
            rounds=rounds, obs=obs)),
        ("sl_shard_map", lambda: bench_spec_variant(
            dataclasses.replace(base, engine=EngineSpec("sl", "shard_map")),
            rounds=rounds, obs=obs)),
        ("fl_scan", lambda: bench_spec_variant(
            dataclasses.replace(base, engine=EngineSpec("fl", "scan")),
            rounds=rounds, obs=obs)),
        ("fl_vmap", lambda: bench_spec_variant(
            dataclasses.replace(base, engine=EngineSpec("fl", "vmap")),
            rounds=rounds, obs=obs)),
        ("fl_shard_map", lambda: bench_spec_variant(
            dataclasses.replace(base, engine=EngineSpec("fl", "shard_map")),
            rounds=rounds, obs=obs)),
    ]
    variants: dict[str, float] = {}
    rows = []
    with obs.span("bench", model=model, case=case, commit=commit):
        for name, fn in variant_fns:
            with obs.span(name) as sp:
                variants[name] = fn()
                sp.note(steps_per_s=round(variants[name], 2))
        rows += [{"commit": commit, "bench": "engine_perf", "model": model,
                  "case": case, "variant": v, "steps_per_s": round(sps, 2)}
                 for v, sps in variants.items()]
        # the MC workload is its own fixed case (c4s2b8x<seeds>) independent
        # of this invocation's engine case; pass --mc-seeds 0 to skip it
        # when benching several engine cases in one session (avoids
        # duplicate rows)
        mc: dict[str, float] = {}
        if mc_seeds > 0:
            with obs.span("monte_carlo", seeds=mc_seeds):
                mc = bench_monte_carlo(model, seeds=mc_seeds, obs=obs)
        mc_case = f"c4s2b8x{mc_seeds}"
        rows += [{"commit": commit, "bench": "engine_perf", "model": model,
                  "case": mc_case, "variant": v, "steps_per_s": round(sps, 2)}
                 for v, sps in mc.items()]
        # population cohort rounds: one fixed case per M (c8s2b8m<M>), each
        # trend-gated on steps/s like every other variant; state_bytes rides
        # along so the log pins the O(cohort) claim per commit. Pass
        # --population 0 to skip.
        for pop in [p for p in populations if p > 0]:
            with obs.span(f"cohort_m{pop}", population=pop):
                cres = bench_cohort(model, pop, rounds=rounds, obs=obs)
            rows += [{"commit": commit, "bench": "engine_perf",
                      "model": model, "case": f"c8s2b8m{pop}", "variant": v,
                      "steps_per_s": round(r["steps_per_s"], 2),
                      "state_bytes": r["state_bytes"]}
                     for v, r in cres.items()]
        # per-kernel rows (--kernels): fixed model "kernels", one case per
        # hot-path kernel, pallas/fused vs xla variants — trend-gated like
        # every other key
        if kernels:
            with obs.span("kernels"):
                kres = bench_kernels(rounds=max(rounds, 10), obs=obs)
            rows += [{"commit": commit, "bench": "engine_perf",
                      "model": "kernels", "case": case, "variant": v,
                      "steps_per_s": round(sps, 2)}
                     for (case, v), sps in kres.items()]
    # health probe: the timed benches go through raw_round (no record
    # assembly), so with --obs metrics enabled run a couple of recorded
    # rounds too — they stream `metrics` events into the run dir, which
    # the CI smoke gates on zero nonfinite slot-steps
    # (tools/obs_report.py --health-gate)
    if obs and obs.config.metrics is not None:
        with obs.span("health_probe", rounds=2):
            probe = compile_experiment(dataclasses.replace(
                base, engine=EngineSpec("sl", "vmap"), global_rounds=2),
                obs=obs)
            probe.run(with_eval=False)
    if obs:
        obs.manifest(bench={"bench": "engine_perf", "model": model,
                            "case": case, "commit": commit,
                            "rows": len(rows)})
        obs.flush()
    os.makedirs("results", exist_ok=True)
    log = []
    if os.path.exists(CACHE):
        try:
            log = json.load(open(CACHE))
        except ValueError:
            log = []
    json.dump(log + rows, open(CACHE, "w"), indent=1)
    if print_csv:
        sl_speed = variants["sl_scanned"] / max(variants["sl_host_loop"], 1e-9)
        fl_delta = variants["fl_vmap"] / max(variants["fl_scan"], 1e-9)
        sm_delta = variants["sl_shard_map"] / max(variants["sl_fleet"], 1e-9)
        for r in rows:
            print(f"{r['bench']},{r['model']}/{r['case']}/{r['variant']},0,"
                  f"{r['steps_per_s']}steps/s")
        summary = (f"scanned_vs_host={sl_speed:.2f}x;"
                   f"fl_vmap_vs_scan={fl_delta:.2f}x;"
                   f"sl_shard_map_vs_vmap={sm_delta:.2f}x")
        if mc:
            summary += (f";mc_vmap_vs_loop="
                        f"{mc['mc_vmap'] / max(mc['mc_loop'], 1e-9):.2f}x")
        print(f"engine_perf,{model}/{case}/summary,0,{summary}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tinycnn", choices=sorted(CNN_BUILDERS))
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--image", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--mc-seeds", type=int, default=16,
                    help="Monte-Carlo sweep width for the mc_vmap/mc_loop "
                         "rows (acceptance gate: >=3x at 16 seeds)")
    ap.add_argument("--population", type=int, action="append", default=None,
                    dest="populations", metavar="M",
                    help="log fl_cohort/sl_cohort rows (steps/s + engine-"
                         "state bytes, cohort of 8 sampled from M); "
                         "repeatable; default 1e4/1e5/1e6; 0 skips")
    ap.add_argument("--kernels", action="store_true",
                    help="log per-kernel rows (flash attention + fused int8 "
                         "link vs their XLA references; interpret-mode "
                         "Pallas off-accelerator)")
    ap.add_argument("--commit", default=None,
                    help="override the logged commit label (used to append "
                         "same-machine re-measured baseline rows next to a "
                         "new commit's rows, so the trend gate compares "
                         "like with like)")
    ap.add_argument("--obs", action="store_true",
                    help="stream telemetry (phase spans, recompile/memory "
                         "gauges, manifest, the default metrics-bus tap "
                         "set + health probe) for this bench session to "
                         "results/runs/<run_id>/; render with "
                         "tools/obs_report.py")
    ap.add_argument("--obs-root", default="results/runs",
                    help="run-dir root for --obs (default results/runs)")
    args = ap.parse_args()
    from repro.obs.metrics import MetricsConfig
    obs = (Obs(ObsConfig(run_root=args.obs_root, metrics=MetricsConfig()))
           if args.obs else None)
    run(model=args.model, clients=args.clients, steps=args.steps,
        batch=args.batch, image=args.image, rounds=args.rounds,
        commit=args.commit, mc_seeds=args.mc_seeds,
        populations=(tuple(args.populations)
                     if args.populations is not None else None),
        kernels=args.kernels, obs=obs)
    if obs is not None:
        obs.close()
        print(f"obs,run_dir,0,{obs.run_dir}")


if __name__ == "__main__":
    main()
