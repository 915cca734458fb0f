"""The in-graph metrics bus (``repro.obs.metrics``) — PR acceptance gates.

The contract under test:

  * metrics-off plans are untouched: a plan compiled without a
    ``MetricsConfig`` lowers to the BYTE-identical round program (canonical
    jaxpr comparison through the same audit handles ``repro_lint --jaxpr``
    traces), and metrics-on runs reproduce every non-metrics
    ``RoundRecord`` field bitwise across fl/sl x scan/vmap/shard_map, the
    EPSL shared cohort tier, the degenerate population corner, and hetero
    buckets;
  * taps ride the round's own scan outputs — enabling the default tap set
    costs < 3% wall on a measured 20-round run (interleaved A/B, same
    estimator as ``test_obs_overhead_under_2pct``);
  * the NaN guard localizes an injected nonfinite batch to its exact
    (round, step, client slot) on every engine variant, recording under
    ``health/*`` or raising :class:`NonfiniteError` per policy;
  * Monte-Carlo sweeps stack taps per seed: seed 0 of a ``seed=0`` sweep
    replays ``plan.run()``'s metric stream (health/mask keys exactly;
    float taps within the same rtol=2e-5 the loss replay pin uses), and
    ``summary()`` reports across-seed tap spread;
  * the JSONL sink carries the round summaries as ``metrics`` events,
    rendered by ``tools/obs_report.py`` (tap sparklines, health table,
    ``--health-gate``, ``--compare``), and ``benchmarks/report.py
    --compact`` prunes the perf log the CI artifact uploads.
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from repro.api import (ClientSpec, CutPolicy, DataSpec, EngineSpec,
                       ExperimentSpec, LinkPolicy, ModelSpec,
                       compile_experiment)
from repro.obs import NULL_OBS, ObsConfig
from repro.obs.metrics import (MetricsConfig, NonfiniteError, TAPS,
                               engine_tap_names, first_nonfinite_coord,
                               summarize_round_metrics)
from repro.obs.timeline import fenced

NUM_CLASSES = 4

BASE = ExperimentSpec(
    model=ModelSpec(name="tinycnn", num_classes=NUM_CLASSES),
    data=DataSpec(kind="synthetic", image_size=12, classes_per_client=2,
                  n_train=32, n_test=16),
    clients=ClientSpec(num_clients=3),
    cut_policy=CutPolicy(mode="fraction", fraction=0.4),
    engine=EngineSpec(kind="sl", client_axis="vmap"),
    global_rounds=2, local_steps=3, batch_size=4, seed=0)

ENGINES = [("fl", "scan"), ("fl", "vmap"), ("fl", "shard_map"),
           ("sl", "scan"), ("sl", "vmap"), ("sl", "shard_map")]

# every RoundRecord field that must stay bitwise identical metrics-on vs
# metrics-off (i.e. everything except `metrics` itself)
NON_METRICS_FIELDS = ("round", "loss", "accuracy", "link_bytes",
                      "link_time_s", "link_energy_j", "client_energy_j",
                      "server_energy_j", "uav_energy_j", "client_time_s",
                      "server_time_s", "active_clients", "engine",
                      "cohort_pids")


def _metrics_obs(**kw):
    return ObsConfig(enabled=False, metrics=MetricsConfig(**kw))


def _engine_spec(kind, axis, **kw):
    return dataclasses.replace(
        BASE, engine=EngineSpec(kind=kind, client_axis=axis), **kw)


def _poison(batches, client, step):
    """The round's own batch stack with NaN planted at one
    (client slot, local step) — both engine batch formats."""
    if isinstance(batches, dict):                      # SL
        bx = np.asarray(batches["inputs"]).copy()
        bx[client, step] = np.nan
        return {"inputs": jnp.asarray(bx), "targets": batches["targets"]}
    bx, by = batches                                   # FL
    bx = np.asarray(bx).copy()
    bx[client, step] = np.nan
    return jnp.asarray(bx), by


# ---------------------------------------------------------------------------
# config + pure helpers
# ---------------------------------------------------------------------------

def test_metrics_config_validation():
    assert MetricsConfig().taps == TAPS
    with pytest.raises(ValueError, match="unknown metrics taps"):
        MetricsConfig(taps=("grad_norms", "nope"))
    with pytest.raises(ValueError, match="on_nonfinite"):
        MetricsConfig(on_nonfinite="explode")


def test_engine_tap_names_resolution():
    cfg = MetricsConfig()
    sl = engine_tap_names(cfg, kind="sl", has_link=True)
    assert "quant_error" in sl and "grad_norm_server" in sl
    sl_fp32 = engine_tap_names(cfg, kind="sl", has_link=False)
    assert "quant_error" not in sl_fp32
    fl = engine_tap_names(cfg, kind="fl", has_link=False)
    # FL has no server tier and no link boundary
    assert fl == ("grad_norm_client", "update_norm_client", "nonfinite")
    assert engine_tap_names(None, kind="sl", has_link=True) == ()
    # host-only taps lower nothing in-graph
    host_only = MetricsConfig(taps=("loss_spread", "mask"), nan_guard=False)
    assert engine_tap_names(host_only, kind="sl", has_link=True) == ()


def test_first_nonfinite_coord_layouts():
    # SL layout (steps, clients) passes through; FL (clients, steps) is
    # transposed to time-major before the argwhere
    sl = np.zeros((3, 2), np.float32)
    sl[2, 1] = 1.0
    assert first_nonfinite_coord(sl, "sl") == (2, 1, 1)
    fl = np.zeros((2, 3), np.float32)                  # (clients, steps)
    fl[1, 2] = 1.0
    assert first_nonfinite_coord(fl, "fl") == (2, 1, 1)
    assert first_nonfinite_coord(np.zeros((3, 2)), "sl") is None


def test_summarize_round_metrics_is_pure_numpy():
    cfg = MetricsConfig()
    taps = {"grad_norm_client": np.array([[1.0, 3.0], [2.0, 4.0]]),
            "nonfinite": np.zeros((2, 2), np.float32)}
    losses = np.array([[1.0, 2.0], [1.5, 2.5]])
    out = summarize_round_metrics(cfg, taps, losses=losses, kind="sl",
                                  n=2, active=2)
    assert out["grad_norm_client/mean"] == pytest.approx(2.5)
    assert out["grad_norm_client/max"] == 4.0
    assert out["loss/spread"] == pytest.approx(0.5)
    assert out["mask/active"] == 2 and out["mask/fraction"] == 1.0
    assert out["health/nonfinite"] == 0
    assert out["health/first_step"] == -1
    # identical inputs -> identical floats (the MC replay relies on this)
    again = summarize_round_metrics(cfg, taps, losses=losses, kind="sl",
                                    n=2, active=2)
    assert out == again


# ---------------------------------------------------------------------------
# metrics-off stays byte-identical; metrics-on perturbs nothing it reports on
# ---------------------------------------------------------------------------

def _round_jaxpr(plan) -> str:
    """Canonical jaxpr of the plan's jitted round via the same audit handle
    ``repro_lint --jaxpr`` traces."""
    from repro.analyze.jaxpr_audit import _canon_jaxpr, example_round_args
    args, audit = example_round_args(plan)
    return _canon_jaxpr(jax.make_jaxpr(audit["jit_fn"])(*args))


@pytest.mark.parametrize("kind,axis", [("fl", "vmap"), ("sl", "scan"),
                                       ("sl", "vmap")])
def test_metrics_off_program_bit_identical(kind, axis):
    spec = _engine_spec(kind, axis)
    base = _round_jaxpr(compile_experiment(spec))
    # an ObsConfig WITHOUT metrics compiles the same program as obs=None
    off = _round_jaxpr(compile_experiment(spec, obs=ObsConfig(enabled=False)))
    assert off == base
    # ... and the tap-carrying twin is a genuinely different program
    on = _round_jaxpr(compile_experiment(spec, obs=_metrics_obs()))
    assert on != base


def _assert_streams_match(spec, rounds=2):
    _, recs_off = compile_experiment(spec).run(rounds)
    _, recs_on = compile_experiment(spec, obs=_metrics_obs()).run(rounds)
    assert len(recs_off) == len(recs_on) == rounds
    for a, b in zip(recs_off, recs_on):
        assert a.metrics == {} and b.metrics
        assert b.metrics["health/nonfinite"] == 0
        for f in NON_METRICS_FIELDS:
            assert getattr(a, f) == getattr(b, f), f
    return recs_on


@pytest.mark.parametrize("kind,axis", ENGINES)
def test_record_parity_engine_matrix(kind, axis):
    recs = _assert_streams_match(_engine_spec(kind, axis))
    m = recs[0].metrics
    assert "grad_norm_client/mean" in m and "update_norm_client/max" in m
    if kind == "sl":
        assert "grad_norm_server/mean" in m and "smashed_std/mean" in m
    else:
        assert "grad_norm_server/mean" not in m and "smashed_std/mean" not in m
    assert "quant_error/mean" not in m                 # fp32 link


def test_record_parity_shared_cohort_tier():
    # population > num_clients lowers the EPSL shared client tier; its
    # update_norm_client channel is the per-step shared-update scalar
    spec = dataclasses.replace(
        BASE, clients=ClientSpec(num_clients=3, population=9))
    recs = _assert_streams_match(spec)
    assert len(recs[0].cohort_pids) == 3
    assert "update_norm_client/mean" in recs[0].metrics


def test_record_parity_degenerate_population():
    # population == num_clients reproduces the materialized fleet
    spec = dataclasses.replace(
        BASE, clients=ClientSpec(num_clients=3, population=3))
    _assert_streams_match(spec)


def test_record_parity_hetero_buckets():
    spec = dataclasses.replace(BASE, cut_policy=CutPolicy(mode="adaptive"))
    recs = _assert_streams_match(spec)
    assert "grad_norm_client/mean" in recs[0].metrics


def test_quant_error_tap_requires_int8_link():
    spec = dataclasses.replace(BASE, link_policy=LinkPolicy(compress="int8"))
    plan = compile_experiment(spec, obs=_metrics_obs())
    st = plan.init()
    _, rec = plan.run_round(st, with_eval=False)
    assert "quant_error/mean" in rec.metrics
    assert rec.metrics["quant_error/mean"] > 0         # int8 is lossy
    # record is JSON round-trippable with the metrics dict aboard
    d = json.loads(json.dumps(rec.to_dict()))
    assert d["metrics"]["quant_error/mean"] == rec.metrics["quant_error/mean"]


# ---------------------------------------------------------------------------
# the NaN guard localizes exactly, on every engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,axis", ENGINES)
def test_nan_localized_exactly(kind, axis):
    plan = compile_experiment(_engine_spec(kind, axis), obs=_metrics_obs())
    state = plan.init()
    state, rec0 = plan.run_round(state, with_eval=False)
    assert rec0.metrics["health/nonfinite"] == 0
    bad = _poison(plan.round_batches(state), client=2, step=1)
    state, rec1 = plan.run_round(state, bad, with_eval=False)
    m = rec1.metrics
    assert m["health/nonfinite"] >= 1
    assert m["health/first_step"] == 1
    assert m["health/first_client"] == 2


def test_nan_raise_policy_carries_coordinate():
    plan = compile_experiment(
        BASE, obs=_metrics_obs(on_nonfinite="raise"))
    state = plan.init()
    state, _ = plan.run_round(state, with_eval=False)  # round 0 clean
    bad = _poison(plan.round_batches(state), client=1, step=2)
    with pytest.raises(NonfiniteError) as ei:
        plan.run_round(state, bad, with_eval=False)
    assert ei.value.round_index == 1
    assert ei.value.step == 2 and ei.value.client == 1
    assert ei.value.count >= 1 and "round=1" in str(ei.value)


# ---------------------------------------------------------------------------
# overhead: taps ride the scan carry, no extra syncs
# ---------------------------------------------------------------------------

def test_metrics_overhead_under_3pct():
    """Default tap set on a measured 20-round run stays under 3%: taps
    ride the round's existing device->host pull, and the NaN guard reuses
    the tapped norms instead of a second elementwise pass.

    Estimator: 20 interleaved off/on rounds each; the ratio of per-round
    MINIMA (scheduler interference only ever ADDS time, so the min
    converges to the true floor while round-level interleaving keeps both
    arms exposed to the same machine state — tighter than the trial-level
    A/B in ``test_obs_overhead_under_2pct``). A failing measurement is
    re-taken up to twice: the quantity pinned is the program's floor
    cost, not one noisy sample. The workload is sized so training compute
    dominates: tap cost is O(params) per slot-step, independent of
    batch/image, so tiny rounds would measure small-op dispatch, not the
    bus."""
    spec = dataclasses.replace(
        BASE, data=DataSpec(kind="synthetic", image_size=32,
                            classes_per_client=2, n_train=256, n_test=32),
        clients=ClientSpec(num_clients=4),
        global_rounds=20, local_steps=2, batch_size=64)
    plan_off = compile_experiment(spec)
    plan_on = compile_experiment(spec, obs=_metrics_obs())
    assert plan_off.obs is NULL_OBS and plan_off.metrics_config is None
    batches = plan_off.round_batches(plan_off.init())

    def one_round(plan, st):
        _, wall = fenced(
            lambda: plan.run_round(st, batches, with_eval=False))
        return wall

    st_off, st_on = plan_off.init(), plan_on.init()
    for _ in range(2):                                 # compile + warm
        one_round(plan_off, st_off)
        one_round(plan_on, st_on)

    def measure():
        pairs = [(one_round(plan_off, st_off), one_round(plan_on, st_on))
                 for _ in range(20)]
        return (min(b for _, b in pairs) / min(a for a, _ in pairs))

    ratio = measure()
    for _ in range(2):                                 # noisy-sample retries
        if ratio < 1.03:
            break
        ratio = min(ratio, measure())
    assert ratio < 1.03, f"metrics-bus overhead {ratio:.4f}x"


# ---------------------------------------------------------------------------
# Monte-Carlo: per-seed tap stacks, seed-0 replay, across-seed spread
# ---------------------------------------------------------------------------

def _stoch_metrics_plan(rounds=3):
    from repro.api import MissionSpec
    from repro.sim import AvailabilityParams, ChannelParams, ScenarioSpec
    scn = ScenarioSpec(
        channel=ChannelParams(kind="a2g"),
        availability=AvailabilityParams(kind="markov", p_drop=0.4,
                                        p_recover=0.6),
        num_uavs=2, serve_mode="relay", seed=1)
    return compile_experiment(
        dataclasses.replace(BASE, global_rounds=rounds,
                            mission=MissionSpec(farm_acres=100.0),
                            scenario=scn),
        obs=_metrics_obs())


def test_monte_carlo_seed_zero_replays_plan_metrics():
    plan = _stoch_metrics_plan()
    _, recs = plan.run(with_eval=False)
    mc = __import__("repro.sim", fromlist=["run_monte_carlo"]) \
        .run_monte_carlo(plan, 2, rounds=3, seed=0)
    mrecs = mc.records_for_seed(0)
    for a, b in zip(recs, mrecs):
        assert set(a.metrics) == set(b.metrics)
        for k in a.metrics:
            if k.startswith(("health/", "mask/")):
                assert a.metrics[k] == b.metrics[k], k
            else:
                # same tolerance the loss replay pin uses (vmap may
                # reassociate float reductions); in practice bit-exact
                np.testing.assert_allclose(a.metrics[k], b.metrics[k],
                                           rtol=2e-5, atol=1e-7, err_msg=k)


def test_monte_carlo_metrics_stacks_and_summary():
    plan = _stoch_metrics_plan()
    mc = plan and __import__("repro.sim", fromlist=["run_monte_carlo"]) \
        .run_monte_carlo(plan, 3, rounds=2)
    tap_keys = [k for k in mc.stacks if k.startswith("metrics/")]
    assert "metrics/grad_norm_client" in tap_keys
    for k in tap_keys:
        assert mc.stacks[k].shape[:2] == (3, 2)        # (seeds, rounds, ...)
    assert mc.stacks["loss_stack"].shape[:2] == (3, 2)
    s = mc.summary()["metrics"]
    assert s is not None and "grad_norm_client" in s
    assert s["grad_norm_client"]["min"] <= s["grad_norm_client"]["mean"] \
        <= s["grad_norm_client"]["max"]
    # loop mode carries the same tap stacks
    lc = __import__("repro.sim", fromlist=["run_monte_carlo"]) \
        .run_monte_carlo(plan, 2, rounds=2, mode="loop")
    for k in tap_keys:
        assert k in lc.stacks


def test_monte_carlo_without_metrics_unchanged():
    plan = compile_experiment(dataclasses.replace(BASE, global_rounds=2))
    from repro.sim import run_monte_carlo
    mc = run_monte_carlo(plan, 2, rounds=2)
    assert not any(k.startswith("metrics/") for k in mc.stacks)
    assert "loss_stack" not in mc.stacks
    assert mc.records_for_seed(0)[0].metrics == {}
    assert mc.summary()["metrics"] is None


# ---------------------------------------------------------------------------
# sink + report tooling
# ---------------------------------------------------------------------------

def test_metrics_events_stream_and_health_gate(tmp_path):
    obs_cfg = ObsConfig(run_root=str(tmp_path), run_id="mx",
                        metrics=MetricsConfig())
    plan = compile_experiment(dataclasses.replace(BASE, global_rounds=2),
                              obs=obs_cfg)
    plan.run(with_eval=False)
    plan.obs.close()
    import obs_report
    _, events = obs_report.load_run(plan.obs.run_dir)
    mev = obs_report.metrics_rounds(events)
    assert [e["round"] for e in mev] == [0, 1]
    assert all("grad_norm_client/mean" in e for e in mev)
    assert obs_report.health_nonfinite_total(events) == 0
    lines = obs_report.metrics_section(events)
    assert any("metrics taps" in ln for ln in lines)
    assert any("0 nonfinite" in ln for ln in lines)
    rendered = obs_report.render(plan.obs.run_dir, *obs_report.load_run(
        plan.obs.run_dir))
    assert any("grad_norm_client/mean" in ln for ln in rendered)


def test_obs_report_compare_two_runs(tmp_path):
    import obs_report
    for rid in ("a", "b"):
        obs_cfg = ObsConfig(run_root=str(tmp_path), run_id=rid)
        plan = compile_experiment(dataclasses.replace(BASE, global_rounds=1),
                                  obs=obs_cfg)
        plan.run(with_eval=False)
        plan.obs.close()
    lines = obs_report.compare_runs(os.path.join(str(tmp_path), "a"),
                                    os.path.join(str(tmp_path), "b"))
    assert lines[0].startswith("compare")
    body = "\n".join(lines)
    assert "run/round/execute" in body and "d_wall" in body
    assert "root wall" in lines[-1]


def test_perf_log_compaction():
    from benchmarks.report import compact_perf_log, perf_trend
    rows = [{"commit": c, "bench": "engine_perf", "model": "m", "case": "c",
             "variant": v, "steps_per_s": 100.0 + i}
            for i, c in enumerate(["c1", "c2", "c3", "c4"])
            for v in ("sl_fleet", "fl_vmap")]
    rows.append({"bench": "other", "note": "passthrough"})
    pruned = compact_perf_log(rows, 2)
    kept = {r["commit"] for r in pruned if "commit" in r}
    assert kept == {"c3", "c4"}
    assert any(r.get("bench") == "other" for r in pruned)   # untouched
    # the trend gate sees the same last-two comparison before and after
    before = perf_trend(rows)[0]
    after = perf_trend(pruned)[0]
    assert before == after
    with pytest.raises(ValueError):
        compact_perf_log(rows, 0)
