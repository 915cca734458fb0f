"""UAV mission simulation, fleet edition: deployment/trajectory sweep plus a
full fleet *campaign* — the sharded parallel-SL engine training an 8-client
fleet under the UAV's energy budget, with fp32 vs int8 link modes compared
per round (energy / accuracy / wire bytes).

    PYTHONPATH=src python examples/uav_mission_sim.py

``--monte-carlo N`` additionally sweeps N stochastic scenario seeds (a2g
channel fading/shadowing + markov client availability, 2 relay UAVs) in one
vectorized rollout (``repro.sim.run_monte_carlo``) and prints the spread of
mission energy and final loss across realizations.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import dataclasses  # noqa: E402

import numpy as np  # noqa: E402

from repro.api import (ClientSpec, DataSpec, EngineSpec, ExperimentSpec,  # noqa: E402
                       LinkPolicy, MissionSpec, ModelSpec,
                       compile_experiment)

args = argparse.ArgumentParser()
args.add_argument("--monte-carlo", type=int, default=0, metavar="N",
                  help="also sweep N stochastic scenario seeds")
args = args.parse_args()
from repro.core.deployment import (deploy_edge_devices, deploy_gasbac,  # noqa: E402
                                   deploy_kmeans, uniform_grid_sensors)
from repro.core.trajectory import greedy_tour_plan, plan_tour  # noqa: E402

# ---- deployment + trajectory sweep (paper Fig. 2 / Table II) --------------
print(f"{'farm':>6} {'method':>14} {'devices':>8} {'tour_m':>8} "
      f"{'kJ/round':>9} {'rounds':>7}")
for acres, n in ((100, 25), (140, 36), (200, 49), (250, 64)):
    pts = uniform_grid_sensors(acres, n)
    base = np.zeros(2)
    for name, dep_fn, planner in (
            ("eEnergy-Split", deploy_edge_devices, plan_tour),
            ("K-means", deploy_kmeans, greedy_tour_plan),
            ("GASBAC", deploy_gasbac, greedy_tour_plan)):
        dep = dep_fn(pts, 200.0)
        plan = planner(dep.edge_coords, base)
        print(f"{acres:>5}a {name:>14} {len(dep.edge_indices):>8} "
              f"{plan.tour_length:>8.0f} {plan.e_per_round/1e3:>9.1f} "
              f"{plan.rounds:>7}")

# ---- fleet campaign: 8 clients, fp32 vs int8 link -------------------------
# One declarative spec; the link sweep edits ONLY the LinkPolicy field.
base = ExperimentSpec(
    model=ModelSpec(name="tinycnn", num_classes=12),
    data=DataSpec(kind="synthetic", image_size=16),
    clients=ClientSpec(num_clients=8),
    engine=EngineSpec(kind="sl", client_axis="vmap"),     # parallel fleet SL
    mission=MissionSpec(farm_acres=100.0),                # UAV budget caps rounds
    global_rounds=3, local_steps=2, batch_size=8)
print(f"\nfleet campaign: {base.clients.num_clients} clients, "
      f"{base.model.name}, {base.mission.farm_acres:.0f} acres")
results = {}
for mode in ("none", "int8"):
    spec = dataclasses.replace(base, link_policy=LinkPolicy(
        rate_bps=100e6, compress=mode))
    exp = compile_experiment(spec)
    _, records = exp.run()
    results[mode] = records
    if mode == "none":
        tour = exp.tour
        print(f"tour {tour.tour_length:.0f} m, budget affords {tour.rounds} "
              f"rounds ({tour.e_per_round/1e3:.0f} kJ/round)")
print(f"{'link':>5} {'rnd':>4} {'loss':>7} {'acc':>6} {'wire_MB':>8} "
      f"{'link_s':>7} {'link_J':>7} {'client_J':>9} {'uav_kJ':>8}")
for mode, records in results.items():
    for r in records:
        print(f"{mode:>5} {r.round:>4} {r.loss:>7.3f} {r.accuracy:>6.3f} "
              f"{r.link_bytes/1e6:>8.3f} {r.link_time_s:>7.3f} "
              f"{r.link_energy_j:>7.3f} "
              f"{r.client_energy_j:>9.4f} {r.uav_energy_j/1e3:>8.1f}")
b_none, b_int8 = (sum(r.link_bytes for r in results[m])
                  for m in ("none", "int8"))
print(f"\nint8 link moves {b_none/b_int8:.2f}x "
      f"fewer wire bytes than fp32 on the same campaign "
      f"({b_none/1e6:.2f} MB -> {b_int8/1e6:.2f} MB)")

# ---- Monte-Carlo scenario sweep (--monte-carlo N) -------------------------
# The campaign above is ONE realization with an idealized constant-rate
# link. A ScenarioSpec attaches the stochastic environment; run_monte_carlo
# sweeps seeds in one jitted vmapped rollout.
if args.monte_carlo > 0:
    from repro.sim import (AvailabilityParams, ChannelParams, ScenarioSpec,
                           run_monte_carlo)

    scn = ScenarioSpec(
        channel=ChannelParams(kind="a2g"),
        availability=AvailabilityParams(kind="markov", p_drop=0.25,
                                        p_recover=0.5),
        num_uavs=2, serve_mode="relay")
    plan = compile_experiment(dataclasses.replace(base, scenario=scn))
    mc = run_monte_carlo(plan, args.monte_carlo)
    s = mc.summary()
    print(f"\nmonte-carlo: {mc.num_seeds} scenario seeds x {mc.rounds} "
          f"rounds (a2g channel, markov availability, "
          f"{scn.num_uavs} relay UAVs) in {mc.wall_s*1e3:.0f} ms vectorized")
    print(f"{'metric':>22} {'mean':>10} {'std':>9} {'p10':>10} {'p90':>10}")
    for name in ("final_loss", "mean_active_clients", "total_link_time_s",
                 "total_link_energy_j", "total_energy_j"):
        st = s[name]
        print(f"{name:>22} {st['mean']:>10.3g} {st['std']:>9.3g} "
              f"{st['p10']:>10.3g} {st['p90']:>10.3g}")
