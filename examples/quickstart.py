"""Quickstart: the whole eEnergy-Split stack in one script.

    PYTHONPATH=src python examples/quickstart.py

1. deploy edge devices on a simulated 100-acre farm (Algorithm 1)
2. plan the energy-optimal UAV tour (Algorithm 2, exact TSP)
3. declare a split-learning experiment as ONE ``ExperimentSpec``
   (Algorithm 3), compile it, and stream per-round records with
   accuracy + per-tier energy
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import (ClientSpec, CutPolicy, DataSpec, EngineSpec,  # noqa: E402
                       ExperimentSpec, ModelSpec, compile_experiment)
from repro.core.deployment import deploy_edge_devices, uniform_grid_sensors
from repro.core.trajectory import plan_tour
from repro.data.synthetic import SyntheticPestImages

# 1. deployment -------------------------------------------------------------
sensors = uniform_grid_sensors(acres=100, n_sensors=25)
dep = deploy_edge_devices(sensors, cr=200.0)
print(f"[1] {len(sensors)} sensors -> {len(dep.edge_indices)} edge devices "
      f"(loads: {dep.loads.tolist()})")

# 2. UAV tour ---------------------------------------------------------------
plan = plan_tour(dep.edge_coords, base=np.zeros(2))
print(f"[2] optimal tour {plan.tour_length:.0f} m, "
      f"{plan.e_per_round/1e3:.1f} kJ/round, gamma={plan.rounds} rounds "
      f"on one battery")

# 3. split learning: one declarative spec -----------------------------------
gen = SyntheticPestImages(image_size=32)
x, y = map(np.asarray, gen.dataset(800))
xt, yt = map(np.asarray, gen.sample(jax.random.PRNGKey(99), 160))
num_clients = len(dep.edge_indices) if len(dep.edge_indices) >= 2 else 4
spec = ExperimentSpec(
    model=ModelSpec(name="mobilenetv2", num_classes=12),
    data=DataSpec(kind="arrays", image_size=32, shrink_batches=True),
    clients=ClientSpec(num_clients=num_clients),
    cut_policy=CutPolicy(mode="fraction", fraction=0.25),   # SL_{25,75}
    engine=EngineSpec(kind="sl", client_axis="scan"),       # sequential Alg. 3
    global_rounds=min(4, plan.rounds), local_steps=3, batch_size=16)
exp = compile_experiment(spec, data=(x, y, xt, yt))
state, records = exp.run()
m = state.last_metrics
print(f"[3] SL_25,75 ({exp.engine_label}) after {len(records)} UAV rounds: "
      f"acc={m['accuracy']:.3f} f1={m['f1']:.3f} "
      f"client={sum(r.client_energy_j for r in records)/1e3:.3f}kJ "
      f"server={sum(r.server_energy_j for r in records)/1e3:.4f}kJ "
      f"link={sum(r.link_bytes for r in records)/1e6:.1f}MB")
print("    swap EngineSpec(kind='fl') for the FL baseline, "
      "client_axis='vmap' for the fleet engine,")
print("    CutPolicy(mode='adaptive') for per-client cuts — same spec, "
      "same records.")
print("done — see benchmarks/ for the full paper tables.")
