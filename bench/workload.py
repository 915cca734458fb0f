"""One cell's job, built from its configuration and traffic files.

This is the benchmark's one general generator: every cell is a pair of
data files (``configs/<config>.json``, ``traffic/<traffic>.json``) that it
reads, and nothing here names a cell or a model family. The family's
module in ``jobs/`` makes the cell's data from the seed and lowers the
configuration to the program's ``ExperimentSpec`` around ``job_spec``,
the fields the traffic sets; this module reads the program's engine state
into named views (parameters, Adam first moments) that the comparison
with the plain reference pairs leaf by leaf.

The sampling rules (client partition, per-round batch draw) are written out
here from the program's documented semantics, so the reference can follow
the same rows without taking anything the program made.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys
from typing import Any, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    bench_dir: str = BENCH

    @property
    def family(self) -> str:
        return self.config["family"]

    @property
    def clients(self) -> int:
        return int(self.traffic["clients"])

    @property
    def batch(self) -> int:
        return int(self.traffic["batch"])

    @property
    def steps(self) -> int:
        return int(self.traffic["local_steps"])

    @property
    def seq_len(self) -> Optional[int]:
        s = self.traffic.get("seq_len")
        return None if s is None else int(s)

    @property
    def samples_per_round(self) -> int:
        return self.clients * self.batch * self.steps

    @property
    def tokens_per_round(self) -> int:
        return self.samples_per_round * (self.seq_len or 1)


def benchmark_file(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT, bench_dir: str = BENCH) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its two data files."""
    bench = benchmark_file(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{w['traffic']}.json"))
    return Cell(name=name, config=config, traffic=traffic,
                chips=int(w["chips"]), bench_dir=bench_dir)


def seed32(seed: int) -> int:
    """The seed as the program's host RNGs take it (numpy's RandomState
    needs it below 2**32; the program also draws with ``seed + 1``)."""
    return int(seed) % (2 ** 32 - 2)


# ---------------------------------------------------------------------------
# what depends on the family: one module per family and kind
# ---------------------------------------------------------------------------

def load_module(name: str, path: str):
    """The Python file ``path`` as a fresh module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_module(cell: Cell, kind: str):
    """The cell's family's module of ``kind`` (``jobs``, ``reference``,
    ``flops``): ``<kind>/<family>.py`` in the cell's bench dir, else in this
    harness's own. One found elsewhere than here is loaded from its file
    under the same package name, ``bench.<kind>.<family>``, so that its
    relative imports (``from . import common``) resolve to this harness."""
    dirs = list(dict.fromkeys(os.path.realpath(d)
                              for d in (cell.bench_dir, BENCH)))
    name = f"bench.{kind}.{cell.family}"
    for d in dirs:
        path = os.path.join(d, kind, f"{cell.family}.py")
        if os.path.isfile(path):
            return (importlib.import_module(name)
                    if d == os.path.realpath(BENCH) else load_module(name, path))
    raise ModuleNotFoundError(
        f"no {kind} module for family {cell.family!r} of workload "
        f"{cell.name!r}: looked for {kind}/{cell.family}.py in "
        + " and ".join(dirs))


def make_data(cell: Cell, seed: int):
    """``(x_train, y_train, x_test, y_test)`` as host arrays, made on the
    device in one jitted call from the seed by the family's ``jobs``
    module."""
    return family_module(cell, "jobs").make_data(cell, seed)


def make_spec(cell: Cell, seed: int):
    """The cell's job as the program's ``ExperimentSpec``, built by the
    family's ``jobs`` module around ``job_spec``."""
    return family_module(cell, "jobs").make_spec(cell, seed)


# ---------------------------------------------------------------------------
# the program's sampling rules, written out
# ---------------------------------------------------------------------------

def client_parts(cell: Cell, y_train: np.ndarray, seed: int) -> list:
    """Each client's training rows: ``classes`` deals ``classes_per_client``
    classes to each client round-robin over a seeded class permutation and
    shuffles each client's rows; ``iid`` splits a seeded permutation."""
    n = cell.clients
    rng = np.random.RandomState(seed32(seed))
    if cell.traffic["partition"] == "iid":
        order = rng.permutation(len(y_train))
        return [np.sort(p) for p in np.array_split(order, n)]
    ncls = int(cell.config["num_classes"])
    per = int(cell.traffic["classes_per_client"])
    order = rng.permutation(ncls)
    owners = [[] for _ in range(n)]
    for i in range(n * per):
        owners[i % n].append(int(order[i % ncls]))
    parts = []
    for c in range(n):
        idx = np.where(np.isin(y_train, owners[c]))[0]
        rng.shuffle(idx)
        parts.append(idx)
    return parts


class BatchStream:
    """The program's per-round batch draw: one seeded RandomState, one
    ``choice`` with replacement of (steps, batch) rows per client."""

    def __init__(self, cell: Cell, y_train: np.ndarray, seed: int):
        self.cell = cell
        self.parts = client_parts(cell, y_train, seed)
        self.rng = np.random.RandomState(seed32(seed))

    def next_round(self) -> np.ndarray:
        c = self.cell
        return np.stack([self.rng.choice(p, size=(c.steps, c.batch),
                                         replace=True) for p in self.parts])


# ---------------------------------------------------------------------------
# the program's entry point
# ---------------------------------------------------------------------------

def add_program_path(root: str = ROOT) -> None:
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def job_spec(cell: Cell, seed: int) -> dict:
    """The ``ExperimentSpec`` fields that the traffic sets for every family:
    the clients, the link, the engine, and one round of ``local_steps``
    steps of ``batch`` rows at ``lr``."""
    from repro.api import ClientSpec, EngineSpec, LinkPolicy
    t = cell.traffic
    engine = EngineSpec(kind=t["job"], client_axis=t["client_axis"],
                        link_kernel=t["link_kernel"])
    return dict(clients=ClientSpec(num_clients=cell.clients),
                link_policy=LinkPolicy(compress=t["link"]), engine=engine,
                global_rounds=1, local_steps=cell.steps, batch_size=cell.batch,
                lr=float(t["lr"]), seed=seed32(seed))


def make_mesh(cell: Cell):
    """The fleet mesh of a cell whose traffic spreads clients over
    ``data_axis`` chips (None on one chip)."""
    if int(cell.traffic.get("data_axis", 1)) <= 1:
        return None
    from repro.launch.mesh import make_fleet_mesh
    return make_fleet_mesh(cell.clients)


def state_views(cell: Cell, engine_state) -> dict:
    """Named views of the program's engine state: ``params`` (the global
    model: client tier row 0, which FedAvg made equal to every row, and the
    server tier) and ``moment`` (Adam's first moments, every client row)."""
    if cell.traffic["job"] == "fl":
        return {"params": {"model": engine_state}, "moment": None}
    pc, ps, oc, os_ = engine_state
    import jax
    row0 = jax.tree_util.tree_map(lambda v: v[0], pc)
    return {"params": {"client": row0, "server": ps},
            "moment": {"client": oc.mu, "server": os_.mu}}


def model_flops_per_round(cell: Cell) -> float:
    """Forward and backward FLOPs of one round's training (no recompute),
    plus the forward of the round's eval where the traffic evaluates every
    round, from the family's counter in ``flops/``."""
    return float(family_module(cell, "flops").flops_per_round(
        cell.config, cell.traffic))
