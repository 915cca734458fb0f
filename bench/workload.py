"""One cell's job, built from its configuration and traffic files.

This is the benchmark's one general generator: every cell is a pair of
data files (``configs/<config>.json``, ``traffic/<traffic>.json``) that it
reads, and nothing here names a cell. It makes the cell's data from the
seed, lowers the job to the program's ``ExperimentSpec``, and reads the
program's engine state into named views (parameters, Adam first moments)
that the comparison with the plain reference pairs leaf by leaf.

The sampling rules (client partition, per-round batch draw) are written out
here from the program's documented semantics, so the reference can follow
the same rows without taking anything the program made.
"""
from __future__ import annotations

import dataclasses
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
# data, made on the device in one jitted call from the seed
# ---------------------------------------------------------------------------

def make_data(cell: Cell, seed: int):
    """``(x_train, y_train, x_test, y_test)`` as host arrays."""
    import jax
    import jax.numpy as jnp

    t = cell.traffic
    n_tr, n_te = int(t["train_examples"]), int(t["test_examples"])
    key = jax.random.PRNGKey(seed32(seed))
    if cell.family == "lm":
        seq, vocab = cell.seq_len, int(cell.config["vocab_size"])

        @jax.jit
        def gen(k):
            return jax.random.randint(k, (n_tr + n_te, seq + 1), 0, vocab,
                                      jnp.int32)
        toks = np.asarray(gen(key))
        return (toks[:n_tr, :-1], toks[:n_tr, 1:],
                toks[n_tr:, :-1], toks[n_tr:, 1:])
    size, ch = int(cell.config["image_size"]), int(cell.config["in_channels"])
    ncls = int(cell.config["num_classes"])

    @jax.jit
    def gen(k):
        kx, ky = jax.random.split(k)
        x = jax.random.normal(kx, (n_tr + n_te, size, size, ch), jnp.float32)
        y = jax.random.randint(ky, (n_tr + n_te,), 0, ncls, jnp.int32)
        return x, y
    x, y = gen(key)
    x, y = np.asarray(x), np.asarray(y)
    return x[:n_tr], y[:n_tr], x[n_tr:], y[n_tr:]


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


def make_spec(cell: Cell, seed: int):
    """The cell's job as the program's ``ExperimentSpec``."""
    from repro.api import (ClientSpec, DataSpec, EngineSpec, ExperimentSpec,
                           LinkPolicy, ModelSpec)
    c, t = cell.config, cell.traffic
    engine = EngineSpec(kind=t["job"], client_axis=t["client_axis"],
                        link_kernel=t["link_kernel"])
    link = LinkPolicy(compress=t["link"])
    common = dict(clients=ClientSpec(num_clients=cell.clients),
                  link_policy=link, engine=engine, global_rounds=1,
                  local_steps=cell.steps, batch_size=cell.batch,
                  lr=float(t["lr"]), seed=seed32(seed))
    if cell.family == "lm":
        from repro.configs.base import ArchConfig
        arch = ArchConfig(
            name=c["name"], family="dense",
            n_layers=int(c["num_hidden_layers"]), d_model=int(c["hidden_size"]),
            n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]), d_ff=int(c["intermediate_size"]),
            vocab=int(c["vocab_size"]), rope_theta=float(c["rope_theta"]),
            tie_embeddings=bool(c["tie_word_embeddings"]),
            dtype=c["block_param_dtype"], source=c["source"])
        return ExperimentSpec(
            model=ModelSpec(family="transformer", arch=arch,
                            attn_impl=c["attn_impl"]),
            data=DataSpec(kind="tokens", seq_len=cell.seq_len,
                          partition="iid"), **common)
    from repro.api import CutPolicy
    return ExperimentSpec(
        model=ModelSpec(name=c["program_model"],
                        num_classes=int(c["num_classes"])),
        data=DataSpec(kind="arrays", image_size=int(c["image_size"]),
                      partition=t["partition"],
                      classes_per_client=int(t.get("classes_per_client", 3))),
        cut_policy=CutPolicy(fraction=float(c["cut_fraction"])), **common)


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
    import importlib
    mod = importlib.import_module(f"bench.flops.{cell.family}")
    return float(mod.flops_per_round(cell.config, cell.traffic))
