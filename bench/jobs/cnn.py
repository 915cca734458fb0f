"""The CNN family's job: images and labels from the seed, and the
configuration as one of the program's CNN stage lists, cut at a fraction
of its depth."""
from __future__ import annotations

import numpy as np

from bench import workload as wl


def make_data(cell: wl.Cell, seed: int):
    """``(x_train, y_train, x_test, y_test)`` as host arrays: standard
    normal images and uniform labels drawn in one jitted call."""
    import jax
    import jax.numpy as jnp

    t = cell.traffic
    n_tr, n_te = int(t["train_examples"]), int(t["test_examples"])
    key = jax.random.PRNGKey(wl.seed32(seed))
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


def make_spec(cell: wl.Cell, seed: int):
    """The cell's job as the program's ``ExperimentSpec``."""
    from repro.api import CutPolicy, DataSpec, ExperimentSpec, ModelSpec
    c, t = cell.config, cell.traffic
    return ExperimentSpec(
        model=ModelSpec(name=c["program_model"],
                        num_classes=int(c["num_classes"])),
        data=DataSpec(kind="arrays", image_size=int(c["image_size"]),
                      partition=t["partition"],
                      classes_per_client=int(t.get("classes_per_client", 3))),
        cut_policy=CutPolicy(fraction=float(c["cut_fraction"])),
        **wl.job_spec(cell, seed))
