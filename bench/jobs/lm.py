"""The split LM family's job: uniform tokens from the seed, and the
configuration as the program's dense split decoder (``ArchConfig`` under
``ModelSpec(family="transformer")``).

A family of another block kind keeps a module of its own beside this one
and may import ``make_data`` from here: its ``make_spec`` builds the
``ArchConfig`` its configuration file describes.
"""
from __future__ import annotations

import numpy as np

from bench import workload as wl


def make_data(cell: wl.Cell, seed: int):
    """``(x_train, y_train, x_test, y_test)`` as host arrays: rows of
    ``seq_len + 1`` tokens drawn in one jitted call, inputs and their
    next-token targets."""
    import jax
    import jax.numpy as jnp

    t = cell.traffic
    n_tr, n_te = int(t["train_examples"]), int(t["test_examples"])
    key = jax.random.PRNGKey(wl.seed32(seed))
    seq, vocab = cell.seq_len, int(cell.config["vocab_size"])

    @jax.jit
    def gen(k):
        return jax.random.randint(k, (n_tr + n_te, seq + 1), 0, vocab,
                                  jnp.int32)
    toks = np.asarray(gen(key))
    return (toks[:n_tr, :-1], toks[:n_tr, 1:],
            toks[n_tr:, :-1], toks[n_tr:, 1:])


def make_spec(cell: wl.Cell, seed: int):
    """The cell's job as the program's ``ExperimentSpec``."""
    from repro.api import DataSpec, ExperimentSpec, ModelSpec
    from repro.configs.base import ArchConfig
    c = cell.config
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
        data=DataSpec(kind="tokens", seq_len=cell.seq_len, partition="iid"),
        **wl.job_spec(cell, seed))
