"""With the timed path broken underneath the harness, ``correct`` comes out
false: a round that returns its state unchanged, and a round that leaves
half of each batch out and takes the mean over the rest. (The tiny split
LM stands for every cell: the harness path is the same.)"""
import time

import jax
import jax.numpy as jnp

from bench import run


def _run(root, hook):
    return run.run_cell("tiny_lm", 5, 0.3, False, t_start=time.perf_counter(),
                        allow_cpu=True, root=root, plan_hook=hook)


def _wrap(plan, fn):
    inner = plan._run

    def broken(state, batches, mask):
        return fn(inner, state, batches, mask)
    plan._run = broken


def test_sound_run_is_correct(tiny_root):
    assert _run(tiny_root("tiny_lm"), None)["correct"]


def test_state_left_unchanged_is_caught(tiny_root):
    def unchanged(inner, state, batches, mask):
        keep = jax.tree_util.tree_map(jnp.copy, state)
        _, losses = inner(state, batches, mask)
        return keep, losses
    res = _run(tiny_root("tiny_lm"), lambda p: _wrap(p, unchanged))
    assert not res["correct"]
    assert res["check"]["change_gap"]["value"] > 0.99


def test_half_batch_is_caught(tiny_root):
    def half(inner, state, batches, mask):
        b = batches["inputs"].shape[2]
        cut = jax.tree_util.tree_map(lambda v: v[:, :, :b // 2], batches)
        return inner(state, cut, mask)
    res = _run(tiny_root("tiny_lm"), lambda p: _wrap(p, half))
    assert not res["correct"]
