"""The benchmark's analytic FLOP counters against XLA's own count
(``cost_analysis``) of the plain reference's forward at a small size. XLA
also counts normalisation and activations, which the model FLOPs leave
out, so it may read a little higher, never lower."""
import os

import jax
import jax.numpy as jnp
import pytest

from bench import workload as wl
from bench.flops import cnn as flops_cnn
from bench.flops import lm as flops_lm
from bench.reference import cnn, lm
from benchtools import BENCH, TINY, load


def xla_flops(fn, *args):
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def test_cnn_forward_flops():
    c = {**load(os.path.join(BENCH, "configs", "mobilenetv2-224-split.json")),
         "image_size": 64}
    table = cnn.stage_table(c)

    def fwd(params, x):
        for (kind, args, _), p in zip(table, params):
            x = cnn._apply_stage(kind, args, p, x, None, 1e-5)
        return x
    counted = xla_flops(fwd, cnn.init_params(c, 0), jnp.zeros((1, 64, 64, 3)))
    analytic = flops_cnn.forward_flops_per_example(c)
    assert analytic <= counted <= 1.15 * analytic


def test_lm_forward_flops_causal_half():
    """XLA multiplies out the full S x S score and value products (and
    counts the blocks only when they are not inside a loop); the model
    FLOPs count the causal half, so the non-causal count is the analytic
    count plus that half again."""
    c = {**load(os.path.join(BENCH, "configs", "smollm-135m-split.json")),
         **TINY["tiny_lm"][2]}
    s = 128
    cell = wl.Cell("flops", c, {"lr": 1e-3}, 1)
    ref = lm.Reference(cell, 0)
    n = int(c["num_hidden_layers"])
    layer = lambda t, i: jax.tree_util.tree_map(lambda v: v[i], t)  # noqa

    def fwd(pc, ps, toks):
        h = pc["embed"][toks]
        for i in range(pc["blocks"]["ln1"]["scale"].shape[0]):
            h = ref._block(layer(pc["blocks"], i), h)
        for i in range(ps["blocks"]["ln1"]["scale"].shape[0]):
            h = ref._block(layer(ps["blocks"], i), h)
        return ref._mm(h, ps["head"])
    counted = xla_flops(fwd, ref.pc0, ref.ps0, jnp.zeros((1, s), jnp.int32))
    hq = int(c["num_attention_heads"]) * int(c["head_dim"])
    analytic = flops_lm.forward_flops_per_token(c, s) * s
    full_attention = analytic + 2.0 * s * s * hq * n
    assert full_attention <= counted <= 1.15 * full_attention
    assert flops_lm.train_flops_per_token(c, s) == pytest.approx(
        3 * flops_lm.forward_flops_per_token(c, s))
