"""Device-resident multi-client engine — regression vs the sequential
reference.

The scanned engine (``make_fl_round`` / ``make_multi_client_round``) must be
numerically equivalent to the plain per-client Python loops it replaced, and
the trainers' energy accounting must come from *symmetric* FLOP counting
(XLA-counted fwd+bwd on both tiers for both pipelines).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import compile_experiment
from repro.core.fedavg import fedavg, fedavg_stack
from repro.core.paper_train import (PaperTrainConfig, count_fl_step_flops,
                                    count_sl_step_flops, paper_spec)
from repro.core.split import (SplitStep, apply_stages, init_stages,
                              make_fl_round, make_multi_client_round,
                              partition_stages)
from repro.models.cnn import CNN_BUILDERS, cross_entropy_loss
from repro.optim import adamw, apply_updates, init_stacked

C, S, B = 3, 2, 4          # clients, local steps, batch
NUM_CLASSES = 4


@pytest.fixture(scope="module")
def tiny_setup():
    stages = CNN_BUILDERS["tinycnn"](NUM_CLASSES)
    key = jax.random.PRNGKey(0)
    params = init_stages(key, stages)
    bx = jax.random.uniform(jax.random.fold_in(key, 1), (C, S, B, 16, 16, 3))
    by = jax.random.randint(jax.random.fold_in(key, 2), (C, S, B), 0,
                            NUM_CLASSES)
    return stages, params, bx, by


def _assert_trees_close(a, b, atol=1e-4):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32), atol=atol)


def test_fl_round_matches_sequential(tiny_setup):
    """One scanned FL global round == the per-client Python-loop reference."""
    stages, params, bx, by = tiny_setup
    opt = adamw(1e-3)

    def grad_fn(p, batch):
        xx, yy = batch
        return jax.value_and_grad(
            lambda q: cross_entropy_loss(apply_stages(stages, q, xx), yy))(p)

    new_params, losses = jax.jit(make_fl_round(grad_fn, opt))(params, (bx, by))
    assert losses.shape == (C, S)

    # sequential reference: the seed's host loop
    step = jax.jit(lambda p, o, xx, yy: _fl_step(grad_fn, opt, p, o, xx, yy))
    client_models, ref_losses = [], []
    for ci in range(C):
        p = jax.tree_util.tree_map(jnp.copy, params)
        o = opt.init(p)
        for si in range(S):
            p, o, loss = step(p, o, bx[ci, si], by[ci, si])
            ref_losses.append(float(loss))
        client_models.append(p)
    ref_params = fedavg(client_models)

    np.testing.assert_allclose(np.asarray(losses).ravel(),
                               np.asarray(ref_losses), atol=1e-4)
    _assert_trees_close(new_params, ref_params)


def _fl_step(grad_fn, opt, p, o, xx, yy):
    loss, g = grad_fn(p, (xx, yy))
    up, o = opt.update(g, o, p)
    return apply_updates(p, up), o, loss


def test_sl_round_matches_sequential(tiny_setup):
    """One scanned Alg. 3 global round == the seed's step-major host loop
    (sequential server updates per client batch, FedAvg of prefixes)."""
    stages, params, bx, by = tiny_setup
    cs, cp0, ss, sp, _ = partition_stages(stages, params, 0.4)
    opt_c, opt_s = adamw(1e-3), adamw(1e-3)
    step = SplitStep(
        client_fwd=lambda pc, xx: apply_stages(cs, pc, xx),
        server_loss=lambda ps, sm, yy: (
            cross_entropy_loss(apply_stages(ss, ps, sm), yy), {}),
    )
    engine = jax.jit(make_multi_client_round(step, opt_c, opt_s,
                                             local_rounds=S))
    client_stack = jax.tree_util.tree_map(
        lambda v: jnp.broadcast_to(v[None], (C,) + v.shape), cp0)
    oc_stack = init_stacked(opt_c, cp0, C)
    out_stack, out_sp, _, _, losses = engine(
        client_stack, sp, oc_stack, opt_s.init(sp),
        {"inputs": bx, "targets": by})
    assert losses.shape == (S, C)

    # sequential reference: the seed's host loop (step-major client visits)
    @jax.jit
    def split_step(cp, cop, spar, sop, xx, yy):
        loss, _, gc, gs = step.grads(cp, spar, {"inputs": xx, "targets": yy})
        upc, cop = opt_c.update(gc, cop, cp)
        ups, sop = opt_s.update(gs, sop, spar)
        return apply_updates(cp, upc), cop, apply_updates(spar, ups), sop, loss

    cps = [jax.tree_util.tree_map(jnp.copy, cp0) for _ in range(C)]
    cops = [opt_c.init(cp0) for _ in range(C)]
    spar, sop = sp, opt_s.init(sp)
    ref_losses = np.zeros((S, C))
    for si in range(S):
        for ci in range(C):
            cps[ci], cops[ci], spar, sop, loss = split_step(
                cps[ci], cops[ci], spar, sop, bx[ci, si], by[ci, si])
            ref_losses[si, ci] = float(loss)
    ref_stack = fedavg_stack(jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *cps))

    np.testing.assert_allclose(np.asarray(losses), ref_losses, atol=1e-4)
    _assert_trees_close(out_stack, ref_stack)
    _assert_trees_close(out_sp, spar)


def test_symmetric_flop_accounting(tiny_setup):
    """SL's client+server per-step FLOPs are counted with the same
    methodology as FL's full step: their sum must be close to the full
    fwd+bwd count, and each tier strictly positive (never a silent 0)."""
    stages, params, bx, by = tiny_setup
    cs, cp0, ss, sp, _ = partition_stages(stages, params, 0.4)
    full = count_fl_step_flops(stages, params, bx[0, 0], by[0, 0])
    client_fl, server_fl, smashed = count_sl_step_flops(
        cs, cp0, ss, sp, bx[0, 0], by[0, 0])
    assert full > 0 and client_fl > 0 and server_fl > 0
    assert smashed.shape[0] == B
    # split-step total ~ full-model total (cut gradient work double-counts
    # only the cut boundary, a small slice of the whole)
    assert 0.5 * full < client_fl + server_fl < 1.5 * full


def test_xla_flops_counts_on_host_from_shapes():
    """The bill's FLOP count compiles for the host CPU from the arguments'
    shapes alone, whatever device is the default: on an accelerator host a
    Pallas kernel in the program is counted in interpret mode."""
    from repro.core.flops import xla_flops
    from repro.kernels.dispatch import pallas_interpret
    from repro.kernels.quant.ops import quant_dequant

    def fn(x, scale):
        y = quant_dequant(x * scale, use_pallas=True,
                          interpret=pallas_interpret())
        return jnp.sum(y @ x.T)

    x = jnp.ones((64, 128), jnp.float32)
    counted = xla_flops(fn, x, 2.0)
    assert counted > 2 * 64 * 64 * 128
    assert xla_flops(fn, jax.ShapeDtypeStruct(x.shape, x.dtype), 2.0) \
        == counted
    with jax.default_device("tpu"):          # as on a TPU host
        assert xla_flops(fn, x, 2.0) == counted


def test_paper_spec_energy_ratio_and_records():
    """End-to-end via the spec layer (``paper_spec`` — the mapping the
    dropped ``train_fl``/``train_sl`` shims used): both pipelines run on
    the tiny backbone and a shallow split spends less client energy than
    FL under the symmetric accounting (the paper's headline direction)."""
    rng = np.random.RandomState(0)
    n = 96
    x = rng.uniform(0, 1, size=(n, 16, 16, 3)).astype(np.float32)
    y = rng.randint(0, 12, size=(n,))
    cfg = PaperTrainConfig(model="tinycnn", num_clients=3, global_rounds=2,
                           local_steps=2, batch_size=4, image_size=16,
                           client_fraction=0.4)
    data = (x, y, x[:24], y[:24])
    plan_fl = compile_experiment(paper_spec(cfg, "fl"), data=data)
    plan_sl = compile_experiment(paper_spec(cfg, "sl"), data=data)
    _, rec_fl = plan_fl.run()
    _, rec_sl = plan_sl.run()
    assert len(rec_fl) == len(rec_sl) == cfg.global_rounds

    # symmetric accounting: the SL client runs a strict subset of the FL
    # client's per-step work, so its energy must be strictly smaller
    k = plan_sl.cut_of_client[0]
    client_flops, server_flops, _smashed = plan_sl.flops[k]
    assert client_flops > 0 and server_flops > 0
    assert client_flops < plan_fl.flops["full"]
    assert (sum(r.client_energy_j for r in rec_sl)
            < sum(r.client_energy_j for r in rec_fl))
    assert sum(r.link_bytes for r in rec_sl) > 0
    assert sum(r.link_bytes for r in rec_fl) == 0
