"""Fleet subsystem — sharded engine equivalence, hetero bucketing, link
compression, and the campaign acceptance scenario.

Equivalence assertions use ``repro.fleet.engine.FLEET_EQUIV_ATOL``, the
documented loosened tolerance: vmapping/sharding the client axis
reassociates fp32 reductions vs the sequential scan reference (the scanned
engine itself holds a 1e-4 bound vs the host loop — see test_engine.py).
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import compile_experiment
from repro.core.energy import HardwareProfile, JETSON_AGX_ORIN
from repro.core.link import LinkConfig
from repro.core.split import (SplitStep, apply_stages, init_stages,
                              make_fl_round, partition_stages)
from repro.fleet import (CampaignConfig, FleetLink, HeteroFleet,
                         FLEET_EQUIV_ATOL, assign_cuts_cnn, bucket_by_cut,
                         campaign_spec, campaign_totals, cnn_split_program,
                         make_fleet_fl_round, make_fleet_sl_round,
                         stack_split_program)
from repro.kernels.quant.ref import roundtrip_error_bound
from repro.models.cnn import CNN_BUILDERS, cross_entropy_loss
from repro.optim import adamw, apply_updates, init_stacked

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
C, S, B = 4, 2, 4          # clients, local steps, batch
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


def _max_tree_diff(a, b) -> float:
    return max(float(jnp.abs(la.astype(jnp.float32)
                             - lb.astype(jnp.float32)).max())
               for la, lb in zip(jax.tree_util.tree_leaves(a),
                                 jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------------------
# engine: vmapped client axis + sharding
# ---------------------------------------------------------------------------

def test_fleet_fl_vmap_matches_scan(tiny_setup):
    """The vmapped FL client axis (fleet engine / client_axis='vmap') tracks
    the sequential scan engine within the documented loosened tolerance."""
    stages, params, bx, by = tiny_setup
    opt = adamw(1e-3)

    def grad_fn(p, batch):
        xx, yy = batch
        return jax.value_and_grad(
            lambda q: cross_entropy_loss(apply_stages(stages, q, xx), yy))(p)

    scan_round = jax.jit(make_fl_round(grad_fn, opt, client_axis="scan"))
    fleet_round = jax.jit(make_fleet_fl_round(grad_fn, opt))
    p_scan, p_fleet = params, params
    for _ in range(2):   # two consecutive rounds so drift compounds
        p_scan, l_scan = scan_round(p_scan, (bx, by))
        p_fleet, l_fleet = fleet_round(p_fleet, (bx, by))
        assert l_fleet.shape == (C, S)
        np.testing.assert_allclose(np.asarray(l_fleet), np.asarray(l_scan),
                                   atol=FLEET_EQUIV_ATOL)
    assert _max_tree_diff(p_fleet, p_scan) < FLEET_EQUIV_ATOL


def test_fl_round_rejects_unknown_client_axis(tiny_setup):
    stages, params, bx, by = tiny_setup
    with pytest.raises(ValueError):
        make_fl_round(lambda p, b: (0.0, p), adamw(1e-3),
                      client_axis="pmap")(params, (bx, by))


def test_fleet_sl_round_matches_parallel_reference(tiny_setup):
    """The compiled parallel-SL round == a host loop with the same semantics
    (batched client fwd/bwd, ONE server update per step on the client-mean
    gradient, FedAvg of prefixes at round end)."""
    stages, params, bx, by = tiny_setup
    cs, cp0, ss, sp, _ = partition_stages(stages, params, 0.4)
    opt_c, opt_s = adamw(1e-3), adamw(1e-3)
    step = SplitStep(
        client_fwd=lambda pc, xx: apply_stages(cs, pc, xx),
        server_loss=lambda ps, sm, yy: (
            cross_entropy_loss(apply_stages(ss, ps, sm), yy), {}),
    )
    engine = jax.jit(make_fleet_sl_round(step, opt_c, opt_s, local_rounds=S))
    client_stack = jax.tree_util.tree_map(
        lambda v: jnp.broadcast_to(v[None], (C,) + v.shape), cp0)
    out_stack, out_sp, _, _, losses = engine(
        client_stack, sp, init_stacked(opt_c, cp0, C), opt_s.init(sp),
        {"inputs": bx, "targets": by})
    assert losses.shape == (S, C)

    # host reference of the same parallel semantics
    cps = [jax.tree_util.tree_map(jnp.copy, cp0) for _ in range(C)]
    cops = [opt_c.init(cp0) for _ in range(C)]
    spar, sop = sp, opt_s.init(sp)
    ref_losses = np.zeros((S, C))
    for si in range(S):
        grads_c, grads_s, step_losses = [], [], []
        for ci in range(C):
            loss, _, g_c, g_s = step.grads(
                cps[ci], spar, {"inputs": bx[ci, si], "targets": by[ci, si]})
            grads_c.append(g_c)
            grads_s.append(g_s)
            step_losses.append(float(loss))
        for ci in range(C):
            up, cops[ci] = opt_c.update(grads_c[ci], cops[ci], cps[ci])
            cps[ci] = apply_updates(cps[ci], up)
        g_mean = jax.tree_util.tree_map(
            lambda *g: jnp.mean(jnp.stack(g), axis=0), *grads_s)
        up_s, sop = opt_s.update(g_mean, sop, spar)
        spar = apply_updates(spar, up_s)
        ref_losses[si] = step_losses
    from repro.core.fedavg import fedavg_stack
    ref_stack = fedavg_stack(jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *cps))

    np.testing.assert_allclose(np.asarray(losses), ref_losses,
                               atol=FLEET_EQUIV_ATOL)
    assert _max_tree_diff(out_stack, ref_stack) < FLEET_EQUIV_ATOL
    assert _max_tree_diff(out_sp, spar) < FLEET_EQUIV_ATOL


def test_sharded_round_matches_unsharded_host_mesh():
    """8 clients on a forced 4-device host mesh: the sharded fleet FL and
    SL rounds — GSPMD-constrained vmap AND explicit-collective shard_map —
    match the unsharded engine within FLEET_EQUIV_ATOL; the shard_map
    dropout masks (fedavg_pmean_masked, psum'd active counts) match the
    vmap masked-FedAvg result at the same gate; the vmap engine also runs
    the 2D (data=2, fsdp=2) layout with fleet_server_pspecs. Runs in a
    subprocess because forcing 4 host devices must precede jax init."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.core.split import (SplitStep, apply_stages, init_stages,
                                      partition_stages)
        from repro.fleet.engine import (FLEET_EQUIV_ATOL, make_fleet_fl_round,
                                        make_fleet_sl_round,
                                        shard_client_stack,
                                        shard_fleet_sl_state)
        from repro.launch.mesh import make_fleet_mesh
        from repro.launch.steps import fleet_server_pspecs
        from repro.models.cnn import CNN_BUILDERS, cross_entropy_loss
        from repro.optim import adamw, init_stacked

        C, S, B = 8, 2, 2
        stages = CNN_BUILDERS["tinycnn"](4)
        key = jax.random.PRNGKey(0)
        params = init_stages(key, stages)
        bx = jax.random.uniform(jax.random.fold_in(key, 1),
                                (C, S, B, 16, 16, 3))
        by = jax.random.randint(jax.random.fold_in(key, 2), (C, S, B), 0, 4)
        mask = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 1], jnp.float32)
        mesh = make_fleet_mesh(C)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        assert sizes == {"data": 4, "fsdp": 1, "tp": 1}, sizes

        def tree_diff(a, b):
            return max(float(jnp.abs(x - y).max()) for x, y in zip(
                jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))

        diffs = {}
        opt = adamw(1e-3)
        def grad_fn(p, batch):
            xx, yy = batch
            return jax.value_and_grad(lambda q: cross_entropy_loss(
                apply_stages(stages, q, xx), yy))(p)
        plain = jax.jit(make_fleet_fl_round(grad_fn, opt))(params, (bx, by))
        for name, axis in (("fl_vmap", "vmap"), ("fl_smap", "shard_map")):
            out = jax.jit(make_fleet_fl_round(
                grad_fn, opt, mesh=mesh, client_axis=axis))(
                    params, shard_client_stack((bx, by), mesh))
            diffs[name + "_loss"] = float(jnp.abs(plain[1] - out[1]).max())
            diffs[name + "_par"] = tree_diff(plain[0], out[0])
        # dropout: shard_map masked FedAvg (fedavg_pmean_masked) == vmap
        plain_m = jax.jit(make_fleet_fl_round(
            grad_fn, opt, client_dropout=True))(params, (bx, by), mask)
        smap_m = jax.jit(make_fleet_fl_round(
            grad_fn, opt, mesh=mesh, client_axis="shard_map",
            client_dropout=True))(
                params, shard_client_stack((bx, by), mesh),
                shard_client_stack(mask, mesh))
        diffs["fl_mask_loss"] = float(jnp.abs(plain_m[1] - smap_m[1]).max())
        diffs["fl_mask_par"] = tree_diff(plain_m[0], smap_m[0])

        cs, cp0, ss, sp, _ = partition_stages(stages, params, 0.4)
        opt_c, opt_s = adamw(1e-3), adamw(1e-3)
        step = SplitStep(
            client_fwd=lambda pc, xx: apply_stages(cs, pc, xx),
            server_loss=lambda ps, sm, yy: (
                cross_entropy_loss(apply_stages(ss, ps, sm), yy), {}))
        stack = jax.tree_util.tree_map(
            lambda v: jnp.broadcast_to(v[None], (C,) + v.shape), cp0)
        batches = {"inputs": bx, "targets": by}

        def sl_state():
            return (stack, sp, init_stacked(opt_c, cp0, C), opt_s.init(sp))

        def sl_sharded_state(m, **kw):
            return shard_fleet_sl_state(sl_state(), m, **kw)

        plain_sl = jax.jit(make_fleet_sl_round(
            step, opt_c, opt_s, local_rounds=S))(*sl_state(), batches)
        for name, axis in (("sl_vmap", "vmap"), ("sl_smap", "shard_map")):
            out = jax.jit(make_fleet_sl_round(
                step, opt_c, opt_s, local_rounds=S, mesh=mesh,
                client_axis=axis))(*sl_sharded_state(mesh),
                                   shard_client_stack(batches, mesh))
            diffs[name + "_loss"] = float(jnp.abs(plain_sl[4] - out[4]).max())
            diffs[name + "_par"] = max(tree_diff(plain_sl[0], out[0]),
                                       tree_diff(plain_sl[1], out[1]))
        # dropout through the in-map collectives: masked clients frozen,
        # psum'd server reduction, fedavg_pmean_stack_masked closing agg
        plain_ms = jax.jit(make_fleet_sl_round(
            step, opt_c, opt_s, local_rounds=S, client_dropout=True))(
                *sl_state(), batches, mask)
        smap_ms = jax.jit(make_fleet_sl_round(
            step, opt_c, opt_s, local_rounds=S, mesh=mesh,
            client_axis="shard_map", client_dropout=True))(
                *sl_sharded_state(mesh), shard_client_stack(batches, mesh),
                shard_client_stack(mask, mesh))
        diffs["sl_mask_loss"] = float(
            jnp.abs(plain_ms[4] - smap_ms[4]).max())
        diffs["sl_mask_par"] = max(tree_diff(plain_ms[0], smap_ms[0]),
                                   tree_diff(plain_ms[1], smap_ms[1]))

        # 2D layout: (data=2, fsdp=2) mesh, server suffix sharded with the
        # build_step tier specs, vmap engine (GSPMD; shard_map x fsdp>1 is
        # gated off XLA:CPU — see fleet.engine)
        mesh2d = make_fleet_mesh(C, fsdp=2)
        sizes2d = dict(zip(mesh2d.axis_names, mesh2d.devices.shape))
        assert sizes2d == {"data": 2, "fsdp": 2, "tp": 1}, sizes2d
        sps = fleet_server_pspecs(sp, mesh2d)
        assert any(any(ax == "fsdp" for ax in s)
                   for s in jax.tree_util.tree_leaves(sps))
        out2d = jax.jit(make_fleet_sl_round(
            step, opt_c, opt_s, local_rounds=S, mesh=mesh2d,
            server_pspecs=sps))(
                *sl_sharded_state(mesh2d, server_pspecs=sps),
                shard_client_stack(batches, mesh2d))
        diffs["sl_2d_loss"] = float(jnp.abs(plain_sl[4] - out2d[4]).max())
        diffs["sl_2d_par"] = max(tree_diff(plain_sl[0], out2d[0]),
                                 tree_diff(plain_sl[1], out2d[1]))
        server_specs_out = {str(l.sharding.spec)
                            for l in jax.tree_util.tree_leaves(out2d[1])}

        # the same layout through the SPEC layer: EngineSpec(server_mesh=)
        # auto-builds the ('data','fsdp','tp') mesh and plan.init() places
        # the live server params + Adam moments with shard_fleet_sl_state
        # (incl. the OptState(step=P(), mu=specs, nu=specs) spec tree)
        from repro.api import (ClientSpec, CutPolicy, DataSpec, EngineSpec,
                               ExperimentSpec, ModelSpec, compile_experiment)
        spec = ExperimentSpec(
            model=ModelSpec(name="tinycnn", num_classes=4),
            data=DataSpec(kind="synthetic", image_size=16,
                          classes_per_client=2),
            clients=ClientSpec(num_clients=C),
            cut_policy=CutPolicy(mode="fraction", fraction=0.4),
            engine=EngineSpec(kind="sl", client_axis="vmap",
                              server_mesh=(2, 1)),
            global_rounds=1, local_steps=S, batch_size=2)
        plan = compile_experiment(spec)
        ms = dict(zip(plan.mesh.axis_names, plan.mesh.devices.shape))
        assert ms == {"data": 2, "fsdp": 2, "tp": 1}, ms
        state = plan.init()
        init_specs = {str(l.sharding.spec) for l in
                      jax.tree_util.tree_leaves(state.engine_state[1])}
        assert any("fsdp" in s for s in init_specs), init_specs
        state, rec = plan.run_round(state)
        assert rec.loss == rec.loss and rec.active_clients == C

        diffs["atol"] = FLEET_EQUIV_ATOL
        diffs["server_specs_out"] = sorted(server_specs_out)
        print(json.dumps(diffs))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    for k, v in rec.items():
        if k.endswith("_loss") or k.endswith("_par"):
            assert v < rec["atol"], (k, rec)
    # the 2D run's server suffix really lives on the fsdp axis
    assert any("fsdp" in s for s in rec["server_specs_out"]), rec


def test_mesh_plan_compiles_round_once():
    """Fleet plans on a forced 4-device mesh (``data=4`` under both client
    axes, ``server_mesh=(2, 2)`` under vmap): ``plan.init()`` places the
    engine state where the round returns it, so the rounds after the first
    compile nothing (the first round would otherwise run a program of its
    own, and GSPMD would pick where the server-mesh round leaves the
    client optimizer state)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json
        from repro.api import (ClientSpec, DataSpec, EngineSpec,
                               ExperimentSpec, ModelSpec, compile_experiment)
        from repro.launch.mesh import make_fleet_mesh
        from repro.obs.gauges import RecompileCounter

        counter = RecompileCounter().install()
        compiles = {}
        for name, axis, server_mesh in (("vmap", "vmap", None),
                                        ("shard_map", "shard_map", None),
                                        ("vmap_2x2", "vmap", (2, 2))):
            spec = ExperimentSpec(
                model=ModelSpec(name="tinycnn", num_classes=4),
                data=DataSpec(kind="synthetic", image_size=16,
                              classes_per_client=2),
                clients=ClientSpec(num_clients=8),
                engine=EngineSpec(kind="sl", client_axis=axis,
                                  server_mesh=server_mesh),
                global_rounds=3, local_steps=2, batch_size=2)
            plan = compile_experiment(
                spec, mesh=None if server_mesh else make_fleet_mesh(8))
            state = plan.init()
            state, _ = plan.run_round(state, with_eval=False)
            n0 = counter.snapshot()[0]
            for _ in range(2):
                state, _ = plan.run_round(state, with_eval=False)
            compiles[name] = counter.snapshot()[0] - n0
        print(json.dumps(compiles))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    compiles = json.loads(out.stdout.strip().splitlines()[-1])
    assert compiles == {"vmap": 0, "shard_map": 0, "vmap_2x2": 0}, compiles


# ---------------------------------------------------------------------------
# hetero: cut assignment + bucketed dispatch
# ---------------------------------------------------------------------------

def test_bucketing_partitions_fleet():
    """bucket_by_cut is a partition: every client exactly once, buckets
    keyed by distinct cuts, deterministic order."""
    cuts = [2, 1, 2, 1, 1, 3, 2, 1]
    buckets = bucket_by_cut(cuts)
    seen = [cid for b in buckets for cid in b.client_ids]
    assert sorted(seen) == list(range(len(cuts)))
    assert len(seen) == len(set(seen)) == len(cuts)
    assert [b.cut_index for b in buckets] == [1, 2, 3]
    for b in buckets:
        assert all(cuts[cid] == b.cut_index for cid in b.client_ids)


def test_assign_cuts_cnn_profiles(tiny_setup):
    """Per-client cut selection: valid range, and identical (hardware, link)
    profiles always agree on the cut."""
    stages, params, bx, _ = tiny_setup
    mcu = HardwareProfile("mcu-class", fp32_tflops=0.02, mem_bw_gbs=2.0,
                          tensor_tflops=0.04, cpu_passmark=400.0, power_w=2.0)
    edges = [JETSON_AGX_ORIN, mcu, JETSON_AGX_ORIN, mcu]
    cuts = assign_cuts_cnn(stages, params, bx[0, 0], edges=edges)
    assert len(cuts) == 4
    assert all(1 <= k <= len(stages) - 1 for k in cuts)
    assert cuts[0] == cuts[2] and cuts[1] == cuts[3]


def test_hetero_fleet_round_covers_every_client(tiny_setup):
    """Bucketed dispatch: a mixed-cut fleet runs one global round and every
    client's losses are filled exactly once (from its own bucket)."""
    stages, params, bx, by = tiny_setup
    cuts = [1, 2, 1, 2]
    fleet = HeteroFleet(
        lambda k: cnn_split_program(stages, params, k,
                                    loss_fn=cross_entropy_loss),
        cuts, adamw(1e-3), adamw(1e-3), local_rounds=S)
    assert fleet.cut_of_client == cuts
    assert [b.cut_index for b in fleet.buckets] == [1, 2]
    losses = fleet.run_round({"inputs": bx, "targets": by})
    assert losses.shape == (S, C)
    assert np.isfinite(losses).all() and (losses > 0).all()
    # second round trains on (donated-through) bucket state
    losses2 = fleet.run_round({"inputs": bx, "targets": by})
    assert np.isfinite(losses2).all()
    assert losses2.mean() < losses.mean()   # same batches -> loss drops


def test_stack_split_program_matches_full_forward():
    """split_stack generalization: client scan + server scan == scanning the
    whole stacked-block model, and the fleet round trains it."""
    L, D, Bz = 6, 8, 4
    key = jax.random.PRNGKey(0)
    stacked = {"w": 0.3 * jax.random.normal(key, (L, D, D)),
               "b": jnp.zeros((L, D))}

    def block_apply(blk, h):
        return jnp.tanh(h @ blk["w"] + blk["b"])

    def loss_fn(h, targets):
        return jnp.mean((h.mean(-1) - targets) ** 2)

    prog = stack_split_program(stacked, 2, block_apply=block_apply,
                               loss_fn=loss_fn)
    x = jax.random.normal(jax.random.fold_in(key, 1), (Bz, D))
    full = x
    for i in range(L):
        full = block_apply(jax.tree_util.tree_map(lambda v: v[i], stacked),
                           full)
    smashed = prog.step.client_fwd(prog.params_c0, x)
    assert smashed.shape == (Bz, D)
    loss, _ = prog.step.server_loss(prog.params_s0, smashed,
                                    jnp.zeros((Bz,)))
    served = prog.step.client_fwd(prog.params_s0, smashed)  # same scan body
    np.testing.assert_allclose(np.asarray(served), np.asarray(full),
                               rtol=1e-5, atol=1e-6)
    assert np.isfinite(float(loss))

    # one fleet round over 4 clients of the stacked model
    opt_c, opt_s = adamw(1e-2), adamw(1e-2)
    engine = jax.jit(make_fleet_sl_round(prog.step, opt_c, opt_s,
                                         local_rounds=S))
    stack = jax.tree_util.tree_map(
        lambda v: jnp.broadcast_to(v[None], (C,) + v.shape), prog.params_c0)
    bx = jax.random.normal(jax.random.fold_in(key, 2), (C, S, Bz, D))
    by = jax.random.normal(jax.random.fold_in(key, 3), (C, S, Bz))
    *_, losses = engine(stack, prog.params_s0,
                        init_stacked(opt_c, prog.params_c0, C),
                        opt_s.init(prog.params_s0),
                        {"inputs": bx, "targets": by})
    assert losses.shape == (S, C) and bool(jnp.isfinite(losses).all())


# ---------------------------------------------------------------------------
# link: int8 boundary
# ---------------------------------------------------------------------------

def test_int8_link_roundtrip_and_straight_through():
    """The compressed boundary respects the quantizer's roundtrip error
    bound and passes gradients straight through."""
    link = FleetLink(config=LinkConfig(compress="int8"))
    boundary = link.boundary()
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 128)) * 3.0
    y = boundary(x)
    bound = roundtrip_error_bound(x.reshape(-1, x.shape[-1]))
    assert np.all(np.abs(np.asarray(x - y)) <= np.asarray(bound) + 1e-7)
    # straight-through: d/dx sum(compress(x)) == 1 everywhere
    g = jax.grad(lambda v: boundary(v).sum())(x)
    np.testing.assert_allclose(np.asarray(g), 1.0)


def test_int8_link_in_split_step_grads_flow(tiny_setup):
    """Attaching the int8 boundary keeps the split step differentiable:
    client and server grads stay finite/nonzero and near the uncompressed
    ones (straight-through estimator)."""
    stages, params, bx, by = tiny_setup
    cs, cp0, ss, sp, _ = partition_stages(stages, params, 0.4)
    step = SplitStep(
        client_fwd=lambda pc, xx: apply_stages(cs, pc, xx),
        server_loss=lambda ps, sm, yy: (
            cross_entropy_loss(apply_stages(ss, ps, sm), yy), {}),
    )
    step8 = FleetLink(config=LinkConfig(compress="int8")).attach(step)
    assert step8.link_constraint is not None and step.link_constraint is None
    _, _, g_c, g_s = step.grads(cp0, sp, {"inputs": bx[0, 0],
                                          "targets": by[0, 0]})
    _, _, g_c8, g_s8 = step8.grads(cp0, sp, {"inputs": bx[0, 0],
                                             "targets": by[0, 0]})
    for g in (g_c8, g_s8):
        leaves = jax.tree_util.tree_leaves(g)
        assert all(bool(jnp.isfinite(l).all()) for l in leaves)
        assert any(float(jnp.abs(l).max()) > 0 for l in leaves)
    # compression perturbs but does not derail the gradients
    num = sum(float(jnp.sum(jnp.abs(a - b)))
              for a, b in zip(jax.tree_util.tree_leaves(g_c),
                              jax.tree_util.tree_leaves(g_c8)))
    den = sum(float(jnp.sum(jnp.abs(a)))
              for a in jax.tree_util.tree_leaves(g_c))
    assert num / den < 0.5


def test_int8_wire_bytes_ratio():
    """int8 wire volume = 1 byte/elem + one f32 scale per quantizer row
    (the smashed tensor's last dim), matching what the kernel actually
    emits — a 4/(1 + 4/last_dim) shrink vs f32."""
    sd = jax.ShapeDtypeStruct((16, 8, 8, 32), jnp.float32)
    plain = FleetLink(config=LinkConfig()).step_wire_bytes(sd)
    comp = FleetLink(config=LinkConfig(compress="int8")).step_wire_bytes(sd)
    assert plain == 2 * sd.size * 4          # roundtrip fp32
    np.testing.assert_allclose(plain / comp, 4.0 / (1.0 + 4.0 / 32.0),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# campaign (acceptance scenario)
# ---------------------------------------------------------------------------

def test_campaign_link_sweep_records():
    """>=8 simulated clients produce per-round energy/accuracy/link-bytes
    records for both fp32 and int8 link modes; int8 moves ~4x fewer bytes
    on the same scenario; the UAV budget caps the rounds. The sweep is two
    campaign specs differing only in the link policy (the shape the dropped
    ``run_link_sweep`` shim used to package)."""
    cfg = CampaignConfig(model="tinycnn", num_clients=8, global_rounds=2,
                         local_steps=2, batch_size=4, image_size=16,
                         num_classes=NUM_CLASSES, classes_per_client=2)
    results = {}
    for mode in ("none", "int8"):
        spec = campaign_spec(dataclasses.replace(
            cfg, link=dataclasses.replace(cfg.link, compress=mode)))
        plan = compile_experiment(spec)
        _, records = plan.run()
        results[mode] = (plan, records)
    for mode, (plan, records) in results.items():
        assert plan.rounds_budget >= len(records) > 0
        assert len(plan.cut_of_client) == 8
        for rec in records:
            d = rec.to_dict()
            assert d["link_bytes"] > 0 and d["client_energy_j"] > 0
            assert d["server_energy_j"] > 0 and d["uav_energy_j"] > 0
            assert d["link_energy_j"] > 0
            assert 0.0 <= d["accuracy"] <= 1.0
            assert np.isfinite(d["loss"])

    totals = {mode: campaign_totals(records, plan.tour)
              for mode, (plan, records) in results.items()}
    for mode, (plan, records) in results.items():
        # mission totals include the return-to-base leg no record bills
        assert totals[mode]["uav_energy_j"] == pytest.approx(
            sum(r.uav_energy_j for r in records) + plan.tour.e_return)
        assert totals[mode]["rounds_run"] == len(records)

    ratio = totals["none"]["link_bytes"] / totals["int8"]["link_bytes"]
    # 4/(1 + 4/last_dim): narrow CNN smashed tensors pay more scale overhead
    assert 2.5 < ratio < 4.0, ratio
    # the compressed link also cuts radio transmit energy by the same factor
    e_ratio = (totals["none"]["link_energy_j"]
               / totals["int8"]["link_energy_j"])
    np.testing.assert_allclose(e_ratio, ratio, rtol=1e-6)
    # same seed + fleet -> identical tours; only the link differs
    assert results["none"][0].tour.order == results["int8"][0].tour.order


def test_campaign_adaptive_cuts():
    """Adaptive per-client cuts on a heterogeneous fleet: every client gets
    a valid cut and the campaign still produces records."""
    mcu = HardwareProfile("mcu-class", fp32_tflops=0.02, mem_bw_gbs=2.0,
                          tensor_tflops=0.04, cpu_passmark=400.0, power_w=2.0)
    cfg = CampaignConfig(model="tinycnn", num_clients=8, global_rounds=1,
                         local_steps=2, batch_size=4, image_size=16,
                         num_classes=NUM_CLASSES, classes_per_client=2,
                         adaptive_cuts=True,
                         edge_profiles=(JETSON_AGX_ORIN, mcu))
    plan = compile_experiment(campaign_spec(cfg))
    _, records = plan.run()
    assert len(plan.cut_of_client) == 8
    assert all(k >= 1 for k in plan.cut_of_client)
    assert len(records) == 1 and np.isfinite(records[0].loss)


def test_fleet_mesh_divisible_or_none():
    """make_fleet_mesh picks a ('data','fsdp','tp') layout whose data axis
    divides the fleet, or returns None when only one device is usable
    (device count varies with test order — earlier tests may force extra
    host devices)."""
    from repro.launch.mesh import make_fleet_mesh, single_device_fleet_mesh
    mesh = make_fleet_mesh(8)
    if len(jax.devices()) == 1:
        assert mesh is None
    else:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        assert 8 % sizes["data"] == 0
        assert sizes["fsdp"] == sizes["tp"] == 1    # server axes default off
    assert make_fleet_mesh(8, max_data=1) is None   # capped to one device
    assert make_fleet_mesh(1) is None               # one client, no mesh
    # the server sub-mesh consumes devices before the client axis
    n = len(jax.devices())
    assert make_fleet_mesh(8, fsdp=n + 1) is None   # over budget
    if n > 1:
        mesh2d = make_fleet_mesh(8, fsdp=n)
        sizes = dict(zip(mesh2d.axis_names, mesh2d.devices.shape))
        assert sizes == {"data": 1, "fsdp": n, "tp": 1}
    sd = single_device_fleet_mesh()
    assert dict(zip(sd.axis_names, sd.devices.shape)) == {
        "data": 1, "fsdp": 1, "tp": 1}


def test_server_only_mesh_keeps_server_axes():
    """A bucket whose size does not divide `data` falls back to the mesh
    with data collapsed to 1 — the fsdp/tp server sub-mesh survives
    instead of being silently dropped."""
    from repro.fleet.hetero import _server_only_mesh
    assert _server_only_mesh(None) is None
    if len(jax.devices()) >= 2:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(8, fsdp=len(jax.devices()) // 1, tp=1)
        # build a (data=1, fsdp=n) mesh directly: collapse is identity
        assert _server_only_mesh(mesh) is mesh
        mesh_d = make_fleet_mesh(8)          # data>1, fsdp=tp=1
        sub = _server_only_mesh(mesh_d)
        sizes = dict(zip(sub.axis_names, sub.devices.shape))
        assert sizes["data"] == 1
        assert sizes["fsdp"] == mesh_d.devices.shape[1]
        assert sizes["tp"] == mesh_d.devices.shape[2]


def test_fleet_server_pspecs_divisibility_guard():
    """fleet_server_pspecs mirrors build_step's server tier rule on the
    fleet mesh: matrix last-two dims (fsdp, tp), vectors over tp, every
    dim guarded — a non-dividing dim falls back to replicated."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import abstract_mesh
    from repro.launch.steps import fleet_server_pspecs
    mesh = abstract_mesh((1, 2, 4), ("data", "fsdp", "tp"))
    params = {"w": jnp.zeros((3, 3, 8, 16)),   # conv kernel: cin/fsdp, cout/tp
              "v": jnp.zeros((6, 16)),         # dense: 6%2==0 -> fsdp
              "odd": jnp.zeros((5, 7)),        # nothing divides -> replicated
              "b": jnp.zeros((16,)),           # bias follows cout -> tp
              "s": jnp.zeros(())}              # scalar -> replicated
    specs = fleet_server_pspecs(params, mesh)
    assert specs["w"] == P(None, None, "fsdp", "tp")
    assert specs["v"] == P("fsdp", "tp")
    assert specs["odd"] == P(None, None)
    assert specs["b"] == P("tp")
    assert specs["s"] == P()
