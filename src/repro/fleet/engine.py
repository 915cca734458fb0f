"""Sharded SPMD fleet rounds: the stacked client axis over the `data` mesh axis.

PR 1's round builders carry every per-client quantity (params, Adam moments,
minibatches) on a leading client axis but walk that axis with ``lax.scan`` —
sequential by construction. Here the client axis becomes a *batch* axis, in
one of two layouts (``client_axis=``):

  * ``'vmap'`` — ``jax.vmap`` over clients plus ``with_sharding_constraint``
    hints: XLA's GSPMD partitioner infers the collective schedule (FedAvg
    and the server's client-mean gradient lower to all-reduces over
    ``data``). One-host friendly; layout is advisory.
  * ``'shard_map'`` — the per-client step runs INSIDE ``jax.shard_map`` over
    the ``data`` mesh axis: every device owns ``clients/data`` rows of the
    stack, FedAvg is the explicit ``core.fedavg.fedavg_pmean`` family
    (masked variants included, so dropout semantics survive the
    collective), and the parallel-SL server gradient is an in-map
    ``lax.pmean``. The collective schedule is pinned in the program — the
    prerequisite for multi-host meshes, where GSPMD inference can differ
    per host. The non-``data`` mesh axes (``fsdp``, ``tp``) stay
    automatic (outside the map's ``axis_names``).

The 2D (clients x server-model) layout: ``launch.mesh.make_fleet_mesh``
builds the ``('data','fsdp','tp')`` mesh, ``launch.steps
.fleet_server_pspecs`` derives the server suffix's tier specs (the same
DESIGN.md §3 rule ``build_step`` applies), and ``server_pspecs=`` wires
them into the SL round — server params/optimizer state shard fsdp x tp
while the client stack shards over ``data`` (``fleet_sl_state_shardings``
is that layout; place live state with ``shard_fleet_sl_state``). The
combination with ``shard_map`` compiles for the TPU but is gated to fsdp =
tp = 1 on the CPU backend, whose SPMD partitioner refuses it (see
``make_fleet_sl_round``); the vmap engine runs the full 2D layout on every
backend.

Round semantics per engine:

  * FL — ``make_fleet_fl_round``: clients are fully independent until
    FedAvg, i.e. ``make_fl_round(..., client_axis='vmap')`` per shard.
  * SL — ``make_fleet_sl_round``: Efficient *Parallel* Split Learning (Lin
    et al., arXiv:2303.15991): every client's prefix fwd/bwd runs batched
    against the shared server suffix, and the server applies ONE update per
    local step on the client-mean gradient, instead of Algorithm 3's
    sequential per-client server updates. This is a deliberate semantic
    variant (the UAV relays all clients' smashed data per hover window); it
    is NOT numerically equivalent to ``make_multi_client_round`` — its
    reference is the parallel host loop in ``tests/test_fleet.py``.

Equivalence tolerance
---------------------
``FLEET_EQUIV_ATOL`` is the documented loosened bound for fleet-vs-scan
comparisons. The scanned engine matches the per-client host loop to 1e-4;
vmapping the client axis batches the convolutions and reassociates their
fp32 reductions (and sharding/shard_map re-tiles them again), which drifts
losses by up to ~1e-3 after a few Adam steps on the tiny test models.
Independent clients make this pure arithmetic reassociation, not a semantic
change. The shard_map engines are gated against the vmap engines by the
same bound (``tests/test_fleet.py``, forced multi-device host mesh).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.fedavg import (fedavg_mean, fedavg_mean_masked, fedavg_pmean,
                           fedavg_pmean_masked, fedavg_pmean_stack,
                           fedavg_pmean_stack_masked, fedavg_stack,
                           fedavg_stack_masked)
from ..core.split import (CLIENT_SCOPE, SERVER_SCOPE, SplitStep,
                          make_fl_round)
from ..obs.metrics import tree_nonfinite, tree_norm
from ..optim.optimizers import OptState, apply_updates

# Documented loosened tolerance for vmapped/sharded vs sequential rounds
# (see module docstring; tests and benches assert against this bound).
FLEET_EQUIV_ATOL = 1e-3

# the mesh axis the stacked client dimension shards over — every other
# fleet-mesh axis belongs to the server suffix (fsdp x tp) and stays
# GSPMD-auto inside the shard_map engines
CLIENT_AXIS_NAME = "data"

CLIENT_AXES = ("vmap", "shard_map")


def jit_round(raw_fn: Callable, name: str, **jit_kw):
    """``jax.jit`` of a round function under a stable ``name``: the
    compiled module is ``jit_<name>`` (as the profiler's ``XLA Modules``
    line reads it), whatever ``raw_fn`` itself is called."""
    def round_fn(*args):
        return raw_fn(*args)
    round_fn.__name__ = round_fn.__qualname__ = name
    return jax.jit(round_fn, **jit_kw)


def fleet_sharding(mesh) -> NamedSharding:
    """Sharding of a client-stacked leaf: leading axis over ``data``."""
    return NamedSharding(mesh, P(CLIENT_AXIS_NAME))


def validate_fleet_mesh(mesh, num_clients: int) -> None:
    """The client axis must divide evenly over ``data`` — no silent padding."""
    if mesh is None:
        return
    data = dict(zip(mesh.axis_names,
                    mesh.devices.shape)).get(CLIENT_AXIS_NAME, 1)
    if num_clients % data:
        raise ValueError(
            f"{num_clients} clients do not divide over data={data}; pick a "
            f"fleet size divisible by the mesh's data axis (launch.mesh."
            f"make_fleet_mesh chooses one automatically)")


def shard_client_stack(tree, mesh):
    """Host-side placement of a client-stacked pytree onto the fleet mesh."""
    if mesh is None:
        return tree
    s = fleet_sharding(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, s), tree)


def _constrain(tree, mesh):
    if mesh is None:
        return tree
    s = fleet_sharding(mesh)
    return jax.tree_util.tree_map(
        lambda x: jax.lax.with_sharding_constraint(x, s), tree)


def _resolve_shard_map_mesh(mesh):
    """A shard_map engine always needs a concrete mesh: default to the
    degenerate single-device fleet mesh (collectives become no-ops) so the
    explicit-collective path compiles anywhere."""
    if mesh is None:
        from ..launch.mesh import single_device_fleet_mesh
        return single_device_fleet_mesh()
    if CLIENT_AXIS_NAME not in mesh.axis_names:
        raise ValueError(f"fleet shard_map mesh needs a '{CLIENT_AXIS_NAME}' "
                         f"axis, got {mesh.axis_names}")
    return mesh


def _client_shard_map(body, mesh, in_specs, out_specs):
    """shard_map manual over ``data`` only; every other mesh axis (fsdp/tp)
    stays automatic (left out of ``axis_names``) so in-map sharding
    constraints can lay out the server suffix."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={CLIENT_AXIS_NAME},
                         check_vma=False)


def server_mesh_sizes(mesh) -> tuple[int, int]:
    """(fsdp, tp) sizes of the fleet mesh's server sub-mesh (1, 1 when the
    axes are absent — e.g. the legacy ('data','model') mesh)."""
    if mesh is None:
        return 1, 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("fsdp", 1), sizes.get("tp", 1)


def fleet_sl_state_shardings(state, mesh, *, shared: bool = False,
                             server_pspecs=None):
    """Shardings of a fleet SL round's state ``(client params, server
    params, client opt state, server opt state)`` on ``mesh`` — the one
    source of the 2D (clients x server-model) layout. The per-client stacks
    shard over ``data`` (replicated for the shared client tier); the server
    suffix and its optimizer moments follow ``server_pspecs``
    (``launch.steps.fleet_server_pspecs``), replicated when None.
    ``shard_fleet_sl_state`` places live state by it, and a round jitted
    with it as ``out_shardings`` returns its state where it found it, so
    every round of a run is one program. ``state`` may be abstract
    (``jax.eval_shape``)."""
    pc, ps, oc, os_ = state
    client = NamedSharding(mesh, P() if shared else P(CLIENT_AXIS_NAME))
    if server_pspecs is None:
        server = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), ps)
    else:
        server = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), server_pspecs,
            is_leaf=lambda x: isinstance(x, P))
    per_client = lambda tree: jax.tree_util.tree_map(lambda _: client, tree)
    moments = lambda m: None if m is None else server
    return (per_client(pc), server, per_client(oc),
            OptState(step=NamedSharding(mesh, P()), mu=moments(os_.mu),
                     nu=moments(os_.nu)))


def shard_fleet_sl_state(state, mesh, **kw):
    """Host-side placement of a fleet SL state per
    ``fleet_sl_state_shardings`` (same keywords)."""
    if mesh is None:
        return state
    return jax.device_put(state, fleet_sl_state_shardings(state, mesh, **kw))


def _server_constrainer(mesh, server_pspecs) -> Optional[Callable]:
    """tree -> tree applying the fsdp x tp tier specs to the server suffix
    at round/map-body entry; GSPMD propagates the layout through the
    round's scan carry. Trivial spec trees (every dim replicated — fsdp =
    tp = 1) collapse to None so the shard_map body stays constraint-free
    on 1D meshes. (Inside a manual-over-``data`` body the constraint must
    also stay OUTSIDE the scan: XLA:CPU's SPMD partitioner refuses
    auto-axis resharding inside a while-loop of a manual computation —
    see ``api.plan`` for the backend gate.)"""
    if mesh is None or server_pspecs is None:
        return None
    if all(all(ax is None for ax in s)
           for s in jax.tree_util.tree_leaves(
               server_pspecs, is_leaf=lambda s: isinstance(s, P))):
        return None
    def constrain(tree):
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)),
            tree, server_pspecs)
    return constrain


def _check_client_axis(client_axis: str) -> None:
    if client_axis not in CLIENT_AXES:
        raise ValueError(f"fleet client_axis must be one of {CLIENT_AXES}, "
                         f"got {client_axis!r} (the sequential engine is "
                         f"core.split's client_axis='scan')")


# ---------------------------------------------------------------------------
# FL rounds
# ---------------------------------------------------------------------------

def make_fleet_fl_round(grad_fn: Callable, opt, *, mesh=None,
                        client_dropout: bool = False,
                        client_axis: str = "vmap", taps: tuple = ()):
    """FL baseline round with the client axis batched and (optionally)
    sharded over ``data``. Same signature/returns as ``make_fl_round``:
    ``f(global_params, batches) -> (new_global_params, losses[C, S])``;
    with ``taps`` the round additionally returns the (clients, steps)
    metrics-bus tap stacks (see ``make_fl_round``), sharded like the
    losses.

    ``client_axis='vmap'`` leaves layout to GSPMD via sharding constraints
    (``mesh`` optional); ``client_axis='shard_map'`` runs the per-client
    local scan inside ``jax.shard_map`` over ``data`` and aggregates with
    the explicit ``fedavg_pmean`` collective (``mesh`` defaults to the
    single-device fleet mesh).

    With ``client_dropout`` the round takes a trailing ``client_mask``
    (clients,) 0/1 argument: masked clients still execute (the program is
    shape-static) but are excluded from FedAvg — stragglers that missed
    the round contribute nothing to the new global model (the shard_map
    path psums the masked sums and active count: ``fedavg_pmean_masked``).
    All-masked rounds leave the global params unchanged.
    """
    _check_client_axis(client_axis)
    vmapped = make_fl_round(grad_fn, opt, client_axis="vmap",
                            aggregate=False, taps=taps)

    if client_axis == "shard_map":
        mesh = _resolve_shard_map_mesh(mesh)
        spec_c = P(CLIENT_AXIS_NAME)
        # every FL tap leaf is (clients, steps): sharded like the losses
        tap_specs = ({name: spec_c for name in taps},) if taps else ()

        if not client_dropout:
            def body(global_params, batches):
                out = vmapped(global_params, batches)
                agg = fedavg_pmean(out[0], CLIENT_AXIS_NAME)
                return (agg,) + out[1:]

            return _client_shard_map(body, mesh, in_specs=(P(), spec_c),
                                     out_specs=(P(), spec_c) + tap_specs)

        def body_masked(global_params, batches, client_mask):
            out = vmapped(global_params, batches)
            new_params = fedavg_pmean_masked(out[0], client_mask,
                                             global_params, CLIENT_AXIS_NAME)
            return (new_params,) + out[1:]

        return _client_shard_map(body_masked, mesh,
                                 in_specs=(P(), spec_c, spec_c),
                                 out_specs=(P(), spec_c) + tap_specs)

    if not client_dropout:
        def global_round(global_params, batches):
            batches = _constrain(batches, mesh)
            out = vmapped(global_params, batches)
            # FedAvg reduces the client axis (an all-reduce over `data`
            # when sharded); losses/taps keep the client-sharded layout.
            return (fedavg_mean(out[0]),) + tuple(
                _constrain(o, mesh) for o in out[1:])

        return global_round

    def global_round_masked(global_params, batches, client_mask):
        batches = _constrain(batches, mesh)
        out = vmapped(global_params, batches)
        new_params = fedavg_mean_masked(out[0], client_mask,
                                        global_params)
        return (new_params,) + tuple(_constrain(o, mesh) for o in out[1:])

    return global_round_masked


# ---------------------------------------------------------------------------
# parallel-SL rounds
# ---------------------------------------------------------------------------

def make_fleet_sl_round(step: SplitStep, opt_c, opt_s, *, local_rounds: int,
                        mesh=None, server_reduce: str = "mean",
                        client_dropout: bool = False,
                        client_axis: str = "vmap", server_pspecs=None,
                        client_tier: str = "stacked", taps: tuple = ()):
    """One global round of *parallel* split learning over a sharded fleet.

    Per local step: every client's prefix runs fwd/bwd batched (vmap over
    the stacked client params/opt-states/batches) against the shared server
    suffix; client updates are per-client, the server takes one update on
    the ``server_reduce`` ('mean' | 'sum') of the per-client server
    gradients. After ``local_rounds`` steps the client prefixes are
    FedAvg'd, all inside the one compiled program.

    ``client_axis='shard_map'`` runs the whole round body inside
    ``jax.shard_map`` over ``data``: the server gradient is reduced with an
    in-map ``lax.pmean`` (``lax.psum`` of masked sums under dropout), the
    closing FedAvg is ``fedavg_pmean_stack(_masked)``, and the server
    update — fed the identical all-reduced gradient on every shard — stays
    replicated over ``data``.

    ``server_pspecs`` (a PartitionSpec tree from
    ``launch.steps.fleet_server_pspecs``) constrains the server suffix over
    the mesh's ``fsdp`` x ``tp`` axes at round entry, giving the 2D
    (clients x server-model) layout; ``shard_fleet_sl_state`` places the
    live state to match. Fully supported under ``client_axis='vmap'`` (pure
    GSPMD). Under ``shard_map`` those axes stay automatic and the
    combination is the intended multi-host layout. The TPU compiler
    partitions it; XLA:CPU's SPMD partitioner refuses the partial-manual
    all-reduce (RET_CHECK ``IsManualSubgroup``), so ``api.plan`` gates the
    CPU backend to fsdp = tp = 1 for shard_map.

    Signature matches ``make_multi_client_round``:
    ``f(params_c_stack, params_s, oc_stack, os_, batches)`` with ``batches``
    leading (clients, local_rounds) axes; losses return as
    ``(local_rounds, clients)``.

    With ``client_dropout`` the round takes a trailing ``client_mask``
    (clients,) 0/1 argument (traced — one compile serves every mask):
    P3SL-style stragglers. Masked clients keep their params/opt state
    frozen for the round, contribute nothing to the server's reduced
    gradient, and are excluded from the closing FedAvg (they rejoin later
    from their stale prefix). A fully-masked round is a no-op on all state.

    ``client_tier`` picks the client-state representation:

      "stacked" — today's resident fleet: per-client params + Adam moments
                  on the leading client axis, closing FedAvg. State is
                  O(clients).
      "shared"  — EPSL cohort mode (Lin et al.): ONE set of client params +
                  opt state serves every cohort slot. Per local step the
                  prefix fwd/bwd is vmapped over cohort batches with the
                  shared params broadcast (``in_axes=(0, None, None)``) and
                  the client takes one update on the masked cohort-MEAN
                  gradient — mirroring the server's update, so there is no
                  closing FedAvg and no per-slot state to leak between the
                  different population clients occupying a slot across
                  rounds. Signature/state shape changes: ``params_c`` /
                  ``oc`` are UNSTACKED; losses stay (local_rounds, clients).
                  Under ``shard_map`` the client state is replicated and
                  its gradient all-reduced (psum of masked sums / active
                  count) exactly like the server's, so every shard applies
                  the identical update. State is O(1) in both the cohort
                  and the population.

    ``taps`` enables the metrics bus (``repro.obs.metrics``): the round
    additionally returns a dict of float32 tap stacks riding the same
    local-step scan as the losses. Per-slot channels (grad norms,
    nonfinite, the SplitStep's smashed/quant taps) come back
    (local_rounds, clients) in the loss layout; one-update-per-step
    channels are (local_rounds,) — ``update_norm_server`` always, and
    ``update_norm_client`` too under the shared tier (EPSL takes one
    client update per step). Taps report the RAW per-slot computation:
    masked stragglers still execute, their rows are excluded from state
    but visible on the bus (``mask`` tallies let consumers filter). Empty
    taps lowers the exact tap-free program.
    """
    if server_reduce not in ("mean", "sum"):
        raise ValueError(server_reduce)
    if client_tier not in ("stacked", "shared"):
        raise ValueError(f"client_tier must be 'stacked' or 'shared', "
                         f"got {client_tier!r}")
    _check_client_axis(client_axis)
    if client_axis == "shard_map":
        mesh = _resolve_shard_map_mesh(mesh)
        axis = CLIENT_AXIS_NAME
        # the body is manual over `data`: no host-level constraints inside
        constrain_mesh = None
    else:
        axis = None
        constrain_mesh = mesh
    constrain_server = _server_constrainer(mesh, server_pspecs)
    # vmap's client axis is the sharded `data` axis: per-device kernels
    # (kernels.dispatch.per_device) inside the step keep it sharded
    spmd_axis = CLIENT_AXIS_NAME if constrain_mesh is not None else None

    def allreduce_sum(x):
        return jax.lax.psum(x, axis) if axis is not None else x

    def _run_round(params_c_stack, params_s, oc_stack, os_, batches, mask):
        params_c_stack = _constrain(params_c_stack, constrain_mesh)
        oc_stack = _constrain(oc_stack, constrain_mesh)
        batches = _constrain(batches, constrain_mesh)
        if constrain_server is not None:
            params_s = constrain_server(params_s)
        # (clients, local_rounds, ...) -> (local_rounds, clients, ...)
        batches_rm = jax.tree_util.tree_map(
            lambda x: jnp.swapaxes(x, 0, 1), batches)
        # round constants hoisted above the local-step scan: under shard_map
        # each is ONE psum per round, not one per step
        n_active = (None if mask is None
                    else jnp.maximum(allreduce_sum(mask.sum()), 1.0))
        any_active = None if mask is None else allreduce_sum(mask.sum()) > 0

        def per_client_grads(pc, batch, ps):
            loss, aux, g_c, g_s = step.grads(pc, ps, batch)
            if taps:
                return loss, aux.get("taps", {}), g_c, g_s
            return loss, g_c, g_s

        def masked_rows(new, old):
            """Keep masked clients' leading-axis rows at their old value."""
            def sel(n, o):
                w = mask.reshape((n.shape[0],) + (1,) * (n.ndim - 1))
                return jnp.where(w > 0, n, o)
            return jax.tree_util.tree_map(sel, new, old)

        def round_body(carry, batch_r):
            params_c_stack, oc_stack, params_s, os_ = carry
            grads_out = jax.vmap(
                per_client_grads, in_axes=(0, 0, None),
                spmd_axis_name=spmd_axis)(params_c_stack, batch_r, params_s)
            if taps:
                losses, aux_t, g_c_stack, g_s_stack = grads_out
            else:
                losses, g_c_stack, g_s_stack = grads_out
                aux_t = {}
            with jax.named_scope(CLIENT_SCOPE):
                up_c, oc_new = jax.vmap(opt_c.update)(
                    g_c_stack, oc_stack, params_c_stack)
                pc_new = apply_updates(params_c_stack, up_c)
                if mask is not None:
                    pc_new = masked_rows(pc_new, params_c_stack)
                    oc_new = masked_rows(oc_new, oc_stack)
            params_c_stack, oc_stack = pc_new, oc_new
            # server: ONE update on the fleet-reduced gradient — under
            # shard_map an explicit in-map lax.pmean/psum over `data`, under
            # vmap an all-reduce GSPMD infers when the client axis is sharded
            def reduce_g(g):
                g32 = g.astype(jnp.float32)
                if mask is None:
                    if server_reduce == "mean":
                        m = jnp.mean(g32, axis=0)
                        if axis is not None:
                            m = jax.lax.pmean(m, axis)
                        return m.astype(g.dtype)
                    return allreduce_sum(jnp.sum(g32, axis=0)).astype(g.dtype)
                w = mask.reshape((g.shape[0],) + (1,) * (g.ndim - 1))
                s = allreduce_sum((g32 * w).sum(axis=0))
                if server_reduce == "mean":
                    s = s / n_active
                return s.astype(g.dtype)
            with jax.named_scope(SERVER_SCOPE):
                g_s = jax.tree_util.tree_map(reduce_g, g_s_stack)
                up_s, os_new = opt_s.update(g_s, os_, params_s)
                ps_new = apply_updates(params_s, up_s)
                if mask is not None:
                    # zero active clients -> the server also sits the round out
                    ps_new = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(any_active, n, o), ps_new,
                        params_s)
                    os_new = jax.tree_util.tree_map(
                        lambda n, o: jnp.where(any_active, n, o), os_new, os_)
            if taps:
                t = dict(aux_t)
                if "grad_norm_client" in taps:
                    t["grad_norm_client"] = jax.vmap(tree_norm)(g_c_stack)
                if "grad_norm_server" in taps:
                    t["grad_norm_server"] = jax.vmap(tree_norm)(g_s_stack)
                if "update_norm_client" in taps:
                    t["update_norm_client"] = jax.vmap(tree_norm)(up_c)
                if "update_norm_server" in taps:
                    t["update_norm_server"] = tree_norm(up_s)
                if "nonfinite" in taps:
                    # tapped norms double as the guard (NaN/inf propagate
                    # through the L2 reduction); untapped tiers pay the
                    # elementwise pass
                    bad = (~jnp.isfinite(losses)).astype(jnp.float32)
                    for k, stk in (("grad_norm_client", g_c_stack),
                                   ("grad_norm_server", g_s_stack)):
                        bad = jnp.maximum(
                            bad,
                            (~jnp.isfinite(t[k])).astype(jnp.float32)
                            if k in t else jax.vmap(tree_nonfinite)(stk))
                    t["nonfinite"] = bad
                out = (losses, t)
            else:
                out = losses
            return (params_c_stack, oc_stack, ps_new, os_new), out

        carry = (params_c_stack, oc_stack, params_s, os_)
        carry, out = jax.lax.scan(round_body, carry, batches_rm)
        params_c_stack, oc_stack, params_s, os_ = carry
        if axis is not None:
            agg = (fedavg_pmean_stack(params_c_stack, axis) if mask is None
                   else fedavg_pmean_stack_masked(params_c_stack, mask, axis))
        else:
            agg = (fedavg_stack(params_c_stack) if mask is None
                   else fedavg_stack_masked(params_c_stack, mask))
        params_c_stack = _constrain(agg, constrain_mesh)
        if taps:
            losses, tap_stack = out
            return (params_c_stack, params_s, oc_stack, os_, losses,
                    tap_stack)
        return params_c_stack, params_s, oc_stack, os_, out

    def _run_round_shared(params_c, params_s, oc, os_, batches, mask):
        batches = _constrain(batches, constrain_mesh)
        if constrain_server is not None:
            params_s = constrain_server(params_s)
        batches_rm = jax.tree_util.tree_map(
            lambda x: jnp.swapaxes(x, 0, 1), batches)
        n_active = (None if mask is None
                    else jnp.maximum(allreduce_sum(mask.sum()), 1.0))
        any_active = None if mask is None else allreduce_sum(mask.sum()) > 0

        def per_client_grads(batch, pc, ps):
            loss, aux, g_c, g_s = step.grads(pc, ps, batch)
            if taps:
                return loss, aux.get("taps", {}), g_c, g_s
            return loss, g_c, g_s

        def reduce_g(g, reduce):
            """Cohort reduction of a per-slot gradient stack: masked mean
            (or sum), all-reduced over `data` under shard_map."""
            g32 = g.astype(jnp.float32)
            if mask is None:
                if reduce == "mean":
                    m = jnp.mean(g32, axis=0)
                    if axis is not None:
                        m = jax.lax.pmean(m, axis)
                    return m.astype(g.dtype)
                return allreduce_sum(jnp.sum(g32, axis=0)).astype(g.dtype)
            w = mask.reshape((g.shape[0],) + (1,) * (g.ndim - 1))
            s = allreduce_sum((g32 * w).sum(axis=0))
            if reduce == "mean":
                s = s / n_active
            return s.astype(g.dtype)

        def guard(new, old):
            # zero active clients -> the whole round is a no-op on state
            return jax.tree_util.tree_map(
                lambda nw, o: jnp.where(any_active, nw, o), new, old)

        def round_body(carry, batch_r):
            params_c, oc, params_s, os_ = carry
            grads_out = jax.vmap(
                per_client_grads, in_axes=(0, None, None),
                spmd_axis_name=spmd_axis)(batch_r, params_c, params_s)
            if taps:
                losses, aux_t, g_c_stack, g_s_stack = grads_out
            else:
                losses, g_c_stack, g_s_stack = grads_out
                aux_t = {}
            # the shared client tier updates like the server: one step on
            # the masked cohort-MEAN prefix gradient (EPSL)
            with jax.named_scope(CLIENT_SCOPE):
                g_c = jax.tree_util.tree_map(lambda g: reduce_g(g, "mean"),
                                             g_c_stack)
                up_c, oc_new = opt_c.update(g_c, oc, params_c)
                pc_new = apply_updates(params_c, up_c)
                if mask is not None:
                    pc_new, oc_new = guard(pc_new, params_c), guard(oc_new, oc)
            with jax.named_scope(SERVER_SCOPE):
                g_s = jax.tree_util.tree_map(
                    lambda g: reduce_g(g, server_reduce), g_s_stack)
                up_s, os_new = opt_s.update(g_s, os_, params_s)
                ps_new = apply_updates(params_s, up_s)
                if mask is not None:
                    ps_new = guard(ps_new, params_s)
                    os_new = guard(os_new, os_)
            if taps:
                t = dict(aux_t)
                if "grad_norm_client" in taps:
                    t["grad_norm_client"] = jax.vmap(tree_norm)(g_c_stack)
                if "grad_norm_server" in taps:
                    t["grad_norm_server"] = jax.vmap(tree_norm)(g_s_stack)
                # EPSL: ONE shared client update per step -> scalar channel
                if "update_norm_client" in taps:
                    t["update_norm_client"] = tree_norm(up_c)
                if "update_norm_server" in taps:
                    t["update_norm_server"] = tree_norm(up_s)
                if "nonfinite" in taps:
                    # tapped norms double as the guard, as above
                    bad = (~jnp.isfinite(losses)).astype(jnp.float32)
                    for k, stk in (("grad_norm_client", g_c_stack),
                                   ("grad_norm_server", g_s_stack)):
                        bad = jnp.maximum(
                            bad,
                            (~jnp.isfinite(t[k])).astype(jnp.float32)
                            if k in t else jax.vmap(tree_nonfinite)(stk))
                    t["nonfinite"] = bad
                out = (losses, t)
            else:
                out = losses
            return (pc_new, oc_new, ps_new, os_new), out

        carry = (params_c, oc, params_s, os_)
        carry, out = jax.lax.scan(round_body, carry, batches_rm)
        params_c, oc, params_s, os_ = carry
        if taps:
            losses, tap_stack = out
            return params_c, params_s, oc, os_, losses, tap_stack
        return params_c, params_s, oc, os_, out

    run_body = _run_round_shared if client_tier == "shared" else _run_round

    if client_axis == "shard_map":
        spec_c = P(CLIENT_AXIS_NAME)
        # shared client state is replicated (its update is all-reduced);
        # stacked client state shards over `data`
        state_c = P() if client_tier == "shared" else spec_c
        # losses carry the client axis SECOND: (local_rounds, clients)
        out_specs = (state_c, P(), state_c, P(), P(None, CLIENT_AXIS_NAME))
        if taps:
            # per-slot tap channels share the loss layout; one-update-per-
            # step channels are replicated (the update is all-reduced
            # identically on every shard)
            scalar = {"update_norm_server"}
            if client_tier == "shared":
                scalar.add("update_norm_client")
            out_specs = out_specs + ({
                name: (P(None) if name in scalar
                       else P(None, CLIENT_AXIS_NAME))
                for name in taps},)

        if client_dropout:
            def body_masked(params_c_stack, params_s, oc_stack, os_, batches,
                            client_mask):
                mask = jnp.asarray(client_mask, jnp.float32)
                return run_body(params_c_stack, params_s, oc_stack, os_,
                                batches, mask)
            return _client_shard_map(
                body_masked, mesh,
                in_specs=(state_c, P(), state_c, P(), spec_c, spec_c),
                out_specs=out_specs)

        def body(params_c_stack, params_s, oc_stack, os_, batches):
            return run_body(params_c_stack, params_s, oc_stack, os_,
                            batches, None)
        return _client_shard_map(
            body, mesh, in_specs=(state_c, P(), state_c, P(), spec_c),
            out_specs=out_specs)

    if client_dropout:
        def global_round_masked(params_c_stack, params_s, oc_stack, os_,
                                batches, client_mask):
            mask = jnp.asarray(client_mask, jnp.float32)
            return run_body(params_c_stack, params_s, oc_stack, os_,
                            batches, mask)
        return global_round_masked

    def global_round(params_c_stack, params_s, oc_stack, os_, batches):
        return run_body(params_c_stack, params_s, oc_stack, os_, batches,
                        None)

    return global_round
