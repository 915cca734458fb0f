"""Split learning core — paper Algorithm 3 (SplitFed pattern), generalized.

Two composition styles are supported:

1. **Stage lists** (heterogeneous stacks — the paper's CNNs): a model is a
   list of ``Stage(init, apply, name)``; ``partition_stages`` cuts it into
   client/server prefix/suffix at a layer fraction. Used by the faithful
   reproduction benches.

2. **Stacked blocks** (homogeneous transformer stacks, scan-over-layers):
   block params carry a leading n_layers axis; ``split_stack`` slices that
   axis at the cut index. Used by the 10 assigned architectures, where the
   cut is additionally a sharding boundary (client prefix: pure DP; server
   suffix: DP x TP) — see DESIGN.md §3.

The split train step is ONE differentiable program: client forward ->
(link: sharding-constraint boundary whose bytes = smashed data L) -> server
forward + loss; ``jax.grad`` over (params_c, params_s) yields exactly the
distributed backward of Algorithm 3. The U-shaped variant keeps labels (and
the final head) on the client so labels never cross the link.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

Params = Any

# Named scopes of the compiled round (``jax.named_scope``): every HLO op of
# a tier, its backward (``transpose(jvp(...))``) and its recompute carries
# the tier's scope in its ``op_name`` metadata, so a device profile charges
# each op to the client tier, the link or the server tier.
CLIENT_SCOPE = "sl/client"
LINK_SCOPE = "sl/link"
SERVER_SCOPE = "sl/server"
FL_SCOPE = "fl/client"


# ---------------------------------------------------------------------------
# 1. stage lists (CNN repro)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Stage:
    name: str
    init: Callable[[jax.Array], Params]
    apply: Callable[[Params, jax.Array], jax.Array]
    # relative depth weight for cut placement (a "stage" may hold several
    # paper-layers, e.g. a ResNet group of 2 blocks)
    depth: int = 1


def init_stages(key: jax.Array, stages: Sequence[Stage]) -> list[Params]:
    keys = jax.random.split(key, len(stages))
    return [s.init(k) for s, k in zip(stages, keys)]


def apply_stages(stages: Sequence[Stage], params: Sequence[Params], x: jax.Array) -> jax.Array:
    for s, p in zip(stages, params):
        x = s.apply(p, x)
    return x


def cut_index_for_fraction(stages: Sequence[Stage], client_fraction: float) -> int:
    """Smallest prefix whose depth-share >= client_fraction (paper's SL_{a,b}:
    client holds a% of layers). Always leaves >=1 stage per side."""
    total = sum(s.depth for s in stages)
    acc = 0
    for i, s in enumerate(stages):
        acc += s.depth
        if acc / total >= client_fraction - 1e-9:
            return min(max(i + 1, 1), len(stages) - 1)
    return len(stages) - 1


def partition_stages(stages: Sequence[Stage], params: Sequence[Params],
                     client_fraction: float) -> tuple[list, list, list, list, int]:
    """Returns (client_stages, client_params, server_stages, server_params, k)."""
    k = cut_index_for_fraction(stages, client_fraction)
    return list(stages[:k]), list(params[:k]), list(stages[k:]), list(params[k:]), k


# ---------------------------------------------------------------------------
# 2. stacked blocks (transformers; scan-over-layers)
# ---------------------------------------------------------------------------

def split_stack(stacked: Params, k: int) -> tuple[Params, Params]:
    """Slice every leaf's leading (layer) axis at k."""
    client = jax.tree_util.tree_map(lambda x: x[:k], stacked)
    server = jax.tree_util.tree_map(lambda x: x[k:], stacked)
    return client, server


def merge_stack(client: Params, server: Params) -> Params:
    return jax.tree_util.tree_map(lambda a, b: jnp.concatenate([a, b], axis=0),
                                  client, server)


def stack_cut_index(n_layers: int, client_fraction: float,
                    *, max_client: Optional[int] = None) -> int:
    """Cut index for a homogeneous stack; optionally clamped (e.g. MoE archs
    force the cut at/below the first MoE layer — experts can't live on the
    edge tier, DESIGN.md §4)."""
    k = max(1, min(n_layers - 1, int(math.ceil(client_fraction * n_layers))))
    if max_client is not None:
        k = min(k, max(1, max_client))
    return k


# ---------------------------------------------------------------------------
# split train/eval steps (differentiable end-to-end)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SplitStep:
    """Builds jit-able split-learning steps from client/server apply fns.

    client_fwd(params_c, inputs)            -> smashed
    server_loss(params_s, smashed, targets) -> (loss, aux)
    For the U-shaped variant additionally:
    server_body(params_s, smashed)          -> features   (no labels server-side)
    client_head_loss(params_c, feats, tgts) -> (loss, aux)
    """
    client_fwd: Callable
    server_loss: Optional[Callable] = None
    server_body: Optional[Callable] = None
    client_head_loss: Optional[Callable] = None
    link_constraint: Optional[Callable] = None  # smashed -> smashed (sharding)
    variant: str = "vanilla"  # "vanilla" | "ushaped"
    # metrics-bus taps computed inside the step (they need the smashed
    # tensor): subset of {"smashed_mean","smashed_std","smashed_absmax",
    # "quant_error"}, carried out through aux["taps"]. Empty = the exact
    # tap-free trace.
    taps: tuple = ()

    def _link(self, x):
        if self.link_constraint is None:
            return x
        with jax.named_scope(LINK_SCOPE):
            return self.link_constraint(x)

    def loss_fn(self, params_c, params_s, batch):
        inputs, targets = batch["inputs"], batch["targets"]
        with jax.named_scope(CLIENT_SCOPE):
            raw_smashed = self.client_fwd(params_c, inputs)
        smashed = self._link(raw_smashed)
        if self.variant == "vanilla":
            with jax.named_scope(SERVER_SCOPE):
                loss, aux = self.server_loss(params_s, smashed, targets)
        elif self.variant == "ushaped":
            with jax.named_scope(SERVER_SCOPE):
                feats = self.server_body(params_s, smashed)
            feats = self._link(feats)
            with jax.named_scope(CLIENT_SCOPE):
                loss, aux = self.client_head_loss(params_c, feats, targets)
        else:
            raise ValueError(self.variant)
        aux = dict(aux)
        aux["smashed_elems"] = jnp.asarray(
            sum(x.size for x in jax.tree_util.tree_leaves(smashed)), jnp.float32)
        if self.taps:
            from ..obs.metrics import smashed_tap_values
            aux["taps"] = smashed_tap_values(self.taps, raw_smashed, smashed)
        return loss, aux

    def grads(self, params_c, params_s, batch):
        (loss, aux), (g_c, g_s) = jax.value_and_grad(
            self.loss_fn, argnums=(0, 1), has_aux=True)(params_c, params_s, batch)
        return loss, aux, g_c, g_s


def make_split_train_step(step: SplitStep, opt_c, opt_s):
    """Returns f(params_c, params_s, oc, os, batch) -> (params_c, params_s, oc, os, metrics)."""
    from ..optim.optimizers import apply_updates

    def train_step(params_c, params_s, oc, os_, batch):
        loss, aux, g_c, g_s = step.grads(params_c, params_s, batch)
        with jax.named_scope(CLIENT_SCOPE):
            up_c, oc = opt_c.update(g_c, oc, params_c)
            params_c = apply_updates(params_c, up_c)
        with jax.named_scope(SERVER_SCOPE):
            up_s, os_ = opt_s.update(g_s, os_, params_s)
            params_s = apply_updates(params_s, up_s)
        metrics = {"loss": loss, **aux}
        return params_c, params_s, oc, os_, metrics

    return train_step


# ---------------------------------------------------------------------------
# multi-client engine (faithful Algorithm 3 + the FL baseline), device-resident
# ---------------------------------------------------------------------------
#
# Both round builders below compile one *global* round into a single XLA
# program: per-client params/opt-states/minibatches carry a leading client
# axis, the round is nested ``lax.scan``s over (local steps x clients), and
# FedAvg (Alg. 3 line 19) happens inside the compiled program — no host
# round-trips between steps. Callers jit them with donated state buffers.

def make_multi_client_round(step: SplitStep, opt_c, opt_s, *, local_rounds: int,
                            taps: tuple = ()):
    """One global round of Algorithm 3 over an explicit client axis.

    params_c carries a leading client axis; the single server model is
    shared — the UAV visits clients one at a time, so server updates are
    sequential per client batch (inner scan over clients), matching Alg. 3's
    inner loop; the outer scan runs the ``local_rounds`` visits. After the
    visits, client params are FedAvg'd (leading-axis mean) and re-broadcast,
    all inside the one compiled round.

    ``batches`` is a pytree with leading (clients, local_rounds) axes;
    returned losses have shape (local_rounds, clients).

    ``taps`` enables the metrics bus (``repro.obs.metrics``): the round
    additionally returns a dict of float32 tap stacks, every leaf
    (local_rounds, clients) — the server updates once per client visit
    here, so even the server-tier taps are per-client. Empty taps lowers
    the exact tap-free program (the conditionals below are trace-time).
    """
    from ..obs.metrics import step_taps
    from ..optim.optimizers import apply_updates
    from .fedavg import fedavg_stack

    def one_client_update(carry, client_state):
        params_s, os_ = carry
        params_c, oc, batch = client_state
        loss, aux, g_c, g_s = step.grads(params_c, params_s, batch)
        with jax.named_scope(CLIENT_SCOPE):
            up_c, oc = opt_c.update(g_c, oc, params_c)
            params_c = apply_updates(params_c, up_c)
        with jax.named_scope(SERVER_SCOPE):
            up_s, os_ = opt_s.update(g_s, os_, params_s)
            params_s = apply_updates(params_s, up_s)
        if taps:
            t = step_taps(taps, loss=loss, aux_taps=aux.get("taps"),
                          g_c=g_c, g_s=g_s, up_c=up_c, up_s=up_s)
            return (params_s, os_), (params_c, oc, loss, t)
        return (params_s, os_), (params_c, oc, loss)

    def global_round(params_c_stack, params_s, oc_stack, os_, batches):
        # (clients, local_rounds) -> scan over rounds, inner scan over clients
        batches_rm = jax.tree_util.tree_map(
            lambda x: jnp.swapaxes(x, 0, 1), batches)

        def round_body(carry, batch_r):
            params_c_stack, oc_stack, params_s, os_ = carry
            (params_s, os_), stacked = jax.lax.scan(
                one_client_update, (params_s, os_),
                (params_c_stack, oc_stack, batch_r))
            if taps:
                params_c_stack, oc_stack, loss_c, t = stacked
                out = (loss_c, t)
            else:
                params_c_stack, oc_stack, loss_c = stacked
                out = loss_c
            return (params_c_stack, oc_stack, params_s, os_), out

        carry = (params_c_stack, oc_stack, params_s, os_)
        carry, out = jax.lax.scan(round_body, carry, batches_rm)
        params_c_stack, oc_stack, params_s, os_ = carry
        # FedAvg of client sub-models (Alg. 3 line 19)
        params_c_stack = fedavg_stack(params_c_stack)
        if taps:
            losses, tap_stack = out
            return params_c_stack, params_s, oc_stack, os_, losses, tap_stack
        return params_c_stack, params_s, oc_stack, os_, out

    return global_round


def make_fl_round(grad_fn: Callable, opt, *, client_axis: str = "scan",
                  aggregate: bool = True, taps: tuple = ()):
    """One global round of the FL baseline over an explicit client axis.

    ``grad_fn(params, batch) -> (loss, grads)`` on the full model. Each
    client starts the round from the shared global params with a fresh
    optimizer state (the paper's per-round local training), runs its local
    minibatches via the inner scan, and the round ends with FedAvg of the
    client models — all one compiled program.

    ``client_axis`` picks how the independent clients are laid out:

      "scan" — sequential ``lax.scan`` over clients. Bit-compatible with the
               per-client host loop it replaced (1e-4 equivalence bound).
      "vmap" — clients batched into one SPMD program. Faster (the client
               axis becomes a data-parallel batch dim XLA can fuse and the
               fleet layer can shard over the ``data`` mesh axis), but
               batched convs/reductions reassociate fp32 arithmetic, so
               equivalence to the scan/host reference holds only to the
               loosened ``repro.fleet.engine.FLEET_EQUIV_ATOL`` tolerance.
               The measured steps/s delta is recorded by
               ``benchmarks/bench_engine_perf.py``.

    ``batches`` is a pytree with leading (clients, local_steps) axes;
    returns (new_global_params, losses[clients, local_steps]). With
    ``aggregate=False`` the FedAvg reduction is skipped and the raw
    client-stacked models are returned instead (the fleet layer's dropout
    path aggregates with a per-round client mask).

    The round is STATELESS in the client axis: every client starts from
    ``global_params`` with a fresh optimizer state, so the leading batch
    axis is a *cohort* axis, not a resident-fleet axis — feeding K
    cohort-gathered batch rows sampled from a population of M >> K clients
    (``ClientSpec.population``) runs the identical program with engine
    state O(1) in M (just the global params).

    ``taps`` enables the metrics bus (``repro.obs.metrics``): the round
    additionally returns a dict of float32 tap stacks, every leaf laid out
    (clients, local_steps) like the losses. FL has one tier, so only the
    client-side channels (grad/update norm, nonfinite) apply. Empty taps
    lowers the exact tap-free program (the conditionals are trace-time).
    """
    from ..obs.metrics import step_taps
    from ..optim.optimizers import apply_updates
    from .fedavg import fedavg_mean

    def global_round(global_params, batches):
        opt_state0 = opt.init(global_params)

        def local_step(carry, batch):
            params, opt_state = carry
            with jax.named_scope(FL_SCOPE):
                loss, grads = grad_fn(params, batch)
                updates, opt_state = opt.update(grads, opt_state, params)
                new_carry = (apply_updates(params, updates), opt_state)
            if taps:
                t = step_taps(taps, loss=loss, g_c=grads, up_c=updates)
                return new_carry, (loss, t)
            return new_carry, loss

        def per_client(batch_c):
            (params, _), out = jax.lax.scan(
                local_step, (global_params, opt_state0), batch_c)
            return params, out

        if client_axis == "vmap":
            client_stack, out = jax.vmap(per_client)(batches)
        elif client_axis == "scan":
            _, (client_stack, out) = jax.lax.scan(
                lambda _, b: (None, per_client(b)), None, batches)
        else:
            raise ValueError(f"client_axis must be 'scan' or 'vmap', "
                             f"got {client_axis!r}")
        losses, tap_stack = out if taps else (out, None)
        agg = client_stack if not aggregate else fedavg_mean(client_stack)
        if taps:
            return agg, losses, tap_stack
        return agg, losses

    return global_round
