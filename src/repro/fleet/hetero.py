"""Per-client cut personalization with bucketed dispatch.

Heterogeneous edge fleets (P3SL, arXiv:2507.17228) don't share one best cut:
a Jetson-class client wants a deeper prefix than a microcontroller-class
one, and a starved link moves the optimum toward smaller smashed tensors.
Here every client gets its own cut from ``core.adaptive_cut.select_cut`` on
its own (hardware, link) profile, clients are grouped into *cut buckets*,
and each bucket runs its own compiled fleet round (``engine``): XLA programs
are shape-specialized per cut, so the bucket — not the client — is the
compilation unit. Every client belongs to exactly one bucket.

Both model families split the same way through ``SplitProgram``:

  * CNN ``Stage`` lists — slice the stage/param lists at k
    (``cnn_split_program``).
  * transformer ``split_stack`` models — slice the stacked layer axis at k
    and scan blocks on each side (``stack_split_program``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.adaptive_cut import (profile_cuts_cnn, profile_cuts_transformer,
                                 select_cut)
from ..core.energy import HardwareProfile
from ..core.link import LinkConfig
from ..core.split import SplitStep, Stage, apply_stages, split_stack
from ..optim.optimizers import init_stacked
from .engine import jit_round, make_fleet_sl_round, validate_fleet_mesh


# ---------------------------------------------------------------------------
# cut assignment + bucketing
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CutBucket:
    cut_index: int
    client_ids: tuple[int, ...]   # global client indices, ascending


def bucket_by_cut(cut_indices: Sequence[int]) -> list[CutBucket]:
    """Group clients by cut index. Deterministic (ascending cut, ascending
    client id); the buckets partition the fleet — every client exactly once."""
    by_cut: dict[int, list[int]] = {}
    for cid, k in enumerate(cut_indices):
        by_cut.setdefault(int(k), []).append(cid)
    return [CutBucket(k, tuple(ids)) for k, ids in sorted(by_cut.items())]


def _assign_cuts(profile_fn: Callable, edges: Sequence[HardwareProfile],
                 links: Optional[Sequence[LinkConfig]],
                 max_link_s: Optional[float]) -> list[int]:
    """Shared per-client selection loop: identical (hardware, link) profiles
    share one cut-curve evaluation. ``profile_fn(edge, link)`` returns the
    cut choices for one profile."""
    links = list(links) if links is not None else [LinkConfig()] * len(edges)
    if len(links) != len(edges):
        raise ValueError("edges and links must be per-client (same length)")
    cache: dict[tuple, int] = {}
    cuts = []
    for edge, link in zip(edges, links):
        key = (edge, link)
        if key not in cache:
            cache[key] = select_cut(profile_fn(edge, link),
                                    max_link_s=max_link_s).cut_index
        cuts.append(cache[key])
    return cuts


def assign_cuts_cnn(stages: Sequence[Stage], params, sample_x, *,
                    edges: Sequence[HardwareProfile],
                    links: Optional[Sequence[LinkConfig]] = None,
                    min_client_layers: int = 1,
                    max_link_s: Optional[float] = None) -> list[int]:
    """Per-client minimum-energy cut for a CNN stage list. ``edges`` (and
    optionally ``links``) give each client its own profile."""
    return _assign_cuts(
        lambda edge, link: profile_cuts_cnn(
            stages, params, sample_x, edge=edge, link=link,
            min_client_layers=min_client_layers),
        edges, links, max_link_s)


def assign_cuts_transformer(cfg, *, batch: int, seq: int,
                            edges: Sequence[HardwareProfile],
                            links: Optional[Sequence[LinkConfig]] = None,
                            max_link_s: Optional[float] = None) -> list[int]:
    """Per-client minimum-energy cut for a transformer ArchConfig stack."""
    return _assign_cuts(
        lambda edge, link: profile_cuts_transformer(
            cfg, batch=batch, seq=seq, edge=edge, link=link),
        edges, links, max_link_s)


# ---------------------------------------------------------------------------
# split programs: one cut of one model family, as a SplitStep + params
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SplitProgram:
    """A model split at one cut: the differentiable step + per-tier inits
    (every client in a bucket starts from the same prefix init)."""
    step: SplitStep
    params_c0: object
    params_s0: object
    cut_index: int


def cnn_split_program(stages: Sequence[Stage], params, k: int, *,
                      loss_fn: Callable,
                      link_boundary: Optional[Callable] = None,
                      taps: tuple = ()) -> SplitProgram:
    """Split a CNN stage list at stage index ``k``. ``loss_fn(logits,
    targets) -> scalar`` closes the server side. ``taps`` are the
    step-level metrics-bus channels (``SplitStep.taps``)."""
    if not 1 <= k <= len(stages) - 1:
        raise ValueError(f"cut {k} outside (0, {len(stages)})")
    cs, cp = list(stages[:k]), list(params[:k])
    ss, sp = list(stages[k:]), list(params[k:])
    step = SplitStep(
        client_fwd=lambda pc, xx: apply_stages(cs, pc, xx),
        server_loss=lambda ps, sm, yy: (loss_fn(apply_stages(ss, ps, sm), yy),
                                        {}),
        link_constraint=link_boundary,
        taps=taps,
    )
    return SplitProgram(step=step, params_c0=cp, params_s0=sp, cut_index=k)


def transformer_block_apply(cfg, *, window="cfg",
                            attn_impl: str = "xla") -> Callable:
    """``block_apply`` for ``stack_split_program`` backed by the *real*
    transformer forward (``models.transformer.group_apply``).

    Applies ONE attention layer of an ``ArchConfig`` stack: the un-stacked
    layer params are re-lifted to a one-layer stack and run through the
    same ``group_apply`` scan the production launcher uses, so the split
    model is bit-identical to slicing the full model's layer axis. Dense
    attention groups only (MoE groups carry a router-aux scalar that the
    stacked-block interface has no channel for).
    """
    from ..models.transformer import GroupSpec, group_apply

    if cfg.n_experts:
        raise ValueError("transformer_block_apply serves dense attention "
                         "stacks; MoE groups need the aux-carrying "
                         "launch-layer forward")
    g = GroupSpec("attn", 1, 0)
    win = cfg.swa_window if window == "cfg" else window

    def block_apply(blk, h):
        stacked = jax.tree_util.tree_map(lambda v: v[None], blk)
        b, s = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        h, _aux = group_apply(cfg, g, stacked, h,
                              jnp.zeros((), jnp.float32),
                              positions=positions, window=win,
                              attn_impl=attn_impl)
        return h

    return block_apply


def arch_split_program(cfg, key, k: int, *, loss_fn: Callable,
                       link_boundary: Optional[Callable] = None,
                       window="cfg", attn_impl: str = "xla") -> SplitProgram:
    """Split a real transformer ``ArchConfig`` at layer ``k`` through the
    stacked-block interface: init one homogeneous attention stack
    (``models.transformer.group_init``) and cut its layer axis. The smashed
    tensor is the (batch, seq, d_model) residual stream at the cut — the
    paper's transformer SL boundary."""
    from ..models.transformer import GroupSpec, group_init

    if not 1 <= k <= cfg.n_layers - 1:
        raise ValueError(f"cut {k} outside (0, {cfg.n_layers})")
    stacked = group_init(key, cfg, GroupSpec("attn", cfg.n_layers, 0))
    return stack_split_program(stacked, k,
                               block_apply=transformer_block_apply(
                                   cfg, window=window, attn_impl=attn_impl),
                               loss_fn=loss_fn, link_boundary=link_boundary)


@dataclasses.dataclass(frozen=True)
class LMSplitProgram:
    """A trainable split *language model*: embed + block stack + LM head.

    Extends ``SplitProgram``'s contract with the pieces a real token
    pipeline needs — the client tier owns the embedding (raw tokens never
    cross the link, the split-learning privacy floor), the server tier owns
    its block slice plus the output head, and ``server_logits`` exposes the
    full forward for held-out evaluation.

    ``step`` recomputes each block in its backward pass rather than saving
    its activations (``jax.checkpoint``): at SmolLM-135M widths and seq
    2048 the activations the 30 blocks would save do not fit one 16 GB
    v5e. ``cost_step`` is the same program without that recompute and
    without taps: the work the energy bill prices, since the recompute is
    a device-memory choice, not work of the paper's client hardware. Its
    attention is the jnp path where the step runs the Pallas kernel: XLA's
    cost analysis prices a kernel's interpret-mode grid loop as one step.
    """
    step: SplitStep
    cost_step: SplitStep
    params_c0: object             # {"embed": (V, d), "blocks": client stack}
    params_s0: object             # {"blocks": server stack, "head": (d, V)}
    cut_index: int
    server_logits: Callable       # (params_s, smashed) -> (B, S, V)


def lm_split_program(cfg, key, k: int, *,
                     link_boundary: Optional[Callable] = None,
                     window="cfg", attn_impl: str = "xla",
                     taps: tuple = ()) -> LMSplitProgram:
    """Split a next-token LM built on a real transformer ``ArchConfig``
    stack (``models.transformer.group_apply`` blocks) at layer ``k``.

    The differentiable program is: client = embed + first ``k`` blocks
    (smashed tensor: the (B, S, d_model) residual stream at the cut);
    server = remaining blocks + output head + next-token cross entropy.
    Batches are ``{"inputs": tokens (B, S), "targets": next tokens (B, S)}``
    — what ``repro.api``'s token data pipeline feeds (``ModelSpec(family=
    "transformer")``).
    """
    from ..models.transformer import GroupSpec, group_init

    if not 1 <= k <= cfg.n_layers - 1:
        raise ValueError(f"cut {k} outside (0, {cfg.n_layers})")
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    stacked = group_init(k_blocks, cfg, GroupSpec("attn", cfg.n_layers, 0))
    blocks_c, blocks_s = split_stack(stacked, k)
    scale = 0.02
    embed = scale * jax.random.normal(k_embed, (cfg.vocab, cfg.d_model),
                                      jnp.float32)
    head = scale * jax.random.normal(k_head, (cfg.d_model, cfg.vocab),
                                     jnp.float32)
    block_apply = transformer_block_apply(cfg, window=window,
                                          attn_impl=attn_impl)
    cost_block_apply = transformer_block_apply(
        cfg, window=window, attn_impl="xla" if attn_impl == "pallas"
        else attn_impl)

    def run_blocks(stack, h, remat=False, apply=block_apply):
        def body(h, blk):
            return apply(blk, h), None
        h, _ = jax.lax.scan(jax.checkpoint(body) if remat else body, h,
                            stack)
        return h

    def server_logits(ps, smashed):
        return run_blocks(ps["blocks"], smashed) @ ps["head"]

    def make_step(remat, apply=block_apply, **kw):
        def client_fwd(pc, tokens):
            return run_blocks(pc["blocks"], pc["embed"][tokens], remat,
                              apply)

        def server_loss(ps, smashed, targets):
            logits = run_blocks(ps["blocks"], smashed, remat,
                                apply) @ ps["head"]
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return jnp.mean(nll), {}

        return SplitStep(client_fwd=client_fwd, server_loss=server_loss,
                         **kw)

    return LMSplitProgram(step=make_step(True, link_constraint=link_boundary,
                                         taps=taps),
                          cost_step=make_step(False, cost_block_apply),
                          params_c0={"embed": embed, "blocks": blocks_c},
                          params_s0={"blocks": blocks_s, "head": head},
                          cut_index=k, server_logits=server_logits)


def stack_split_program(stacked_params, k: int, *, block_apply: Callable,
                        loss_fn: Callable,
                        link_boundary: Optional[Callable] = None,
                        taps: tuple = ()) -> SplitProgram:
    """Split a stacked-block (scan-over-layers) model at layer ``k``.

    ``block_apply(block_params, h) -> h`` applies ONE block (params without
    the stacked layer axis); ``loss_fn(h, targets) -> scalar`` closes the
    server side on the last hidden state. Each tier scans its slice of the
    stack, so the same program serves any transformer ``split_stack`` model
    (``arch_split_program`` builds one straight from an ``ArchConfig``).
    """
    params_c, params_s = split_stack(stacked_params, k)

    def run_blocks(stack, h):
        def body(h, blk):
            return block_apply(blk, h), None
        h, _ = jax.lax.scan(body, h, stack)
        return h

    step = SplitStep(
        client_fwd=run_blocks,
        server_loss=lambda ps, sm, yy: (loss_fn(run_blocks(ps, sm), yy), {}),
        link_constraint=link_boundary,
        taps=taps,
    )
    return SplitProgram(step=step, params_c0=params_c, params_s0=params_s,
                        cut_index=k)


# ---------------------------------------------------------------------------
# bucketed dispatch
# ---------------------------------------------------------------------------

def _stack_replicas(tree, n: int):
    return jax.tree_util.tree_map(
        lambda v: jnp.broadcast_to(v[None], (n,) + v.shape), tree)


def _server_only_mesh(mesh):
    """The fleet mesh with its ``data`` axis collapsed to 1: same
    ``fsdp``/``tp`` server sub-mesh, no client-axis sharding. Used by
    buckets whose size does not divide ``data``."""
    if mesh is None or "data" not in mesh.axis_names:
        return None
    i = mesh.axis_names.index("data")
    if mesh.devices.shape[i] == 1:
        return mesh
    sl = [slice(None)] * mesh.devices.ndim
    sl[i] = slice(0, 1)
    return jax.sharding.Mesh(mesh.devices[tuple(sl)], mesh.axis_names)


class HeteroFleet:
    """Per-cut-bucket fleet engines over one shared client population.

    ``build_program(k) -> SplitProgram`` specializes the model to a cut;
    each bucket owns a compiled ``make_fleet_sl_round`` (its own server
    suffix — a cut-group is also a server-model group) and the stacked state
    of its clients. ``run_round(batches)`` slices the global
    (clients, local_steps, ...) batch stack per bucket, runs every bucket's
    compiled round, and reassembles losses into (local_steps, clients).
    """

    def __init__(self, build_program: Callable[[int], SplitProgram],
                 cut_indices: Sequence[int], opt_c, opt_s, *,
                 local_rounds: int, mesh=None, client_dropout: bool = False,
                 server_reduce: str = "mean", client_axis: str = "vmap",
                 server_pspecs_fn: Optional[Callable] = None,
                 taps: tuple = ()):
        """``client_axis`` ('vmap' | 'shard_map') and ``server_pspecs_fn``
        (``lambda params_s, mesh: pspecs`` — e.g. wrapping
        ``launch.steps.fleet_server_pspecs``) pass through to each bucket's
        ``make_fleet_sl_round``; a bucket whose size does not divide the
        mesh's data axis falls back to its unsharded (single-device for
        shard_map) engine rather than padding. ``taps`` (engine-level
        metrics-bus channels) also pass through: ``run_round_on`` then
        reassembles each bucket's tap stacks into global
        (local_rounds, num_clients) arrays — a bucket's one-update-per-step
        server channel is broadcast to its client columns, since each cut
        bucket owns its own server suffix."""
        self.buckets = bucket_by_cut(cut_indices)
        self.local_rounds = local_rounds
        self.num_clients = len(cut_indices)
        self.client_dropout = client_dropout
        self.client_axis = client_axis
        self.taps = tuple(taps)
        self._ids: list[np.ndarray] = []
        self._engines = []
        self._init_states = []
        self.programs: dict[int, SplitProgram] = {}
        for bucket in self.buckets:
            prog = build_program(bucket.cut_index)
            if prog.cut_index != bucket.cut_index:
                raise ValueError("build_program returned a different cut")
            n = len(bucket.client_ids)
            # shard a bucket's CLIENT axis only when its size divides the
            # data axis; a non-dividing bucket keeps the server fsdp x tp
            # sub-mesh (data collapsed to 1) rather than silently dropping
            # the requested server sharding
            b_mesh = mesh
            try:
                validate_fleet_mesh(b_mesh, n)
            except ValueError:
                b_mesh = _server_only_mesh(mesh)
            pspecs = (server_pspecs_fn(prog.params_s0, b_mesh)
                      if server_pspecs_fn is not None and b_mesh is not None
                      else None)
            # donate the bucket's stacked state round-over-round (batches
            # and the dropout mask are fresh each round and not donated)
            engine = jit_round(make_fleet_sl_round(
                prog.step, opt_c, opt_s, local_rounds=local_rounds,
                mesh=b_mesh, client_dropout=client_dropout,
                server_reduce=server_reduce, client_axis=client_axis,
                server_pspecs=pspecs, taps=self.taps), "sl_round",
                donate_argnums=(0, 1, 2, 3))
            state = (_stack_replicas(prog.params_c0, n), prog.params_s0,
                     init_stacked(opt_c, prog.params_c0, n),
                     opt_s.init(prog.params_s0))
            self.programs[bucket.cut_index] = prog
            self._ids.append(np.asarray(bucket.client_ids))
            self._engines.append(engine)
            # the engine donates its state buffers; the initial tiers alias
            # the caller's (shared) model params, so fresh copies are made
            # whenever live/external state is materialized
            self._init_states.append(state)
        # the fleet's OWN live state (run_round/bucket_state surface) is
        # materialized lazily: callers threading state externally through
        # init_states()/run_round_on never pay for the internal copy
        self._states = None

    def reset(self) -> None:
        """Re-initialize every bucket's live state (compiled engines are
        kept), so one fleet can run several independent experiments."""
        self._states = self.init_states()

    def _live_states(self) -> list[tuple]:
        if self._states is None:
            self._states = self.init_states()
        return self._states

    def init_states(self) -> list[tuple]:
        """Fresh per-bucket state tuples, independent of the fleet's own
        live state — for callers that thread state externally through
        ``run_round_on`` (each copy may be donated exactly once)."""
        return [jax.tree_util.tree_map(jnp.copy, s)
                for s in self._init_states]

    @property
    def cut_of_client(self) -> list[int]:
        cuts = [0] * self.num_clients
        for bucket in self.buckets:
            for cid in bucket.client_ids:
                cuts[cid] = bucket.cut_index
        return cuts

    def bucket_state(self, i: int):
        """(params_c_stack, params_s, oc_stack, os) of bucket ``i``."""
        return self._live_states()[i]

    def run_round(self, batches, client_mask=None):
        """One global round. ``batches`` is a pytree with leading
        (num_clients, local_rounds) axes; returns losses
        (local_rounds, num_clients) with every client filled exactly once —
        plus the reassembled tap dict when the fleet was built with
        metrics ``taps``.

        ``client_mask`` (global (num_clients,) 0/1 vector) drops stragglers
        for the round; requires the fleet to be built with
        ``client_dropout=True`` (the mask is sliced per bucket and fed to
        each bucket's compiled round).
        """
        out = self.run_round_on(self._live_states(), batches, client_mask)
        self._states = out[0]
        return out[1] if not self.taps else out[1:]

    def run_round_on(self, states: list[tuple], batches, client_mask=None):
        """``run_round`` over caller-owned per-bucket states (as produced
        by ``init_states``): returns ``(new_states, losses)`` —
        ``(new_states, losses, taps)`` when built with metrics ``taps``,
        every tap a (local_rounds, num_clients) float32 array. The input
        state buffers are donated to the compiled rounds — reuse the
        returned list, never the argument."""
        if client_mask is not None and not self.client_dropout:
            raise ValueError("client_mask needs HeteroFleet("
                             "client_dropout=True)")
        losses = np.zeros((self.local_rounds, self.num_clients), np.float32)
        tap_out = {name: np.zeros((self.local_rounds, self.num_clients),
                                  np.float32) for name in self.taps}
        new_states = list(states)
        for i, ids in enumerate(self._ids):
            sub = jax.tree_util.tree_map(
                lambda x: jnp.take(x, jnp.asarray(ids), axis=0), batches)
            if self.client_dropout:
                mask = (np.ones(len(ids), np.float32) if client_mask is None
                        else np.asarray(client_mask, np.float32)[ids])
                out = self._engines[i](*states[i], sub, jnp.asarray(mask))
            else:
                out = self._engines[i](*states[i], sub)
            if self.taps:
                *state, bucket_losses, bucket_taps = out
                for name, v in bucket_taps.items():
                    v = np.asarray(v, np.float32)
                    # (local_rounds,) channels = this bucket's one server
                    # update per step, broadcast to its client columns
                    tap_out[name][:, ids] = v if v.ndim == 2 else v[:, None]
            else:
                *state, bucket_losses = out
            new_states[i] = tuple(state)
            losses[:, ids] = np.asarray(bucket_losses)
        if self.taps:
            return new_states, losses, tap_out
        return new_states, losses
