"""``compile_experiment``: lower one declarative spec to one compiled plan.

A ``Plan`` is the uniform run surface every entry point now shares:

    plan = compile_experiment(spec, mesh=..., data=...)
    state = plan.init()
    state, rec = plan.run_round(state)          # one RoundRecord per round
    metrics = plan.evaluate(state)

Internally the plan dispatches on ``spec.engine`` to the existing compiled
engines (see ``api.spec`` for the lowering table), wires the policies —
FedAvg, adaptive cuts, the int8 link boundary, client dropout, UAV mission
budgeting — into that engine, and hoists every energy/FLOP/link constant
out of the hot loop at compile time (the paper's analytic Eq. 8/9
accounting). Nothing is metered per step; ``run_round`` multiplies
pre-computed per-client constants by the step counts of the round that
actually ran (dropout masks excluded clients from both training and
billing).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import keys
from ..core.energy import RTX_A5000
from ..core.link import LinkConfig
from ..core.split import (SplitStep, apply_stages, cut_index_for_fraction,
                          init_stages, make_fl_round,
                          make_multi_client_round, stack_cut_index)
from ..core.trajectory import TourPlan, plan_tour
from ..data.partition import (partition_dirichlet, partition_iid,
                              partition_non_iid, population_partition_count)
from ..data.synthetic import SyntheticPestImages, synthetic_tokens
from ..fleet.engine import (fleet_sl_state_shardings, jit_round,
                            make_fleet_fl_round, make_fleet_sl_round,
                            server_mesh_sizes, validate_fleet_mesh)
from ..launch.mesh import make_fleet_mesh, single_device_fleet_mesh
from ..fleet.hetero import (HeteroFleet, assign_cuts_cnn, cnn_split_program,
                            lm_split_program)
from ..fleet.link import FleetLink
from ..kernels.dispatch import (ATTN_IMPLS, LINK_KERNELS, resolve_attn_impl,
                                resolve_link_kernel)
from ..models.cnn import CNN_BUILDERS, cross_entropy_loss
from ..obs import NULL_OBS, Obs
from ..obs.metrics import (NonfiniteError, engine_tap_names,
                           split_step_tap_names, summarize_round_metrics)
from ..optim import adamw, init_stacked
from ..sim.channel import deterministic_rate_bps, sample_rates_bps
from ..sim.mission import MissionTimeline, rollout_mission
from ..sim.scenario import (COHORT_DOWN_WEIGHT, availability_init,
                            availability_step, sample_cohort)
from .records import RoundRecord
from .runtime import (accuracy_from_logits, classification_metrics,
                      client_coords, client_step_time_s, count_fl_step_flops,
                      count_sl_step_flops, count_split_step_flops,
                      mission_max_link_s, roofline_s, round_batches,
                      stack_replicas)
from .spec import ExperimentSpec

# time billed to the FL server per round: aggregation only (negligible
# FLOPs; the historical constant from the faithful reproduction trainer)
FL_SERVER_AGG_S = 1e-3


@dataclasses.dataclass
class PlanState:
    """Mutable run state threaded through ``run_round``."""
    round: int
    engine_state: Any               # pytree tuple, or the HeteroFleet
    rng: np.random.RandomState      # minibatch sampling stream
    dropout_rng: np.random.RandomState
    last_metrics: Optional[dict] = None   # full metric dict of the last eval
    avail_up: Optional[np.ndarray] = None  # scenario availability (clients,)
    #                                        up/down state carried per round


class Plan:
    """A compiled experiment. Built by ``compile_experiment`` — the
    attributes below are its public read surface; the engine closures are
    private."""

    def __init__(self, spec: ExperimentSpec, *, mesh, arrays, parts, stages,
                 params0, tour: Optional[TourPlan], cut_of_client,
                 flops: dict, edges, consts, engine_fns,
                 timeline: Optional[MissionTimeline] = None,
                 serve_dist_m=None, rate_nominal=None, prof_consts=None,
                 obs: Optional[Obs] = None, metrics=None,
                 graph_taps: tuple = ()):
        self.spec = spec
        self.mesh = mesh
        # metrics bus (repro.obs.metrics): the MetricsConfig the plan was
        # compiled with (None = off) and the in-graph tap channels its
        # engine rounds emit — with any graph taps the round closures
        # return (state, losses, taps) instead of (state, losses)
        self.metrics_config = metrics
        self.graph_taps = tuple(graph_taps)
        # telemetry facade (repro.obs): the shared disabled instance unless
        # compile_experiment was handed an ObsConfig — disabled, every
        # hot-path touch is a branch + no-op call
        self.obs = obs if obs is not None else NULL_OBS
        self.engine_label = f"{spec.engine.kind}/{spec.engine.client_axis}"
        self.x_train, self.y_train, self.x_test, self.y_test = arrays
        self.parts = parts
        self.stages = stages
        self.params0 = params0
        self.tour = tour
        self.timeline = timeline      # scenario missions (sim.rollout_mission)
        budget = (timeline.rounds if timeline is not None
                  else tour.rounds if tour is not None else None)
        self.rounds_budget = budget
        self.num_rounds = (min(spec.global_rounds, budget)
                           if budget is not None else spec.global_rounds)
        self.cut_of_client = list(cut_of_client)
        self.flops = flops            # {"full": f} | {cut: (client, server, sd)}
        self.edges = edges
        n = spec.clients.num_clients
        # scenario runtime: serving distances + the nominal (deterministic)
        # per-client rates the link constants were hoisted at
        self.serve_dist_m = (np.zeros(n) if serve_dist_m is None
                             else np.asarray(serve_dist_m))
        self.rate_nominal = (np.full(n, spec.link_policy.rate_bps)
                             if rate_nominal is None
                             else np.asarray(rate_nominal))
        scn = spec.scenario
        self._channel = scn.channel if scn is not None else None
        self._scn_key = (jax.random.PRNGKey(scn.seed)
                         if scn is not None else None)
        self._mask_in_engine = _needs_mask(spec)
        # cohort sampling (ClientSpec.population): the environment key the
        # per-round cohort draw folds from — the scenario's stream when one
        # is attached (so Monte-Carlo sweep seed i replays realization
        # scn.seed + i, cohorts included), the seed-0 environment otherwise
        # (matching run_monte_carlo's default ScenarioSpec())
        self._population = spec.clients.population
        self._env_key = (self._scn_key if self._scn_key is not None
                         else jax.random.PRNGKey(0))
        # per-PROFILE per-step constants for cohort billing (edge_profiles
        # cycle over population ids, gathered to the sampled cohort); None
        # when the fleet is fully materialized (per-slot consts suffice)
        self._t_client_prof, self._p_edge_prof = (
            prof_consts if prof_consts is not None else (None, None))
        # hoisted per-client constants (np arrays over the client axis)
        (self._t_client, self._t_server, self._link_bytes, self._link_time,
         self._link_energy, self._server_base_s) = consts
        # engine closures: (init_state, run, eval, raw unjitted run, raw
        # jittable held-out accuracy — the raw pair is None for hetero
        # plans, which have no single jittable round)
        (self._init_state, self._run, self._eval, self._run_raw,
         self._eval_acc_raw) = engine_fns
        self._round_ops_written = False

    # ---- lifecycle --------------------------------------------------------

    def init(self) -> PlanState:
        """Fresh run state (per-client model/optimizer stacks, RNG streams).
        The batch stream matches the legacy trainers' (one RandomState
        seeded with ``spec.seed``, one ``choice`` per client per round)."""
        scn = self.spec.scenario
        # availability runs over the POPULATION when one is declared (the
        # trace both masks the sampled cohort and weights the next draw);
        # O(population) scalars, never O(population) model state
        n_avail = (self._population if self._population is not None
                   else self.spec.clients.num_clients)
        avail_up = (np.asarray(availability_init(n_avail))
                    if scn is not None and scn.needs_mask else None)
        return PlanState(
            round=0, engine_state=self._init_state(),
            rng=np.random.RandomState(self.spec.seed),
            dropout_rng=np.random.RandomState(self.spec.seed + 1),
            avail_up=avail_up)

    def round_batches(self, state: PlanState, cohort=None):
        """Pre-gathered (clients, local_steps, ...) stacks for one round, in
        the engine's batch format (FL: ``(bx, by)``; SL: dict).

        Population plans draw the FULL partition pool (one leading row per
        distinct partition, the same RNG call sequence as a materialized
        fleet) and gather rows by ``cohort`` population ids; with
        ``cohort=None`` the raw pool is returned — the Monte-Carlo sweeps
        stack pools per round and gather inside the jitted rollout, where
        the cohort is a traced value."""
        bx, by = round_batches(self.x_train, self.y_train, self.parts,
                               self.spec.batch_size, self.spec.local_steps,
                               state.rng, shrink=self.spec.data.shrink_batches)
        if cohort is not None:
            sel = np.asarray(cohort) % len(self.parts)
            bx, by = bx[sel], by[sel]
        if self.spec.engine.kind == "fl":
            return bx, by
        return {"inputs": bx, "targets": by}

    def _round_cohort(self, state: PlanState) -> Optional[np.ndarray]:
        """The round's sorted cohort population ids (None when the fleet is
        fully materialized). Key-folded from the environment key
        (``keys.ENV_COHORT`` — mask is ``ENV_MASK``, rates ``ENV_RATES``)
        so Monte-Carlo sweeps replay the identical cohort stream; weighted
        by the availability state ENTERING the round when a scenario trace
        runs (down clients draw at ``COHORT_DOWN_WEIGHT``), uniform
        otherwise."""
        if self._population is None:
            return None
        key = keys.fold(keys.round_env_key(self._env_key, state.round),
                        keys.ENV_COHORT)
        weights = None
        scn = self.spec.scenario
        if scn is not None and scn.needs_mask:
            up = jnp.asarray(state.avail_up)
            weights = up + (1.0 - up) * COHORT_DOWN_WEIGHT
        return np.asarray(sample_cohort(key, self._population,
                                        self.spec.clients.num_clients,
                                        weights=weights))

    def _round_mask(self, state: PlanState,
                    cohort=None) -> Optional[np.ndarray]:
        scn = self.spec.scenario
        if scn is not None and scn.needs_mask:
            # scenario availability trace: jax-native + key-folded per round,
            # bit-identical to the Monte-Carlo rollout's mask stream
            key = keys.fold(keys.round_env_key(self._scn_key, state.round),
                            keys.ENV_MASK)
            mask, up = availability_step(key, jnp.asarray(state.avail_up),
                                         scn.availability)
            state.avail_up = np.asarray(up)
            mask = np.asarray(mask, np.float32)
            if cohort is not None:
                # population trace -> cohort slots. availability_step's
                # >=1-active guard holds for the population, not the slice:
                # an all-down cohort keeps slot 0 (same rule as the MC
                # rollout's jnp.where guard)
                mask = mask[cohort]
                if mask.sum() == 0:
                    mask[0] = 1.0
            return mask
        rate = self.spec.clients.dropout_rate
        if rate <= 0.0:
            return None
        n = self.spec.clients.num_clients
        mask = (state.dropout_rng.uniform(size=n) >= rate).astype(np.float32)
        if mask.sum() == 0:          # never drop the whole fleet
            mask[state.dropout_rng.randint(n)] = 1.0
        return mask

    def _round_rate_ratio(self, round_index: int) -> Optional[np.ndarray]:
        """nominal/sampled channel rate per client for one round (None when
        no channel is attached — keep the hoisted constants verbatim)."""
        if self._channel is None:
            return None
        key = keys.fold(keys.round_env_key(self._scn_key, round_index),
                        keys.ENV_RATES)
        rates = sample_rates_bps(key, self._channel,
                                 jnp.asarray(self.serve_dist_m),
                                 self.spec.link_policy.rate_bps)
        return np.asarray(self.rate_nominal / np.asarray(rates))

    def run_round(self, state: PlanState, batches=None, *,
                  with_eval: bool = True) -> tuple[PlanState, RoundRecord]:
        """Execute one global round; returns (state, RoundRecord). Batches
        default to the plan's own stream; pass them explicitly to drive the
        engine with external data (the perf benches do).

        With telemetry enabled (``compile_experiment(..., obs=)``) the
        round decomposes into spans — ``round/sample`` (cohort/mask draw +
        host batch gather), ``round/execute`` (engine dispatch, fenced so
        device wait lands in ``sync_s``), ``round/eval``, ``round/account``
        (record assembly) — plus one gauge stamp (engine-state bytes, host
        RSS, recompiles since the last stamp) and the record itself."""
        obs = self.obs
        r = state.round
        obs.round_started(r)
        with obs.span("round", round=r):
            with obs.span("round/sample", round=r):
                cohort = self._round_cohort(state)
                if batches is None:
                    batches = self.round_batches(state, cohort=cohort)
                mask = self._round_mask(state, cohort=cohort)
            if obs and not self._round_ops_written:
                self._write_round_ops(state.engine_state, batches, mask)
            with obs.span("round/execute", round=r) as sp:
                out = self._run(state.engine_state, batches, mask)
                if self.graph_taps:
                    # taps ride the SAME device->host pull as the losses:
                    # one fence for the whole round output
                    state.engine_state, losses, taps = out
                    losses, taps = sp.fence((losses, taps))
                else:
                    state.engine_state, losses = out
                    losses = sp.fence(losses)
                    taps = None
            rec = self._assemble_record(state, losses, mask, cohort,
                                        taps=taps, with_eval=with_eval)
            if obs:
                n = self.spec.clients.num_clients
                obs.gauge(r, engine_state=state.engine_state,
                          active_clients=rec.active_clients,
                          dropped=n - rec.active_clients,
                          cohort=len(rec.cohort_pids),
                          link_bytes=rec.link_bytes)
                obs.record(rec)
                if rec.metrics:
                    obs.event("metrics", round=r, engine=self.engine_label,
                              **rec.metrics)
        obs.round_finished(r)
        state.round += 1
        return state, rec

    def _write_round_ops(self, engine_state, batches, mask) -> None:
        """Hand the compiled round's HLO to ``Obs.op_scopes`` (each op's
        scope, for a profile of the round), lowered from the arguments the
        first round runs with: its call then reuses this compile."""
        self._round_ops_written = True
        audit = getattr(self._run, "_audit", None)
        if audit is None:       # hetero buckets: no single round program
            return
        args = round_args(audit, engine_state, batches, mask,
                          self.spec.clients.num_clients)
        with self.obs.span("round/ops"):
            self.obs.op_scopes(
                audit["jit_fn"].lower(*args).compile().as_text())

    def _assemble_record(self, state: PlanState, losses, mask, cohort, *,
                         with_eval: bool, taps=None) -> RoundRecord:
        """Host-side accounting of one executed round: loss extraction,
        optional held-out eval, the analytic energy/link bill, and — when
        the plan carries a MetricsConfig — the metrics-bus summary (with
        the ``on_nonfinite='raise'`` health policy applied)."""
        obs = self.obs
        n = self.spec.clients.num_clients
        steps = self.spec.local_steps
        with obs.span("round/account", round=state.round):
            active = (np.arange(n) if mask is None
                      else np.flatnonzero(mask > 0))
            # losses: FL (clients, steps); SL (steps, clients)
            loss_c = np.asarray(losses)
            loss = float((loss_c[active, :] if self.spec.engine.kind == "fl"
                          else loss_c[:, active]).mean())
            uav = 0.0
            if self.timeline is not None:
                uav = self.timeline.uav_energy_j(state.round)
            elif self.tour is not None:
                uav = float(self.tour.e_first if state.round == 0
                            else self.tour.e_per_round)
            # compute time/energy price the SAMPLED clients' hardware: under
            # a population, per-profile constants are gathered to the
            # cohort's pids (profiles cycle over pids); materialized fleets
            # keep the per-slot arrays (identical values when cohort ==
            # identity)
            if cohort is not None and self._t_client_prof is not None:
                prof = cohort % len(self._t_client_prof)
                t_client, p_edge = (self._t_client_prof[prof],
                                    self._p_edge_prof[prof])
            else:
                t_client = self._t_client
                p_edge = np.asarray([e.power_w for e in self.edges])
            t_cli = float(t_client[active].sum() * steps)
            e_cli = float(sum(t_client[c] * steps * p_edge[c]
                              for c in active))
            t_srv = float(self._t_server[active].sum() * steps
                          + self._server_base_s)
            # channel-attached scenarios re-bill link time/energy per round
            # at the sampled rates (constants x nominal/sampled ratio);
            # otherwise the hoisted constants stand verbatim
            ratio = self._round_rate_ratio(state.round)
            l_time, l_energy = self._link_time, self._link_energy
            if ratio is not None:
                l_time, l_energy = l_time * ratio, l_energy * ratio
            metrics = {}
            if self.metrics_config is not None:
                tm = ({} if taps is None
                      else {k: np.asarray(v) for k, v in taps.items()})
                metrics = summarize_round_metrics(
                    self.metrics_config, tm, losses=loss_c,
                    kind=self.spec.engine.kind, n=n, active=len(active))
                if (self.metrics_config.on_nonfinite == "raise"
                        and metrics.get("health/nonfinite", 0)):
                    raise NonfiniteError(
                        round_index=state.round,
                        step=metrics["health/first_step"],
                        client=metrics["health/first_client"],
                        count=metrics["health/nonfinite"])
        if with_eval:
            with obs.span("round/eval", round=state.round):
                state.last_metrics = self.evaluate(state)
            accuracy = state.last_metrics["accuracy"]
        else:
            accuracy = float("nan")
        return RoundRecord(
            round=state.round, loss=loss, accuracy=accuracy,
            link_bytes=float(self._link_bytes[active].sum() * steps),
            link_time_s=float(l_time[active].sum() * steps),
            link_energy_j=float(l_energy[active].sum() * steps),
            client_time_s=t_cli, client_energy_j=e_cli,
            server_time_s=t_srv,
            server_energy_j=t_srv * RTX_A5000.power_w,
            uav_energy_j=uav, active_clients=len(active),
            engine=self.engine_label,
            cohort_pids=(() if cohort is None
                         else tuple(int(p) for p in cohort)),
            metrics=metrics)

    def raw_round(self, engine_state, batches, mask=None):
        """One engine round with NO record assembly or host synchronization:
        ``(engine_state, losses_device_array)`` — plus the device tap dict
        as a third element when the plan carries in-graph metrics taps
        (``graph_taps``). The throughput benches use this to queue rounds
        back-to-back (jax async dispatch) and block once at the end —
        ``run_round``'s per-round loss extraction would otherwise serialize
        dispatch against compute."""
        return self._run(engine_state, batches, mask)

    def evaluate(self, state: PlanState) -> dict:
        """Held-out classification metrics of the current global model."""
        return self._eval(state.engine_state)

    def run(self, rounds: Optional[int] = None, *, with_eval: bool = True
            ) -> tuple[PlanState, list[RoundRecord]]:
        """Init + run ``rounds`` (default: the mission-budgeted round count)
        and collect the record stream. With telemetry enabled the whole run
        is one ``run`` span over per-round spans; mission plans additionally
        emit the tour-leg decomposition (travel/hover/comm on the simulated
        mission clock — ``fleet.campaign.mission_obs_events``) and the sink
        is flushed before returning."""
        obs = self.obs
        num = self.num_rounds if rounds is None else rounds
        records = []
        with obs.span("run", rounds=num):
            with obs.span("init"):
                state = self.init()
            for _ in range(num):
                state, rec = self.run_round(state, with_eval=with_eval)
                records.append(rec)
        if obs:
            if self.tour is not None or self.timeline is not None:
                # deferred: fleet.campaign imports api.records at module
                # level; importing it here avoids the package cycle
                from ..fleet.campaign import mission_obs_events
                for ev in mission_obs_events(self, records):
                    obs.event(**ev)
            obs.flush()
        return state, records


# ---------------------------------------------------------------------------
# compilation
# ---------------------------------------------------------------------------

def _resolve_data(spec: ExperimentSpec, data):
    if data is not None or spec.data.kind == "arrays":
        if data is None:
            raise ValueError("DataSpec(kind='arrays') needs data=(x_train, "
                             "y_train, x_test, y_test) at compile time")
        return tuple(np.asarray(a) for a in data)
    key = jax.random.PRNGKey(spec.seed)
    if spec.data.kind == "tokens":
        # synthetic LM stream: inputs are tokens[:, :-1], targets the next
        # token — the transformer family's data pipeline
        vocab = spec.model.arch.vocab
        n_train = spec.data.n_train or max(24 * spec.clients.num_clients, 96)
        n_test = spec.data.n_test or max(n_train // 4, 32)
        seq = spec.data.seq_len
        toks_tr = synthetic_tokens(keys.fold(key, keys.DATA_TRAIN), n_train,
                                   seq + 1, vocab)
        toks_te = synthetic_tokens(keys.fold(key, keys.DATA_TEST), n_test,
                                   seq + 1, vocab)
        return (np.asarray(toks_tr[:, :-1]), np.asarray(toks_tr[:, 1:]),
                np.asarray(toks_te[:, :-1]), np.asarray(toks_te[:, 1:]))
    gen = SyntheticPestImages(num_classes=spec.model.num_classes,
                              image_size=spec.data.image_size, seed=spec.seed)
    n_train = spec.data.n_train or max(24 * spec.clients.num_clients,
                                       12 * spec.model.num_classes)
    n_test = spec.data.n_test or max(n_train // 4, 48)
    x_train, y_train = gen.sample(keys.fold(key, keys.DATA_TRAIN), n_train)
    x_test, y_test = gen.sample(keys.fold(key, keys.DATA_TEST), n_test)
    return (np.asarray(x_train), np.asarray(y_train),
            np.asarray(x_test), np.asarray(y_test))


def _resolve_parts(spec: ExperimentSpec, y_train: np.ndarray) -> list:
    """Client data partition per ``DataSpec.partition``. With a population,
    partitioning is by population id: ``population_partition_count`` distinct
    shards cycled over pids (``pid % count``), gathered to the sampled
    cohort per round — the materialized corner (population == num_clients)
    builds exactly today's per-client partitions."""
    n = spec.clients.num_clients
    if spec.clients.population is not None:
        n = population_partition_count(spec.clients.population, len(y_train))
    if spec.data.partition == "dirichlet":
        return partition_dirichlet(y_train, n, alpha=spec.data.dirichlet_alpha,
                                   seed=spec.seed, min_size=1)
    if spec.data.partition == "iid":
        return partition_iid(len(y_train), n, seed=spec.seed)
    return partition_non_iid(y_train, n, spec.data.classes_per_client,
                             num_classes=spec.model.num_classes,
                             seed=spec.seed)


def _profile_consts(spec: ExperimentSpec, client_flops):
    """Per-PROFILE ``(t_client_s, power_w)`` arrays for cohort billing.
    Only materialized under a population with one homogeneous per-step
    client cost (``client_flops``): device profiles cycle over population
    ids exactly as they cycle over materialized slots, so the per-round
    gather ``cohort % n_profiles`` reproduces per-slot constants bit-for-bit
    in the degenerate corner."""
    if spec.clients.population is None or client_flops is None:
        return None
    profs = spec.clients.edge_profiles
    return (np.asarray([client_step_time_s(client_flops, p) for p in profs]),
            np.asarray([p.power_w for p in profs]))


def _needs_mask(spec: ExperimentSpec) -> bool:
    """Whether the compiled engine must accept a per-round client mask
    (i.i.d. dropout policy, or a stochastic scenario availability trace)."""
    if spec.clients.dropout_rate > 0:
        return True
    scn = spec.scenario
    return scn is not None and scn.needs_mask


def _validate(spec: ExperimentSpec):
    eng = spec.engine
    cli = spec.clients
    if cli.num_clients < 1:
        raise ValueError(f"ClientSpec.num_clients must be >= 1, got "
                         f"{cli.num_clients}")
    if not 0.0 <= cli.dropout_rate < 1.0:
        raise ValueError(f"ClientSpec.dropout_rate must be in [0, 1), got "
                         f"{cli.dropout_rate} (1.0 would drop every client "
                         f"every round)")
    if cli.population is not None:
        if cli.population < cli.num_clients:
            raise ValueError(
                f"ClientSpec.population={cli.population} is smaller than the "
                f"cohort num_clients={cli.num_clients}; a round samples "
                f"num_clients participants FROM the population (use "
                f"population=None for a fully-materialized fleet)")
        if cli.population > cli.num_clients:
            if eng.kind == "sl" and not eng.is_fleet:
                raise ValueError(
                    "population sampling with sl/scan is unsupported: the "
                    "sequential Algorithm 3 engine keeps per-slot client "
                    "params + Adam moments across rounds, which would leak "
                    "state between the different population clients a slot "
                    "maps to; use sl/vmap or sl/shard_map (the EPSL shared "
                    "client tier) or fl/* (stateless rounds)")
            if spec.cut_policy.mode == "adaptive":
                raise ValueError(
                    "adaptive per-client cuts re-bucket (and so recompile) "
                    "per sampled cohort; population sampling supports "
                    "fraction cuts only")
    if eng.kind not in ("fl", "sl"):
        raise ValueError(f"engine.kind must be 'fl' or 'sl', got {eng.kind!r}")
    if eng.client_axis not in ("scan", "vmap", "shard_map"):
        raise ValueError(f"engine.client_axis must be 'scan', 'vmap' or "
                         f"'shard_map', got {eng.client_axis!r}")
    if spec.model.family not in ("cnn", "transformer"):
        raise ValueError(f"unknown model family {spec.model.family!r}")
    if spec.model.family == "transformer":
        if spec.model.arch is None:
            raise ValueError("ModelSpec(family='transformer') needs arch="
                             "ArchConfig (the stacked attention blocks to "
                             "split)")
        if spec.model.arch.n_experts:
            raise ValueError("MoE stacks can't split through the stacked-"
                             "block interface (see transformer_block_apply)")
        if eng.kind != "sl":
            raise ValueError("the transformer family trains split (sl); the "
                             "full-model FL baseline is a CNN-family path")
        if spec.cut_policy.mode != "fraction":
            raise ValueError("transformer cuts are fraction-placed "
                             "(stack_cut_index); adaptive per-client cuts "
                             "are a CNN-stage path for now")
        if spec.data.kind not in ("tokens",):
            raise ValueError("transformer specs train on DataSpec("
                             "kind='tokens')")
        if spec.data.partition != "iid":
            raise ValueError("token streams carry no label classes to skew; "
                             "use DataSpec(partition='iid')")
        if eng.server_mesh is not None:
            raise ValueError("server_mesh tier specs are wired for the CNN "
                             "stage path only; the transformer family would "
                             "silently replicate the server suffix (plumb "
                             "fleet_server_pspecs through _compile_sl_stack "
                             "to lift this)")
    elif spec.model.name not in CNN_BUILDERS:
        raise ValueError(f"unknown CNN {spec.model.name!r}")
    if spec.model.attn_impl not in ATTN_IMPLS:
        raise ValueError(f"ModelSpec.attn_impl must be one of {ATTN_IMPLS}, "
                         f"got {spec.model.attn_impl!r}")
    if spec.model.attn_impl != "xla" and spec.model.family != "transformer":
        raise ValueError("ModelSpec.attn_impl selects the transformer "
                         "attention kernel; CNN stage lists have no "
                         "attention to dispatch")
    if eng.link_kernel not in LINK_KERNELS:
        raise ValueError(f"EngineSpec.link_kernel must be one of "
                         f"{LINK_KERNELS}, got {eng.link_kernel!r}")
    if eng.link_kernel != "xla" and spec.link_policy.compress != "int8":
        raise ValueError("EngineSpec.link_kernel fuses the int8 boundary; "
                         "it needs LinkPolicy(compress='int8')")
    if spec.data.kind not in ("synthetic", "arrays", "tokens"):
        raise ValueError(f"DataSpec.kind must be 'synthetic', 'arrays' or "
                         f"'tokens', got {spec.data.kind!r}")
    if spec.data.kind == "tokens" and spec.model.family != "transformer":
        raise ValueError("DataSpec(kind='tokens') is the transformer "
                         "family's pipeline; CNN specs train on 'synthetic' "
                         "or 'arrays'")
    if spec.data.partition not in ("classes", "dirichlet", "iid"):
        raise ValueError(f"DataSpec.partition must be 'classes', 'dirichlet' "
                         f"or 'iid', got {spec.data.partition!r}")
    if spec.cut_policy.mode not in ("fraction", "adaptive"):
        raise ValueError(spec.cut_policy.mode)
    if spec.cut_policy.mode == "adaptive" and not (
            eng.kind == "sl" and eng.is_fleet):
        raise ValueError("adaptive cuts produce per-client programs; they "
                         "need the bucketed fleet engine (sl/vmap or "
                         "sl/shard_map)")
    if spec.clients.dropout_rate > 0 and not eng.is_fleet:
        raise ValueError("client dropout is a fleet policy; use a vmap or "
                         "shard_map client axis")
    if spec.scenario is not None:
        spec.scenario.validate(has_mission=spec.mission is not None)
        if spec.scenario.needs_mask and not eng.is_fleet:
            raise ValueError("availability traces mask clients per round; "
                             "they need a fleet engine (vmap or shard_map "
                             "client axis)")
        if spec.scenario.needs_mask and spec.clients.dropout_rate > 0:
            raise ValueError("pick ONE straggler process: ClientSpec."
                             "dropout_rate (i.i.d.) or the scenario's "
                             "availability trace")
        if spec.scenario.num_uavs > spec.clients.num_clients:
            raise ValueError(f"{spec.scenario.num_uavs} UAVs for "
                             f"{spec.clients.num_clients} clients")
    if eng.server_mesh is not None:
        if eng.kind != "sl" or not eng.is_fleet:
            raise ValueError("server_mesh shards the SL server suffix; it "
                             "needs a fleet SL engine (sl/vmap or "
                             "sl/shard_map)")
        f, t = eng.server_mesh
        if f < 1 or t < 1:
            raise ValueError(f"server_mesh sizes must be >= 1, got "
                             f"{eng.server_mesh}")


def _resolve_mesh(spec: ExperimentSpec, mesh):
    """Pick/validate the fleet mesh for a fleet-axis engine. ``server_mesh``
    grows a ('data','fsdp','tp') layout; shard_map always gets a concrete
    mesh (single-device fallback) so the explicit-collective program
    compiles anywhere."""
    eng = spec.engine
    if not eng.is_fleet:
        return mesh
    n = spec.clients.num_clients
    if mesh is None and eng.server_mesh is not None:
        f, t = eng.server_mesh
        mesh = make_fleet_mesh(n, fsdp=f, tp=t)
        if mesh is None and f * t > 1:
            raise ValueError(
                f"server_mesh={eng.server_mesh} needs at least {f * t} "
                f"devices ({len(jax.devices())} available)")
    elif mesh is not None and eng.server_mesh is not None:
        # an explicit mesh must deliver the server sub-mesh the spec asked
        # for — never silently fall back to a replicated server suffix
        if server_mesh_sizes(mesh) != tuple(eng.server_mesh):
            raise ValueError(
                f"server_mesh={eng.server_mesh} but the supplied mesh has "
                f"(fsdp, tp)={server_mesh_sizes(mesh)}; build it with "
                f"launch.mesh.make_fleet_mesh(num_clients, fsdp=, tp=) or "
                f"drop one of the two")
    if mesh is None and eng.client_axis == "shard_map":
        mesh = make_fleet_mesh(n) or single_device_fleet_mesh()
    validate_fleet_mesh(mesh, n)
    f, t = server_mesh_sizes(mesh)
    if (eng.client_axis == "shard_map" and f * t > 1
            and jax.default_backend() == "cpu"):
        # XLA:CPU's SPMD partitioner (jax 0.9) still refuses this layout:
        # it fails the compile with RET_CHECK "IsManualSubgroup()
        # Cross-partition allreduce must be in (partial) manual
        # partitioning mode". The TPU compiler partitions it. The vmap
        # engine runs the full 2D layout on every backend.
        raise ValueError(
            "client_axis='shard_map' with a >1 server_mesh is gated off "
            "the CPU backend (XLA:CPU's SPMD partitioner refuses the "
            "partial-manual all-reduce); use client_axis='vmap' for the "
            "2D layout on CPU")
    return mesh


def compile_experiment(spec: ExperimentSpec, *, mesh=None, data=None,
                       obs=None) -> Plan:
    """Lower ``spec`` to a ``Plan``. ``data`` is an optional
    ``(x_train, y_train, x_test, y_test)`` tuple (required for
    ``DataSpec(kind='arrays')``); ``mesh`` an optional fleet mesh
    (``launch.mesh.make_fleet_mesh`` — built automatically for
    ``client_axis='shard_map'`` or a ``server_mesh``): the stacked client
    axis of fleet engines shards over ``data``, the SL server suffix over
    ``fsdp`` x ``tp``.

    ``obs`` opts into telemetry: an ``repro.obs.ObsConfig`` (or a live
    ``Obs`` to share one run dir across several plans). Lowering phases
    emit ``compile/*`` spans, the plan stamps its row into the run
    manifest, and every ``run_round`` streams spans/gauges/records to
    ``results/runs/<run_id>/`` (see ``repro.obs``). ``None`` (default)
    attaches the shared disabled instance — hot paths pay one branch."""
    obs = Obs.ensure(obs)
    with obs.span("compile", spec=spec.describe()):
        plan = _compile_plan(spec, mesh=mesh, data=data, obs=obs)
    if obs:
        mesh_shape = (None if plan.mesh is None
                      else {k: int(v) for k, v in plan.mesh.shape.items()})
        obs.manifest(plan={
            "spec": spec.describe(), "engine": plan.engine_label,
            "model": (spec.model.name if spec.model.family == "cnn"
                      else spec.model.family),
            "num_clients": spec.clients.num_clients,
            "population": spec.clients.population,
            "rounds": plan.num_rounds, "local_steps": spec.local_steps,
            "batch_size": spec.batch_size, "mesh": mesh_shape})
        obs.flush()
    return plan


def _compile_plan(spec: ExperimentSpec, *, mesh, data, obs: Obs) -> Plan:
    _validate(spec)
    n = spec.clients.num_clients
    mesh = _resolve_mesh(spec, mesh)
    # metrics bus: resolve the in-graph tap channels at compile time. No
    # MetricsConfig (the default) -> empty taps -> every round builder
    # lowers its exact tap-free program (the bit-identity the jaxpr audit
    # pins). ObsConfig(enabled=False, metrics=...) is honored: taps work
    # without a sink.
    metrics = obs.config.metrics
    graph_taps = engine_tap_names(
        metrics, kind=spec.engine.kind,
        has_link=spec.link_policy.compress == "int8")
    step_tap_names = split_step_tap_names(graph_taps)
    with obs.span("compile/data"):
        arrays = _resolve_data(spec, data)
        x_train, y_train, x_test, y_test = arrays
        parts = _resolve_parts(spec, y_train)
    edges = [spec.clients.edge_profiles[i % len(spec.clients.edge_profiles)]
             for i in range(n)]
    use_pallas_link, interpret_link = resolve_link_kernel(
        spec.engine.link_kernel)
    link = FleetLink(config=spec.link_policy.config(),
                     use_pallas=use_pallas_link, interpret=interpret_link,
                     mesh=mesh)
    scn = spec.scenario

    # ---- mission: placement, tour/timeline, round budget -----------------
    tour = None
    timeline = None
    if spec.mission is not None:
        with obs.span("compile/mission"):
            coords = client_coords(spec.mission.farm_acres, n, seed=spec.seed)
            if scn is not None:
                # scenario missions roll out in time (multi-UAV dispatch,
                # serve geometry); single-UAV hover is the verbatim
                # plan_tour plan
                timeline = rollout_mission(
                    coords, np.zeros(2), params=spec.mission.uav,
                    hover_s_per_stop=spec.mission.hover_s_per_stop,
                    comm_s_per_stop=spec.mission.comm_s_per_stop,
                    num_uavs=scn.num_uavs, serve_mode=scn.serve_mode)
                if scn.num_uavs == 1:
                    tour = timeline.routes[0].tour
            else:
                tour = plan_tour(
                    coords, np.zeros(2), params=spec.mission.uav,
                    hover_s_per_stop=spec.mission.hover_s_per_stop,
                    comm_s_per_stop=spec.mission.comm_s_per_stop)

    # ---- channel: nominal per-client rates -------------------------------
    # link constants are hoisted at the channel's *deterministic* rate; the
    # per-round stochastic draw scales them by nominal/sampled
    serve_dist = (timeline.serve_dist_m if timeline is not None
                  else np.zeros(n))
    rate_nominal = np.full(n, spec.link_policy.rate_bps)
    if scn is not None and scn.channel is not None:
        rate_nominal = np.asarray(deterministic_rate_bps(
            scn.channel, jnp.asarray(serve_dist),
            spec.link_policy.rate_bps), dtype=np.float64)

    def client_link(cid: int) -> FleetLink:
        lp = spec.link_policy
        return FleetLink(config=LinkConfig(rate_bps=float(rate_nominal[cid]),
                                           compress=lp.compress,
                                           radio_power_w=lp.radio_power_w))

    # ---- per-client constants (filled per engine below) ------------------
    t_client = np.zeros(n)
    t_server = np.zeros(n)
    link_bytes = np.zeros(n)
    link_time = np.zeros(n)
    link_energy = np.zeros(n)
    server_base_s = 0.0
    flops: dict = {}

    if spec.model.family == "transformer":
        cfg = spec.model.arch
        k = stack_cut_index(cfg.n_layers, spec.cut_policy.fraction)
        cut_of_client = [k] * n
        with obs.span("compile/params"):
            prog = lm_split_program(cfg, jax.random.PRNGKey(spec.seed), k,
                                    link_boundary=link.boundary(),
                                    attn_impl=resolve_attn_impl(
                                        spec.model.attn_impl),
                                    taps=step_tap_names)
            sample_bx = jnp.asarray(x_train[:spec.batch_size])
            sample_by = jnp.asarray(y_train[:spec.batch_size])
        with obs.span("compile/flops"):
            # FLOPs are counted on the step's tap-free, recompute-free twin
            # so the hoisted energy/link constants — and every non-metrics
            # record field — stay bitwise identical with the metrics bus on
            fl_client, fl_server, smashed_sd = count_split_step_flops(
                prog.cost_step, prog.params_c0, prog.params_s0, sample_bx,
                sample_by)
        flops[k] = (fl_client, fl_server, smashed_sd)
        for cid in range(n):
            lc = client_link(cid)
            t_client[cid] = client_step_time_s(fl_client, edges[cid])
            t_server[cid] = roofline_s(fl_server, RTX_A5000)
            link_bytes[cid] = lc.step_wire_bytes(smashed_sd)
            link_time[cid] = lc.step_time_s(smashed_sd)
            link_energy[cid] = lc.step_energy_j(smashed_sd)
        with obs.span("compile/lower"):
            engine_fns = _compile_sl_stack(spec, mesh, prog,
                                           jnp.asarray(x_test), y_test,
                                           taps=graph_taps)
        consts = (t_client, t_server, link_bytes, link_time, link_energy,
                  server_base_s)
        return Plan(spec, mesh=mesh, arrays=arrays, parts=parts, stages=None,
                    params0=(prog.params_c0, prog.params_s0), tour=tour,
                    cut_of_client=cut_of_client, flops=flops, edges=edges,
                    consts=consts, engine_fns=engine_fns, timeline=timeline,
                    serve_dist_m=serve_dist, rate_nominal=rate_nominal,
                    prof_consts=_profile_consts(spec, fl_client), obs=obs,
                    metrics=metrics, graph_taps=graph_taps)

    # ---- model + params ---------------------------------------------------
    with obs.span("compile/params"):
        stages = CNN_BUILDERS[spec.model.name](spec.model.num_classes)
        params0 = init_stages(jax.random.PRNGKey(spec.seed), stages)
        sample_x = jnp.asarray(x_train[:spec.batch_size])
        sample_y = jnp.asarray(y_train[:spec.batch_size])
        x_test_j = jnp.asarray(x_test)

    if spec.engine.kind == "fl":
        cut_of_client: list[int] = []
        with obs.span("compile/flops"):
            step_flops = count_fl_step_flops(stages, params0, sample_x,
                                             sample_y)
        flops["full"] = step_flops
        for c in range(n):
            t_client[c] = client_step_time_s(step_flops, edges[c])
        server_base_s = FL_SERVER_AGG_S
        with obs.span("compile/lower"):
            engine_fns = _compile_fl(spec, mesh, stages, params0, x_test_j,
                                     y_test, taps=graph_taps)
    else:
        # cut assignment: one fraction-derived cut, or per-client adaptive
        # cuts under the (optionally mission-derived) link deadline checked
        # against each client's nominal channel rate
        with obs.span("compile/cuts"):
            max_link_s = spec.cut_policy.max_link_s
            if max_link_s is None and spec.mission is not None:
                max_link_s = mission_max_link_s(
                    spec.mission.hover_s_per_stop,
                    spec.mission.comm_s_per_stop, spec.local_steps)
            if spec.cut_policy.mode == "adaptive":
                cut_of_client = assign_cuts_cnn(
                    stages, params0, sample_x, edges=edges,
                    links=[client_link(c).config for c in range(n)],
                    min_client_layers=spec.cut_policy.min_client_layers,
                    max_link_s=max_link_s)
            else:
                cut_of_client = [cut_index_for_fraction(
                    stages, spec.cut_policy.fraction)] * n
        # hoisted per-step constants, per distinct cut
        by_cut: dict[int, list[int]] = {}
        for cid, k in enumerate(cut_of_client):
            by_cut.setdefault(int(k), []).append(cid)
        with obs.span("compile/flops"):
            for k, ids in by_cut.items():
                cs, cp = list(stages[:k]), list(params0[:k])
                ss, sp = list(stages[k:]), list(params0[k:])
                fl_client, fl_server, smashed_sd = count_sl_step_flops(
                    cs, cp, ss, sp, sample_x, sample_y)
                flops[k] = (fl_client, fl_server, smashed_sd)
                for cid in ids:
                    lc = client_link(cid)
                    t_client[cid] = client_step_time_s(fl_client, edges[cid])
                    t_server[cid] = roofline_s(fl_server, RTX_A5000)
                    link_bytes[cid] = lc.step_wire_bytes(smashed_sd)
                    link_time[cid] = lc.step_time_s(smashed_sd)
                    link_energy[cid] = lc.step_energy_j(smashed_sd)
        with obs.span("compile/lower"):
            if spec.engine.client_axis == "scan":
                engine_fns = _compile_sl_scan(spec, stages, params0,
                                              cut_of_client[0], link,
                                              x_test_j, y_test,
                                              taps=graph_taps)
            else:
                engine_fns = _compile_sl_fleet(spec, mesh, stages, params0,
                                               cut_of_client, link, x_test_j,
                                               y_test, taps=graph_taps)

    consts = (t_client, t_server, link_bytes, link_time, link_energy,
              server_base_s)
    # one homogeneous per-step client cost exists for FL (full model) and
    # single-cut SL; heterogeneous adaptive cuts fall back to the per-slot
    # constants (only reachable with population == num_clients, where the
    # cohort is the identity and per-slot billing is exact)
    if spec.engine.kind == "fl":
        cli_fl = flops["full"]
    elif len(set(cut_of_client)) == 1:
        cli_fl = flops[cut_of_client[0]][0]
    else:
        cli_fl = None
    return Plan(spec, mesh=mesh, arrays=arrays, parts=parts, stages=stages,
                params0=params0, tour=tour, cut_of_client=cut_of_client,
                flops=flops, edges=edges, consts=consts,
                engine_fns=engine_fns, timeline=timeline,
                serve_dist_m=serve_dist, rate_nominal=rate_nominal,
                prof_consts=_profile_consts(spec, cli_fl), obs=obs,
                metrics=metrics, graph_taps=graph_taps)


# ---------------------------------------------------------------------------
# per-engine lowering: (init_state, run(state, batches, mask), eval(state),
#                       run_raw, eval_acc_raw) — the raw pair is unjitted /
#                       jittable closures the Monte-Carlo sweeps lower into
#                       one vmapped rollout (None, None for hetero fleets)
# ---------------------------------------------------------------------------

def _sl_audit(round_fn, masked: bool) -> dict:
    """The jaxpr auditor's handle onto an SL engine round: the jitted
    callable plus how the uniform run surface maps to its positional
    signature (``repro.analyze.jaxpr_audit`` consumes this)."""
    return {"jit_fn": round_fn, "donate_argnums": (0, 1, 2, 3),
            "unpack_state": True, "masked": masked}


def round_args(audit: dict, engine_state, batches, mask, n: int) -> tuple:
    """The positional arguments a plan's run closure hands its jitted round
    (``audit`` is the closure's ``_audit`` handle; ``mask`` None on a
    mask-aware round means every one of the ``n`` clients)."""
    args = tuple(engine_state) if audit["unpack_state"] else (engine_state,)
    args += (batches,)
    if audit["masked"]:
        args += (jnp.ones(n, jnp.float32) if mask is None
                 else jnp.asarray(mask),)
    return args


def _mask_runner(round_fn, masked: bool, n: int, audit: dict = None,
                 with_taps: bool = False):
    """Uniform ``run(state, batches, mask)`` closure over a round builder
    that takes a trailing mask only when built mask-aware. With
    ``with_taps`` the round emits the metrics-bus tap dict after the
    losses and ``run`` returns ``(state, losses, taps)``."""
    full_mask = jnp.ones(n, jnp.float32)   # hoisted: one buffer, not per round

    def run(engine_state, batches, mask):
        if masked:
            m = full_mask if mask is None else jnp.asarray(mask)
            out = round_fn(*engine_state, batches, m)
        else:
            assert mask is None, \
                "mask fed to a mask-free engine (validated at compile)"
            out = round_fn(*engine_state, batches)
        if with_taps:
            *state, losses, taps = out
            return tuple(state), losses, taps
        *state, losses = out
        return tuple(state), losses
    if audit is not None:
        run._audit = audit
    return run


def _compile_fl(spec, mesh, stages, params0, x_test_j, y_test, taps=()):
    opt = adamw(spec.lr)

    def grad_fn(params, batch):
        bx, by = batch
        return jax.value_and_grad(
            lambda p: cross_entropy_loss(apply_stages(stages, p, bx), by))(
                params)

    masked = _needs_mask(spec)
    if spec.engine.is_fleet:
        raw_fn = make_fleet_fl_round(grad_fn, opt, mesh=mesh,
                                     client_dropout=masked,
                                     client_axis=spec.engine.client_axis,
                                     taps=taps)
    else:
        raw_fn = make_fl_round(grad_fn, opt, client_axis="scan", taps=taps)
    round_fn = jit_round(raw_fn, "fl_round", donate_argnums=(0,))

    def init_state():
        return jax.tree_util.tree_map(jnp.copy, params0)

    full_mask = jnp.ones(spec.clients.num_clients, jnp.float32)

    def make_run(fn, audit=None):
        def run(engine_state, batches, mask):
            if masked:
                m = full_mask if mask is None else jnp.asarray(mask)
                return fn(engine_state, batches, m)
            assert mask is None, \
                "mask fed to a mask-free engine (validated at compile)"
            return fn(engine_state, batches)
        if audit is not None:
            run._audit = audit
        return run

    eval_logits = jax.jit(lambda p: apply_stages(stages, p, x_test_j))

    def evaluate(engine_state):
        return classification_metrics(eval_logits(engine_state), y_test,
                                      spec.model.num_classes)

    y_test_j = jnp.asarray(y_test)

    def eval_acc_raw(engine_state):
        return accuracy_from_logits(
            apply_stages(stages, engine_state, x_test_j), y_test_j)

    audit = {"jit_fn": round_fn, "donate_argnums": (0,),
             "unpack_state": False, "masked": masked}
    return (init_state, make_run(round_fn, audit=audit), evaluate,
            make_run(raw_fn), eval_acc_raw)


def _eval_prefix(client_stack, dropout: bool):
    """The global client prefix to evaluate with. Rows are identical after
    FedAvg (row 0 suffices); under dropout they may hold stale straggler
    prefixes, so the row mean stands in for the active average."""
    if dropout:
        return jax.tree_util.tree_map(
            lambda v: jnp.mean(v.astype(jnp.float32), axis=0).astype(v.dtype),
            client_stack)
    return jax.tree_util.tree_map(lambda v: v[0], client_stack)


def _split_step(stages, params0, k, link, step_taps=()):
    cs, cp = list(stages[:k]), list(params0[:k])
    ss, sp = list(stages[k:]), list(params0[k:])
    step = SplitStep(
        client_fwd=lambda pc, xx: apply_stages(cs, pc, xx),
        server_loss=lambda ps, sm, yy: (
            cross_entropy_loss(apply_stages(ss, ps, sm), yy), {}),
        link_constraint=link.boundary(),
        taps=step_taps,
    )
    return cs, cp, ss, sp, step


def _compile_sl_scan(spec, stages, params0, k, link, x_test_j, y_test,
                     taps=()):
    """Sequential Algorithm 3: one shared server model updated per client
    visit (``make_multi_client_round``), homogeneous cut."""
    cs, cp0, ss, sp, step = _split_step(stages, params0, k, link,
                                        step_taps=split_step_tap_names(taps))
    opt_c, opt_s = adamw(spec.lr), adamw(spec.lr)
    n = spec.clients.num_clients
    raw_fn = make_multi_client_round(step, opt_c, opt_s,
                                     local_rounds=spec.local_steps,
                                     taps=taps)
    round_fn = jit_round(raw_fn, "sl_round", donate_argnums=(0, 1, 2, 3))

    def init_state():
        state = (stack_replicas(cp0, n), sp, init_stacked(opt_c, cp0, n),
                 opt_s.init(sp))
        return jax.tree_util.tree_map(jnp.copy, state)

    eval_logits = jax.jit(
        lambda cp, sp_: apply_stages(ss, sp_, apply_stages(cs, cp, x_test_j)))

    def evaluate(engine_state):
        client_stack, sp_, _, _ = engine_state
        prefix = _eval_prefix(client_stack, dropout=False)
        return classification_metrics(eval_logits(prefix, sp_), y_test,
                                      spec.model.num_classes)

    y_test_j = jnp.asarray(y_test)

    def eval_acc_raw(engine_state):
        client_stack, sp_, _, _ = engine_state
        prefix = _eval_prefix(client_stack, dropout=False)
        return accuracy_from_logits(
            apply_stages(ss, sp_, apply_stages(cs, prefix, x_test_j)),
            y_test_j)

    return (init_state,
            _mask_runner(round_fn, False, n, audit=_sl_audit(round_fn, False),
                         with_taps=bool(taps)),
            evaluate, _mask_runner(raw_fn, False, n, with_taps=bool(taps)),
            eval_acc_raw)


def _compile_sl_fleet(spec, mesh, stages, params0, cut_of_client, link,
                      x_test_j, y_test, taps=()):
    """Parallel fleet SL (``make_fleet_sl_round``, vmap or shard_map client
    axis). Homogeneous cuts run the engine directly — one compiled round,
    no host-side bucket reassembly; heterogeneous cuts dispatch through
    ``HeteroFleet`` (one compiled round + server suffix per cut bucket).
    With a >1 ``server_mesh`` the ``launch.steps.fleet_server_pspecs`` tier
    specs shard the server suffix (params + optimizer moments) fsdp x tp
    while the client axis shards over ``data``."""
    opt_c, opt_s = adamw(spec.lr), adamw(spec.lr)
    dropout = _needs_mask(spec)
    n = spec.clients.num_clients
    pop = spec.clients.population
    # EPSL shared client tier: a sampled cohort (population > cohort) can't
    # keep per-slot client params/Adam moments — slot i maps to a different
    # population client every round — so the fleet trains ONE client model
    # broadcast across the cohort axis (state O(1) in both population and
    # cohort). The materialized corner (population in (None, num_clients))
    # keeps the stacked tier and its exact record stream.
    shared = pop is not None and pop > n
    client_axis = spec.engine.client_axis
    fsdp, tp = server_mesh_sizes(mesh)
    server_pspecs_fn = None
    if mesh is not None and fsdp * tp > 1:
        from ..launch.steps import fleet_server_pspecs
        server_pspecs_fn = fleet_server_pspecs

    if len(set(cut_of_client)) == 1:
        k = cut_of_client[0]
        cs, cp0, ss, sp, step = _split_step(
            stages, params0, k, link,
            step_taps=split_step_tap_names(taps))
        sps_specs = (server_pspecs_fn(sp, mesh)
                     if server_pspecs_fn is not None else None)
        raw_fn = make_fleet_sl_round(step, opt_c, opt_s,
                                     local_rounds=spec.local_steps, mesh=mesh,
                                     server_reduce=spec.engine.server_reduce,
                                     client_dropout=dropout,
                                     client_axis=client_axis,
                                     client_tier="shared" if shared
                                     else "stacked",
                                     server_pspecs=sps_specs, taps=taps)

        def fresh_state():
            if shared:
                state = (cp0, sp, opt_c.init(cp0), opt_s.init(sp))
            else:
                state = (stack_replicas(cp0, n), sp,
                         init_stacked(opt_c, cp0, n), opt_s.init(sp))
            return jax.tree_util.tree_map(jnp.copy, state)

        if mesh is None:
            round_fn = jit_round(raw_fn, "sl_round",
                                 donate_argnums=(0, 1, 2, 3))
            init_state = fresh_state
        else:
            # the round returns its state where init_state places it, so
            # every round of a run (the first included) is one program
            shardings = fleet_sl_state_shardings(
                jax.eval_shape(fresh_state), mesh, shared=shared,
                server_pspecs=sps_specs)
            round_fn = jit_round(
                raw_fn, "sl_round", donate_argnums=(0, 1, 2, 3),
                out_shardings=shardings + (None,) * (2 if taps else 1))

            def init_state():
                return jax.device_put(fresh_state(), shardings)

        def global_prefix(client_stack):
            return (client_stack if shared
                    else _eval_prefix(client_stack, dropout))

        eval_logits = jax.jit(
            lambda cp, sp_: apply_stages(ss, sp_,
                                         apply_stages(cs, cp, x_test_j)))

        def evaluate(engine_state):
            client_stack, sp_, _, _ = engine_state
            return classification_metrics(
                eval_logits(global_prefix(client_stack), sp_), y_test,
                spec.model.num_classes)

        y_test_j = jnp.asarray(y_test)

        def eval_acc_raw(engine_state):
            client_stack, sp_, _, _ = engine_state
            prefix = global_prefix(client_stack)
            return accuracy_from_logits(
                apply_stages(ss, sp_, apply_stages(cs, prefix, x_test_j)),
                y_test_j)

        return (init_state,
                _mask_runner(round_fn, dropout, n,
                             audit=_sl_audit(round_fn, dropout),
                             with_taps=bool(taps)),
                evaluate, _mask_runner(raw_fn, dropout, n,
                                       with_taps=bool(taps)),
                eval_acc_raw)

    def build_program(k):
        return cnn_split_program(stages, params0, k,
                                 loss_fn=cross_entropy_loss,
                                 link_boundary=link.boundary(),
                                 taps=split_step_tap_names(taps))

    fleet = HeteroFleet(build_program, cut_of_client, opt_c, opt_s,
                        local_rounds=spec.local_steps, mesh=mesh,
                        client_dropout=dropout,
                        server_reduce=spec.engine.server_reduce,
                        client_axis=client_axis,
                        server_pspecs_fn=server_pspecs_fn, taps=taps)

    bucket_eval = []
    for bucket in fleet.buckets:
        k = bucket.cut_index
        cs, ss = list(stages[:k]), list(stages[k:])
        bucket_eval.append(jax.jit(
            lambda cp, sp_, cs=cs, ss=ss: apply_stages(
                ss, sp_, apply_stages(cs, cp, x_test_j))))

    def init_state():
        # per-bucket state tuples threaded EXTERNALLY through run_round_on,
        # so every PlanState owns independent fresh state (the fleet object
        # only holds the compiled engines)
        return fleet.init_states()

    def run(engine_state, batches, mask):
        return fleet.run_round_on(engine_state, batches, client_mask=mask)

    def evaluate(engine_state):
        # every bucket's model votes on the held-out set, weighted by its
        # client count
        logits = jnp.zeros((len(y_test), spec.model.num_classes), jnp.float32)
        for i, bucket in enumerate(fleet.buckets):
            client_stack, params_s, _, _ = engine_state[i]
            prefix = _eval_prefix(client_stack, dropout)
            out = bucket_eval[i](prefix, params_s)
            logits = logits + out.astype(jnp.float32) * len(bucket.client_ids)
        return classification_metrics(logits / n, y_test,
                                      spec.model.num_classes)

    # hetero rounds dispatch per bucket on the host: no single jittable
    # round exists, so Monte-Carlo vectorization is unsupported (raw=None)
    return init_state, run, evaluate, None, None


def _compile_sl_stack(spec, mesh, prog, x_test_j, y_test, taps=()):
    """Transformer-family lowering: the ``lm_split_program`` step through
    the sequential (scan) or fleet (vmap/shard_map) SL engines — same
    wiring as the CNN paths, token logits evaluated over all positions."""
    opt_c, opt_s = adamw(spec.lr), adamw(spec.lr)
    masked = _needs_mask(spec)
    n = spec.clients.num_clients
    pop = spec.clients.population
    shared = pop is not None and pop > n   # EPSL shared client tier (see
    #                                        _compile_sl_fleet)
    vocab = spec.model.arch.vocab
    if spec.engine.client_axis == "scan":
        raw_fn = make_multi_client_round(prog.step, opt_c, opt_s,
                                         local_rounds=spec.local_steps,
                                         taps=taps)
    else:
        raw_fn = make_fleet_sl_round(prog.step, opt_c, opt_s,
                                     local_rounds=spec.local_steps, mesh=mesh,
                                     server_reduce=spec.engine.server_reduce,
                                     client_dropout=masked,
                                     client_axis=spec.engine.client_axis,
                                     client_tier="shared" if shared
                                     else "stacked", taps=taps)
    round_fn = jit_round(raw_fn, "sl_round", donate_argnums=(0, 1, 2, 3))

    def init_state():
        if shared:
            state = (prog.params_c0, prog.params_s0,
                     opt_c.init(prog.params_c0), opt_s.init(prog.params_s0))
        else:
            state = (stack_replicas(prog.params_c0, n), prog.params_s0,
                     init_stacked(opt_c, prog.params_c0, n),
                     opt_s.init(prog.params_s0))
        return jax.tree_util.tree_map(jnp.copy, state)

    def global_prefix(client_stack):
        return client_stack if shared else _eval_prefix(client_stack, masked)

    eval_logits = jax.jit(
        lambda cp, sp_: prog.server_logits(
            sp_, prog.step.client_fwd(cp, x_test_j)))

    def evaluate(engine_state):
        client_stack, sp_, _, _ = engine_state
        logits = eval_logits(global_prefix(client_stack), sp_)
        return classification_metrics(logits.reshape(-1, vocab),
                                      np.asarray(y_test).reshape(-1), vocab)

    y_test_flat = jnp.asarray(np.asarray(y_test).reshape(-1))

    def eval_acc_raw(engine_state):
        client_stack, sp_, _, _ = engine_state
        logits = prog.server_logits(
            sp_, prog.step.client_fwd(global_prefix(client_stack), x_test_j))
        return accuracy_from_logits(logits.reshape(-1, vocab), y_test_flat)

    return (init_state,
            _mask_runner(round_fn, masked, n,
                         audit=_sl_audit(round_fn, masked),
                         with_taps=bool(taps)),
            evaluate, _mask_runner(raw_fn, masked, n, with_taps=bool(taps)),
            eval_acc_raw)
