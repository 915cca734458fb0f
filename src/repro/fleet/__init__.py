"""Fleet subsystem — many heterogeneous split-learning clients as one system.

PR 1's scanned-round engine (``repro.core.split``) made a single round one
compiled XLA program; this package scales that engine along the *client*
axis so a whole edge fleet trains as one SPMD program, and wraps it in the
mission-level simulator the paper's energy claims need at scale.

Layout
------
``engine.py``   sharded fleet rounds: the stacked client axis of the FL and
                SL round builders is batched (independent clients — Efficient
                Parallel Split Learning, Lin et al., arXiv:2303.15991) and
                sharded over the ``data`` mesh axis, so N clients run as one
                SPMD program — either ``client_axis='vmap'`` (GSPMD-inferred
                collectives via sharding constraints) or
                ``client_axis='shard_map'`` (explicit ``fedavg_pmean`` /
                in-map ``lax.pmean`` collectives, pinned schedule; the
                multi-host path). ``launch.mesh.make_fleet_mesh`` builds the
                2D ``('data','fsdp','tp')`` mesh; ``server_pspecs`` shards
                the SL server suffix fsdp x tp. Defines ``FLEET_EQUIV_ATOL``,
                the documented loosened equivalence tolerance vs the
                sequential reference.
``hetero.py``   per-client cut personalization (P3SL, arXiv:2507.17228):
                clients are assigned cut indices via
                ``core.adaptive_cut.select_cut`` on their own hardware/link
                profile, bucketed by cut, and each cut-group runs its own
                compiled fleet round. Works for both CNN ``Stage`` lists and
                transformer ``split_stack`` models.
``link.py``     the compressed link boundary: wires the
                ``kernels/quant`` int8 straight-through compressor into
                ``SplitStep`` (opt-in) and turns smashed-tensor shapes into
                per-step wire-bytes/time/energy constants via
                ``core.link.LinkConfig`` (int8 payload = 1 byte/elem + f32
                scale overhead).
``campaign.py`` campaign configs: ``CampaignConfig`` -> ``campaign_spec``
                maps the historical mission surface onto one
                ``repro.api.ExperimentSpec`` (fleet SL engine + TSP tour +
                UAV round budget + link/energy accounting); run it through
                ``compile_experiment`` for the paper's rounds-vs-energy
                tradeoff across fleet sizes, cuts and link modes.
"""
from .engine import (CLIENT_AXES, FLEET_EQUIV_ATOL, fleet_sharding,
                     fleet_sl_state_shardings, make_fleet_fl_round,
                     make_fleet_sl_round, server_mesh_sizes,
                     shard_client_stack, shard_fleet_sl_state,
                     validate_fleet_mesh)
from .hetero import (CutBucket, HeteroFleet, SplitProgram,
                     arch_split_program, assign_cuts_cnn,
                     assign_cuts_transformer, bucket_by_cut,
                     cnn_split_program, stack_split_program,
                     transformer_block_apply)
from .link import FleetLink
from .campaign import (CampaignConfig, RoundRecord, campaign_spec,
                       campaign_totals)

__all__ = [n for n in dir() if not n.startswith("_")]
