"""Bring-up check: the split-learning round on a TPU, through the entry points
a user calls (``ExperimentSpec -> compile_experiment -> Plan.run()``).

    python chip_smoke.py              # phases (a)-(d) on one chip
    python chip_smoke.py --chips 4    # only the fleet mesh over four chips

Phases on one chip:

  (a) the paper's mobilenetv2 split round at 224 px: 8 clients, batch 16,
      sl/vmap, int8 link through the fused Pallas kernel, 3 rounds; one
      round again (without eval) with the XLA link, which must agree on
      loss and bytes, and one under the shard_map engine with the fused
      link (the kernel then runs per device inside the map), which must
      agree on loss;
  (b) the README quick-start spec (shard_map, adaptive cuts, int8 link,
      UAV mission), 2 rounds;
  (c) the FL baseline of spec (a), 2 rounds;
  (d) a split SmolLM-135M (30 layers, d_model 576, vocab 49152) with
      Pallas flash attention at seq 2048: 2 clients, batch 2, 2 rounds;
      the same rounds again (without eval) with XLA attention, which must
      agree on each round's loss (round 2 follows the flash backward).

With ``--chips 4``: spec (a), 2 rounds without eval, with the client axis
over a ``data=4`` mesh and with the server suffix over ``server_mesh=(2,
2)``, each under both client axes, against the one-device vmap run.

Compared runs use ``jax.default_matmul_precision("highest")``: the kernels
compute in f32 and the default TPU f32 matmul does not, and the mesh runs
then differ from the one-device run only in the order of f32 sums (the
one-device reference is also the program of phase (a), which a compile
cache kept from a one-chip run serves). ``compile_s``, ``round_s`` (one
extra fenced round) and ``loss`` lines are informational.
The script runs in one process, exits non-zero with no result unless JAX's
first device is a TPU, and prints as its last line one JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# specs (full size by default; the arguments shrink them for a CPU rehearsal)
# ---------------------------------------------------------------------------

def cnn_spec(*, kind="sl", client_axis="vmap", link_kernel="fused",
             server_mesh=None, rounds=3, image_size=224, batch_size=16,
             model="mobilenetv2"):
    """Spec (a): the paper's CNN split round at its input size."""
    from repro.api import (ClientSpec, DataSpec, EngineSpec, ExperimentSpec,
                           LinkPolicy, ModelSpec)
    return ExperimentSpec(
        model=ModelSpec(name=model, num_classes=12),
        data=DataSpec(kind="synthetic", image_size=image_size),
        clients=ClientSpec(num_clients=8),
        link_policy=LinkPolicy(compress="int8"),
        engine=EngineSpec(kind=kind, client_axis=client_axis,
                          link_kernel=link_kernel, server_mesh=server_mesh),
        global_rounds=rounds, local_steps=2, batch_size=batch_size)


def readme_spec(*, rounds=2, model="mobilenetv2"):
    """Spec (b): the README quick start."""
    from repro.api import (ClientSpec, CutPolicy, EngineSpec, ExperimentSpec,
                           LinkPolicy, MissionSpec, ModelSpec)
    return ExperimentSpec(
        model=ModelSpec(name=model, num_classes=12),
        clients=ClientSpec(num_clients=8),
        cut_policy=CutPolicy(mode="adaptive"),
        link_policy=LinkPolicy(compress="int8"),
        engine=EngineSpec(kind="sl", client_axis="shard_map"),
        mission=MissionSpec(farm_acres=100.0),
        global_rounds=rounds)


def lm_spec(*, attn_impl="pallas", rounds=2, arch=None, seq_len=2048):
    """Spec (d): split SmolLM-135M at its published widths, 2 clients of 2
    sequences each. The held-out set is 2 sequences: eval logits are
    (n_test, seq, vocab) f32. Adam at lr 1e-4: at the default 1e-3 the
    30-block stack, whose head has no final norm, jumps several nats in
    its second round, where the two attention paths' trajectories part
    further than at a small step."""
    from repro.api import (ClientSpec, DataSpec, EngineSpec, ExperimentSpec,
                           ModelSpec)
    from repro.configs import ARCHS
    return ExperimentSpec(
        model=ModelSpec(family="transformer",
                        arch=arch or ARCHS["smollm-135m"],
                        attn_impl=attn_impl),
        data=DataSpec(kind="tokens", seq_len=seq_len, partition="iid",
                      n_test=2),
        clients=ClientSpec(num_clients=2),
        engine=EngineSpec(kind="sl", client_axis="vmap"),
        global_rounds=rounds, batch_size=2, lr=1e-4)


# ---------------------------------------------------------------------------
# one phase
# ---------------------------------------------------------------------------

def compile_round(plan):
    """AOT-compile the plan's round (``plan.run()`` then reuses the
    executable). Returns the interpret flag of each pallas_call in it and
    the compiled HLO text, or None for a hetero-bucketed plan, which has no
    single round program and compiles per bucket inside ``plan.run()``."""
    import jax

    from repro.analyze.jaxpr_audit import example_round_args, iter_eqns
    if getattr(plan._run, "_audit", None) is None:
        return None
    args, audit = example_round_args(plan)
    closed = jax.make_jaxpr(audit["jit_fn"])(*args)
    interp = [bool(e.params["interpret"]) for e in iter_eqns(closed.jaxpr)
              if e.primitive.name == "pallas_call"]
    return interp, audit["jit_fn"].lower(*args).compile().as_text()


def run_phase(name: str, spec, *, mesh=None, kernels: bool = False,
              precision: str = "default", on_chip: bool = True,
              with_eval: bool = True):
    """Compile ``spec``, run it with ``plan.run(with_eval=)``, then time one
    more fenced round. The round is compiled and run at ``precision``. With
    ``kernels`` the round must hold a pallas_call, none of it in interpret
    mode and each a ``tpu_custom_call`` (checked on the chip only).
    Returns the ``RoundRecord`` list of ``plan.run()``."""
    import jax

    from repro.api import compile_experiment
    t0 = time.perf_counter()
    plan = compile_experiment(spec, mesh=mesh)
    with jax.default_matmul_precision(precision):
        compiled = compile_round(plan)
        compile_s = time.perf_counter() - t0
        if kernels:
            interp, hlo = compiled
            n_custom = hlo.count("custom_call_target=\"tpu_custom_call\"")
            log(f"[{name}] pallas_calls={len(interp)} "
                f"interpret={sum(interp)} tpu_custom_calls={n_custom}")
            if not interp:
                raise AssertionError(f"{name}: no pallas_call in the round")
            if on_chip and (any(interp) or n_custom == 0):
                raise AssertionError(f"{name}: a kernel is interpreted or "
                                     f"missing from the compiled round")
        t0 = time.perf_counter()
        state, records = plan.run(with_eval=with_eval)
        run_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        plan.run_round(state, with_eval=False)      # fenced: pulls the loss
        round_s = time.perf_counter() - t0
    devices = (list(plan.mesh.devices.flat) if plan.mesh is not None
               else jax.devices()[:1])
    stats = [d.memory_stats() or {} for d in devices]
    log(f"[{name}] mesh={None if plan.mesh is None else dict(plan.mesh.shape)}"
        f" bytes_in_use={[m.get('bytes_in_use') for m in stats]!r}"
        f" peak_bytes_in_use={[m.get('peak_bytes_in_use') for m in stats]!r}")
    del state
    losses = [r.loss for r in records]
    if not records or not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"{name}: non-finite or missing losses {losses}")
    log(f"[{name}] engine={plan.engine_label} rounds={len(records)} "
        f"precision={precision} link_bytes={records[0].link_bytes!r}")
    log(f"[{name}] compile_s={compile_s!r}")
    log(f"[{name}] run_s={run_s!r}")
    log(f"[{name}] round_s={round_s!r}")
    log(f"[{name}] losses={losses!r}")
    log(f"[{name}] loss={losses[-1]!r}")
    return records


def agree(name: str, ref, other, *, bytes_equal: bool = False) -> None:
    """Round-by-round loss agreement within FLEET_EQUIV_ATOL (over the
    rounds both ran)."""
    from repro.fleet.engine import FLEET_EQUIV_ATOL
    diffs = [abs(a.loss - b.loss) for a, b in zip(ref, other)]
    log(f"[{name}] max_abs_loss_diff={max(diffs)!r} "
        f"atol={FLEET_EQUIV_ATOL!r}")
    if max(diffs) > FLEET_EQUIV_ATOL:
        raise AssertionError(f"{name}: losses differ by {diffs}")
    if bytes_equal and any(a.link_bytes != b.link_bytes
                           for a, b in zip(ref, other)):
        raise AssertionError(f"{name}: link_bytes differ")


# ---------------------------------------------------------------------------
# phase groups
# ---------------------------------------------------------------------------

def one_chip_phases(*, on_chip: bool = True, cnn_kw=None, readme_kw=None,
                    lm_kw=None) -> None:
    import dataclasses
    cnn_kw, readme_kw, lm_kw = cnn_kw or {}, readme_kw or {}, lm_kw or {}
    fused = run_phase("a.sl_vmap_int8_fused", cnn_spec(**cnn_kw),
                      kernels=True, precision="highest", on_chip=on_chip)
    xla = run_phase("a.sl_vmap_int8_xla",
                    cnn_spec(**{**cnn_kw, "link_kernel": "xla", "rounds": 1}),
                    precision="highest", with_eval=False)
    agree("a.fused_vs_xla", fused, xla, bytes_equal=True)
    smap = run_phase("a.sl_shard_map_int8_fused",
                     cnn_spec(**{**cnn_kw, "client_axis": "shard_map",
                                 "rounds": 1}),
                     kernels=True, precision="highest", on_chip=on_chip,
                     with_eval=False)
    agree("a.fused_vmap_vs_shard_map", fused, smap, bytes_equal=True)
    run_phase("b.readme_quickstart", readme_spec(**readme_kw))
    spec_c = cnn_spec(**cnn_kw)
    run_phase("c.fl_vmap", dataclasses.replace(
        spec_c, engine=dataclasses.replace(spec_c.engine, kind="fl"),
        global_rounds=2))
    pallas = run_phase("d.lm_pallas", lm_spec(**lm_kw), kernels=True,
                       precision="highest", on_chip=on_chip)
    xla = run_phase("d.lm_xla", lm_spec(**{**lm_kw, "attn_impl": "xla"}),
                    precision="highest", with_eval=False)
    agree("d.pallas_vs_xla", pallas, xla)


def four_chip_phases(*, cnn_kw=None) -> None:
    from repro.launch.mesh import make_fleet_mesh
    cnn_kw = {"rounds": 2, **(cnn_kw or {})}
    ref = run_phase("4.vmap_1dev", cnn_spec(**cnn_kw), precision="highest",
                    with_eval=False)
    for axis in ("vmap", "shard_map"):
        name = f"4.{axis}_data4"
        recs = run_phase(name, cnn_spec(client_axis=axis, **cnn_kw),
                         mesh=make_fleet_mesh(8), precision="highest",
                         with_eval=False)
        agree(name, ref, recs)
    for axis in ("vmap", "shard_map"):
        name = f"4.{axis}_server2x2"
        recs = run_phase(name, cnn_spec(client_axis=axis, server_mesh=(2, 2),
                                        **cnn_kw), precision="highest",
                         with_eval=False)
        agree(name, ref, recs)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    # The energy bill's FLOP count compiles for the host CPU (core/flops.py):
    # where JAX_PLATFORMS names the platforms, keep the CPU among them.
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    log(f"device_kind={dev.device_kind!r} count={len(devices)} "
        f"jax={jax.__version__} platforms={os.environ.get('JAX_PLATFORMS')!r}"
        f" cache={enable_compile_cache()}")
    if args.chips == 4:
        four_chip_phases()
    else:
        one_chip_phases()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
