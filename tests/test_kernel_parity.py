"""Engine-level golden tests: kernel-enabled plans == kernel-off plans.

The PR-9 acceptance gate: one spec compiled with the Pallas kernels on
(``ModelSpec.attn_impl="pallas"`` / ``EngineSpec.link_kernel="fused"``,
interpret mode on this CPU container) must produce an equivalent
``RoundRecord`` stream to the same spec with kernels off, within
``FLEET_EQUIV_ATOL``, on every engine variant — the same style of matrix
``tests/test_fleet.py`` / ``tests/test_api.py`` gate engine axes with.
"""
import dataclasses

import pytest

from repro.api import (ClientSpec, CutPolicy, DataSpec, EngineSpec,
                       ExperimentSpec, LinkPolicy, ModelSpec,
                       compile_experiment)
from repro.configs.base import ArchConfig
from repro.fleet import FLEET_EQUIV_ATOL

TINY_ARCH = ArchConfig(name="tinylm", family="attn", n_layers=2, d_model=32,
                       n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                       dtype="float32")

LM_BASE = ExperimentSpec(
    model=ModelSpec(family="transformer", name="tinylm", arch=TINY_ARCH),
    data=DataSpec(kind="tokens", partition="iid", seq_len=16,
                  n_train=32, n_test=16),
    clients=ClientSpec(num_clients=2),
    cut_policy=CutPolicy(mode="fraction", fraction=0.5),
    engine=EngineSpec(kind="sl", client_axis="vmap"),
    global_rounds=2, local_steps=1, batch_size=4, seed=0)

CNN_BASE = ExperimentSpec(
    model=ModelSpec(name="tinycnn", num_classes=4),
    data=DataSpec(kind="synthetic", image_size=12, classes_per_client=2,
                  n_train=32, n_test=16),
    clients=ClientSpec(num_clients=2),
    cut_policy=CutPolicy(mode="fraction", fraction=0.4),
    link_policy=LinkPolicy(compress="int8"),
    engine=EngineSpec(kind="sl", client_axis="vmap"),
    global_rounds=2, local_steps=1, batch_size=4, seed=0)


def _assert_equiv_records(rec_off, rec_on):
    assert len(rec_off) == len(rec_on) > 0
    for a, b in zip(rec_off, rec_on):
        assert abs(a.loss - b.loss) <= FLEET_EQUIV_ATOL
        assert abs(a.accuracy - b.accuracy) <= FLEET_EQUIV_ATOL
        # the wire volume is shape-derived: kernels must not change it
        assert a.link_bytes == b.link_bytes
        assert a.active_clients == b.active_clients
        # the energy bill derives from XLA cost analysis of the ACTUAL
        # program, and a different kernel impl legitimately counts slightly
        # different FLOPs — hold it to a few percent, not bit equality
        assert a.client_energy_j == pytest.approx(b.client_energy_j,
                                                  rel=0.05)
        assert a.server_energy_j == pytest.approx(b.server_energy_j,
                                                  rel=0.05)


@pytest.mark.parametrize("axis", ["scan", "vmap", "shard_map"])
@pytest.mark.parametrize("attn_impl", ["pallas", "ref"])
def test_lm_attn_kernel_matches_xla(axis, attn_impl):
    """Split-LM rounds with the flash kernel (or the O(S²) oracle) in the
    server-suffix AND client-prefix blocks track the chunked-XLA plans."""
    off = dataclasses.replace(LM_BASE, engine=EngineSpec("sl", axis))
    on = dataclasses.replace(
        off, model=dataclasses.replace(LM_BASE.model, attn_impl=attn_impl))
    _, rec_off = compile_experiment(off).run()
    _, rec_on = compile_experiment(on).run()
    _assert_equiv_records(rec_off, rec_on)


@pytest.mark.parametrize("axis", ["scan", "vmap", "shard_map"])
def test_int8_link_fused_matches_xla_sl(axis):
    """The fused one-kernel int8 boundary inside the SL split step tracks
    the two-op jnp reference boundary round-for-round."""
    off = dataclasses.replace(CNN_BASE, engine=EngineSpec("sl", axis))
    on = dataclasses.replace(
        CNN_BASE, engine=EngineSpec("sl", axis, link_kernel="fused"))
    _, rec_off = compile_experiment(off).run()
    _, rec_on = compile_experiment(on).run()
    _assert_equiv_records(rec_off, rec_on)


@pytest.mark.parametrize("axis", ["scan", "vmap", "shard_map"])
def test_int8_link_kernel_flag_is_inert_for_fl(axis):
    """FL rounds have no link boundary: flipping the link kernel must not
    change a single record (completeness row of the kernels-on/off
    matrix)."""
    off = dataclasses.replace(CNN_BASE, engine=EngineSpec("fl", axis))
    on = dataclasses.replace(
        CNN_BASE, engine=EngineSpec("fl", axis, link_kernel="fused"))
    _, rec_off = compile_experiment(off).run()
    _, rec_on = compile_experiment(on).run()
    for a, b in zip(rec_off, rec_on):
        assert a.loss == b.loss and a.accuracy == b.accuracy


def test_lm_attn_and_fused_link_compose():
    """Both kernels on at once — flash attention in the blocks and the
    fused int8 boundary at the cut — still match the all-XLA plan."""
    off = dataclasses.replace(LM_BASE,
                              link_policy=LinkPolicy(compress="int8"))
    on = dataclasses.replace(
        off,
        model=dataclasses.replace(LM_BASE.model, attn_impl="pallas"),
        engine=EngineSpec("sl", "vmap", link_kernel="fused"))
    _, rec_off = compile_experiment(off).run()
    _, rec_on = compile_experiment(on).run()
    _assert_equiv_records(rec_off, rec_on)


def test_kernel_spec_validation():
    with pytest.raises(ValueError, match="attn_impl"):
        compile_experiment(dataclasses.replace(
            CNN_BASE, model=dataclasses.replace(CNN_BASE.model,
                                                attn_impl="pallas")))
    with pytest.raises(ValueError, match="link_kernel"):
        compile_experiment(dataclasses.replace(
            LM_BASE, engine=EngineSpec("sl", "vmap", link_kernel="tf32")))
    with pytest.raises(ValueError, match="int8"):
        # fused boundary without a compressed link is a spec error
        compile_experiment(dataclasses.replace(
            CNN_BASE, link_policy=LinkPolicy(compress="none"),
            engine=EngineSpec("sl", "vmap", link_kernel="fused")))


def test_lm_bill_excludes_block_recompute():
    """The split LM recomputes its blocks in the backward pass (a device
    memory choice), but the energy bill prices the program without that
    recompute: LM_BASE's client and server step FLOPs, as XLA:CPU counts
    them, are those of the plain forward + backward."""
    import jax
    import jax.numpy as jnp
    from repro.fleet.hetero import lm_split_program

    prog = lm_split_program(TINY_ARCH, jax.random.PRNGKey(0), 1)
    tokens = jnp.zeros((4, 16), jnp.int32)
    text = lambda step: str(jax.make_jaxpr(step.client_fwd)(
        prog.params_c0, tokens))
    assert "remat" in text(prog.step)
    assert "remat" not in text(prog.cost_step)
    (cut, (fl_client, fl_server, _)), = compile_experiment(
        LM_BASE).flops.items()
    assert (cut, fl_client, fl_server) == (1, 4613664.0, 5422432.0)
