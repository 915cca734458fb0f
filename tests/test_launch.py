"""Launch layer: sharding specs, step builders, and a miniature dry-run.

The miniature dry-run runs in a subprocess because
``--xla_force_host_platform_device_count`` must be set before jax
initializes (the main test process keeps its 1 real device).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, INPUT_SHAPES
from repro.launch.mesh import abstract_mesh as _abstract_mesh
from repro.launch.steps import (batch_sds, effective_window, shape_supported,
                                tier_fn_for)
from repro.models.transformer import default_cut_layer, model_init
from repro.parallel.sharding import param_pspecs

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_pspecs_rules():
    cfg = ARCHS["yi-9b"]
    params = jax.eval_shape(lambda k: model_init(cfg, k),
                            jax.random.PRNGKey(0))
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    specs = param_pspecs(params, mesh)
    # embed: vocab over model, d_model over data
    assert specs["embed"]["table"] == P("model", "data")
    g0 = specs["groups"][0]
    # stacked layer axis replicated; col-parallel q
    assert g0["attn"]["wq"]["w"] == P(None, "data", "model")
    assert g0["attn"]["wo"]["w"] == P(None, "model", "data")
    assert g0["ffn"]["gate"]["w"] == P(None, "data", "model")
    assert g0["ffn"]["down"]["w"] == P(None, "model", "data")
    assert g0["ln1"]["scale"] == P()


def test_param_pspecs_client_tier_no_tp():
    cfg = ARCHS["yi-9b"]
    cut = default_cut_layer(cfg, 0.25)
    params = jax.eval_shape(lambda k: model_init(cfg, k, cut_layer=cut),
                            jax.random.PRNGKey(0))
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    specs = param_pspecs(params, mesh, tier_fn=tier_fn_for(cfg, cut))
    client = specs["groups"][0]
    server = specs["groups"][1]
    # client tier: NO 'model' axis anywhere (edge devices can't do TP)
    for leaf in jax.tree_util.tree_leaves(
            client, is_leaf=lambda s: isinstance(s, P)):
        assert "model" not in [a for a in leaf if a]
    assert server["attn"]["wq"]["w"] == P(None, "data", "model")


def test_divisibility_guard():
    cfg = ARCHS["whisper-tiny"]  # d_model=384: 384/16=24 ok; heads 6 not
    params = jax.eval_shape(lambda k: model_init(cfg, k),
                            jax.random.PRNGKey(0))
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    specs = param_pspecs(params, mesh)
    # vocab padded to 51872 => divisible; embed sharded
    assert specs["embed"]["table"] == P("model", "data")


def test_moe_expert_parallel_specs():
    cfg = ARCHS["deepseek-moe-16b"]
    params = jax.eval_shape(lambda k: model_init(cfg, k),
                            jax.random.PRNGKey(0))
    mesh = _abstract_mesh((16, 16), ("data", "model"))
    specs = param_pspecs(params, mesh)
    moe_group = specs["groups"][1]
    assert moe_group["moe"]["w_gate"] == P(None, "model", "data", None)
    assert moe_group["moe"]["w_down"] == P(None, "model", "data", None)


def test_effective_window_variants():
    assert effective_window(ARCHS["yi-9b"], INPUT_SHAPES["train_4k"]) is None
    assert effective_window(ARCHS["yi-9b"], INPUT_SHAPES["long_500k"]) == 8192
    assert effective_window(ARCHS["h2o-danube-1.8b"],
                            INPUT_SHAPES["train_4k"]) == 4096


def test_shape_support_matrix():
    ok, _ = shape_supported(ARCHS["whisper-tiny"], INPUT_SHAPES["long_500k"])
    assert not ok
    for arch in ARCHS.values():
        for shape in INPUT_SHAPES.values():
            if arch.name == "whisper-tiny" and shape.name == "long_500k":
                continue
            ok, why = shape_supported(arch, shape)
            assert ok, (arch.name, shape.name, why)


def test_batch_sds_shapes():
    d = batch_sds(ARCHS["pixtral-12b"], INPUT_SHAPES["train_4k"],
                  with_labels=True)
    n_text = 4096 - ARCHS["pixtral-12b"].frontend_tokens
    assert d["tokens"].shape == (256, n_text)
    assert d["patch_embeds"].shape == (256, 1024, 5120)


@pytest.mark.slow
def test_mini_dryrun_subprocess(tmp_path):
    """8-device miniature of the production dry-run (2x4 mesh analogue):
    lower+compile a train step for the reduced smollm on a (2,4) mesh."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        from repro.configs import ARCHS
        from repro.launch.steps import build_step
        import dataclasses
        cfg = dataclasses.replace(
            ARCHS["smollm-135m"].reduced(), vocab=512, d_model=256, d_ff=512)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        import repro.configs.base as base
        shape = base.InputShape("mini", 64, 8, "train")
        import repro.launch.steps as steps
        built = steps.build_train_step(cfg, shape, mesh)
        with mesh:
            comp = jax.jit(built.fn, in_shardings=built.in_shardings,
                           out_shardings=built.out_shardings
                           ).lower(*built.args_sds).compile()
        from repro.core.flops import compiled_cost
        print(json.dumps({"flops": float(compiled_cost(comp).get("flops", -1))}))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
