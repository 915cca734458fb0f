"""Compile the main path's Pallas kernels for one described TPU v5e chip.

Nothing runs here: each test lowers and compiles a kernel at the widths the
chip smoke drives (mobilenetv2's cut tensor at 224 px, SmolLM-135M's
attention and residual stream) for a v5e that is described, not attached,
and checks that the kernel reached the compiled program as a
``tpu_custom_call``. The TPU compiler refuses here what it would refuse on
the chip: blocks not aligned to the tiling, more VMEM than a kernel may use,
a Mosaic kernel left for the partitioner to split.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.attn.flash import flash_attention
from repro.kernels.quant.int8 import quant_dequant_int8, quantize_int8
from repro.kernels.quant.ops import make_link_compress

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_compiles(one_chip, no_cache, grad):
    """SmolLM-135M heads at seq 2048, bf16, causal."""
    q = jax.ShapeDtypeStruct((2, 9, 2048, 64), jnp.bfloat16,
                             sharding=one_chip)
    if grad:
        def fn(q, k, v):
            return jax.grad(lambda *a: flash_attention(*a).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    else:
        fn = flash_attention
    assert CUSTOM_CALL in _compiled_text(fn, q, q, q)


@pytest.mark.parametrize("shape", [(50176, 24), (509, 576)],
                         ids=["mobilenetv2_cut", "width576"])
def test_quant_dequant_int8_compiles(one_chip, no_cache, shape):
    """The fused link kernel at mobilenetv2's 224 px cut tensor (batch 16 x
    56 x 56 rows of 24 channels) and at d_model 576 with a prime row count."""
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda x: quant_dequant_int8(x, out_dtype=jnp.float32), x)
    assert CUSTOM_CALL in text


@pytest.mark.parametrize("shape,dtype", [((300, 256), jnp.float32),
                                         ((4096, 576), jnp.bfloat16)],
                         ids=["f32", "bf16"])
def test_quantize_int8_compiles(one_chip, no_cache, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    assert CUSTOM_CALL in _compiled_text(quantize_int8, x)


@pytest.mark.parametrize("layout", ["vmap", "shard_map"])
@pytest.mark.parametrize("shape", [(4, 1, 1), (1, 2, 2)],
                         ids=["data4", "server2x2"])
def test_link_kernel_runs_per_device_on_mesh(topo, no_cache, layout, shape):
    """The straight-through int8 link on a four-chip fleet mesh, batched
    over clients the way each fleet engine batches it: the Mosaic kernel
    must stay a per-device custom call with no all-gather of the clients."""
    mesh = Mesh(np.asarray(topo.devices).reshape(shape),
                ("data", "fsdp", "tp"))
    compress = make_link_compress(use_pallas=True, mesh=mesh)

    def client(w, x):
        return jnp.sum(compress(x @ w) ** 2)

    if layout == "vmap":
        def fn(w, xs):
            return jax.vmap(jax.grad(client), in_axes=(None, 0),
                            spmd_axis_name="data")(w, xs)
    else:
        def fn(w, xs):
            body = jax.vmap(jax.grad(client), in_axes=(None, 0))
            return jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(), P("data")),
                                 out_specs=P("data"), axis_names={"data"},
                                 check_vma=False)(w, xs)
    w = jax.ShapeDtypeStruct((64, 128), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    xs = jax.ShapeDtypeStruct((8, 16, 64), jnp.float32,
                              sharding=NamedSharding(mesh, P("data")))
    text = _compiled_text(fn, w, xs)
    assert CUSTOM_CALL in text
    assert "all-gather" not in text
