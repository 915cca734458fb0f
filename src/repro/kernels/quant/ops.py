"""jit'd wrappers: straight-through int8 link compressor for split learning.

``link_compress`` is differentiable (straight-through estimator): forward
quantize→dequantize, backward identity — so the split train step can keep
the compressed link inside one autodiff program (Algorithm 3 with the
compression future-work enabled).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..dispatch import per_device
from .int8 import quant_dequant_int8
from .ref import dequantize_int8_ref, quantize_int8_ref


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def quant_dequant(x: jax.Array, *, use_pallas: bool = False,
                  interpret: bool = False) -> jax.Array:
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if use_pallas:
        # ONE fused kernel: quant + per-row scale + dequant, codes/scales
        # never round-trip through HBM (vs the two-op XLA reference)
        y = quant_dequant_int8(x2, out_dtype=x.dtype, interpret=interpret)
    else:
        q, s = quantize_int8_ref(x2)
        y = dequantize_int8_ref(q, s, out_dtype=x.dtype)
    return y.reshape(shape)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def quant_dequant_residual(x: jax.Array, residual: jax.Array, *,
                           use_pallas: bool = False,
                           interpret: bool = False) -> jax.Array:
    """Server-side fused epilogue: ``dequant(quant(x)) + residual`` in one
    kernel — the serve tier adds the incoming smashed activations onto the
    server residual stream without materializing the dequantized tensor."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    r2 = residual.reshape(-1, shape[-1])
    if use_pallas:
        y = quant_dequant_int8(x2, residual=r2, out_dtype=x.dtype,
                               interpret=interpret)
    else:
        q, s = quantize_int8_ref(x2)
        y = (dequantize_int8_ref(q, s, out_dtype=jnp.float32)
             + r2.astype(jnp.float32)).astype(x.dtype)
    return y.reshape(shape)


def make_link_compress(*, use_pallas: bool = False, interpret: bool = False,
                       mesh=None):
    """Build a straight-through int8 link compressor bound to one kernel path.

    The fleet link layer (``repro.fleet.link``) uses this to wire the Pallas
    kernel (or its jnp reference) into ``SplitStep`` as an opt-in compressed
    boundary, with ``interpret`` from ``kernels.dispatch``; the returned
    callable is vmap-able, so the sharded fleet engine can batch it over
    the client axis. On a multi-device fleet ``mesh`` the Pallas kernel runs
    per device (``kernels.dispatch.per_device``).
    """
    def kernel(x):
        return quant_dequant(x, use_pallas=use_pallas, interpret=interpret)

    if use_pallas:
        kernel = per_device(kernel, mesh)

    @jax.custom_vjp
    def compress(x: jax.Array) -> jax.Array:
        return kernel(x)

    def _fwd(x):
        return kernel(x), None

    def _bwd(_, g):
        return (g,)   # straight-through

    compress.defvjp(_fwd, _bwd)
    return compress


# default compressor: the jnp reference path (runs on every backend)
link_compress = make_link_compress()
