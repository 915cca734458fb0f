"""Pallas TPU kernel: row-blockwise int8 quantization of smashed activations.

This is the SL link compressor (the paper's stated future work — activation
compression — promoted here to a first-class feature): the client quantizes
the smashed tensor before the UAV hop, the server dequantizes. Wire volume
L drops ~4x vs f32 (Eq. 8: T_SL = L/R shrinks proportionally).

Tiling: grid over row blocks; each program sees an (block_rows, d) VMEM
tile, computes a per-row absmax scale, and emits int8 codes + f32 scales.
``d`` is expected to be a multiple of 128 (lane width); row blocks of 256
keep tiles ~64KB-1MB for typical d. Row counts that do not divide the
block are zero-padded up to the block multiple (padded rows quantize to
code 0 at the 1e-8 scale floor and are sliced off) — never shrunk toward
bm=1.

``quant_dequant_int8`` is the fused link-boundary kernel: ONE pallas_call
does quant + per-row scale + dequant (no int8/scale HBM round-trip), with
an optional fused residual-stream epilogue for the server side.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                  # (bm, d)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # (bm, 1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    q_ref[...] = q
    s_ref[...] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(x_ref.dtype)


def _quant_dequant_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    o_ref[...] = (q * scale).astype(o_ref.dtype)


def _quant_dequant_residual_kernel(x_ref, r_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    o_ref[...] = (q * scale + r_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _pad_rows(x: jax.Array, m_pad: int) -> jax.Array:
    if m_pad == x.shape[0]:
        return x
    return jnp.pad(x, ((0, m_pad - x.shape[0]), (0, 0)))


def _row_blocks(m: int, block_rows: int) -> tuple[int, int]:
    """(block size, padded row count): pad M up to the block multiple
    instead of shrinking the block toward 1 on awkward (e.g. prime) M."""
    bm = min(block_rows, m)
    return bm, -(-m // bm) * bm


def quantize_int8(x: jax.Array, *, block_rows: int = 256,
                  interpret: bool = False):
    """x (M, D) -> (codes int8 (M, D), scales f32 (M, 1))."""
    m, d = x.shape
    bm, m_pad = _row_blocks(m, block_rows)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(m_pad // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((m_pad, d), jnp.int8),
                   jax.ShapeDtypeStruct((m_pad, 1), jnp.float32)],
        interpret=interpret,
        name="int8_quantize",
    )(_pad_rows(x, m_pad))
    return (q[:m], s[:m]) if m_pad != m else (q, s)


def dequantize_int8(codes: jax.Array, scales: jax.Array, *,
                    out_dtype=jnp.float32, block_rows: int = 256,
                    interpret: bool = False) -> jax.Array:
    m, d = codes.shape
    bm, m_pad = _row_blocks(m, block_rows)
    y = pl.pallas_call(
        _dequant_kernel,
        grid=(m_pad // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, d), out_dtype),
        interpret=interpret,
        name="int8_dequantize",
    )(_pad_rows(codes, m_pad), _pad_rows(scales, m_pad))
    return y[:m] if m_pad != m else y


def quant_dequant_int8(x: jax.Array, *, residual: jax.Array | None = None,
                       out_dtype=None, block_rows: int = 256,
                       interpret: bool = False) -> jax.Array:
    """Fused int8 link boundary: quant + per-row scale + dequant in ONE
    kernel (the int8 codes and scales never leave VMEM). With ``residual``
    the server-side epilogue ``dequant(x) + residual`` fuses in too."""
    m, d = x.shape
    out_dtype = out_dtype or x.dtype
    bm, m_pad = _row_blocks(m, block_rows)
    spec = pl.BlockSpec((bm, d), lambda i: (i, 0))
    if residual is None:
        kernel, in_specs = _quant_dequant_kernel, [spec]
        operands = (_pad_rows(x, m_pad),)
        name = "int8_quant_dequant"
    else:
        kernel, in_specs = _quant_dequant_residual_kernel, [spec, spec]
        operands = (_pad_rows(x, m_pad), _pad_rows(residual, m_pad))
        name = "int8_quant_dequant_residual"
    y = pl.pallas_call(
        kernel,
        grid=(m_pad // bm,),
        in_specs=in_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, d), out_dtype),
        interpret=interpret,
        name=name,
    )(*operands)
    return y[:m] if m_pad != m else y
