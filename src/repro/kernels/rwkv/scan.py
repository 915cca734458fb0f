"""Pallas TPU kernel: RWKV-6 ("Finch") linear-recurrence scan.

    S_t = diag(w_t) S_{t-1} + k_t^T v_t        (per head, S: hd x hd)
    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

This is the compute hot-spot of rwkv6-7b: a sequential recurrence whose
state (hd x hd = 64x64 f32 = 16KB/head) lives in VMEM scratch across the
sequential time-block grid axis, while r/k/v/w stream through VMEM in
(block_t, hd) tiles. Grid: (B, H, nt) with
dimension_semantics ("parallel","parallel","arbitrary") — the time axis is
sequential and carries the state.

Inside a time block the recurrence is an unrolled fori_loop of rank-1
updates — on TPU these map to VPU ops over the (hd, hd) tile; the matmul
y_t = r_t S is a (1,hd)x(hd,hd) MXU op. hd=64 keeps every operand
128-lane-aligned after the natural (8,128) retiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_scr, *,
                 block_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0].astype(jnp.float32)                       # (hd,)

    def step(t, S):
        r_t = r_ref[0, 0, t].astype(jnp.float32)           # (hd,)
        k_t = k_ref[0, 0, t].astype(jnp.float32)
        v_t = v_ref[0, 0, t].astype(jnp.float32)
        w_t = w_ref[0, 0, t].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]                   # (hd, hd)
        y_t = r_t @ (S + u[:, None] * kv)                  # (hd,)
        y_ref[0, 0, t] = y_t.astype(y_ref.dtype)
        return w_t[:, None] * S + kv

    s_scr[...] = jax.lax.fori_loop(0, block_t, step, s_scr[...])


def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, *, block_t: int = 128,
               interpret: bool = False) -> jax.Array:
    """r/k/v/w: (B, H, T, hd) — w is the per-step decay in (0,1);
    u: (H, hd) bonus. Returns y (B, H, T, hd) f32."""
    b, h, t, hd = r.shape
    bt = min(block_t, t)
    while t % bt:
        bt //= 2
    nt = t // bt
    kernel = functools.partial(_rwkv_kernel, block_t=bt)
    spec = pl.BlockSpec((1, 1, bt, hd), lambda ib, ih, it: (ib, ih, it, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, h, nt),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, hd), lambda ib, ih, it: (ih, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="rwkv6_scan",
    )(r, k, v, w, u)
