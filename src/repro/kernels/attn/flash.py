"""Pallas TPU kernel: flash attention (causal / sliding-window, GQA-ready).

Canonical TPU tiling: grid (B, H, nq, nk) with ``dimension_semantics``
("parallel","parallel","parallel","arbitrary") — the innermost kv axis runs
sequentially per q block, carrying the online-softmax state (m, l, acc) in
VMEM scratch. Block shapes are explicit BlockSpecs; q/kv block defaults
(256, 512) keep the working set (q + k + v + acc tiles) well under VMEM
while the (bq x bk) score tile feeds the MXU with 128-aligned dims.

Causal + window masking is done per-tile; fully-masked tiles are skipped
with @pl.when so SWA costs O(S * window), and every kernel's index maps
clamp a skipped tile to one of the live span (``_q_span``, ``_k_span``), so
a skipped step fetches no new block. One predicate (``_live``) and one mask
(``_tile_mask``) serve the forward and both backward kernels.
Non-block-aligned sequence lengths are zero-padded up to the block multiple
(never shrunk toward bq=1): padded key positions are masked with ``kv_len``
inside the kernel, padded query rows are sliced off the output.

``flash_attention`` is differentiable through ``jax.custom_vjp``. The
forward rule also writes each row's logsumexp ``m + log l`` as a residual,
f32 and lane-dense, (B,H,1,S); the primal call writes the output alone. The
backward is two tiled kernels that rebuild each probability tile as
``exp(s - lse)`` in VMEM and never write an S×S tensor to HBM:

- ``flash_bwd_dkv``: grid (B, H, nk, nq), the q axis innermost; dK and dV of
  one kv tile accumulate in VMEM scratch across the q tiles.
- ``flash_bwd_dq``: grid (B, H, nq, nk), the kv axis innermost; dQ of one q
  tile accumulates across the kv tiles.

Their kv tile is ``block_k`` and their q tile ``max(block_q, block_k)``,
both clipped to the sequence.

Both take ``delta = rowsum(dO∘O)`` from a jnp reduction. Precision follows
the platform default that the jnp path had (one bf16 pass, f32
accumulation): compiled, the MXU operands (q, k, v, dO and the P and dS
tiles) are cast to bf16 with f32 results; in interpret mode they stay f32. ``exp``, the mask, ``dS = P∘(dP − delta)`` and
every accumulator are f32. The kernels' names hold no ``attention``: that
pattern finds the forward alone on a device trace.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def _live(q_lo, k_lo, bq, bk, causal, window, kv_len):
    """Whether any query of the q tile at ``q_lo`` sees any key of the kv
    tile at ``k_lo``; a dead tile is skipped."""
    live = True
    if causal:
        live = k_lo <= q_lo + bq - 1
    if window is not None:
        live = jnp.logical_and(live, k_lo + bk - 1 > q_lo - window)
    if kv_len is not None:    # kv was padded: trailing tiles may be all-pad
        live = jnp.logical_and(live, k_lo < kv_len)
    return live


def _tile_mask(q_lo, k_lo, bq, bk, causal, window, kv_len, q_axis=0):
    """Visibility of the keys to the queries inside a live tile: (bq, bk),
    or (bk, bq) with ``q_axis=1``."""
    shape = (bq, bk) if q_axis == 0 else (bk, bq)
    qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = jnp.ones(shape, jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    if kv_len is not None:
        mask &= kpos < kv_len
    return mask


def _transpose_vec(x):
    """(n, 1) <-> (1, n): the per-row vectors live lane-dense, (1, n), in
    HBM. Broadcast to a full 128-wide tile, transpose, keep one line."""
    if x.shape[1] == 1:
        return jnp.broadcast_to(x, (x.shape[0], 128)).T[:1]
    return jnp.broadcast_to(x, (128, x.shape[1])).T[:, :1]


def _q_span(ik, bq, bk, nq, causal, window):
    """First and last q tile that can be live for kv tile ``ik``."""
    k_lo = ik * bk
    lo, hi = 0, nq - 1
    if causal:
        lo = k_lo // bq
    if window is not None:
        hi = jnp.minimum(hi, (k_lo + bk + window - 2) // bq)
    return lo, hi


def _k_span(iq, bq, bk, nk, causal, window, kv_len):
    """First and last kv tile that can be live for q tile ``iq``."""
    q_lo = iq * bq
    lo, hi = 0, nk - 1
    if causal:
        hi = jnp.minimum(hi, (q_lo + bq - 1) // bk)
    if window is not None:
        lo = jnp.maximum(lo, (q_lo - window + 1) // bk)
    if kv_len is not None:
        hi = jnp.minimum(hi, (kv_len - 1) // bk)
    return lo, hi


def _clip(i, span):
    lo, hi = span
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs, scale: float, bq: int,
                  bk: int, nk: int, causal: bool, window, kv_len,
                  with_lse: bool):
    lse_ref, m_scr, l_scr, acc_scr = refs if with_lse else (None, *refs)
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * bq
    k_lo = ik * bk

    @pl.when(_live(q_lo, k_lo, bq, bk, causal, window, kv_len))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32)  # (bq, bk)
        s = jnp.where(_tile_mask(q_lo, k_lo, bq, bk, causal, window, kv_len),
                      s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = _transpose_vec(m_scr[...] + jnp.log(l_scr[...]))


def _pad_axis(x: jax.Array, n_pad: int, axis: int = 2) -> jax.Array:
    if n_pad == x.shape[axis]:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, n_pad - x.shape[axis])
    return jnp.pad(x, widths)


def _tiling(s, sk, block_q, block_k):
    """Block sizes, padded lengths and the kv padding mask's length: the
    blocks are clipped to the sequence, the sequence padded to the block."""
    bq = min(block_q, s)
    bk = min(block_k, sk)
    # pad to the block multiple instead of shrinking the block (the old
    # ``while s % bq: bq //= 2`` fallback degrades toward bq=1 on prime S)
    s_pad = -(-s // bq) * bq
    sk_pad = -(-sk // bk) * bk
    kv_len = sk if sk_pad != sk else None
    return bq, bk, s_pad, sk_pad, kv_len


def _compiler_params(interpret):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _flash_forward(q, k, v, causal, window, block_q, block_k, interpret,
                   with_lse=False):
    """Output (B,H,S,D); with ``with_lse`` also the rows' logsumexp, f32
    (B,H,1,S)."""
    b, h, s, d = q.shape
    bq, bk, s_pad, sk_pad, kv_len = _tiling(s, k.shape[2], block_q, block_k)
    q = _pad_axis(q, s_pad)
    k = _pad_axis(k, sk_pad)
    v = _pad_axis(v, sk_pad)
    nq, nk = s_pad // bq, sk_pad // bk

    def kv_index(ib, ih, iq, ik):   # a dead kv tile maps to a live one
        return ib, ih, _clip(ik, _k_span(iq, bq, bk, nk, causal, window,
                                         kv_len)), 0

    kernel = functools.partial(_flash_kernel, scale=1.0 / math.sqrt(d),
                               bq=bq, bk=bk, nk=nk, causal=causal,
                               window=window, kv_len=kv_len,
                               with_lse=with_lse)
    out_specs = pl.BlockSpec((1, 1, bq, d),
                             lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    out_shape = jax.ShapeDtypeStruct((b, h, s_pad, d), q.dtype)
    if with_lse:
        out_specs = [out_specs, pl.BlockSpec(
            (1, 1, 1, bq), lambda ib, ih, iq, ik: (ib, ih, 0, iq))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, h, 1, s_pad), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, d), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        # the profiler names the call after this: keep "attention" in it
        name="flash_attention_fwd",
    )(q, k, v)
    if not with_lse:
        return out[:, :, :s] if s_pad != s else out
    o, lse = out
    return (o[:, :, :s], lse[..., :s]) if s_pad != s else (o, lse)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale, bq, bk,
                          nq, causal, window, kv_len, mxu):
    """dK, dV of one kv tile, in transposed space: keys on the sublanes and
    queries on the lanes, so the lane-dense lse and delta rows broadcast
    as they are and no tile is transposed."""
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_lo = iq * bq
    k_lo = ik * bk

    @pl.when(_live(q_lo, k_lo, bq, bk, causal, window, kv_len))
    def _compute():
        # q carries the scale, so dS^T q is already dK
        q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(mxu)  # (bq, d)
        do = do_ref[0, 0].astype(mxu)                              # (bq, d)
        st = jax.lax.dot_general(k_ref[0, 0].astype(mxu), q, _NT,
                                 preferred_element_type=jnp.float32)
        st = jnp.where(_tile_mask(q_lo, k_lo, bq, bk, causal, window,
                                  kv_len, q_axis=1), st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0, 0])                           # (bk, bq)
        dv_scr[...] += jax.lax.dot(pt.astype(mxu), do,
                                   preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0, 0].astype(mxu), do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, 0])
        dk_scr[...] += jax.lax.dot(dst.astype(mxu), q,
                                   preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, lse_scr, delta_scr, *, scale, bq,
                         bk, nk, causal, window, kv_len, mxu):
    """dQ of one q tile; its lse and delta rows are turned into columns
    once, at its first kv step."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        lse_scr[...] = _transpose_vec(lse_ref[0, 0])
        delta_scr[...] = _transpose_vec(delta_ref[0, 0])

    q_lo = iq * bq
    k_lo = ik * bk

    @pl.when(_live(q_lo, k_lo, bq, bk, causal, window, kv_len))
    def _compute():
        q = (q_ref[0, 0].astype(jnp.float32) * scale).astype(mxu)  # (bq, d)
        k = k_ref[0, 0].astype(mxu)                                # (bk, d)
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        s = jnp.where(_tile_mask(q_lo, k_lo, bq, bk, causal, window, kv_len),
                      s, NEG_INF)
        p = jnp.exp(s - lse_scr[...])                              # (bq, bk)
        dp = jax.lax.dot_general(do_ref[0, 0].astype(mxu),
                                 v_ref[0, 0].astype(mxu), _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_scr[...])
        dq_scr[...] += jax.lax.dot(ds.astype(mxu), k,
                                   preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal, window, block_q, block_k,
                    interpret):
    """dQ, dK, dV from the residuals and the output's cotangent ``g``.

    The q tile is at least the kv tile: ``flash_bwd_dq`` fetches a k and a
    v tile at each step and ``flash_bwd_dkv`` a q and a dO tile, and on a
    TPU v5e at (2,2,9,2048,64) f32, causal, (512, 512) tiles took 1.94 ms
    a call where the forward's (256, 512) took 2.32 (PERF.md §6)."""
    b, h, s, d = q.shape
    sk = k.shape[2]
    bq, bk, s_pad, sk_pad, kv_len = _tiling(s, sk, max(block_q, block_k),
                                            block_k)
    nq, nk = s_pad // bq, sk_pad // bk
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None]                     # (B,H,1,S)
    args = (_pad_axis(q, s_pad), _pad_axis(k, sk_pad), _pad_axis(v, sk_pad),
            _pad_axis(g, s_pad), _pad_axis(lse, s_pad, axis=3),
            _pad_axis(delta, s_pad, axis=3))
    tile = dict(scale=1.0 / math.sqrt(d), bq=bq, bk=bk, causal=causal,
                window=window, kv_len=kv_len,
                mxu=jnp.float32 if interpret else jnp.bfloat16)

    def seq(blk, idx):      # a (1,1,blk,d) block at sequence tile idx(i, j)
        return pl.BlockSpec((1, 1, blk, d),
                            lambda ib, ih, i, j: (ib, ih, idx(i, j), 0))

    def row(idx):           # a lane-dense (1,1,1,bq) block of lse or delta
        return pl.BlockSpec((1, 1, 1, bq),
                            lambda ib, ih, i, j: (ib, ih, 0, idx(i, j)))

    # grid (B, H, nk, nq); a dead q tile maps to a live one of kv tile i
    q_of = lambda i, j: _clip(j, _q_span(i, bq, bk, nq, causal, window))
    kv = seq(bk, lambda i, j: i)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, nq=nq, **tile),
        grid=(b, h, nk, nq),
        in_specs=[seq(bq, q_of), kv, kv, seq(bq, q_of), row(q_of),
                  row(q_of)],
        out_specs=[kv, kv],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk_pad, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*args)

    # grid (B, H, nq, nk); a dead kv tile maps to a live one of q tile i
    k_of = lambda i, j: _clip(j, _k_span(i, bq, bk, nk, causal, window,
                                         kv_len))
    qs = seq(bq, lambda i, j: i)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, nk=nk, **tile),
        grid=(b, h, nq, nk),
        in_specs=[qs, seq(bk, k_of), seq(bk, k_of), qs,
                  row(lambda i, j: i), row(lambda i, j: i)],
        out_specs=qs,
        out_shape=jax.ShapeDtypeStruct((b, h, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*args)
    return dq[:, :, :s], dk[:, :, :sk], dv[:, :, :sk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, window, block_q, block_k,
                          interpret)


def _flash_vjp_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    o, lse = _flash_forward(q, k, v, causal, window, block_q, block_k,
                            interpret, with_lse=True)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, window, block_q, block_k, interpret, res, g):
    # every op of the backward carries the scope ``flash_bwd`` in its
    # op_name metadata, so a profile charges it to this function
    with jax.named_scope("flash_bwd"):
        return _flash_backward(*res, g, causal, window, block_q, block_k,
                               interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window=None,
                    block_q: int = 256, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q (B,H,S,D); k,v (B,H,Sk,D) — GQA callers repeat KV heads first.
    Returns (B,H,S,D)."""
    return _flash(q, k, v, causal, window, block_q, block_k, interpret)
