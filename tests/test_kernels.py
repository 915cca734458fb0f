"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Differential harness for the kernel layer: hypothesis sweeps over
shape/dtype/causal/sliding-window/GQA plus deterministic parametrized
sweeps (the container image has no hypothesis — those tests skip locally
and run in CI's ``.[dev]`` install; the parametrized rows keep coverage
either way). Gradient parity goes through ``jax.grad`` on BOTH sides:
``flash_attention`` differentiates via its custom_vjp (the tiled backward
kernels) — a genuinely distinct computation path from jax's autodiff of
the oracle.
Non-block-aligned and degenerate shapes (seq < block, prime M) pin the
pad-to-block handling that replaced the old shrink-toward-1 fallback.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.attn.flash import flash_attention
from repro.kernels.attn.ref import flash_attention_ref
from repro.kernels.attn.ops import attention
from repro.kernels.dispatch import (ATTN_IMPLS, LINK_KERNELS,
                                    resolve_attn_impl, resolve_link_kernel)
from repro.kernels.quant.int8 import (_row_blocks, dequantize_int8,
                                      quant_dequant_int8, quantize_int8)
from repro.kernels.quant.ref import (dequantize_int8_ref, quantize_int8_ref,
                                     roundtrip_error_bound)
from repro.kernels.quant.ops import (link_compress, make_link_compress,
                                     quant_dequant, quant_dequant_residual)
from repro.kernels.rwkv.ref import rwkv6_scan_ref
from repro.kernels.rwkv.scan import rwkv6_scan


# ---------------------------------------------------------------------------
# int8 quant
# ---------------------------------------------------------------------------

def _assert_quant_matches_ref(x):
    q, s = quantize_int8(x, interpret=True)
    qr, sr = quantize_int8_ref(x)
    # codes may differ by 1 exactly at .5 rounding boundaries (f32 mul/div
    # association differs between the kernel and the oracle)
    dq = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert dq.max() <= 1
    assert (dq != 0).mean() < 0.01
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    y = dequantize_int8(q, s, interpret=True)
    yr = dequantize_int8_ref(qr, sr)
    # dequantized outputs may differ by one code step where codes differed
    bound = np.asarray(s) + 1e-6
    assert (np.abs(np.asarray(y) - np.asarray(yr)) <= bound).all()
    # and dequantizing the SAME codes must match exactly
    y2 = dequantize_int8(qr, sr, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(yr), atol=1e-6)
    # the fused single-kernel roundtrip must stay within one code step too
    # (compare in f32: an out_dtype=bf16 cast would add its own rounding)
    yf = quant_dequant_int8(x, out_dtype=jnp.float32, interpret=True)
    assert (np.abs(np.asarray(yf) - np.asarray(yr)) <= bound).all()


@settings(max_examples=12, deadline=None)
@given(st.sampled_from([1, 3, 16, 100, 256]),
       st.sampled_from([128, 384, 512]),
       st.sampled_from(["float32", "bfloat16"]),
       st.integers(0, 10**6))
def test_quant_kernel_matches_ref(m, d, dtype, seed):
    x = (jax.random.normal(jax.random.PRNGKey(seed), (m, d)) * 5.0
         ).astype(dtype)
    _assert_quant_matches_ref(x)


@pytest.mark.parametrize("m,d,dtype", [
    (1, 128, "float32"), (3, 384, "bfloat16"), (100, 512, "float32"),
    (256, 128, "bfloat16"), (509, 128, "float32"),   # 509: prime M > block
    (127, 256, "float32"),                           # prime M < block
])
def test_quant_kernel_matches_ref_param(m, d, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(m * d), (m, d)) * 5.0
         ).astype(dtype)
    _assert_quant_matches_ref(x)


def test_quant_prime_rows_pad_not_shrink():
    """Regression for the old ``while m % bm: bm //= 2`` fallback: awkward
    M must pad to the block multiple, not degrade the block toward 1."""
    assert _row_blocks(509, 256) == (256, 512)
    assert _row_blocks(127, 256) == (127, 127)   # M < block: one tile
    assert _row_blocks(512, 256) == (256, 512)   # aligned: no padding
    x = jax.random.normal(jax.random.PRNGKey(0), (509, 128)) * 3.0
    _assert_quant_matches_ref(x)


def test_quant_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256)) * 3.0
    y = quant_dequant(x)
    bound = roundtrip_error_bound(x)
    assert bool((jnp.abs(y - x) <= bound + 1e-6).all())


@pytest.mark.parametrize("m", [8, 100, 509])
def test_fused_quant_dequant_matches_two_op(m):
    """The fused pallas path of quant_dequant must equal its own two-op
    reference (same f32 math, no HBM int8 round-trip)."""
    x = jax.random.normal(jax.random.PRNGKey(m), (m, 128)) * 4.0
    y_fused = quant_dequant(x, use_pallas=True, interpret=True)
    y_ref = quant_dequant(x, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref),
                               atol=1e-6)


@pytest.mark.parametrize("m", [8, 100, 509])
def test_fused_residual_epilogue(m):
    """dequant(quant(x)) + residual fused in one kernel == the unfused
    composition, pallas and jnp paths both."""
    kx, kr = jax.random.split(jax.random.PRNGKey(m))
    x = jax.random.normal(kx, (m, 128)) * 4.0
    r = jax.random.normal(kr, (m, 128))
    want = quant_dequant(x) + r
    for use_pallas in (True, False):
        got = quant_dequant_residual(x, r, use_pallas=use_pallas,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_link_compress_straight_through(use_pallas):
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 128))
    lc = (make_link_compress(use_pallas=True, interpret=True) if use_pallas
          else link_compress)
    g = jax.grad(lambda t: (lc(t) * 2.0).sum())(x)
    np.testing.assert_allclose(np.asarray(g), 2.0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _assert_flash_matches_ref(shape, causal, window, seed, *, block_q=64,
                              block_k=64, grad=False, kv_shape=None,
                              atol=2e-5, gatol=2e-4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], kv_shape or shape)
    v = jax.random.normal(ks[2], kv_shape or shape)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=block_q, block_k=block_k, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=atol)
    if grad:
        def loss(fn):
            def f(q, k, v):
                o = fn(q, k, v)
                return (o * jnp.cos(o)).sum()   # non-trivial cotangent
            return f
        g = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, window=window, block_q=block_q,
            block_k=block_k, interpret=True)), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: flash_attention_ref(
            q, k, v, causal=causal, window=window)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=gatol)


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([(1, 1, 128, 64), (2, 2, 256, 32), (1, 4, 64, 128)]),
       st.booleans(),
       st.sampled_from([None, 32, 100]),
       st.integers(0, 10**6))
def test_flash_matches_ref(shape, causal, window, seed):
    _assert_flash_matches_ref(shape, causal, window, seed)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([(1, 1, 64, 32), (2, 2, 96, 32)]),
       st.booleans(),
       st.sampled_from([None, 16]),
       st.integers(0, 10**6))
def test_flash_grad_matches_ref(shape, causal, window, seed):
    _assert_flash_matches_ref(shape, causal, window, seed, block_q=32,
                              block_k=32, grad=True)


@pytest.mark.parametrize("shape,causal,window", [
    ((1, 1, 128, 64), True, None),
    ((2, 2, 256, 32), True, 32),
    ((1, 4, 64, 128), False, None),
    ((2, 2, 96, 32), False, 16),
])
def test_flash_matches_ref_param(shape, causal, window):
    _assert_flash_matches_ref(shape, causal, window, seed=0, grad=True)


@pytest.mark.parametrize("s,block,causal,window", [
    (100, 64, True, None),    # non-block-aligned: pad 100 -> 128
    (131, 64, True, 32),      # prime S > block, sliding window
    (257, 64, False, None),   # prime S, bidirectional (padded kv masked)
    (7, 64, True, None),      # degenerate: seq < block (single tile)
    (1, 64, True, None),      # single position
    # q and kv tiles of different sizes (block_q, block_k): the backward
    # kernels' grids differ, several tiles are live and several skipped
    pytest.param(160, (32, 64), True, None, id="160-32x64-True-None"),
    pytest.param(160, (64, 32), True, 40, id="160-64x32-True-40"),
])
def test_flash_non_aligned_shapes(s, block, causal, window):
    """Padding path: Q rows pad + slice, padded KV positions masked with
    kv_len — never the old shrink-toward-bq=1 fallback."""
    block_q, block_k = block if isinstance(block, tuple) else (block, block)
    _assert_flash_matches_ref((2, 2, s, 32), causal, window, seed=3,
                              block_q=block_q, block_k=block_k, grad=True)


def test_flash_bf16():
    shape = (1, 2, 128, 64)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, shape).astype(jnp.bfloat16) for kk in ks)
    out = flash_attention(q, k, v, interpret=True, block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.parametrize("h,kh", [(4, 2), (4, 1), (2, 2)])
def test_attention_wrapper_gqa(h, kh):
    """ops.attention in model layout with GQA repeat, fwd + grad."""
    B, S, D = 2, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, h, D))
    k = jax.random.normal(ks[1], (B, S, kh, D))
    v = jax.random.normal(ks[2], (B, S, kh, D))
    out_pallas = attention(q, k, v, use_pallas=True, interpret=True)
    out_ref = attention(q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out_pallas), np.asarray(out_ref),
                               atol=2e-5)
    gp = jax.grad(lambda q: attention(q, k, v, use_pallas=True,
                                      interpret=True).sum())(q)
    gr = jax.grad(lambda q: attention(q, k, v, use_pallas=False).sum())(q)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr), atol=2e-4)


def test_flash_vmaps():
    """The fleet engines vmap the split step over clients; the pallas calls
    must batch (pallas has a vmap rule), the backward kernels included."""
    shape = (3, 2, 2, 64, 32)   # (clients, B, H, S, D)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, shape) for kk in ks)
    f = lambda q, k, v: flash_attention(q, k, v, block_q=32, block_k=32,
                                        interpret=True)
    out = jax.vmap(f)(q, k, v)
    ref = jax.vmap(lambda q, k, v: flash_attention_ref(q, k, v))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def client_grads(fn):
        loss = lambda q, k, v: (fn(q, k, v) ** 2).sum()
        return jax.vmap(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    for a, b in zip(client_grads(f), client_grads(flash_attention_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


# ---------------------------------------------------------------------------
# dispatch resolution
# ---------------------------------------------------------------------------

def test_dispatch_resolution_cpu():
    assert resolve_attn_impl("xla") == "xla"
    assert resolve_attn_impl("pallas") == "pallas"
    assert resolve_attn_impl("ref") == "ref"
    # "auto" resolves to a concrete impl, never itself
    assert resolve_attn_impl("auto") in ("xla", "pallas")
    assert resolve_link_kernel("xla")[0] is False
    assert resolve_link_kernel("fused")[0] is True
    assert isinstance(resolve_link_kernel("auto")[0], bool)
    with pytest.raises(ValueError):
        resolve_attn_impl("cuda")
    with pytest.raises(ValueError):
        resolve_link_kernel("fp8")
    assert "fused" in LINK_KERNELS


def test_dispatch_follows_default_device():
    """``jax.default_device`` names the platform a trace compiles for: a
    described accelerator gets the compiled kernel, the host CPU the
    interpreter, whatever the default backend."""
    from repro.kernels.dispatch import accelerator_backend, pallas_interpret
    with jax.default_device("tpu"):
        assert accelerator_backend() and not pallas_interpret()
    with jax.default_device(jax.devices("cpu")[0]):
        assert not accelerator_backend() and pallas_interpret()


# ---------------------------------------------------------------------------
# rwkv6 scan
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.sampled_from([(1, 1, 32, 8), (2, 2, 64, 16), (1, 3, 128, 32)]),
       st.integers(0, 10**6))
def test_rwkv_scan_matches_ref(shape, seed):
    b, h, t, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    r = jax.random.normal(ks[0], shape) * 0.5
    k = jax.random.normal(ks[1], shape) * 0.5
    v = jax.random.normal(ks[2], shape) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], shape))
    u = jax.random.normal(ks[4], (h, hd)) * 0.3
    y = rwkv6_scan(r, k, v, w, u, block_t=16, interpret=True)
    yr = rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)


def test_rwkv_scan_decay_contracts_state():
    """w in (0,1) means old contributions decay: y at late t should not blow
    up (stability property of the Finch recurrence)."""
    b, h, t, hd = 1, 1, 256, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (b, h, t, hd)) * 0.5
    k = jax.random.normal(ks[1], (b, h, t, hd)) * 0.5
    v = jax.random.normal(ks[2], (b, h, t, hd)) * 0.5
    w = jnp.full((b, h, t, hd), 0.5)
    u = jnp.zeros((h, hd))
    y = rwkv6_scan_ref(r, k, v, w, u)
    assert float(jnp.abs(y[:, :, -32:]).max()) < 100.0
