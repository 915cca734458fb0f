"""jit'd wrapper choosing Pallas (TPU) or the jnp fallback, in model layout.

Models use (B,S,H,D); the kernel uses (B,H,S,D). GQA KV heads are repeated
here. ``interpret`` defaults to the compiled kernel; off-accelerator the
caller passes ``kernels.dispatch.pallas_interpret()``. The
default model path uses the chunked-jnp implementation in
``repro.models.attention`` which XLA fuses natively.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ...models.attention import gqa_repeat
from .flash import flash_attention
from .ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "use_pallas", "interpret"))
def attention(q, k, v, *, causal=True, window=None, use_pallas=False,
              interpret=False):
    """q (B,S,H,D); k,v (B,S,Kh,D) -> (B,S,H,D)."""
    h = q.shape[2]
    k = gqa_repeat(k, h // k.shape[2]).transpose(0, 2, 1, 3)
    v = gqa_repeat(v, h // v.shape[2]).transpose(0, 2, 1, 3)
    qt = q.transpose(0, 2, 1, 3)
    if use_pallas:
        out = flash_attention(qt, k, v, causal=causal, window=window,
                              interpret=interpret)
    else:
        out = flash_attention_ref(qt, k, v, causal=causal, window=window)
    return out.transpose(0, 2, 1, 3)
