"""jit'd wrapper for the RWKV-6 scan: Pallas on TPU, lax.scan oracle on CPU."""
from __future__ import annotations

from functools import partial

import jax

from .ref import rwkv6_scan_ref
from .scan import rwkv6_scan


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def wkv(r, k, v, w, u, *, use_pallas: bool = False, interpret: bool = False):
    if use_pallas:
        return rwkv6_scan(r, k, v, w, u, interpret=interpret)
    return rwkv6_scan_ref(r, k, v, w, u)
