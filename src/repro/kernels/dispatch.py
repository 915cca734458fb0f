"""Backend-aware kernel dispatch: which impl of each hot-path kernel runs.

The spec layer names *intents* (``ModelSpec.attn_impl``,
``EngineSpec.link_kernel``); this module resolves them to concrete kernel
paths at lowering time. Resolution is the ONLY place backend sniffing
happens, and the only place that decides ``interpret``: every kernel
wrapper below takes explicit ``use_pallas``/``interpret`` flags and
defaults to the compiled kernel (``interpret=False``), so interpret mode
runs only where this module, a caller or a test asks for it:

- ``"auto"``   -> Pallas on an accelerator backend (TPU/GPU), the XLA
  reference path on CPU (where Pallas only runs in interpret mode and is
  a correctness oracle, not a win).
- ``"pallas"`` / ``"fused"`` -> force the Pallas kernel; off-accelerator
  it runs in interpret mode (slow, bit-level oracle for parity tests and
  the jaxpr audit of kernel-enabled lowerings).
- ``"xla"``    -> the plain jnp/XLA path (today's default, bit-identical
  to the pre-kernel lowerings).
- ``"ref"``    (attention only) -> the O(S²) ``kernels/attn/ref.py``
  oracle via the same dispatch seam the Pallas path uses.
"""
from __future__ import annotations

from typing import Callable

import jax
from jax.sharding import AxisType, PartitionSpec as P

ATTN_IMPLS = ("auto", "xla", "pallas", "ref")
LINK_KERNELS = ("auto", "xla", "fused")


def accelerator_backend() -> bool:
    """True when the code being traced compiles Pallas natively: on the
    platform of ``jax.default_device`` where one is set (the FLOP count
    compiles for the host this way), else on the default backend."""
    dev = jax.config.jax_default_device
    if dev is None:
        platform = jax.default_backend()
    else:
        platform = dev if isinstance(dev, str) else dev.platform
    return platform in ("tpu", "gpu")


def pallas_interpret() -> bool:
    """Whether a Pallas kernel must run in interpret mode on this backend
    (true only off-accelerator, where it is a correctness oracle)."""
    return not accelerator_backend()


def per_device(fn: Callable, mesh=None) -> Callable:
    """Wrap a one-operand Pallas kernel call so each device runs it on its
    own block: XLA's partitioner cannot split a Mosaic kernel across a
    mesh. Inside a ``shard_map`` the wrapper maps over the context mesh's
    axes that are still automatic; outside one it maps over every axis of
    ``mesh``, with the operand replicated, so a caller that ``vmap``s over
    a sharded batch axis passes ``spmd_axis_name`` to keep that axis
    sharded. With neither a context mesh nor ``mesh``, ``fn`` runs as is.
    """
    def wrapped(x):
        ctx = jax.sharding.get_abstract_mesh()
        if not ctx.empty:
            auto = {a for a, t in zip(ctx.axis_names, ctx.axis_types)
                    if t == AxisType.Auto}
            if not auto:
                return fn(x)
            return jax.shard_map(fn, in_specs=P(), out_specs=P(),
                                 axis_names=auto, check_vma=False)(x)
        if mesh is None:
            return fn(x)
        return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(x)
    return wrapped


def resolve_attn_impl(impl: str) -> str:
    """'auto'|'xla'|'pallas'|'ref' -> concrete impl for this backend."""
    if impl not in ATTN_IMPLS:
        raise ValueError(f"attn_impl must be one of {ATTN_IMPLS}, got {impl!r}")
    if impl == "auto":
        return "pallas" if accelerator_backend() else "xla"
    return impl


def resolve_link_kernel(kind: str) -> tuple[bool, bool]:
    """'auto'|'xla'|'fused' -> ``(use_pallas, interpret)`` for FleetLink."""
    if kind not in LINK_KERNELS:
        raise ValueError(
            f"link_kernel must be one of {LINK_KERNELS}, got {kind!r}")
    if kind == "auto":
        kind = "fused" if accelerator_backend() else "xla"
    return kind == "fused", pallas_interpret()
