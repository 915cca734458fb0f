"""FLOP counting shared by the trainers, the cut profiler and the benches.

Two sources, tried in order:

1. **XLA** — ``compiled.cost_analysis()`` of the program compiled for the
   host CPU, whatever the default backend: the count prices the paper's
   hardware (Jetson clients, an A5000 server), so it is the same number on
   every backend, and it never has to fit an accelerator's memory. Pallas
   kernels in it run in interpret mode, as they do on the CPU. On some
   backends the count is missing or 0; a program that fails to compile
   raises.
2. **Analytic jaxpr walk** — ``jaxpr_flops`` traverses the traced jaxpr and
   counts matmul/conv FLOPs exactly (2*M*N*K style) and one FLOP per output
   element for the remaining arithmetic primitives, recursing through
   pjit/scan/while/cond/custom-vjp sub-jaxprs. This is the roofline fallback:
   approximate on elementwise tails but exact on the dominant contractions.

``flops_of`` is the public entry point and **never returns 0 silently**: if
XLA yields nothing usable it falls back to the analytic count, and raises if
that is zero for a non-trivial program.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import SingleDeviceSharding

# Primitives that move/alias data without arithmetic — zero FLOPs.
_FREE_PRIMS = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad", "rev",
    "gather", "scatter", "convert_element_type", "bitcast_convert_type",
    "copy", "device_put", "stop_gradient", "iota", "eq", "ne", "lt", "le",
    "gt", "ge", "select_n", "argmax", "argmin", "reduce_and", "reduce_or",
    "and", "or", "not", "xor", "sign", "is_finite", "clamp", "squeeze",
})


def _size(aval) -> float:
    return float(math.prod(getattr(aval, "shape", ()) or (1,)))


def _dot_general_flops(eqn) -> float:
    lhs = eqn.invars[0].aval
    (lhs_contract, _), _ = eqn.params["dimension_numbers"]
    k = math.prod(lhs.shape[i] for i in lhs_contract) if lhs_contract else 1
    out = _size(eqn.outvars[0].aval)
    return 2.0 * out * k


def _conv_flops(eqn) -> float:
    rhs_shape = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    kernel_spatial = math.prod(rhs_shape[i] for i in dn.rhs_spec[2:])
    cin_per_group = rhs_shape[dn.rhs_spec[1]]
    out = _size(eqn.outvars[0].aval)
    return 2.0 * out * kernel_spatial * cin_per_group


def _subjaxprs(params: dict):
    """Yield (closed_or_open_jaxpr, repeat_count) pairs inside eqn params."""
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr", "body_jaxpr"):
        if key in params and params[key] is not None:
            yield params[key], 1.0
    for branch in params.get("branches", ()) or ():
        yield branch, 1.0


def _walk(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "scan":
            inner = eqn.params["jaxpr"]
            total += float(eqn.params.get("length", 1)) * _walk(inner.jaxpr)
        elif any(True for _ in _subjaxprs(eqn.params)):
            for sub, reps in _subjaxprs(eqn.params):
                total += reps * _walk(getattr(sub, "jaxpr", sub))
        elif name in _FREE_PRIMS:
            continue
        elif name.startswith("reduce_"):
            total += sum(_size(v.aval) for v in eqn.invars)
        else:
            # elementwise default: one FLOP per output element
            total += sum(_size(v.aval) for v in eqn.outvars)
    return total


def jaxpr_flops(fn, *args) -> float:
    """Analytic FLOP count of ``fn(*args)`` from its traced jaxpr."""
    closed = jax.make_jaxpr(fn)(*args)
    return _walk(closed.jaxpr)


def compiled_cost(compiled) -> dict:
    """``compiled.cost_analysis()`` as a (possibly empty) dict."""
    return dict(compiled.cost_analysis() or {})


def xla_flops(fn, *args) -> Optional[float]:
    """XLA:CPU-counted FLOPs of one invocation, or None when no count is
    reported. Only the shapes of ``args`` are used. A failed compile
    raises."""
    host = jax.local_devices(backend="cpu")[0]
    on_host = SingleDeviceSharding(host)

    def struct(a):
        aval = jax.typeof(a)
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=on_host,
                                    weak_type=aval.weak_type)

    with jax.default_device(host):    # kernels/dispatch: interpret Pallas
        compiled = jax.jit(fn).lower(*jax.tree.map(struct, args)).compile()
    flops = float(compiled_cost(compiled).get("flops", -1.0))
    return flops if flops > 0.0 else None


def flops_of(fn, *args) -> float:
    """FLOPs of ``fn(*args)``: XLA-counted, analytic fallback, never a
    silent 0 (raises if both counters report nothing for a real program)."""
    counted = xla_flops(fn, *args)
    if counted is not None:
        return counted
    fallback = jaxpr_flops(fn, *args)
    if fallback <= 0.0:
        raise RuntimeError(
            "FLOP counting failed: XLA cost_analysis unavailable and the "
            "analytic jaxpr walk found no arithmetic in the program")
    return fallback
