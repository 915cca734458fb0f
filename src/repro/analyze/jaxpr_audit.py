"""Pass 1: structural invariants of compiled engine rounds, at the jaxpr /
lowered-HLO level.

Every compiled ``Plan`` exposes its jitted round through
``plan._run._audit`` (attached by ``api.plan`` at lowering time:
the jitted callable, its ``donate_argnums``, and how the uniform
``run(state, batches, mask)`` surface maps onto its positional
signature). The auditor rebuilds the exact example arguments a round
receives — ``plan.init()`` state, one ``round_batches`` draw, a ones
mask when the engine is mask-aware — then checks, without executing
anything:

``jaxpr-donation``
    every donated input buffer is actually aliased to an output in the
    lowered StableHLO (``tf.aliasing_output``); a donated-but-copied
    buffer silently doubles peak memory for the engine state.
``jaxpr-callback``
    no host callback primitives (``pure_callback`` / ``io_callback`` /
    ``debug_callback`` — incl. ``jax.debug.print``) anywhere in the
    round body, recursively through scan/cond/pjit/shard_map.
``jaxpr-f64``
    no float64/complex128/int64 values under the repo's default x32
    policy — a silent promotion doubles bytes on the wire and on device.
``jaxpr-collective-axis``
    every named collective axis (``psum``/``pmean``/``all_gather``...)
    exists on the plan's bound mesh.
``jaxpr-trace-stability``
    tracing the round twice yields the identical jaxpr — a mismatch
    means some Python-side state (fresh consts, mutable default, id-keyed
    cache) leaks into the trace, the classic silent-retrace hazard the
    obs recompile gauge catches only at runtime.
``jaxpr-const-budget``
    no closure constant above ``const_budget_bytes`` (default 1 MiB)
    is baked into the jaxpr — hoisted energy/link/FLOP constants are
    O(clients) scalars; anything bigger (a captured dataset, a stacked
    batch) should be a traced operand.

``audit_plan`` runs all six over a plan's round; ``audit_mc`` audits the
Monte-Carlo vmap rollout (the other jitted hot path) the same way.
Hetero-bucketed plans have no single jittable round and are rejected,
mirroring ``run_monte_carlo``.
"""

from __future__ import annotations

import re
from typing import Any, Optional

import jax

from .findings import Finding, Report

_CALLBACK_PRIMS = ("callback", "debug_print")
_WIDE_DTYPES = ("float64", "complex128")
_AXIS_PARAM_KEYS = ("axes", "axis_name", "axis_names")


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def iter_eqns(jaxpr):
    """Every equation of ``jaxpr``, recursing into call/control-flow
    sub-jaxprs (scan, cond branches, pjit, shard_map, custom_*)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        yield from _jaxprs_in(v)


def _jaxprs_in(v):
    if hasattr(v, "eqns"):                       # Jaxpr
        yield v
    elif hasattr(v, "jaxpr"):                    # ClosedJaxpr
        yield v.jaxpr
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _jaxprs_in(item)


def _collective_axes(eqn) -> list[str]:
    names: list[str] = []
    for k in _AXIS_PARAM_KEYS:
        v = eqn.params.get(k)
        if v is None:
            continue
        for item in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(item, str):
                names.append(item)
    return names


# ---------------------------------------------------------------------------
# individual checks (each: ClosedJaxpr / lowered text -> findings)
# ---------------------------------------------------------------------------

def check_callbacks(closed, where: str) -> list[Finding]:
    out = []
    for eqn in iter_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if any(tag in prim for tag in _CALLBACK_PRIMS):
            out.append(Finding(
                "jaxpr-callback", where,
                f"host callback primitive {prim!r} inside the compiled "
                f"round body — every call crosses the device boundary "
                f"per step"))
    return out


def check_f64(closed, where: str) -> list[Finding]:
    out = []
    seen = set()

    def dtype_of(v):
        aval = getattr(v, "aval", None)
        return str(getattr(aval, "dtype", ""))

    for eqn in iter_eqns(closed.jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            dt = dtype_of(v)
            if dt in _WIDE_DTYPES and dt not in seen:
                seen.add(dt)
                out.append(Finding(
                    "jaxpr-f64", where,
                    f"{dt} value produced by {eqn.primitive.name!r} under "
                    f"the x32 policy — a silent promotion doubles device "
                    f"and wire bytes"))
    for const in closed.consts:
        dt = str(getattr(const, "dtype", ""))
        if dt in _WIDE_DTYPES and dt not in seen:
            seen.add(dt)
            out.append(Finding(
                "jaxpr-f64", where,
                f"{dt} closure constant baked into the round"))
    return out


def check_collective_axes(closed, mesh, where: str) -> list[Finding]:
    mesh_axes = (set() if mesh is None
                 else {str(a) for a in mesh.axis_names})
    out = []
    for eqn in iter_eqns(closed.jaxpr):
        for axis in _collective_axes(eqn):
            if axis not in mesh_axes:
                out.append(Finding(
                    "jaxpr-collective-axis", where,
                    f"{eqn.primitive.name!r} reduces over axis {axis!r} "
                    f"which is not on the bound mesh "
                    f"(axes: {sorted(mesh_axes) or 'none'})"))
    return out


def check_const_budget(closed, where: str,
                       const_budget_bytes: int = 1 << 20) -> list[Finding]:
    out = []
    for const in closed.consts:
        nbytes = getattr(const, "nbytes", 0)
        if nbytes > const_budget_bytes:
            shape = getattr(const, "shape", ())
            out.append(Finding(
                "jaxpr-const-budget", where,
                f"closure constant of {nbytes} bytes (shape {shape}) baked "
                f"into the jaxpr; budget is {const_budget_bytes} — pass it "
                f"as a traced operand or hoist it to O(clients) scalars"))
    return out


def _canon_jaxpr(closed) -> str:
    # custom_jvp/vjp eqn params embed thunk reprs whose 0x addresses differ
    # per trace; strip them so only structural differences count
    return re.sub(r" at 0x[0-9a-f]+", " at 0x", str(closed))


def check_trace_stability(fn, args, where: str) -> list[Finding]:
    # trace through a fresh wrapper object each time: jax caches traces by
    # function identity, so tracing `fn` twice directly would never re-run
    # the Python and instability could never surface
    first = _canon_jaxpr(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    second = _canon_jaxpr(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    if first != second:
        return [Finding(
            "jaxpr-trace-stability", where,
            "two traces of the round produced different jaxprs — "
            "Python-side state leaks into the trace (fresh consts or an "
            "id-keyed cache), which retraces/recompiles silently at run "
            "time")]
    return []


def check_donation(jit_fn, args, donate_argnums, where: str) -> list[Finding]:
    """Donated-leaf count vs ``tf.aliasing_output`` count in the lowered
    StableHLO. jax on this toolchain emits no catchable warning for a
    donated-but-unused buffer, but an un-aliased donation is visible
    structurally: the input parameter lacks the aliasing attribute."""
    donated_leaves = sum(
        len(jax.tree_util.tree_leaves(args[i])) for i in donate_argnums
        if i < len(args))
    if donated_leaves == 0:
        return []
    txt = jit_fn.lower(*args).as_text()
    # single-device lowerings resolve donation to a concrete output alias
    # (tf.aliasing_output); on a multi-device mesh the parameter is marked
    # jax.buffer_donor instead and XLA picks the alias at compile time —
    # either marker proves the donated leaf is not silently copied
    aliased = (txt.count("tf.aliasing_output")
               + txt.count("jax.buffer_donor"))
    if aliased < donated_leaves:
        return [Finding(
            "jaxpr-donation", where,
            f"only {aliased}/{donated_leaves} donated input buffers are "
            f"aliased to outputs in the lowered program; the rest are "
            f"silently copied (peak memory = 2x engine state for those "
            f"leaves)")]
    return []


# ---------------------------------------------------------------------------
# plan-level entry points
# ---------------------------------------------------------------------------

def example_round_args(plan) -> tuple[tuple, dict]:
    """``(args, audit)``: one round's positional arguments for the plan's
    jitted round (from a fresh ``plan.init()``) and its ``_audit`` handle."""
    audit = getattr(plan._run, "_audit", None)
    if audit is None:
        raise ValueError(
            "plan's run closure carries no _audit handle; hetero-bucketed "
            "plans dispatch per bucket on the host and have no single "
            "jittable round to audit (same restriction as run_monte_carlo)")
    from ..api.plan import round_args
    state = plan.init()
    cohort = plan._round_cohort(state)
    batches = plan.round_batches(state, cohort=cohort)
    return round_args(audit, state.engine_state, batches, None,
                      plan.spec.clients.num_clients), audit


def audit_plan(plan, *, const_budget_bytes: int = 1 << 20) -> Report:
    """All six structural checks over ``plan``'s compiled round."""
    args, audit = example_round_args(plan)
    jit_fn = audit["jit_fn"]
    where = f"round[{plan.spec.describe()}]"
    report = Report(checked=[where])
    closed = jax.make_jaxpr(jit_fn)(*args)
    report.findings += check_donation(jit_fn, args,
                                      audit["donate_argnums"], where)
    report.findings += check_callbacks(closed, where)
    report.findings += check_f64(closed, where)
    report.findings += check_collective_axes(closed, plan.mesh, where)
    report.findings += check_const_budget(
        closed, where, const_budget_bytes=const_budget_bytes)
    report.findings += check_trace_stability(jit_fn, args, where)
    return report


def audit_mc(plan, *, num_seeds: int = 2,
             const_budget_bytes: Optional[int] = None) -> Report:
    """Audit the Monte-Carlo vmap rollout exactly as it would execute.

    The rollout legitimately closes over the stacked per-round batch pool
    (it IS passed as an operand — ``build_vmap_rollout`` returns it in the
    example args), so the const budget defaults to the per-round batch
    bytes plus the 1 MiB scalar allowance.
    """
    from ..sim.monte_carlo import build_vmap_rollout
    mc_fn, example_args = build_vmap_rollout(plan, num_seeds)
    where = f"mc_vmap[{plan.spec.describe()}]"
    if const_budget_bytes is None:
        const_budget_bytes = 1 << 20
    report = Report(checked=[where])
    closed = jax.make_jaxpr(mc_fn)(*example_args)
    report.findings += check_callbacks(closed, where)
    report.findings += check_f64(closed, where)
    report.findings += check_collective_axes(closed, plan.mesh, where)
    report.findings += check_const_budget(
        closed, where, const_budget_bytes=const_budget_bytes)
    report.findings += check_trace_stability(mc_fn, example_args, where)
    return report


def audit_keys() -> Report:
    """Re-validate the central fold-slot registry: per-domain uniqueness of
    both names and values (``keys.register`` enforces this at import; the
    audit proves the loaded registry state, so a bypassing mutation or a
    stale duplicate still fails the gate)."""
    from .. import keys
    report = Report(checked=["repro.keys registry"])
    seen_vals: dict[tuple[str, int], str] = {}
    for slot in keys.registered_slots():
        k = (slot.domain, slot.value)
        if k in seen_vals:
            report.findings.append(Finding(
                "jaxpr-fold-slot", "repro/keys.py",
                f"fold value {slot.value} in domain {slot.domain!r} is "
                f"registered twice ({seen_vals[k]!r} and {slot.name!r})"))
        seen_vals[k] = slot.name
    return report
