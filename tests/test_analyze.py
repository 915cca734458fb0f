"""``repro.analyze`` — the static-analysis subsystem itself.

Contract under test:

  * every jaxpr-audit check fires on a synthetic bad program (undonated
    donate, callback-in-scan, f64 leak, off-mesh collective axis,
    trace-unstable closure, over-budget closure const) and stays silent
    on a clean one,
  * the ``repro.keys`` registry rejects duplicate slot names/values and
    the registered layout matches the historical magic numbers
    bit-for-bit (the replay tests pin the streams themselves),
  * every AST rule fires on a minimal bad source snippet with the exact
    rule id + line, stays silent on the idiomatic counterpart, and the
    ``repro: ignore[<rule>] -- reason`` escape hatch suppresses exactly
    when a reason is present,
  * the compiled engine-variant matrix audits clean — zero findings over
    fl/sl x scan/vmap/shard_map, dropout, population cohorts, and the
    Monte-Carlo vmap rollout (full sweep is slow-marked; a cross-section
    runs in the fast suite),
  * the repo's own source tree lints clean (the CI lint gate, as a test).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import keys
from repro.analyze import (audit_keys, audit_mc, audit_plan,
                           check_callbacks, check_collective_axes,
                           check_const_budget, check_donation, check_f64,
                           check_trace_stability, compiled_variants,
                           lint_paths, lint_source)
from repro.api import compile_experiment

# ---------------------------------------------------------------------------
# keys registry
# ---------------------------------------------------------------------------

def test_registered_slots_match_historical_magic_numbers():
    # load-bearing values: replay tests pin the resulting streams, so the
    # registry must encode exactly the pre-registry literals
    assert (keys.ENV_MASK.value, keys.ENV_RATES.value,
            keys.ENV_COHORT.value) == (1, 2, 3)
    assert (keys.DATA_TRAIN.value, keys.DATA_TEST.value) == (0, 1)
    assert keys.INIT_FFN_ALT.value == 1
    assert keys.INIT_MOE_SHARED.value == 7


def test_fold_equals_raw_fold_in():
    k = jax.random.PRNGKey(0)
    np.testing.assert_array_equal(
        keys.fold(k, keys.ENV_COHORT), jax.random.fold_in(k, 3))
    np.testing.assert_array_equal(
        keys.round_env_key(k, 5), jax.random.fold_in(k, 5))


def test_register_rejects_name_and_value_collisions():
    with pytest.raises(ValueError, match="already registered with value"):
        keys.register("env", "mask", 9)       # name collision, new value
    with pytest.raises(ValueError, match="already taken"):
        keys.register("env", "mask2", 1)      # value collision, new name
    # exact re-registration is idempotent (module reloads)
    assert keys.register("env", "mask", 1) is keys.ENV_MASK
    # same value in a DIFFERENT domain is fine (data/train=0 vs env uses)
    assert keys.DATA_TRAIN.value == 0


def test_audit_keys_clean():
    assert audit_keys().ok


# ---------------------------------------------------------------------------
# jaxpr audit: one synthetic bad program per check
# ---------------------------------------------------------------------------

def test_donation_detects_unconsumed_donated_buffer():
    # 'a' is donated but never aliased into an output -> silently copied
    bad = jax.jit(lambda a, b: b * 2.0, donate_argnums=(0,))
    x = jnp.ones((8, 8))
    findings = check_donation(bad, (x, x), (0,), "bad")
    assert [f.rule for f in findings] == ["jaxpr-donation"]
    assert "0/1" in findings[0].message

    good = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    assert check_donation(good, (x, x), (0,), "good") == []


def test_callback_detected_through_scan():
    def bad(x):
        def body(c, _):
            jax.debug.print("c={c}", c=c)
            return c + 1.0, c
        out, _ = jax.lax.scan(body, x, jnp.arange(3.0))
        return out

    closed = jax.make_jaxpr(bad)(1.0)
    findings = check_callbacks(closed, "bad")
    assert findings and all(f.rule == "jaxpr-callback" for f in findings)

    closed = jax.make_jaxpr(
        lambda x: jax.lax.scan(lambda c, _: (c + 1.0, c), x,
                               jnp.arange(3.0))[0])(1.0)
    assert check_callbacks(closed, "good") == []


def test_f64_promotion_detected():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x * np.float64(2.0))(np.float64(1.0))
    findings = check_f64(closed, "bad")
    assert findings and findings[0].rule == "jaxpr-f64"

    closed = jax.make_jaxpr(lambda x: x * 2.0)(jnp.float32(1.0))
    assert check_f64(closed, "good") == []


def test_collective_axis_checked_against_mesh():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import single_device_fleet_mesh

    mesh = single_device_fleet_mesh()

    def fn(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                             in_specs=P("data"), out_specs=P())(x)

    closed = jax.make_jaxpr(fn)(jnp.ones(4))
    # the psum really names the 'data' axis
    assert check_collective_axes(closed, mesh, "good") == []
    # ... which does not exist on an unbound (None) mesh
    findings = check_collective_axes(closed, None, "bad")
    assert findings and findings[0].rule == "jaxpr-collective-axis"
    assert "'data'" in findings[0].message


def test_trace_instability_detected():
    calls = [0]

    def bad(x):
        calls[0] += 1
        return x + float(calls[0])   # fresh literal every trace

    findings = check_trace_stability(bad, (jnp.ones(2),), "bad")
    assert [f.rule for f in findings] == ["jaxpr-trace-stability"]

    assert check_trace_stability(lambda x: x + 1.0, (jnp.ones(2),),
                                 "good") == []


def test_const_budget_flags_baked_in_arrays():
    big = jnp.zeros((1024, 512), jnp.float32)          # 2 MiB closure const
    closed = jax.make_jaxpr(lambda x: x + big.sum())(jnp.float32(0.0))
    findings = check_const_budget(closed, "bad")
    assert findings and findings[0].rule == "jaxpr-const-budget"

    assert check_const_budget(closed, "ok",
                              const_budget_bytes=4 << 20) == []


# ---------------------------------------------------------------------------
# AST lint: one bad snippet per rule (exact rule + line)
# ---------------------------------------------------------------------------

def _rules_at(findings):
    return [(f.rule, int(f.where.rsplit(":", 1)[1])) for f in findings]


def test_ast_traced_branch():
    bad = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x:\n"
        "        return 1\n"
        "    return 0\n")
    assert _rules_at(lint_source(bad)) == [("traced-branch", 4)]
    # `is None` tests are static and exempt; un-jitted branching is fine
    ok = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x is None:\n"
        "        return 0\n"
        "    return x\n"
        "def g(y):\n"
        "    if y:\n"
        "        return 1\n")
    assert lint_source(ok) == []


def test_ast_traced_branch_through_wrapper_call():
    bad = (
        "import jax\n"
        "def body(c, x):\n"
        "    while c:\n"
        "        c = c - 1\n"
        "    return c, x\n"
        "out = jax.lax.scan(body, 0, None)\n")
    assert _rules_at(lint_source(bad)) == [("traced-branch", 3)]


def test_ast_raw_timer_and_suppression():
    bad = "import time\nt0 = time.perf_counter()\n"
    assert _rules_at(lint_source(bad)) == [("raw-timer", 2)]
    with_reason = ("import time\n"
                   "t0 = time.time()  "
                   "# repro: ignore[raw-timer] -- progress stamp only\n")
    assert lint_source(with_reason) == []
    no_reason = ("import time\n"
                 "t0 = time.time()  # repro: ignore[raw-timer]\n")
    # a reason-less ignore is flagged AND does not suppress
    assert sorted(f.rule for f in lint_source(no_reason)) == [
        "bad-suppression", "raw-timer"]
    unknown = ("import time\n"
               "t0 = time.time()  # repro: ignore[not-a-rule] -- because\n")
    found = lint_source(unknown)
    # the bogus ignore is flagged AND does not suppress the raw timer
    assert sorted(f.rule for f in found) == ["bad-suppression", "raw-timer"]


def test_ast_key_reuse():
    bad = (
        "import jax\n"
        "def f():\n"
        "    k = jax.random.PRNGKey(0)\n"
        "    a = jax.random.normal(k, (2,))\n"
        "    b = jax.random.uniform(k, (2,))\n"
        "    return a, b\n")
    assert _rules_at(lint_source(bad)) == [("key-reuse", 5)]
    ok = (
        "import jax\n"
        "def f():\n"
        "    k = jax.random.PRNGKey(0)\n"
        "    k1, k2 = jax.random.split(k)\n"
        "    return jax.random.normal(k1, (2,)), "
        "jax.random.uniform(k2, (2,))\n")
    assert lint_source(ok) == []


def test_ast_magic_fold():
    bad = "import jax\nk2 = jax.random.fold_in(k, 3)\n"
    assert _rules_at(lint_source(bad)) == [("magic-fold", 2)]
    # non-literal folds (round/step indices) are the blessed pattern
    ok = ("import jax\nfrom repro import keys\n"
          "k2 = jax.random.fold_in(k, r)\n"
          "k3 = keys.fold(k, keys.ENV_MASK)\n")
    assert lint_source(ok) == []


def test_ast_unhoisted_const():
    bad = (
        "import jax.numpy as jnp\n"
        "def f(n):\n"
        "    out = []\n"
        "    for i in range(n):\n"
        "        out.append(jnp.ones((4, 4)) * i)\n"
        "    return out\n")
    assert _rules_at(lint_source(bad)) == [("unhoisted-const", 5)]
    # a def inside the loop is traced, not executed per iteration
    ok = (
        "import jax.numpy as jnp\n"
        "def f(n):\n"
        "    fns = []\n"
        "    for i in range(n):\n"
        "        def g(x):\n"
        "            return x + jnp.ones((4, 4))\n"
        "        fns.append(g)\n"
        "    return fns\n")
    assert lint_source(ok) == []


def test_ast_bare_except():
    bad = "try:\n    x = 1\nexcept:\n    pass\n"
    assert _rules_at(lint_source(bad)) == [("bare-except", 3)]
    assert lint_source("try:\n    x = 1\nexcept ValueError:\n    pass\n") == []


def test_ast_label_link():
    bad = (
        "from repro.core.split import SplitStep\n"
        "step = SplitStep(\n"
        "    client_fwd=lambda pc, xx, yy: fwd(pc, xx, yy),\n"
        "    server_loss=loss_fn)\n")
    found = lint_source(bad)
    assert [f.rule for f in found] == ["label-link"]
    assert "'yy'" in found[0].message
    ok = (
        "from repro.core.split import SplitStep\n"
        "step = SplitStep(\n"
        "    client_fwd=lambda pc, xx: fwd(pc, xx),\n"
        "    server_loss=lambda ps, sm, yy: loss(ps, sm, yy))\n")
    assert lint_source(ok) == []


# ---------------------------------------------------------------------------
# the repo audits clean (the CI gate, as tests)
# ---------------------------------------------------------------------------

def test_repo_source_tree_lints_clean():
    import repro
    from pathlib import Path
    src = Path(next(iter(repro.__path__))).resolve()
    report = lint_paths([src], repo_root=src.parent.parent)
    assert report.ok, "\n".join(str(f) for f in report.findings)
    assert len(report.checked) > 50


def test_variant_cross_section_audits_clean():
    # one representative per engine family; the full matrix is slow-marked
    for name, plan, _ in compiled_variants(mc=False,
                                           match="sl/shard_map"):
        report = audit_plan(plan)
        assert report.ok, (name, [str(f) for f in report.findings])


def test_audit_rejects_hetero_plans():
    import dataclasses
    from repro.api import ClientSpec, CutPolicy
    from repro.core.energy import HardwareProfile, JETSON_AGX_ORIN
    from repro.analyze.variants import _tiny_spec
    mcu = HardwareProfile("mcu-class", fp32_tflops=0.02, mem_bw_gbs=2.0,
                          tensor_tflops=0.04, cpu_passmark=400.0,
                          power_w=2.0)
    spec = dataclasses.replace(
        _tiny_spec("sl", "vmap"),
        clients=ClientSpec(num_clients=4,
                           edge_profiles=(JETSON_AGX_ORIN, mcu)),
        cut_policy=CutPolicy(mode="adaptive"))
    plan = compile_experiment(spec)
    if len(set(plan.cut_of_client)) == 1:
        pytest.skip("adaptive cuts collapsed to one bucket on this host")
    with pytest.raises(ValueError, match="no single"):
        audit_plan(plan)


@pytest.mark.slow
def test_full_variant_matrix_audits_clean():
    for name, plan, with_mc in compiled_variants(mc=True):
        report = audit_plan(plan)
        if with_mc:
            report.extend(audit_mc(plan))
        assert report.ok, (name, [str(f) for f in report.findings])


def test_mc_rollout_audits_clean_and_matches_execution():
    from repro.sim import run_monte_carlo
    from repro.analyze.variants import mc_specs
    name, spec = next(iter(mc_specs()))
    plan = compile_experiment(spec)
    report = audit_mc(plan)
    assert report.ok, [str(f) for f in report.findings]
    # the audited builder is the executed builder: the sweep still runs
    res = run_monte_carlo(plan, 2, rounds=2)
    assert res.stacks["loss"].shape == (2, 2)
