"""Split learning core — the paper's central mechanism.

Key invariants:
  1. split forward == full forward at every paper cut fraction (CNNs)
  2. split backward (client+server grads via the one-program autodiff)
     == joint end-to-end grads — Algorithm 3's distributed backward is
     exactly gradient-correct
  3. FedAvg mean semantics
  4. transformer group-cut partition preserves the function
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fedavg import fedavg, fedavg_stack
from repro.core.split import (SplitStep, apply_stages, cut_index_for_fraction,
                              init_stages, partition_stages, split_stack,
                              merge_stack, stack_cut_index)
from repro.models.cnn import CNN_BUILDERS, cross_entropy_loss
from repro.models.transformer import (build_groups, default_cut_layer,
                                      model_forward, model_init)
from repro.configs import ARCHS

FRACTIONS = (0.15, 0.25, 0.40, 0.75)  # the paper's SL_{a,b} variants


@pytest.fixture(scope="module")
def cnn_setup():
    key = jax.random.PRNGKey(0)
    stages = CNN_BUILDERS["mobilenetv2"](12)
    params = init_stages(key, stages)
    x = jax.random.uniform(jax.random.fold_in(key, 1), (4, 32, 32, 3))
    y = jax.random.randint(jax.random.fold_in(key, 2), (4,), 0, 12)
    return stages, params, x, y


@pytest.mark.parametrize("frac", FRACTIONS)
def test_split_forward_equivalence(cnn_setup, frac):
    stages, params, x, _ = cnn_setup
    full = apply_stages(stages, params, x)
    cs, cp, ss, sp, k = partition_stages(stages, params, frac)
    smashed = apply_stages(cs, cp, x)
    out = apply_stages(ss, sp, smashed)
    assert 1 <= k < len(stages)
    np.testing.assert_allclose(out, full, atol=1e-5)


def test_cut_fraction_monotone(cnn_setup):
    stages, *_ = cnn_setup
    ks = [cut_index_for_fraction(stages, f) for f in FRACTIONS]
    assert ks == sorted(ks)
    assert ks[0] >= 1 and ks[-1] <= len(stages) - 1


@pytest.mark.slow
def test_split_backward_equals_joint(cnn_setup):
    """Invariant 2: Algorithm 3's distributed backward == joint autodiff."""
    stages, params, x, y = cnn_setup
    frac = 0.4
    cs, cp, ss, sp, k = partition_stages(stages, params, frac)

    def joint_loss(all_params):
        out = apply_stages(stages, all_params, x)
        return cross_entropy_loss(out, y)

    g_joint = jax.grad(joint_loss)(params)

    step = SplitStep(
        client_fwd=lambda pc, xx: apply_stages(cs, pc, xx),
        server_loss=lambda ps, sm, yy: (
            cross_entropy_loss(apply_stages(ss, ps, sm), yy), {}),
    )
    _, _, g_c, g_s = step.grads(cp, sp, {"inputs": x, "targets": y})
    for a, b in zip(jax.tree_util.tree_leaves(g_c),
                    jax.tree_util.tree_leaves(g_joint[:k])):
        np.testing.assert_allclose(a, b, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_s),
                    jax.tree_util.tree_leaves(g_joint[k:])):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_ushaped_keeps_labels_clientside(cnn_setup):
    stages, params, x, y = cnn_setup
    cs, cp, ss, sp, k = partition_stages(stages, params, 0.25)
    # server body = all but last stage; client holds the head too
    body, head = ss[:-1], ss[-1]
    bp, hp = sp[:-1], sp[-1]

    step = SplitStep(
        client_fwd=lambda pc, xx: apply_stages(cs, pc["front"], xx),
        server_body=lambda ps, sm: apply_stages(body, ps, sm),
        client_head_loss=lambda pc, feats, yy: (
            cross_entropy_loss(head.apply(pc["head"], feats), yy), {}),
        variant="ushaped",
    )
    loss, aux = step.loss_fn({"front": cp, "head": hp}, bp,
                             {"inputs": x, "targets": y})
    assert jnp.isfinite(loss)
    assert "smashed_elems" in aux


def test_fedavg_mean():
    trees = [{"w": jnp.full((3,), float(i))} for i in range(4)]
    avg = fedavg(trees)
    np.testing.assert_allclose(avg["w"], 1.5)
    weighted = fedavg(trees, weights=[1, 0, 0, 0])
    np.testing.assert_allclose(weighted["w"], 0.0)


def test_fedavg_stack_broadcast():
    stacked = {"w": jnp.arange(8.0).reshape(4, 2)}
    out = fedavg_stack(stacked)
    expect = jnp.tile(jnp.array([[3.0, 4.0]]), (4, 1))
    np.testing.assert_allclose(out["w"], expect)


def test_split_stack_roundtrip():
    stacked = {"w": jnp.arange(12.0).reshape(6, 2)}
    c, s = split_stack(stacked, 2)
    assert c["w"].shape == (2, 2) and s["w"].shape == (4, 2)
    m = merge_stack(c, s)
    np.testing.assert_allclose(m["w"], stacked["w"])


def test_stack_cut_index_moe_clamp():
    assert stack_cut_index(28, 0.5, max_client=1) == 1
    assert stack_cut_index(28, 0.15) == 5


@pytest.mark.parametrize("arch", [
    "smollm-135m",
    pytest.param("jamba-1.5-large-398b", marks=pytest.mark.slow),
    "rwkv6-7b", "whisper-tiny"])
def test_transformer_cut_preserves_function(arch):
    """Cutting a transformer into client/server groups must not change the
    function: evaluating the cut model == evaluating the same weights with
    the cut stacks merged back into one group."""
    cfg = ARCHS[arch].reduced()
    key = jax.random.PRNGKey(0)
    cut = default_cut_layer(cfg, 0.5)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    batch = {"tokens": tokens}
    if cfg.enc_dec:
        batch["frames"] = 0.02 * jax.random.normal(key, (2, cfg.enc_seq_len,
                                                         cfg.d_model))
    p_cut = model_init(cfg, key, cut_layer=cut)
    logits_cut, _ = model_forward(cfg, p_cut, batch, cut_layer=cut)

    # merge adjacent same-kind groups back into the uncut structure
    groups = build_groups(cfg, cut_layer=cut)
    merged, merged_groups = [], []
    for g, gp in zip(groups, p_cut["groups"]):
        if merged_groups and merged_groups[-1].kind == g.kind \
           and merged_groups[-1].moe == g.moe:
            merged[-1] = merge_stack(merged[-1], gp)
            merged_groups[-1] = build_groups(cfg)[len(merged) - 1]
        else:
            merged.append(gp)
            merged_groups.append(g)
    p_plain = dict(p_cut, groups=merged)
    logits_plain, _ = model_forward(cfg, p_plain, batch)
    np.testing.assert_allclose(np.asarray(logits_cut, np.float32),
                               np.asarray(logits_plain, np.float32),
                               atol=1e-4, rtol=1e-4)


def test_cut_tiers_tagged():
    cfg = ARCHS["yi-9b"]
    cut = default_cut_layer(cfg, 0.25)
    groups = build_groups(cfg, cut_layer=cut)
    tiers = [g.tier for g in groups]
    assert "client" in tiers and "server" in tiers
    assert sum(g.count for g in groups if g.tier == "client") == cut


def test_moe_cut_clamped_to_first_moe_layer():
    cfg = ARCHS["deepseek-moe-16b"]
    cut = default_cut_layer(cfg, 0.75)  # would be layer 21 without clamp
    assert cut == 1                      # clamped: experts are server-side


def test_compiled_round_carries_tier_and_kernel_scopes():
    """Every op of a compiled split round names its tier in its op_name
    metadata (``sl/client``, ``sl/server``; backward and update included),
    and the flash-attention backward names itself (``flash_bwd``): what a
    device profile is charged by."""
    from repro.fleet.engine import jit_round, make_fleet_sl_round
    from repro.kernels.attn.flash import flash_attention
    from repro.obs.profiler import hlo_op_scopes
    from repro.optim import adamw, init_stacked

    clients, steps, b, s, d = 2, 2, 2, 16, 8

    def client_fwd(pc, x):
        return jnp.tanh(x @ pc["w"])

    def server_loss(ps, h, y):
        q = (h @ ps["w"])[:, None]                        # (B, 1, S, d)
        o = flash_attention(q, q, q, block_q=8, block_k=8, interpret=True)
        return jnp.mean((o[:, 0] - y) ** 2), {}

    step = SplitStep(client_fwd=client_fwd, server_loss=server_loss)
    opt = adamw(1e-3)
    key = jax.random.PRNGKey(0)
    pc = {"w": jax.random.normal(key, (d, d))}
    ps = {"w": jax.random.normal(jax.random.fold_in(key, 1), (d, d))}
    pcs = jax.tree_util.tree_map(lambda x: jnp.stack([x] * clients), pc)
    x = jax.random.normal(jax.random.fold_in(key, 2), (clients, steps, b, s, d))
    batches = {"inputs": x, "targets": x}
    round_fn = jit_round(make_fleet_sl_round(step, opt, opt,
                                             local_rounds=steps), "sl_round")
    text = round_fn.lower(pcs, ps, init_stacked(opt, pc, clients),
                          opt.init(ps), batches).compile().as_text()
    for scope in ("sl/client", "sl/server", "flash_bwd"):
        assert f'op_name="jit(sl_round)/' in text and scope in text, scope
    module, ops = hlo_op_scopes(text)
    assert module == "jit_sl_round"
    # the client's backward keeps its tier's scope; the attention backward
    # nests in the tier that runs it
    assert any("sl/client" in v and "transpose" in v for v in ops.values())
    assert any("sl/server" in v and "flash_bwd" in v for v in ops.values())
    # the backward's two kernels run under its scope (compiled, each is a
    # custom call; here, interpreted, each is a loop of ops)
    for kernel in ("flash_bwd_dkv", "flash_bwd_dq"):
        names = [v for v in ops.values() if f"/{kernel}/" in v]
        assert names and all(re.search(rf"flash_bwd\b.*/{kernel}/", v)
                             for v in names), kernel
