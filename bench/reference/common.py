"""What the plain references of every family share: AdamW, the
parallel split-learning round, the FedAvg round, and per-leaf norms.

Round semantics, written from the configuration's job:

* ``sl`` (parallel split learning): in each local step every client runs
  its tier forward and backward against the same server tier; each client
  takes an AdamW step on its own gradient, and the server takes one AdamW
  step on the mean of the clients' server gradients. After the round's
  steps the client tiers are replaced by their mean (FedAvg); each client
  keeps its own AdamW moments.
* ``fl``: every client starts from the global model with fresh AdamW
  state, takes its local steps, and the global model becomes the mean.

The recorded loss of a round is the mean over its clients and steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.workload import seed32  # noqa: F401  (the seed as the runs use it)


def same(x):
    return x


def fp8_operand(x):
    """An operand of a product rounded to float8 (e4m3) under a per-tensor
    scale (absmax to 448), as fp8 training feeds its matmuls; the backward
    passes the gradient through unchanged."""
    s = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s
    return x + lax.stop_gradient(q - x)


def precision_of(name: str):
    """(matmul precision, compute dtype, operand rounding) of a reference
    run: ``highest`` is the reference; ``fp8`` the control (the products'
    operands in float8, everything else as the reference); ``bf16`` every
    activation and product in bfloat16."""
    if name == "highest":
        return lax.Precision.HIGHEST, jnp.float32, same
    if name == "fp8":
        return lax.Precision.HIGHEST, jnp.float32, fp8_operand
    if name == "bf16":
        return lax.Precision.DEFAULT, jnp.bfloat16, same
    raise ValueError(f"unknown reference precision {name!r}")


def cast_tree(tree, dtype):
    """Every floating leaf computed in ``dtype`` (float32 leaves stay
    float32 in a float32 run; bfloat16 leaves are widened to it)."""
    return jax.tree_util.tree_map(
        lambda v: v.astype(dtype) if jnp.issubdtype(v.dtype, jnp.floating)
        else v, tree)


class AdamW:
    """AdamW with bias correction and decoupled weight decay; moments in
    float32, the update rounded to the parameter's dtype before it is
    added."""

    def __init__(self, lr: float, cfg: dict):
        self.lr = float(lr)
        self.b1, self.b2 = float(cfg["b1"]), float(cfg["b2"])
        self.eps, self.wd = float(cfg["eps"]), float(cfg["weight_decay"])
        self.step = jax.jit(self._step)

    @staticmethod
    def init(params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
        return (jnp.zeros((), jnp.int32), jax.tree_util.tree_map(z, params),
                jax.tree_util.tree_map(z, params))

    def _step(self, params, state, grads):
        t, mu, nu = state
        t = t + 1
        tf = t.astype(jnp.float32)
        b1c, b2c = 1 - self.b1 ** tf, 1 - self.b2 ** tf

        def leaf(p, g, m, v):
            g = g.astype(jnp.float32)
            m = self.b1 * m + (1 - self.b1) * g
            v = self.b2 * v + (1 - self.b2) * g * g
            d = (m / b1c) / (jnp.sqrt(v / b2c) + self.eps) \
                + self.wd * p.astype(jnp.float32)
            return p + (-self.lr * d).astype(p.dtype), m, v

        out = jax.tree_util.tree_map(leaf, params, grads, mu, nu)
        pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), (t, pick(1), pick(2))


# ---------------------------------------------------------------------------
# per-leaf norms, keyed by the leaf's path
# ---------------------------------------------------------------------------

def _paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(p) for p, _ in flat], [v for _, v in flat]


@jax.jit
def _norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for v in leaves]


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(a, b)]


def leaf_norms(tree) -> dict:
    names, leaves = _paths(tree)
    return dict(zip(names, (float(v) for v in _norms(leaves))))


def change_norms(after, before) -> dict:
    names, a = _paths(after)
    _, b = _paths(before)
    return dict(zip(names, (float(v) for v in _diff_norms(a, b))))


def stacked_norms(per_client: list) -> dict:
    """Norms of leaves stacked over clients: sqrt of the sum of each
    client's squared norm."""
    total = None
    for tree in per_client:
        n = leaf_norms(tree)
        total = ({k: v * v for k, v in n.items()} if total is None
                 else {k: total[k] + v * v for k, v in n.items()})
    return {k: float(np.sqrt(v)) for k, v in total.items()}


# ---------------------------------------------------------------------------
# rounds
# ---------------------------------------------------------------------------

@jax.jit
def _add32(acc, g):
    return jax.tree_util.tree_map(
        lambda a, b: a + b.astype(jnp.float32), acc, g)


def _mean_as(acc, n, like):
    return jax.tree_util.tree_map(lambda a, p: (a / n).astype(p.dtype), acc,
                                  like)


def _zeros32(tree):
    return jax.tree_util.tree_map(
        lambda v: jnp.zeros(v.shape, jnp.float32), tree)


def sl_rounds(ref, pc0, ps0, x, y, rows_per_round) -> dict:
    """Parallel split learning over ``rows_per_round`` (one (clients,
    steps, batch) array of row indices per round). ``ref.split_loss(pc, ps,
    x, y)`` is the family's loss."""
    grad_fn = jax.jit(jax.value_and_grad(ref.split_loss, argnums=(0, 1)))
    n = rows_per_round[0].shape[0]
    pcs = [pc0] * n
    ocs = [ref.opt.init(pc0)] * n
    ps, os_ = ps0, ref.opt.init(ps0)
    losses, moment1, grad1 = [], None, None
    for r, rows in enumerate(rows_per_round):
        step_losses = []
        for s in range(rows.shape[1]):
            acc = _zeros32(ps)
            for c in range(n):
                xb, yb = ref.batch(x, y, rows[c, s])
                loss, (gc, gs) = grad_fn(pcs[c], ps, xb, yb)
                if grad1 is None:
                    grad1 = leaf_norms({"client": gc, "server": gs})
                pcs[c], ocs[c] = ref.opt.step(pcs[c], ocs[c], gc)
                acc = _add32(acc, gs)
                step_losses.append(float(loss))
            ps, os_ = ref.opt.step(ps, os_, _mean_as(acc, n, ps))
        acc = _zeros32(pcs[0])
        for pc in pcs:
            acc = _add32(acc, pc)
        pcs = [_mean_as(acc, n, pc0)] * n
        losses.append(float(np.mean(step_losses)))
        if r == 0:
            moment1 = {**_prefix("client", stacked_norms([o[1] for o in ocs])),
                       **_prefix("server", leaf_norms(os_[1]))}
    change = change_norms({"client": pcs[0], "server": ps},
                          {"client": pc0, "server": ps0})
    return {"losses": losses, "moment1": moment1, "change": change,
            "grad1": grad1}


def fl_rounds(ref, x, y, rows_per_round) -> dict:
    grad_fn = jax.jit(jax.value_and_grad(ref.full_loss))
    p0 = ref.params0
    g = p0
    losses, grad1 = [], None
    for rows in rows_per_round:
        n = rows.shape[0]
        acc, step_losses = _zeros32(g), []
        for c in range(n):
            p, o = g, ref.opt.init(g)
            for s in range(rows.shape[1]):
                xb, yb = ref.batch(x, y, rows[c, s])
                loss, grads = grad_fn(p, xb, yb)
                if grad1 is None:
                    grad1 = leaf_norms({"model": grads})
                p, o = ref.opt.step(p, o, grads)
                step_losses.append(float(loss))
            acc = _add32(acc, p)
        g = _mean_as(acc, n, p0)
        losses.append(float(np.mean(step_losses)))
    return {"losses": losses, "moment1": None,
            "change": change_norms({"model": g}, {"model": p0}),
            "grad1": grad1}


def _prefix(name: str, norms: dict) -> dict:
    return {f"['{name}']{k}": v for k, v in norms.items()}
