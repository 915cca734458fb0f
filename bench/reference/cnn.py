"""Plain reference of the CNN family's split-learning and FedAvg rounds.

Written from the configuration alone, in straightforward ``jax.numpy``:
MobileNetV2 (Sandler et al., arXiv:1801.04381) with GroupNorm, cut into a
client tier and a server tier, an int8 per-row quantise/dequantise of the
smashed activations with a straight-through backward, AdamW, and FedAvg.
Clients run one after another, so one client's step is in memory at a time.

``precision="highest"`` computes in float32 with every product at full
precision; ``precision="fp8"`` is the control: the same rounds with the
operands of every convolution and product rounded to float8
(``common.fp8_operand``); ``precision="bf16"`` runs the forward and
backward in bfloat16 (master weights and moments as configured).

Weights come from the seed by the architecture's initialisation: He-normal
convolutions (std sqrt(2 / fan_in)), LeCun-normal dense layer, GroupNorm
scale 1 and bias 0, one key per stage split from the seed's key.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common


# ---------------------------------------------------------------------------
# architecture
# ---------------------------------------------------------------------------

def stage_table(config: dict) -> list:
    """``[(kind, args, depth)]``: stem, one entry per inverted residual
    block ``(cin, cout, expand, stride)``, head."""
    stages = [("stem", (int(config["in_channels"]),
                        int(config["stem_channels"])), 1)]
    cin = int(config["stem_channels"])
    for t, c, n, s in config["inverted_residuals"]:
        for j in range(n):
            stages.append(("ir", (cin, c, t, s if j == 0 else 1), 1))
            cin = c
    stages.append(("head", (cin, int(config["head_channels"]),
                            int(config["num_classes"])), 2))
    return stages


def cut_index(config: dict) -> int:
    """Smallest prefix of stages whose share of the depth reaches the cut
    fraction, leaving at least one stage on each side."""
    stages = stage_table(config)
    total = sum(d for _, _, d in stages)
    frac = float(config["cut_fraction"])
    acc = 0
    for i, (_, _, d) in enumerate(stages):
        acc += d
        if acc / total >= frac - 1e-9:
            return min(max(i + 1, 1), len(stages) - 1)
    return len(stages) - 1


def _he(key, shape):
    fan_in = shape[2] * shape[0] * shape[1]
    return jax.random.normal(key, shape) * math.sqrt(2.0 / fan_in)


def _gn_params(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def _conv_gn(key, k, cin, cout):
    kc, _ = jax.random.split(key)
    return {"conv": {"w": _he(kc, (k, k, cin, cout))}, "gn": _gn_params(cout)}


def _init_stage(key, kind, args):
    if kind == "stem":
        cin, cout = args
        return _conv_gn(key, 3, cin, cout)
    if kind == "ir":
        cin, cout, t, _ = args
        hid = cin * t
        ks = jax.random.split(key, 3)
        p = {}
        if t != 1:
            p["pw1"] = _conv_gn(ks[0], 1, cin, hid)
        p["dw"] = {"conv": {"w": _he(ks[1], (3, 3, 1, hid))},
                   "gn": _gn_params(hid)}
        p["pw2"] = _conv_gn(ks[2], 1, hid, cout)
        return p
    cin, ch, ncls = args
    k1, k2 = jax.random.split(key)
    kw, _ = jax.random.split(k2)
    return {"pw": _conv_gn(k1, 1, cin, ch),
            "fc": {"w": jax.random.normal(kw, (ch, ncls)) / math.sqrt(ch),
                   "b": jnp.zeros((ncls,))}}


def init_params(config: dict, seed: int) -> list:
    table = stage_table(config)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(table))
    return [_init_stage(k, kind, args) for k, (kind, args, _) in
            zip(keys, table)]


def _conv(x, w, stride, groups, prec, q):
    return lax.conv_general_dilated(
        q(x), q(w.astype(x.dtype)), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=prec)


def _gn(p, x, eps):
    b, h, w, c = x.shape
    g = c // 8 if c % 8 == 0 else (c // 4 if c % 4 == 0 else 1)
    xg = x.reshape(b, h, w, g, c // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    y = ((xg - mu) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def _relu6(x):
    return jnp.clip(x, 0.0, 6.0)


def _apply_stage(kind, args, p, x, prec, eps, q=common.same):
    def conv(y, w, stride=1, groups=1):
        return _conv(y, w, stride, groups, prec, q)
    if kind == "stem":
        return _relu6(_gn(p["gn"], conv(x, p["conv"]["w"], 2), eps))
    if kind == "ir":
        cin, cout, t, stride = args
        y = x
        if t != 1:
            y = _relu6(_gn(p["pw1"]["gn"], conv(y, p["pw1"]["conv"]["w"]),
                           eps))
        hid = y.shape[-1]
        y = _relu6(_gn(p["dw"]["gn"],
                       conv(y, p["dw"]["conv"]["w"], stride, hid), eps))
        y = _gn(p["pw2"]["gn"], conv(y, p["pw2"]["conv"]["w"]), eps)
        return y + x if stride == 1 and cin == cout else y
    y = _relu6(_gn(p["pw"]["gn"], conv(x, p["pw"]["conv"]["w"]), eps))
    pooled = jnp.mean(y, axis=(1, 2))
    return (jnp.dot(q(pooled), q(p["fc"]["w"].astype(y.dtype)),
                    precision=prec) + p["fc"]["b"].astype(y.dtype))


def int8_link(x):
    """Per-row (last axis) absmax int8 quantise then dequantise; the
    backward passes the gradient through unchanged."""
    def qdq(v):
        v2 = v.reshape(-1, v.shape[-1]).astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(v2), axis=-1, keepdims=True)
                            / 127.0, 1e-8)
        q = jnp.clip(jnp.round(v2 / scale), -127, 127)
        return (q * scale).astype(v.dtype).reshape(v.shape)
    return x + lax.stop_gradient(qdq(x) - x)


# ---------------------------------------------------------------------------
# rounds
# ---------------------------------------------------------------------------

class Reference:
    """One cell's reference: parameters from the seed, and the job's rounds
    over the rows the batch stream names."""

    def __init__(self, cell, seed: int, *, precision: str = "highest"):
        self.cell = cell
        self.config = cell.config
        self.params0 = init_params(cell.config, common.seed32(seed))
        self.table = stage_table(cell.config)
        self.k = cut_index(cell.config)
        self.prec, self.dtype, self.q = common.precision_of(precision)
        self.eps = float(cell.config["groupnorm_eps"])
        self.link = cell.traffic["link"] == "int8"
        self.opt = common.AdamW(float(cell.traffic["lr"]),
                                cell.config["optimizer"])

    def _fwd(self, stages, params, x):
        for (kind, args, _), p in zip(stages, params):
            x = _apply_stage(kind, args, p, x, self.prec, self.eps, self.q)
        return x

    def _loss(self, logits, y):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))

    def split_loss(self, pc, ps, x, y):
        cast = lambda t: common.cast_tree(t, self.dtype)  # noqa: E731
        sm = self._fwd(self.table[:self.k], cast(pc), x.astype(self.dtype))
        if self.link:
            sm = int8_link(sm)
        return self._loss(self._fwd(self.table[self.k:], cast(ps), sm), y)

    def full_loss(self, p, x, y):
        p = common.cast_tree(p, self.dtype)
        return self._loss(self._fwd(self.table, p, x.astype(self.dtype)), y)

    @staticmethod
    def batch(x, y, rows):
        return jnp.asarray(x[rows]), jnp.asarray(y[rows])

    def run(self, x_train, y_train, rows_per_round: list) -> dict:
        """Losses, moments after round 1 and the change after the last
        round (``common.sl_rounds`` / ``common.fl_rounds``)."""
        if self.cell.traffic["job"] == "fl":
            return common.fl_rounds(self, x_train, y_train, rows_per_round)
        return common.sl_rounds(self, list(self.params0[:self.k]),
                                list(self.params0[self.k:]), x_train,
                                y_train, rows_per_round)
