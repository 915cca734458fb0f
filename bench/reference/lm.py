"""Plain reference of the split LM family's split-learning rounds.

Written from the configuration alone, in straightforward ``jax.numpy``: a
pre-norm decoder (RMSNorm, rotary embeddings with the rotate-half
convention, grouped-query causal attention as softmax(QK^T / sqrt(d)) V,
SwiGLU feed-forward) split at a layer, with an untied head and next-token
cross entropy. Attention is the plain S x S product, one block at a time
(each block rematerialised in the backward so that it fits). Rounds,
AdamW and FedAvg are ``common``'s.

``precision="highest"``: float32 activations, every product at full
precision, weights held in their configured dtypes. ``precision="fp8"``
(the control): the operands of every product rounded to float8
(``common.fp8_operand``). ``precision="bf16"``: activations, embedding and
head computed in bfloat16.

Weights come from the seed by the program family's initialisation: one key
each for the embedding, the blocks and the head; per block six keys (norm,
attention, norm, -, -, feed-forward), LeCun-normal projections
(std 1 / sqrt(fan_in)) stored in the block dtype, norm scales 1, embedding
and head N(0, 0.02^2) in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def _lecun(key, shape, dtype):
    kw, _ = jax.random.split(key)
    return {"w": (jax.random.normal(kw, shape) / math.sqrt(shape[0]))
            .astype(dtype)}


def _init_block(key, c):
    d, hd = int(c["hidden_size"]), int(c["head_dim"])
    hq = int(c["num_attention_heads"]) * hd
    hkv = int(c["num_key_value_heads"]) * hd
    ff = int(c["intermediate_size"])
    dt = _dtype(c["block_param_dtype"])
    ks = jax.random.split(key, 6)
    kq, kk, kv, ko = jax.random.split(ks[1], 4)
    k1, k2, k3 = jax.random.split(ks[5], 3)
    return {"ln1": {"scale": jnp.ones((d,), dt)},
            "attn": {"wq": _lecun(kq, (d, hq), dt), "wk": _lecun(kk, (d, hkv), dt),
                     "wv": _lecun(kv, (d, hkv), dt), "wo": _lecun(ko, (hq, d), dt)},
            "ln2": {"scale": jnp.ones((d,), dt)},
            "ffn": {"gate": _lecun(k1, (d, ff), dt), "up": _lecun(k2, (d, ff), dt),
                    "down": _lecun(k3, (ff, d), dt)}}


def cut_index(config: dict) -> int:
    n = int(config["num_hidden_layers"])
    return max(1, min(n - 1, int(math.ceil(float(config["cut_fraction"]) * n))))


def init_params(config: dict, seed: int):
    """(client tier, server tier) as ``{"embed", "blocks"}`` and
    ``{"blocks", "head"}``, blocks stacked on a leading layer axis."""
    k_embed, k_blocks, k_head = jax.random.split(jax.random.PRNGKey(seed), 3)
    n = int(config["num_hidden_layers"])
    layers = [_init_block(k, config) for k in jax.random.split(k_blocks, n)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    d, v = int(config["hidden_size"]), int(config["vocab_size"])
    std = float(config["init_std"]["embed"])
    embed = std * jax.random.normal(k_embed, (v, d), jnp.float32)
    head = float(config["init_std"]["head"]) * jax.random.normal(
        k_head, (d, v), jnp.float32)
    k = cut_index(config)
    blocks_c = jax.tree_util.tree_map(lambda x: x[:k], stacked)
    blocks_s = jax.tree_util.tree_map(lambda x: x[k:], stacked)
    return {"embed": embed, "blocks": blocks_c}, {"blocks": blocks_s,
                                                   "head": head}


class Reference:
    def __init__(self, cell, seed: int, *, precision: str = "highest"):
        self.cell = cell
        c = self.config = cell.config
        self.pc0, self.ps0 = init_params(c, common.seed32(seed))
        self.prec, self.dtype, self.q = common.precision_of(precision)
        self.heads = int(c["num_attention_heads"])
        self.kv_heads = int(c["num_key_value_heads"])
        self.hd = int(c["head_dim"])
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.opt = common.AdamW(float(cell.traffic["lr"]), c["optimizer"])

    # ---- one block -------------------------------------------------------

    def _mm(self, x, w):
        return jnp.matmul(self.q(x), self.q(w.astype(x.dtype)),
                          precision=self.prec)

    def _rms(self, p, x):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x / jnp.sqrt(var + self.eps) * p["scale"].astype(x.dtype)

    def _rope(self, x):
        s, d = x.shape[1], x.shape[-1]
        freqs = 1.0 / (self.theta ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                      / d))
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
        cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
        sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _attention(self, q, k, v):
        rep = self.heads // self.kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        s = q.shape[1]
        scores = jnp.einsum("bqhd,bkhd->bhqk", self.q(q), self.q(k),
                            precision=self.prec)
        scores = scores.astype(jnp.float32) / math.sqrt(self.hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", self.q(probs.astype(q.dtype)),
                          self.q(v), precision=self.prec)

    def _block(self, p, x):
        b, s, _ = x.shape
        h = self._rms(p["ln1"], x)
        a = p["attn"]
        q = self._rope(self._mm(h, a["wq"]["w"]).reshape(b, s, self.heads,
                                                         self.hd))
        k = self._rope(self._mm(h, a["wk"]["w"]).reshape(b, s, self.kv_heads,
                                                         self.hd))
        v = self._mm(h, a["wv"]["w"]).reshape(b, s, self.kv_heads, self.hd)
        o = self._attention(q, k, v).reshape(b, s, self.heads * self.hd)
        x = x + self._mm(o, a["wo"]["w"])
        h = self._rms(p["ln2"], x)
        f = p["ffn"]
        gate = self._mm(h, f["gate"]["w"])
        y = self._mm(jax.nn.silu(gate) * self._mm(h, f["up"]["w"]),
                     f["down"]["w"])
        return x + y

    def _blocks(self, stack, x):
        def body(h, blk):
            return self._block(blk, h), None
        h, _ = lax.scan(jax.checkpoint(body), x, stack)
        return h

    # ---- the split model ---------------------------------------------------

    def split_loss(self, pc, ps, tokens, targets):
        h = pc["embed"].astype(self.dtype)[tokens]
        sm = self._blocks(pc["blocks"], h)
        logits = self._mm(self._blocks(ps["blocks"], sm), ps["head"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                             axis=-1))

    @staticmethod
    def batch(x, y, rows):
        return jnp.asarray(x[rows]), jnp.asarray(y[rows])

    def run(self, x_train, y_train, rows_per_round: list) -> dict:
        return common.sl_rounds(self, self.pc0, self.ps0, x_train, y_train,
                                rows_per_round)
