"""Unified transformer family: dense / MoE / SSM / hybrid / VLM / enc-dec.

A model is a ``frontend -> [layer groups] -> final norm -> tied head``
pipeline. Layers are *grouped* into homogeneous stacks (leading layer axis,
scan-over-layers) so HLO size stays O(1) in depth — essential for
compiling 512-device dry-runs of 64-72 layer models on this container.

Group kinds
-----------
  attn   : [norm→GQA attention→res] + [norm→(dense|MoE|MoE+dense)→res]
  rwkv   : [ln→time-mix→res] + [ln→channel-mix→res]   (RWKV-6)
  jamba  : super-block of ``attn_period`` sublayers (mamba×(P-1) + attn×1),
           FFN alternating dense/MoE per the config period
  enc    : bidirectional attention + FFN (whisper encoder)
  xdec   : self-attn + cross-attn + FFN (whisper decoder)

The eEnergy-Split cut is a *group boundary*: ``build_groups(cfg, cut)``
splits the stack there, and the launcher gives client groups DP-only
sharding and server groups TP sharding (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from ..parallel.sharding import shard_act
from . import modules as nn
from .attention import (attn_init, chunked_causal_attention, decode_attention,
                        qkv_project, update_kv_cache)
from .moe import moe_apply, moe_init
from .ssm import (mamba_apply, mamba_empty_state, mamba_init, mamba_step,
                  rwkv6_apply, rwkv6_empty_state, rwkv6_ffn_apply,
                  rwkv6_ffn_init, rwkv6_init, rwkv6_step)

Params = Any


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# group plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupSpec:
    kind: str                 # attn | rwkv | jamba | enc | xdec
    count: int                # layers (or super-blocks for jamba)
    layer_offset: int         # first absolute layer index
    moe: bool = False         # FFN is MoE (attn groups)
    tier: str = "server"      # client | server  (split-learning tier)


def build_groups(cfg: ArchConfig, *, cut_layer: Optional[int] = None) -> list[GroupSpec]:
    """Homogeneous layer groups; optionally split at ``cut_layer``."""
    groups: list[GroupSpec] = []
    if cfg.enc_dec:
        groups.append(GroupSpec("enc", cfg.n_enc_layers, 0))
        groups.append(GroupSpec("xdec", cfg.n_layers, cfg.n_enc_layers))
    elif cfg.ssm_kind == "rwkv6" and cfg.attn_period == 0:
        groups.append(GroupSpec("rwkv", cfg.n_layers, 0))
    elif cfg.ssm_kind == "mamba" and cfg.attn_period > 0:
        assert cfg.n_layers % cfg.attn_period == 0
        groups.append(GroupSpec("jamba", cfg.n_layers // cfg.attn_period, 0))
    else:
        # attention stack; break where the moe-ness changes (deepseek layer 0)
        flags = [cfg.is_moe_layer(i) for i in range(cfg.n_layers)]
        start = 0
        for i in range(1, cfg.n_layers + 1):
            if i == cfg.n_layers or flags[i] != flags[start]:
                groups.append(GroupSpec("attn", i - start, start, moe=flags[start]))
                start = i

    if cut_layer is not None:
        groups = _split_at(groups, cut_layer, cfg)
    return groups


def _split_at(groups: list[GroupSpec], cut_layer: int, cfg: ArchConfig) -> list[GroupSpec]:
    """Split group list at an absolute layer index; tag tiers.

    For enc-dec, the cut lives in the encoder (client = early acoustic
    layers). For jamba the cut snaps to a super-block boundary.
    """
    out: list[GroupSpec] = []
    for g in groups:
        span = g.count * (cfg.attn_period if g.kind == "jamba" else 1)
        lo, hi = g.layer_offset, g.layer_offset + span
        if cut_layer <= lo:
            out.append(dataclasses.replace(g, tier="server"))
        elif cut_layer >= hi:
            out.append(dataclasses.replace(g, tier="client"))
        else:
            per = cfg.attn_period if g.kind == "jamba" else 1
            k = max(1, round((cut_layer - lo) / per))
            k = min(k, g.count - 1) if g.count > 1 else g.count
            if k > 0:
                out.append(dataclasses.replace(g, count=k, tier="client"))
            if g.count - k > 0:
                out.append(dataclasses.replace(
                    g, count=g.count - k, layer_offset=lo + k * per, tier="server"))
    return out


def default_cut_layer(cfg: ArchConfig, client_fraction: float) -> int:
    """Paper SL_{a,b}: client holds a% of layers. MoE archs clamp the cut at
    the first MoE layer when it would otherwise include experts client-side
    (experts cannot live on the edge tier — DESIGN.md §4)."""
    n = cfg.n_enc_layers if cfg.enc_dec else cfg.n_layers
    k = max(1, min(n - 1, int(math.ceil(client_fraction * n))))
    if cfg.n_experts and not cfg.enc_dec:
        first_moe = cfg.first_moe_layer
        if cfg.is_moe_layer(0):
            first_moe = 0
        # find first actually-MoE layer
        fm = next((i for i in range(cfg.n_layers) if cfg.is_moe_layer(i)), n)
        if fm == 0:
            return k           # all layers MoE (arctic): documented exception
        k = min(k, fm)
    return k


# ---------------------------------------------------------------------------
# per-kind layer init
# ---------------------------------------------------------------------------

def _norm_init(key, cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return nn.layernorm_init(key, d, dtype=cfg.param_dtype)
    return nn.rmsnorm_init(key, d, dtype=cfg.param_dtype)


def _norm_apply(cfg, p, x):
    if cfg.norm == "layernorm":
        return nn.layernorm_apply(p, x)
    return nn.rmsnorm_apply(p, x)


def _ffn_init(key, cfg):
    if cfg.ffn == "gelu":
        return nn.gelu_ffn_init(key, cfg.d_model, cfg.d_ff, dtype=cfg.param_dtype)
    return nn.swiglu_ffn_init(key, cfg.d_model, cfg.d_ff, dtype=cfg.param_dtype)


def _ffn_apply(cfg, p, x):
    if cfg.ffn == "gelu":
        return nn.gelu_ffn_apply(p, x)
    return nn.swiglu_ffn_apply(p, x)


def _moe_init(key, cfg):
    return moe_init(key, cfg.d_model, cfg.n_experts,
                    cfg.moe_d_ff or cfg.d_ff, cfg.top_k,
                    n_shared=cfg.n_shared_experts,
                    shared_d_ff=cfg.moe_d_ff or cfg.d_ff,
                    dtype=cfg.param_dtype)


def _attn_layer_init(key, cfg, *, moe: bool, cross: bool = False):
    ks = jax.random.split(key, 6)
    p = {"ln1": _norm_init(ks[0], cfg),
         "attn": attn_init(ks[1], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                           cfg.hd, qkv_bias=cfg.qkv_bias, dtype=cfg.param_dtype),
         "ln2": _norm_init(ks[2], cfg)}
    if cross:
        p["lnx"] = _norm_init(ks[3], cfg)
        p["xattn"] = attn_init(ks[4], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.hd, dtype=cfg.param_dtype)
    if moe:
        p["moe"] = _moe_init(ks[5], cfg)
        if cfg.dense_residual:
            from ..keys import INIT_FFN_ALT, fold
            p["ffn"] = _ffn_init(fold(ks[5], INIT_FFN_ALT), cfg)
    else:
        p["ffn"] = _ffn_init(ks[5], cfg)
    return p


def _rwkv_layer_init(key, cfg):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"ln1": _norm_init(k1, cfg),
            "mix": rwkv6_init(k2, cfg.d_model, head_size=cfg.hd, dtype=cfg.param_dtype),
            "ln2": _norm_init(k3, cfg),
            "ffn": rwkv6_ffn_init(k4, cfg.d_model, cfg.d_ff, dtype=cfg.param_dtype)}


def _jamba_super_init(key, cfg):
    p = {}
    for i in range(cfg.attn_period):
        ki = jax.random.fold_in(key, i)
        is_attn = (i == cfg.attn_period - 1)
        is_moe = cfg.n_experts > 0 and (i % cfg.moe_layer_period == cfg.moe_layer_period - 1)
        ks = jax.random.split(ki, 4)
        sub = {"ln1": _norm_init(ks[0], cfg), "ln2": _norm_init(ks[1], cfg)}
        if is_attn:
            sub["attn"] = attn_init(ks[2], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.hd, dtype=cfg.param_dtype)
        else:
            sub["mamba"] = mamba_init(ks[2], cfg.d_model, expand=cfg.ssm_expand,
                                      state_dim=cfg.ssm_state_dim,
                                      conv_width=cfg.ssm_conv_width,
                                      dtype=cfg.param_dtype)
        sub["moe" if is_moe else "ffn"] = (_moe_init(ks[3], cfg) if is_moe
                                           else _ffn_init(ks[3], cfg))
        p[f"sub{i}"] = sub
    return p


def group_init(key, cfg: ArchConfig, g: GroupSpec) -> Params:
    if g.kind == "attn":
        fn = partial(_attn_layer_init, cfg=cfg, moe=g.moe)
    elif g.kind == "enc":
        fn = partial(_attn_layer_init, cfg=cfg, moe=False)
    elif g.kind == "xdec":
        fn = partial(_attn_layer_init, cfg=cfg, moe=False, cross=True)
    elif g.kind == "rwkv":
        fn = partial(_rwkv_layer_init, cfg=cfg)
    elif g.kind == "jamba":
        fn = partial(_jamba_super_init, cfg=cfg)
    else:
        raise ValueError(g.kind)
    return nn.stack_layers(key, g.count, fn)


# ---------------------------------------------------------------------------
# forward (train / prefill): scan over each group's stack
# ---------------------------------------------------------------------------

def _attn_block(cfg, p, x, positions, *, window, causal=True, kv=None,
                attn_impl="xla"):
    """One attention sublayer (pre-norm residual). kv: external (cross).

    ``attn_impl`` is the kernel-dispatch seam: ``"xla"`` keeps the chunked
    jnp path (bit-identical to the pre-kernel lowerings); ``"pallas"`` /
    ``"ref"`` route through ``kernels.attn.ops.attention`` — the Pallas
    flash kernel (interpret mode off-accelerator) or the O(S²) oracle.
    """
    h = _norm_apply(cfg, p["ln1"], x)
    attn_p = p["attn"]
    b, s, _ = h.shape
    q = nn.linear_apply(attn_p["wq"], h).reshape(b, s, cfg.n_heads, cfg.hd)
    k = nn.linear_apply(attn_p["wk"], h).reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = nn.linear_apply(attn_p["wv"], h).reshape(b, s, cfg.n_kv_heads, cfg.hd)
    if causal:  # rope only for (causal) self-attention stacks
        q = nn.apply_rope(q, positions, theta=cfg.rope_theta)
        k = nn.apply_rope(k, positions, theta=cfg.rope_theta)
    if attn_impl == "xla":
        out = chunked_causal_attention(q, k, v, window=window, causal=causal)
    else:
        from ..kernels.attn.ops import attention
        from ..kernels.dispatch import pallas_interpret
        out = attention(q, k, v, causal=causal, window=window,
                        use_pallas=(attn_impl == "pallas"),
                        interpret=pallas_interpret())
    out = nn.linear_apply(attn_p["wo"], out.reshape(b, s, cfg.n_heads * cfg.hd))
    return x + out


def _ffn_block(cfg, p, x, aux, moe: bool, moe_groups: int = 1):
    h = _norm_apply(cfg, p["ln2"], x)
    if moe:
        y, a = moe_apply(p["moe"], h, top_k=cfg.top_k,
                         capacity_factor=cfg.capacity_factor,
                         n_groups=moe_groups)
        aux = aux + a
        if cfg.dense_residual:
            y = y + _ffn_apply(cfg, p["ffn"], h)
    else:
        y = _ffn_apply(cfg, p["ffn"], h)
    return x + y, aux


def group_apply(cfg: ArchConfig, g: GroupSpec, stacked: Params, x, aux, *,
                positions, window, enc_out=None, unroll: int = 1,
                remat: bool = False, act_spec=("dp", None, None),
                moe_groups: int = 1, attn_impl: str = "xla"):
    """Full-sequence pass (train/prefill). Returns (x, aux). With
    ``remat`` each scanned layer body is rematerialized in the backward
    pass (only the residual-stream carry is saved)."""
    def _maybe_remat(f):
        return jax.checkpoint(f) if remat else f

    if g.kind in ("attn", "enc", "xdec"):
        causal = g.kind != "enc"

        def body(carry, layer):
            h, a = carry
            h = _attn_block(cfg, layer, h, positions, window=window,
                            causal=causal, attn_impl=attn_impl)
            if g.kind == "xdec":
                h = h + _x_cross(cfg, layer, h, enc_out)
            h, a = _ffn_block(cfg, layer, h, a, moe=g.moe,
                              moe_groups=moe_groups)
            h = shard_act(h, act_spec)
            return (h, a), None

        (x, aux), _ = lax.scan(_maybe_remat(body), (x, aux), stacked, unroll=unroll)
        return x, aux

    if g.kind == "rwkv":
        def body(carry, layer):
            h, a = carry
            mix, _ = rwkv6_apply(layer["mix"], _norm_apply(cfg, layer["ln1"], h),
                                 head_size=cfg.hd)
            h = h + mix
            hf = _norm_apply(cfg, layer["ln2"], h)
            h = h + rwkv6_ffn_apply(layer["ffn"], hf,
                                    jnp.zeros_like(hf[:, 0]))
            h = shard_act(h, act_spec)
            return (h, a), None

        (x, aux), _ = lax.scan(_maybe_remat(body), (x, aux), stacked, unroll=unroll)
        return x, aux

    if g.kind == "jamba":
        def body(carry, layer):
            h, a = carry
            for i in range(cfg.attn_period):
                sub = layer[f"sub{i}"]
                if "attn" in sub:
                    h = _attn_block(cfg, sub, h, positions, window=window,
                                    attn_impl=attn_impl)
                else:
                    y, _ = mamba_apply(sub["mamba"], _norm_apply(cfg, sub["ln1"], h),
                                       expand=cfg.ssm_expand,
                                       state_dim=cfg.ssm_state_dim,
                                       conv_width=cfg.ssm_conv_width)
                    h = h + y
                h, a = _ffn_block(cfg, sub, h, a, moe="moe" in sub,
                                  moe_groups=moe_groups)
                h = shard_act(h, act_spec)
            return (h, a), None

        (x, aux), _ = lax.scan(_maybe_remat(body), (x, aux), stacked, unroll=unroll)
        return x, aux

    raise ValueError(g.kind)


def _x_cross(cfg, layer, h, enc_out):
    """Cross-attention sublayer (whisper decoder)."""
    q_in = _norm_apply(cfg, layer["lnx"], h)
    b, s, _ = q_in.shape
    sk = enc_out.shape[1]
    xp = layer["xattn"]
    q = nn.linear_apply(xp["wq"], q_in).reshape(b, s, cfg.n_heads, cfg.hd)
    k = nn.linear_apply(xp["wk"], enc_out).reshape(b, sk, cfg.n_kv_heads, cfg.hd)
    v = nn.linear_apply(xp["wv"], enc_out).reshape(b, sk, cfg.n_kv_heads, cfg.hd)
    out = chunked_causal_attention(q, k, v, window=None, causal=False)
    return nn.linear_apply(xp["wo"], out.reshape(b, s, cfg.n_heads * cfg.hd))


# ---------------------------------------------------------------------------
# model assembly
# ---------------------------------------------------------------------------

def vocab_padded(cfg: ArchConfig) -> int:
    """Pad vocab to a multiple of 16 so the embedding shards over 'model'
    (whisper's 51865 -> 51872). Padded ids never appear as labels."""
    return round_up(cfg.vocab, 16)


def model_init(cfg: ArchConfig, key, *, cut_layer: Optional[int] = None) -> Params:
    groups = build_groups(cfg, cut_layer=cut_layer)
    ks = jax.random.split(key, len(groups) + 3)
    params: dict = {
        "embed": nn.embed_init(ks[0], vocab_padded(cfg), cfg.d_model,
                               dtype=cfg.param_dtype),
        "final_norm": _norm_init(ks[1], cfg),
        "groups": [group_init(ks[2 + i], cfg, g) for i, g in enumerate(groups)],
    }
    if cfg.enc_dec:
        params["enc_norm"] = _norm_init(ks[-1], cfg)
    if not cfg.tie_embeddings:
        params["head"] = nn.linear_init(ks[-1], cfg.d_model, vocab_padded(cfg),
                                        dtype=cfg.param_dtype)
    return params


def _embed_inputs(cfg: ArchConfig, params, batch):
    """Token (+frontend) embedding. Returns (x, positions, enc_x)."""
    tokens = batch["tokens"]
    x = nn.embed_apply(params["embed"], tokens)
    if cfg.frontend == "patch_embed":
        # VLM stub: precomputed patch embeddings prepended to the text
        patches = batch["patch_embeds"].astype(x.dtype)          # (B, Np, D)
        x = jnp.concatenate([patches, x], axis=1)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    enc_x = None
    if cfg.enc_dec:
        enc_x = batch["frames"].astype(x.dtype)                  # (B, Senc, D)
        # sinusoidal positions for the encoder
        senc = enc_x.shape[1]
        d = cfg.d_model
        pos = jnp.arange(senc, dtype=jnp.float32)[:, None]
        dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
        ang = pos / jnp.power(10000.0, 2 * dim / d)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        enc_x = enc_x + pe[None].astype(enc_x.dtype)
    return x, positions, enc_x


def model_forward(cfg: ArchConfig, params, batch, *,
                  window: Optional[int] = "cfg", unroll: int = 1,
                  cut_layer: Optional[int] = None, remat: bool = False,
                  seq_parallel_tiers: tuple = (), moe_groups: int = 1):
    """Full-sequence forward. Returns (logits, aux)."""
    if window == "cfg":
        window = cfg.swa_window
    groups = build_groups(cfg, cut_layer=cut_layer)
    x, positions, enc_x = _embed_inputs(cfg, params, batch)
    x = shard_act(x, ("dp", None, None))
    aux = jnp.zeros((), jnp.float32)
    enc_out = None
    gi = 0
    for g, gp in zip(groups, params["groups"]):
        if g.kind == "enc":
            epos = jnp.broadcast_to(
                jnp.arange(enc_x.shape[1], dtype=jnp.int32),
                (enc_x.shape[0], enc_x.shape[1]))
            enc_x, aux = group_apply(cfg, g, gp, enc_x, aux, positions=epos,
                                     window=None, unroll=unroll, remat=remat)
            gi += 1
            # last encoder group -> encoder output
            if gi == len(groups) - sum(1 for gg in groups if gg.kind != "enc") \
               or all(gg.kind != "enc" for gg in groups[gi:]):
                enc_out = _norm_apply(cfg, params["enc_norm"], enc_x)
        else:
            act = (("dp", "tp", None) if g.tier in seq_parallel_tiers
                   else ("dp", None, None))
            x, aux = group_apply(cfg, g, gp, x, aux, positions=positions,
                                 window=window, enc_out=enc_out, unroll=unroll,
                                 remat=remat, act_spec=act,
                                 moe_groups=moe_groups)
            gi += 1
    x = _norm_apply(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = nn.embed_logits(params["embed"], x)
    else:
        logits = nn.linear_apply(params["head"], x)
    return logits, aux


def lm_loss(cfg: ArchConfig, params, batch, *, window="cfg", unroll: int = 1,
            cut_layer=None, remat: bool = False, seq_parallel_tiers=(),
            moe_groups: int = 1):
    """Next-token CE (+ router aux). Loss only on text positions for VLM."""
    logits, aux = model_forward(cfg, params, batch, window=window,
                                unroll=unroll, cut_layer=cut_layer, remat=remat,
                                seq_parallel_tiers=seq_parallel_tiers,
                                moe_groups=moe_groups)
    labels = batch["labels"]
    # align: for VLM the first Np logits correspond to patches -> skip them
    if cfg.frontend == "patch_embed":
        np_tok = batch["patch_embeds"].shape[1]
        logits = logits[:, np_tok:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp[:, :-1], labels[:, 1:, None], axis=-1)[..., 0]
    ce = -ll.mean()
    loss = ce + cfg.router_aux_coef * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# decode: state init + one-token step
# ---------------------------------------------------------------------------

def decode_state_init(cfg: ArchConfig, batch_size: int, max_len: int, *,
                      window: Optional[int] = "cfg",
                      cut_layer: Optional[int] = None,
                      dtype=None, kv_dtype: str = "param") -> list:
    """Per-group decode state (KV caches / SSM states). Shapes only depend on
    (cfg, batch, max_len) so ShapeDtypeStructs can stand in for the dry-run."""
    if window == "cfg":
        window = cfg.swa_window
    dtype = dtype or cfg.param_dtype
    cache_len = min(window, max_len) if window else max_len
    groups = build_groups(cfg, cut_layer=cut_layer)
    state = []
    for g in groups:
        if g.kind in ("attn",):
            kdt = jnp.int8 if kv_dtype == "int8" else dtype
            st = {
                "k": jnp.zeros((g.count, batch_size, cache_len, cfg.n_kv_heads, cfg.hd), kdt),
                "v": jnp.zeros((g.count, batch_size, cache_len, cfg.n_kv_heads, cfg.hd), kdt),
            }
            if kv_dtype == "int8":
                st["k_scale"] = jnp.zeros((g.count, batch_size, cache_len, cfg.n_kv_heads), jnp.float32)
                st["v_scale"] = jnp.zeros((g.count, batch_size, cache_len, cfg.n_kv_heads), jnp.float32)
            state.append(st)
        elif g.kind == "enc":
            state.append({})  # encoder has no decode state
        elif g.kind == "xdec":
            state.append({
                "k": jnp.zeros((g.count, batch_size, max_len, cfg.n_kv_heads, cfg.hd), dtype),
                "v": jnp.zeros((g.count, batch_size, max_len, cfg.n_kv_heads, cfg.hd), dtype),
                "ck": jnp.zeros((g.count, batch_size, cfg.enc_seq_len, cfg.n_kv_heads, cfg.hd), dtype),
                "cv": jnp.zeros((g.count, batch_size, cfg.enc_seq_len, cfg.n_kv_heads, cfg.hd), dtype),
            })
        elif g.kind == "rwkv":
            state.append({
                "S": jnp.zeros((g.count, batch_size, cfg.d_model // cfg.hd, cfg.hd, cfg.hd), jnp.float32),
                "x_prev": jnp.zeros((g.count, batch_size, cfg.d_model), dtype),
                "ffn_x_prev": jnp.zeros((g.count, batch_size, cfg.d_model), dtype),
            })
        elif g.kind == "jamba":
            st = {}
            for i in range(cfg.attn_period):
                if i == cfg.attn_period - 1:
                    kdt = jnp.int8 if kv_dtype == "int8" else dtype
                    st[f"k{i}"] = jnp.zeros((g.count, batch_size, cache_len, cfg.n_kv_heads, cfg.hd), kdt)
                    st[f"v{i}"] = jnp.zeros((g.count, batch_size, cache_len, cfg.n_kv_heads, cfg.hd), kdt)
                    if kv_dtype == "int8":
                        st[f"k{i}_scale"] = jnp.zeros((g.count, batch_size, cache_len, cfg.n_kv_heads), jnp.float32)
                        st[f"v{i}_scale"] = jnp.zeros((g.count, batch_size, cache_len, cfg.n_kv_heads), jnp.float32)
                else:
                    d_inner = cfg.ssm_expand * cfg.d_model
                    st[f"h{i}"] = jnp.zeros((g.count, batch_size, d_inner, cfg.ssm_state_dim), jnp.float32)
                    st[f"c{i}"] = jnp.zeros((g.count, batch_size, cfg.ssm_conv_width - 1, d_inner), dtype)
            state.append(st)
    return state


def _quant_kv(x):
    """(B,1,Kh,hd) -> int8 codes + per-(B,1,Kh) scale."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale


def _decode_attn_sub(cfg, p_attn, h, pos, cache_k, cache_v, *, window,
                     scales=None):
    """One-token attention against a (possibly ring) cache.
    h (B,1,D); caches (B,C,Kh,hd) in bf16/f32 or int8 (+`scales` dict).
    Returns (out, k_cache, v_cache, new_scales)."""
    b = h.shape[0]
    q = nn.linear_apply(p_attn["wq"], h).reshape(b, 1, cfg.n_heads, cfg.hd)
    k = nn.linear_apply(p_attn["wk"], h).reshape(b, 1, cfg.n_kv_heads, cfg.hd)
    v = nn.linear_apply(p_attn["wv"], h).reshape(b, 1, cfg.n_kv_heads, cfg.hd)
    posb = jnp.full((b, 1), pos, jnp.int32)
    q = nn.apply_rope(q, posb, theta=cfg.rope_theta)
    k = nn.apply_rope(k, posb, theta=cfg.rope_theta)
    cache_size = cache_k.shape[1]
    slot = (pos % cache_size) if window else pos
    new_scales = None
    if scales is not None:                      # int8 KV cache path
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        cache_k, cache_v = update_kv_cache(cache_k, cache_v, kq, vq, slot)
        k_sc = lax.dynamic_update_slice_in_dim(scales["k"], ks, slot, axis=1)
        v_sc = lax.dynamic_update_slice_in_dim(scales["v"], vs, slot, axis=1)
        new_scales = {"k": k_sc, "v": v_sc}
        # dequantize straight to the compute dtype: the convert+mul fuses
        # into the attention dot's operand load (no f32 cache-sized temp)
        k_eff = cache_k.astype(q.dtype) * k_sc[..., None].astype(q.dtype)
        v_eff = cache_v.astype(q.dtype) * v_sc[..., None].astype(q.dtype)
    else:
        cache_k, cache_v = update_kv_cache(cache_k, cache_v, k, v, slot)
        k_eff, v_eff = cache_k, cache_v
    cache_len = jnp.minimum(pos + 1, cache_size)
    out = decode_attention(q, k_eff, v_eff, cache_len)
    out = nn.linear_apply(p_attn["wo"], out.reshape(b, 1, cfg.n_heads * cfg.hd))
    return out, cache_k, cache_v, new_scales


def model_decode_step(cfg: ArchConfig, params, state: list, token, pos, *,
                      window: Optional[int] = "cfg",
                      cut_layer: Optional[int] = None):
    """One decode step. token (B,1) int32; pos scalar int32 (tokens so far).
    Returns (logits (B,1,V), new_state)."""
    if window == "cfg":
        window = cfg.swa_window
    groups = build_groups(cfg, cut_layer=cut_layer)
    x = nn.embed_apply(params["embed"], token)
    x = shard_act(x, (None, None, "tp"))
    new_state = []
    for g, gp, gs in zip(groups, params["groups"], state):
        if g.kind == "enc":
            new_state.append(gs)
            continue
        x, ns = _group_decode(cfg, g, gp, gs, x, pos, window=window)
        new_state.append(ns)
    x = _norm_apply(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = nn.embed_logits(params["embed"], x)
    else:
        logits = nn.linear_apply(params["head"], x)
    return logits, new_state


def _group_decode(cfg, g: GroupSpec, stacked, gstate, x, pos, *, window):
    from .modules import scan_layers_carry

    if g.kind in ("attn", "xdec"):
        def body(carry, inp):
            layer, st = inp
            h = carry
            a_in = _norm_apply(cfg, layer["ln1"], h)
            scales = ({"k": st["k_scale"], "v": st["v_scale"]}
                      if "k_scale" in st else None)
            out, ck, cv, nsc = _decode_attn_sub(cfg, layer["attn"], a_in, pos,
                                                st["k"], st["v"],
                                                window=window, scales=scales)
            h = h + out
            nst = {"k": ck, "v": cv}
            if nsc is not None:
                nst["k_scale"], nst["v_scale"] = nsc["k"], nsc["v"]
            if g.kind == "xdec":
                xq = _norm_apply(cfg, layer["lnx"], h)
                b = xq.shape[0]
                q = nn.linear_apply(layer["xattn"]["wq"], xq).reshape(b, 1, cfg.n_heads, cfg.hd)
                xo = decode_attention(q, st["ck"], st["cv"], st["ck"].shape[1])
                h = h + nn.linear_apply(layer["xattn"]["wo"],
                                        xo.reshape(b, 1, cfg.n_heads * cfg.hd))
                nst["ck"], nst["cv"] = st["ck"], st["cv"]
            hf = _norm_apply(cfg, layer["ln2"], h)
            if g.moe:
                y, _ = moe_apply(layer["moe"], hf, top_k=cfg.top_k,
                                 capacity_factor=cfg.capacity_factor)
                if cfg.dense_residual:
                    y = y + _ffn_apply(cfg, layer["ffn"], hf)
            else:
                y = _ffn_apply(cfg, layer["ffn"], hf)
            h = h + y
            return h, nst

        def wrapped(layer, carry, st):
            return body(carry, (layer, st))

        x, ns = scan_layers_carry(wrapped, stacked, x, gstate)
        return x, ns

    if g.kind == "rwkv":
        def body(layer, carry, st):
            h = carry
            mix, mst = rwkv6_step(layer["mix"], _norm_apply(cfg, layer["ln1"], h),
                                  {"S": st["S"], "x_prev": st["x_prev"]},
                                  head_size=cfg.hd)
            h = h + mix
            hf = _norm_apply(cfg, layer["ln2"], h)
            h = h + rwkv6_ffn_apply(layer["ffn"], hf, st["ffn_x_prev"])
            nst = {"S": mst["S"], "x_prev": mst["x_prev"],
                   "ffn_x_prev": hf[:, -1, :]}
            return h, nst

        x, ns = scan_layers_carry(body, stacked, x, gstate)
        return x, ns

    if g.kind == "jamba":
        def body(layer, carry, st):
            h = carry
            nst = {}
            for i in range(cfg.attn_period):
                sub = layer[f"sub{i}"]
                if "attn" in sub:
                    a_in = _norm_apply(cfg, sub["ln1"], h)
                    scales = ({"k": st[f"k{i}_scale"], "v": st[f"v{i}_scale"]}
                              if f"k{i}_scale" in st else None)
                    out, ck, cv, nsc = _decode_attn_sub(
                        cfg, sub["attn"], a_in, pos,
                        st[f"k{i}"], st[f"v{i}"], window=window,
                        scales=scales)
                    h = h + out
                    nst[f"k{i}"], nst[f"v{i}"] = ck, cv
                    if nsc is not None:
                        nst[f"k{i}_scale"] = nsc["k"]
                        nst[f"v{i}_scale"] = nsc["v"]
                else:
                    m_in = _norm_apply(cfg, sub["ln1"], h)
                    y, ms = mamba_step(sub["mamba"], m_in,
                                       {"h": st[f"h{i}"], "conv": st[f"c{i}"]})
                    h = h + y
                    nst[f"h{i}"], nst[f"c{i}"] = ms["h"], ms["conv"]
                hf = _norm_apply(cfg, sub["ln2"], h)
                if "moe" in sub:
                    y, _ = moe_apply(sub["moe"], hf, top_k=cfg.top_k,
                                     capacity_factor=cfg.capacity_factor)
                else:
                    y = _ffn_apply(cfg, sub["ffn"], hf)
                h = h + y
            return h, nst

        x, ns = scan_layers_carry(body, stacked, x, gstate)
        return x, ns

    raise ValueError(g.kind)
