"""Model FLOPs of the split LM family, counted from the configuration's
shapes: 6 FLOPs per matmul parameter per token (forward and backward, no
recompute; the embedding lookup costs none), plus causal attention's score
and value products, 2 * S * head_dim * heads per token and layer forward
(half of the S x S products), three times that for training.
"""
from __future__ import annotations


def matmul_params(config: dict) -> int:
    d = int(config["hidden_size"])
    hd = int(config["head_dim"])
    q = int(config["num_attention_heads"]) * hd
    kv = int(config["num_key_value_heads"]) * hd
    ff = int(config["intermediate_size"])
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return (int(config["num_hidden_layers"]) * per_layer
            + d * int(config["vocab_size"]))


def train_flops_per_token(config: dict, seq_len: int) -> float:
    hq = int(config["num_attention_heads"]) * int(config["head_dim"])
    attn_fwd = 2.0 * seq_len * hq * int(config["num_hidden_layers"])
    return 6.0 * matmul_params(config) + 3.0 * attn_fwd


def forward_flops_per_token(config: dict, seq_len: int) -> float:
    hq = int(config["num_attention_heads"]) * int(config["head_dim"])
    return (2.0 * matmul_params(config)
            + 2.0 * seq_len * hq * int(config["num_hidden_layers"]))


def flops_per_round(config: dict, traffic: dict) -> float:
    s = int(traffic["seq_len"])
    tokens = (int(traffic["clients"]) * int(traffic["batch"])
              * int(traffic["local_steps"]) * s)
    total = train_flops_per_token(config, s) * tokens
    if traffic["eval_every_round"]:
        total += forward_flops_per_token(config, s) * int(
            traffic["test_examples"]) * s
    return total


def flash_fwd_call(config: dict, sequences: int, seq_len: int) -> tuple:
    """(FLOPs, HBM bytes) one causal flash-attention forward needs over
    ``sequences`` sequences: QK^T and PV over the causal half, and q, k, v
    (KV heads repeated to every query head) read and o written in f32."""
    h, hd = int(config["num_attention_heads"]), int(config["head_dim"])
    flops = 2.0 * sequences * h * seq_len * seq_len * hd
    nbytes = 4.0 * 4 * sequences * h * seq_len * hd
    return flops, nbytes
