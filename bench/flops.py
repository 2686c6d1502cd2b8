"""Model FLOPs of a dense decoder, counted from the configuration's shapes.

Per token, forward: 2 x (every matmul weight: q, k, v and output
projections, the three SwiGLU matrices, the LM head) plus the attention
products, 2 x 2 x seq x hidden per layer (scores and weighted values over
the whole seq x seq matrix, as a dense causal-masked kernel computes it).
Forward and backward: three times the forward.  Embedding lookups, norms,
softmax and the optimizer are not counted, nor is any recompute.
"""
from __future__ import annotations


def matmul_params(config: dict) -> int:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    g = config["num_key_value_heads"]
    hd = d // h
    f = config["intermediate_size"]
    layer = d * h * hd + 2 * d * g * hd + h * hd * d + 3 * d * f
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def attention_flops_fwd(config: dict, seq: int) -> int:
    """Per token, forward: QK^T and PV over ``seq`` keys, every layer."""
    hd = config["hidden_size"] // config["num_attention_heads"]
    return (config["num_hidden_layers"] * 2 * 2 * seq
            * config["num_attention_heads"] * hd)


def train_flops_per_token(config: dict, seq: int) -> int:
    return 3 * (2 * matmul_params(config) + attention_flops_fwd(config, seq))
