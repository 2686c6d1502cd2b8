"""The program's ``ArchSpec`` for a configuration file: every size is the
file's, so the benchmark runs the sizes it states, whatever the program's
own registry holds."""
from __future__ import annotations

import dataclasses

# configuration-file key (the published config.json's name) -> ArchSpec field
FIELDS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "qkv_bias": "qkv_bias",
}


def arch_spec(config: dict):
    from repro.configs import get_arch

    spec = get_arch(config["program_arch"])
    if config["hidden_act"] != "silu" or spec.family != "dense":
        raise ValueError("only dense SwiGLU models map onto this spec")
    spec = dataclasses.replace(
        spec, head_dim=0, act="silu",
        **{f: config[k] for k, f in FIELDS.items()})
    if spec.resolved_head_dim * spec.n_heads != config["hidden_size"]:
        raise ValueError("head size does not divide the hidden size")
    return spec
