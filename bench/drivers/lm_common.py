"""What the language-model drivers share: the program's config at the
configuration file's sizes, and the benchmark's weights in the program's
parameter layout (a driver helper, not a driver)."""

from __future__ import annotations

import dataclasses


def program_config(cfg: dict, layers: int):
    """The program's ``ModelConfig`` at the configuration file's sizes."""
    from repro.configs import get_config

    base = get_config(cfg["name"])
    return dataclasses.replace(
        base, num_layers=layers, d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], head_dim=None,
    )


def to_program(w):
    """The benchmark's weights in the program's parameter layout."""
    L = w["layers"]
    return {
        "embedding": w["embed"],
        "final_norm": w["final_norm"],
        "stack": {"sub0": {
            "norm1": L["attn_norm"], "norm2": L["mlp_norm"],
            "mixer": {"w_q": L["wq"], "w_k": L["wk"], "w_v": L["wv"], "w_o": L["wo"]},
            "ffn": {"w_gate": L["w_gate"], "w_up": L["w_up"], "w_down": L["w_down"]},
        }},
    }


def from_program(p):
    s = p["stack"]["sub0"]
    return {
        "embed": p["embedding"], "final_norm": p["final_norm"],
        "layers": {
            "attn_norm": s["norm1"], "mlp_norm": s["norm2"],
            "wq": s["mixer"]["w_q"], "wk": s["mixer"]["w_k"], "wv": s["mixer"]["w_v"],
            "wo": s["mixer"]["w_o"], "w_gate": s["ffn"]["w_gate"], "w_up": s["ffn"]["w_up"],
            "w_down": s["ffn"]["w_down"],
        },
    }
