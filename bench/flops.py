"""Model FLOPs of a training step, from the cell's shapes alone.

For a llama-style dense decoder of ``L`` layers, width ``d``, MLP width
``f``, vocabulary ``V`` and sequence length ``S``, the forward pass of
one token multiplies by every weight matrix once (2 FLOPs per weight)
and attends causally to ``s`` earlier positions (on average ``S/2``):

    matmul weights   N = L * (4 d^2 + 3 d f) + V d      (q, k, v, o; gate, up, down; tied head)
    forward          2 N + L * 2 * 2 * d * S/2          (q.k and p.v over S/2 keys)
    training         3 x forward                        (backward costs twice the forward)

so ``6 N + 6 L d S`` per token.  The embedding lookup is a gather, not a
matmul, and recomputation under rematerialisation is not counted: MFU
counts the operations the step needs, not the ones it runs.
"""

from __future__ import annotations


def matmul_params(cfg: dict, layers: int) -> int:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return layers * per_layer + v * d


def train_flops_per_token(cfg: dict, layers: int, seq_len: int) -> float:
    d = cfg["hidden_size"]
    return 6.0 * matmul_params(cfg, layers) + 6.0 * layers * d * seq_len
