"""Operations a kernel's algorithm needs, from its shapes.

Recomputed operations do not count: a share of a peak computed from
these can only fall when a kernel recomputes, never pass 100%.
"""
from __future__ import annotations


def flash_attention_train(config: dict) -> float:
    """FLOPs of causal flash attention in one training step, forward and
    backward, over all layers, sequences and heads.

    Forward is two matmuls (Q K^T, P V); backward needs five (S again,
    dP = dO V^T, dQ = dS K, dK = dS^T Q, dV = P^T dO), whether they run
    in one kernel or in the two (dq; dk, dv) the program has, whose
    second S and dP are recomputation. Each is 2 * T * T * d
    multiply-adds a head, and causality needs half of them."""
    m = config["model"]
    T, d = m["max_seq_len"], m["head_dim"]
    per_head = 7 * (2 * T * T * d) / 2
    return per_head * m["num_heads"] * m["num_layers"] * config["batch"]


FUNCTIONS = {"flash_attention_train": flash_attention_train}
