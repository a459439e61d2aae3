"""FLOPs of causal flash attention in one training step, forward and
backward, over all layers, sequences and heads.

Forward is two matmuls (Q K^T, P V); backward needs five (S again,
dP = dO V^T, dQ = dS K, dK = dS^T Q, dV = P^T dO), whether they run in
one kernel or in the two (dq; dk, dv) the program has, whose second S
and dP are recomputation. Each is 2 * T * T * d multiply-adds a head,
and causality needs half of them."""


def count(config: dict, obs: dict) -> float:
    m = config["model"]
    T, d = m["max_seq_len"], m["head_dim"]
    per_head = 7 * (2 * T * T * d) / 2
    return per_head * m["num_heads"] * m["num_layers"] * config["batch"]


SELFTEST_CASE = (
    {"model": {"max_seq_len": 1024, "head_dim": 128, "num_heads": 16,
               "num_layers": 24}, "batch": 6},
    {}, 7 * 1024 * 1024 * 128 * 16 * 24 * 6)
