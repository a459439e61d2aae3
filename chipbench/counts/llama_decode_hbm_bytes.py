"""Bytes one decode step of a Llama-style model has to read from HBM,
whatever implements it: every weight a token passes through once (the
layers, the final norm, the head; of the embedding table only a row a
slot, which is left out), and K and V of the positions that were live in
the step. Live positions are the benchmark's own count
(``obs["host"]["decode_positions"]``: over the traced decode steps, the
mean of the sum over the slots decoding of prompt plus tokens served so
far), not ``max_len`` and not whole pages: a kernel that reads more than
the live positions reads more than this. The new token's K and V
written, and the logits, are a thousandth of it and left out."""

BYTES = {"bfloat16": 2, "float32": 4}


def count(config: dict, obs: dict):
    positions = obs.get("host", {}).get("decode_positions")
    if positions is None:
        return None
    m, b = config["model"], BYTES[config["dtype"]]
    D, F = m["hidden_size"], m["intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    layer = D * q + 2 * D * kv + q * D + 3 * D * F + 2 * D
    weights = m["num_hidden_layers"] * layer + D + D * m["vocab_size"]
    cache = 2 * m["num_hidden_layers"] * kv * positions
    return float(b * (weights + cache))


SELFTEST_CASE = (
    {"dtype": "bfloat16",
     "model": {"hidden_size": 8, "intermediate_size": 16,
               "num_attention_heads": 2, "num_key_value_heads": 1,
               "head_dim": 4, "num_hidden_layers": 3, "vocab_size": 10}},
    {"host": {"decode_positions": 100.0}},
    # a layer: 64 + 64 + 64 + 384 + 16 = 592; weights 3 * 592 + 8 + 80;
    # cache 2 * 3 * 4 * 100
    2.0 * (3 * 592 + 88 + 2400))
