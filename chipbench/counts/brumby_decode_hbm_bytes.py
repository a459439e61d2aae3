"""Bytes one decode step of a power-retention model has to move through
HBM, whatever implements it: every weight a token passes through read
once (the layers with their gate projection and head norms, the final
norm, the head; of the embedding table only a row a slot, which is left
out), and the recurrent state and its normaliser of the slots that were
active in the step, read once and written once: there is no K or V, and
the count does not grow with a request's length. Active slots are the
engine's own count over the window (``obs["host"]["occupancy_pct"]`` of
the configuration's ``max_slots``); a step that reads or rewrites the
state of a slot that is not active moves more than this. The step's
queries, keys, values and logits are a thousandth of it and left out."""

BYTES = {"bfloat16": 2, "float32": 4}
STATE_BYTES = 4     # the state and its normaliser are float32


def state_bytes_a_layer(config: dict, obs: dict):
    """One layer's state (and, with it, normaliser) of the slots active
    in a step: ``(state, normaliser)`` bytes, or None."""
    occupancy = obs.get("host", {}).get("occupancy_pct")
    if occupancy is None:
        return None
    m = config["model"]
    active = occupancy / 100.0 * config["engine"]["max_slots"]
    d = m["head_dim"]
    pairs = m["num_key_value_heads"] * d * (d + 1) // 2
    return (STATE_BYTES * active * pairs * d,
            STATE_BYTES * active * pairs)


def count(config: dict, obs: dict):
    state = state_bytes_a_layer(config, obs)
    if state is None:
        return None
    m, b = config["model"], BYTES[config["dtype"]]
    D, F, d = m["hidden_size"], m["intermediate_size"], m["head_dim"]
    q = m["num_attention_heads"] * d
    kv = m["num_key_value_heads"] * d
    layer = D * q + 2 * D * kv + D * m["num_key_value_heads"] + q * D \
        + 3 * D * F + 2 * D + 2 * d
    weights = m["num_hidden_layers"] * layer + D + D * m["vocab_size"]
    return float(b * weights
                 + 2 * m["num_hidden_layers"] * (state[0] + state[1]))


SELFTEST_CASE = (
    {"dtype": "bfloat16", "engine": {"max_slots": 4},
     "model": {"hidden_size": 8, "intermediate_size": 16,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 4, "num_hidden_layers": 3, "vocab_size": 10}},
    {"host": {"occupancy_pct": 50.0}},
    # a layer: 128 + 128 + 16 + 128 + 384 + 16 + 8 = 808; weights
    # 3 * 808 + 8 + 80 = 2512; 2 slots x 2 heads x 10 pairs: state
    # 4 B x 2 x 20 x 4 = 640, normaliser 160, read and written, 3 layers
    2.0 * 2512 + 2 * 3 * (640 + 160))
