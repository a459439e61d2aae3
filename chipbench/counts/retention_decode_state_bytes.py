"""Bytes one call of the retention decode kernel has to move: one
layer's recurrent state (float32, ``kv_heads x D(D+1)/2 x D`` a slot)
of the slots active in the step, read once and written once. The
normaliser is not the kernel's (``jax.numpy`` around it), and the
step's queries, keys and values are a thousandth of the state. Active
slots as ``brumby_decode_hbm_bytes`` counts them."""
from .brumby_decode_hbm_bytes import state_bytes_a_layer


def count(config: dict, obs: dict):
    state = state_bytes_a_layer(config, obs)
    return None if state is None else 2.0 * state[0]


SELFTEST_CASE = (
    {"dtype": "bfloat16", "engine": {"max_slots": 4},
     "model": {"hidden_size": 8, "intermediate_size": 16,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 4, "num_hidden_layers": 3, "vocab_size": 10}},
    {"host": {"occupancy_pct": 50.0}},
    # brumby_decode_hbm_bytes's case: 640 bytes of state, in and out
    1280.0)
