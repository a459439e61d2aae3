"""Bytes one call of the KDA decode kernel has to move: one layer's
state (float32, ``heads x head_dim x head_dim`` a slot) of the slots
active in the step, read once and written once. The convolution state
is not the kernel's (``jax.numpy`` around it), and the step's queries,
keys, values, decays and step sizes are a hundredth of the state.
Active slots as ``solar_decode_hbm_bytes`` counts them."""
from .solar_decode_hbm_bytes import (STATE_BYTES, _CASE_CONFIG, _CASE_OBS,
                                     observed, shapes)


def count(config: dict, obs: dict):
    seen = observed(config, obs)
    if seen is None:
        return None
    return 2.0 * STATE_BYTES * seen[0] * shapes(config)["state"]


# solar_decode_hbm_bytes's case: 2 slots x 32 floats, in and out
SELFTEST_CASE = (_CASE_CONFIG, _CASE_OBS, 2.0 * 4 * 2 * 32)
