"""Bytes one call of the grouped expert product has to move in a decode
step. A layer makes three calls (gate, up, down), each over one matrix
(``hidden x moe_intermediate_size`` parameters) of every held expert
that the step's tokens chose, read once, with the assignments' rows in
and out (float32; ``hidden + moe_intermediate_size`` numbers an
assignment and call, whichever way the call runs). The held experts hit
and the assignments held, a step, are what the run observed
(``solar_decode_hbm_bytes.observed``: summed over the expert layers, so
divided by them here)."""
from .solar_decode_hbm_bytes import (BYTES, _CASE_CONFIG, _CASE_OBS,
                                     observed, shapes)


def count(config: dict, obs: dict):
    seen = observed(config, obs)
    if seen is None:
        return None
    _, _, hit, tokens, _ = seen
    s, m = shapes(config), config["model"]
    rows = 4.0 * (m["hidden_size"] + m["moe_intermediate_size"])
    return float(BYTES[config["dtype"]] * hit / s["layers"]
                 * s["expert"] / 3 + tokens / s["layers"] * rows)


# solar_decode_hbm_bytes's case: 5 experts hit and 7 assignments over 4
# layers; a matrix 24 parameters; a row in and out 11 floats
SELFTEST_CASE = (_CASE_CONFIG, _CASE_OBS,
                 2.0 * 5 / 4 * 24 + 7 / 4 * 4.0 * 11)
