"""A kernel family's share of a peak: the operations its algorithm needs
(``chipbench/flops.py``) over the peak rate, over the device time of its
step-budget bucket."""
from .. import flops


def read(args: dict, obs: dict):
    tr = obs.get("trace")
    if not tr or not tr["steps"]:
        return None
    ms = tr["buckets_ms_per_step"].get(args["bucket"], 0.0)
    if ms <= 0:
        return None
    need = flops.FUNCTIONS[args["flops"]](obs["config"])
    least_s = need / obs["peaks"][args["peak"]]
    return 100.0 * least_s / (ms / 1e3)
