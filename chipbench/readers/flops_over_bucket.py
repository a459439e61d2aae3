"""A kernel family's share of a peak: the operations its algorithm needs
(``counts/<args["flops"]>.py``) over the peak rate, over the device time
of its step-budget bucket."""
from .. import manifest


def read(args: dict, obs: dict):
    tr = obs.get("trace")
    if not tr or not tr["steps"]:
        return None
    ms = tr["buckets_ms_per_step"].get(args["bucket"], 0.0)
    if ms <= 0:
        return None
    need = manifest.module("counts", args["flops"]).count(
        obs["config"], obs)
    if need is None:
        return None
    return 100.0 * need / obs["peaks"][args["peak"]] / (ms / 1e3)


# the fixture's flash bucket is 2.5 ms a step
SELFTEST_CASE = (
    {"flops": "flash_attention_train", "bucket": "flash",
     "peak": "bf16_flops"},
    {"config": {"model": {"max_seq_len": 1024, "head_dim": 128,
                          "num_heads": 16, "num_layers": 24},
                "batch": 6}},
    100 * (7 * 1024 * 1024 * 128 * 16 * 24 * 6 / 197e12) / 2.5e-3)
