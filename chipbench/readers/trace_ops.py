"""Device time of the operations, or whole programs, whose symbol
contains any of ``args["symbols"]``; or their share of a roofline.

``args["of"]``: ``"ops"`` (default: self times on the per-operation
line, by ``xplane.op_symbol``) or ``"modules"`` (whole programs on the
device's module line, by name without the fingerprint).
``args["per"]``: ``"step"`` (default: over the steps of the traced
window) or ``"call"`` (over the events found).

Without ``args["count"]``: milliseconds a step or a call. With it: 100 x
(``counts/<count>.py`` over ``obs["peaks"][args["peak"]]``) over those
seconds, the least time the chip could take over the time it took; the
count is then of ONE step or ONE call, to match ``per``. A new kernel
needs a metric file and a count file, and no table of symbols edited.
Nothing found, nothing returned: never 0 for a share of a roofline."""
from .. import manifest


def read(args: dict, obs: dict):
    tr = obs.get("trace")
    if not tr:
        return None
    if args.get("of", "ops") == "modules":
        found = [v for k, v in tr.get("modules", {}).items()
                 if any(s in k for s in args["symbols"])]
        seconds = sum(v[0] for v in found)
        calls = sum(v[1] for v in found)
    else:
        keys = [k for k in tr["ops_s"]
                if any(s in k for s in args["symbols"])]
        seconds = sum(tr["ops_s"][k] for k in keys)
        calls = sum(tr.get("ops_n", {}).get(k, 0) for k in keys)
    n = calls if args.get("per", "step") == "call" else tr["steps"]
    if seconds <= 0 or not n:
        return None
    if "count" not in args:
        return seconds / n * 1e3
    need = manifest.module("counts", args["count"]).count(
        obs["config"], obs)
    if need is None:
        return None
    return 100.0 * need / obs["peaks"][args["peak"]] / (seconds / n)


# a made-up program: 8 calls, 0.32 s, so 40 ms a call; the bytes of
# counts/llama_decode_hbm_bytes.py's own case (8528) at 1e6 B/s
SELFTEST_CASE = (
    {"symbols": ["jit_ptpu_decode"], "of": "modules", "per": "call",
     "count": "llama_decode_hbm_bytes", "peak": "hbm_bytes_per_s"},
    {"trace": {"steps": 9, "ops_s": {}, "ops_n": {},
               "modules": {"jit_ptpu_decode": [0.32, 8],
                           "jit_ptpu_prefill": [0.1, 2]}},
     "peaks": {"hbm_bytes_per_s": 1e6},
     "config": {"dtype": "bfloat16",
                "model": {"hidden_size": 8, "intermediate_size": 16,
                          "num_attention_heads": 2,
                          "num_key_value_heads": 1, "head_dim": 4,
                          "num_hidden_layers": 3, "vocab_size": 10}},
     "host": {"decode_positions": 100.0}},
    100.0 * (8528 / 1e6) / 0.04)
