"""Bytes one decode step of the Solar-Open2 share has to move through
HBM, whatever implements it:

- every weight each token passes through, read once: the GQA and KDA
  mixers, the routers (all 320 outputs), the shared experts, the norms,
  the final norm and the head (of the embedding only a row a slot, which
  is left out);
- each HELD expert that the step's tokens chose, its three matrices
  once: the number of such experts is what the run observed (the mean
  over the window's ``serving.decode`` spans of ``experts_hit``, summed
  over the expert layers: what the decode program counted, not an
  assumed routing); an expert no token chose is not read;
- the KDA state and the convolution state of the slots active in the
  step (the engine's own occupancy over the window), float32, read once
  and written once;
- K and V of the live positions of the GQA layers (the benchmark's own
  count over the traced decode steps, ``decode_positions``), read once,
  at the bytes a position the ENGINE's pool holds: the ``page_bytes`` of
  its ``serving.step`` spans (one page over every K/V layer, K and V)
  over the configuration's ``page_size``, so that a pool in another
  dtype is counted as what it is, not as what this file assumed.

The step's activations, the new token's K and V and the logits are a
thousandth of it and left out. ``None`` where an observation is missing
(a program with no such span: the parent commit)."""

BYTES = {"bfloat16": 2, "float32": 4}
STATE_BYTES = 4      # KDA state and convolution state: float32
_SPANS = (("serving.decode", "experts_hit"),
          ("serving.decode", "expert_tokens"),
          ("serving.step", "page_bytes"))


def observed(config: dict, obs: dict):
    """``(active slots, live positions, held experts hit a step,
    assignments held a step, bytes a page)`` as the run saw them, or
    None. The last three are read from the program's span ring unless
    the observations carry them (the selftest's case)."""
    host = obs.get("host", {})
    occupancy, positions = (host.get("occupancy_pct"),
                            host.get("decode_positions"))
    if occupancy is None or positions is None:
        return None
    seen = [host.get(field) for _, field in _SPANS]
    if None in seen:
        from ..readers import program_span
        seen = [program_span.read(
            {"span": span, "phase": "window", "field": field,
             "stat": "mean"}, obs) for span, field in _SPANS]
    if None in seen:
        return None
    active = occupancy / 100.0 * config["engine"]["max_slots"]
    return (active, positions) + tuple(seen)


def shapes(config: dict) -> dict:
    m = config["model"]
    lin = m["linear_attn_config"]
    layers = m["num_hidden_layers"]
    gqa = sum(1 for i in m["gqa_layers"] if i < layers)
    C, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    lq = lin["num_heads"] * lin["head_dim"]
    rank = config.get("kda_rank", 128)
    expert = 3 * C * m["moe_intermediate_size"]
    return {
        "layers": layers, "gqa": gqa, "kda": layers - gqa,
        "gqa_mixer": 3 * C * q + 2 * C * kv,
        "kda_mixer": 4 * C * lq + 2 * (C * rank + rank * lq)
        + C * lin["num_heads"] + 3 * lin["short_conv_kernel_size"] * lq
        + lin["num_heads"] + lq + lin["head_dim"],
        "ffn_dense": C * m["experts_published"]
        + expert * m["n_shared_experts"] + 2 * C,
        "expert": expert, "kv_width": kv,
        "state": lin["num_heads"] * lin["head_dim"] ** 2,
        "conv": (lin["short_conv_kernel_size"] - 1) * 3 * lq,
        "head": C + C * m["vocab_size"]}


def count(config: dict, obs: dict):
    seen = observed(config, obs)
    if seen is None:
        return None
    active, positions, hit, _, page_bytes = seen
    s, b = shapes(config), BYTES[config["dtype"]]
    weights = s["gqa"] * s["gqa_mixer"] + s["kda"] * s["kda_mixer"] \
        + s["layers"] * s["ffn_dense"] + s["head"] + hit * s["expert"]
    state = 2 * STATE_BYTES * active * s["kda"] * (s["state"] + s["conv"])
    cache = page_bytes / config["engine"]["page_size"] * positions
    return float(b * weights + state + cache)


_CASE_CONFIG = {
    "dtype": "bfloat16", "engine": {"max_slots": 4, "page_size": 16},
    "kda_rank": 2,
    "model": {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 4,
              "num_key_value_heads": 2, "num_hidden_layers": 4,
              "gqa_layers": [0, 4, 8], "vocab_size": 10,
              "moe_intermediate_size": 3, "n_shared_experts": 1,
              "experts_published": 16,
              "linear_attn_config": {"num_heads": 2, "head_dim": 4,
                                     "short_conv_kernel_size": 4}}}
# a page of 16 positions, one K/V layer of width 8, K and V, bfloat16
_CASE_OBS = {"host": {"occupancy_pct": 50.0, "decode_positions": 100.0,
                      "experts_hit": 5.0, "expert_tokens": 7.0,
                      "page_bytes": 16 * 2 * 8 * 2.0}}

SELFTEST_CASE = (
    _CASE_CONFIG, _CASE_OBS,
    # GQA mixer 3*8*16 + 2*8*8 = 512; KDA mixer 4*64 + 2*(16+16) + 16
    # + 96 + 2 + 8 + 4 = 446; a layer's router, shared expert and norms
    # 128 + 72 + 16 = 216; head 8 + 80; an expert 72, five hit:
    # weights 512 + 3*446 + 4*216 + 88 + 360 = 3162; state of 2 slots,
    # 3 layers, 32 + 72 floats, in and out; K/V of 100 positions at
    # the pool's 32 bytes a position
    2.0 * 3162 + 2 * 4 * 2 * 3 * (32 + 72) + 32.0 * 100)
