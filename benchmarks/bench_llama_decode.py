"""Serving decode: Llama KV-cached generation throughput.

The static-cache path compiles ONE prefill program and ONE decode-step
program (fixed-size cache buffers + dynamic_update_slice at the write
position) — the TPU-native equivalent of the reference's
fused_multi_transformer serving kernels
(paddle/fluid/inference/api/analysis_predictor.h:105 serving story).

Round 2: bf16 weights (decode is weight-bandwidth-bound, so bf16 ~2x
fp32), batched decode bs in {1, 8, 32}, fp32-vs-bf16 greedy parity
check, and a device-side drain (dispatch is async — timing without
forcing the last token undercounts). The weight-only int8 and int4
phases run in this same process: one process holds the chip.
"""
import _path  # noqa: F401  (repo-root import shim)

import json
import time

import numpy as np


def _gen_tokens_per_s(model, ids, new, runs):
    import jax
    out = model.generate(ids, max_new_tokens=new)  # compile
    # drain BEFORE starting the clock: the warmup run is dispatched
    # asynchronously and would bill to the first timed run
    int(np.asarray(jax.device_get(out._data[0, -1])))
    t0 = time.perf_counter()
    for _ in range(runs):
        out = model.generate(ids, max_new_tokens=new)
    # force the final token to the host: everything upstream must have
    # executed
    int(np.asarray(jax.device_get(out._data[0, -1])))
    dt = (time.perf_counter() - t0) / runs
    return ids.shape[0] * new / dt, out


def _quantized_phase(model, precision, cfg, T0, new, runs,
                     batches=(1, 8)):
    """Weight-only ``precision`` decode of the bf16 ``model``: returns
    ({bs: tokens/s}, (last-logit rel err vs bf16, argmax agrees))."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.quantization import weight_only_int4, weight_only_int8
    q = weight_only_int8(model, inplace=False) if precision == "int8" \
        else weight_only_int4(model, group=128, inplace=False)
    rng = np.random.RandomState(0)
    idsp = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (1, T0))
                            .astype(np.int64))
    lb = np.asarray(jax.device_get(model(idsp)._data))[0, -1] \
        .astype(np.float64)
    li = np.asarray(jax.device_get(q(idsp)._data))[0, -1] \
        .astype(np.float64)
    rel = float(np.max(np.abs(lb - li)) / max(np.max(np.abs(lb)), 1e-9))
    results = {}
    for bs in batches:
        ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (bs, T0))
                               .astype(np.int64))
        tps, _ = _gen_tokens_per_s(q, ids, new, runs)
        results[bs] = round(tps, 1)
    return results, (round(rel, 4), bool(np.argmax(lb) == np.argmax(li)))


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          num_hidden_layers=16, num_attention_heads=16,
                          intermediate_size=5504,
                          max_position_embeddings=1024)
        T0, new, runs = 64, 128, 2
        batches = (1, 8, 32)
    else:
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          num_hidden_layers=2, num_attention_heads=4,
                          intermediate_size=128,
                          max_position_embeddings=128)
        T0, new, runs = 8, 16, 1
        batches = (1, 2)

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    n = sum(int(np.prod(p.shape)) for p in model.parameters())
    rng = np.random.RandomState(0)

    # fp32-vs-bf16 parity on the prompt's last-token logits (token
    # agreement is meaningless on random weights — logits are near-tied)
    ids1 = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (1, T0))
                            .astype(np.int64))
    ref = np.asarray(jax.device_get(model(ids1)._data))[0, -1] \
        .astype(np.float64)
    model.to(dtype="bfloat16")
    got = np.asarray(jax.device_get(model(ids1)._data))[0, -1] \
        .astype(np.float64)
    rel_err = float(np.max(np.abs(ref - got)) /
                    max(np.max(np.abs(ref)), 1e-9))

    results = {}
    for bs in batches:
        ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (bs, T0))
                               .astype(np.int64))
        tps, _ = _gen_tokens_per_s(model, ids, new, runs)
        results[bs] = round(tps, 1)

    # weight-only int8/int4 serving variants: decode at small batch is
    # weight-READ-bound, so int8 weights (+ per-channel scales, dequant
    # on the output side of the int8 MXU dot) halve the per-token HBM
    # floor vs bf16. Greedy agreement is not reported at random weights
    # (near-tied logits make it meaningless — the last-logit rel err
    # vs bf16 is the honest parity stat). TPU only: the int4 kernel in
    # interpret mode is minutes per token on the CPU smoke.
    results8, int8_relerr = {}, None
    results4, int4_relerr = {}, None
    if on_tpu:
        results8, int8_relerr = _quantized_phase(
            model, "int8", cfg, T0, new, runs)
        results4, int4_relerr = _quantized_phase(
            model, "int4", cfg, T0, new, runs)

    bs_hero = batches[-1]
    print(json.dumps({
        "metric": f"Llama decode tokens/s (N={n/1e9:.2f}B, bf16, "
                  f"prompt {T0}, KV-cached static decode; "
                  f"per-bs {results}; weight-only-int8 {results8} "
                  f"(int8 last-logit {int8_relerr}); "
                  f"weight-only-int4 {results4} "
                  f"(int4 last-logit {int4_relerr}); fp32-vs-bf16 "
                  f"last-logit rel err {rel_err:.4f})",
        "value": results[bs_hero], "unit": f"tokens/s@bs{bs_hero}",
        "vs_baseline": results[1]}))


if __name__ == "__main__":
    main()
