"""Benchmark: flagship GPT-1.3B pretraining step, tokens/sec on one chip.

This is the BASELINE.json north-star config (GPT-3 1.3B class: hidden
2048, 24 layers, dh=128) running a full AdamW training step — bf16
compute, bf16 master weights updated with exact stochastic rounding,
int8 Adam moments (m int8-SR, v sqrt-int8-SR, per-row scales —
ops/fused_adamw.fused_adamw_update8; 300-step parity in the
rounds-1-5 notes, git history before PR 23), Pallas flash attention
(grid-pipelined Mosaic
kernels, whole-sequence blocks), ALL-int8 MXU block matmuls (fwd +
dgrad RTN, wgrad stochastic-rounding — ops/quant_matmul.py; 500-step
parity), producer-fused gelu->quantize, a single-pass Pallas AdamW
update with in-kernel stochastic-rounding PRNG, "save_main" remat
(save_qkv_ffn until int8 moments freed the HBM), unchunked fused
cross-entropy.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ...,
"vs_baseline": N, "platform": ..., "device_kind": ..., "device_count": N}.
vs_baseline is the achieved model-FLOPs-utilization (MFU) against the
chip's published peak (PEAK_BF16_FLOPS), since the reference publishes
no in-tree numbers (BASELINE.md). ``JAX_PLATFORMS=cpu python bench.py``
is the CPU smoke: a tiny model, and ``vs_baseline`` is null — a CPU has
no peak to be measured against.
"""
import json
import os
import sys
import time

# bf16 peak FLOP/s of one chip, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
# A TPU that is not in the table is an error, not a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12, "TPU v5e": 197e12}


def build_flagship():
    """Build the flagship (TPU) or smoke (CPU) trainer + batch at the
    COMMITTED bench defaults; returns (trainer, ids, labels, info).
    Shared with ``benchmarks/step_budget.py --run gpt`` so the
    STEP_BUDGET decomposition profiles exactly the recipe behind the
    headline — the two drifting apart would make the artifact lie."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                        num_heads=16, max_seq_len=1024,
                        dtype=jnp.bfloat16)
        batch, seq, steps = 6, 1024, 10
        moment_dtype = jnp.bfloat16  # 1.3B AdamW state on a 16G chip
        size = "1.3B"
    else:  # smoke mode (JAX_PLATFORMS=cpu)
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128, dtype=jnp.float32)
        batch, seq, steps = 4, 128, 3
        moment_dtype = jnp.float32
        size = "tiny"

    # layer_unroll="full" (round-6 tentpole): blocks params live as a
    # per-layer pytree and the stage runs unrolled, so remat-saved
    # residuals and the per-layer wgrad dequants write straight from
    # their producing fusions instead of DUS-stacking into [L, ...]
    # buffers (the 72 ms copy/slice bucket of the r05 decomposition).
    # PTPU_LAYER_UNROLL=1 falls back to the rolled scan; an int >1 is
    # the classic scan-body unroll A/B.
    unroll_env = os.environ.get("PTPU_LAYER_UNROLL", "full")
    layer_unroll = "full" if unroll_env == "full" else int(unroll_env)
    if not on_tpu:
        layer_unroll = 1  # smoke mode keeps the (faster-compiling) scan

    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
    trainer = GPTSpmdTrainer(
        cfg, mesh, microbatches=1,
        remat="save_main" if on_tpu else False,  # save_qkv_ffn until moment8 freed the HBM (r5)
        moment_dtype=moment_dtype,
        master_dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        quant8="wgrad" if on_tpu else False,
        ce_chunks=1 if on_tpu else 16,
        layer_unroll=layer_unroll,
        # int8 moment storage (round-5 lever b): -5 ms/step and 2.4 GB
        # of optimizer HBM; parity earned in round 5 (notes in git
        # history before PR 23)
        moment8=on_tpu)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    info = {"backend": backend, "on_tpu": on_tpu, "batch": batch,
            "seq": seq, "steps": steps, "size": size}
    return trainer, ids, labels, info


def main():
    import jax

    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    trainer, ids, labels, info = build_flagship()
    backend, on_tpu = info["backend"], info["on_tpu"]
    batch, seq, steps = info["batch"], info["seq"], info["steps"]
    size = info["size"]
    dev = jax.devices()[0]
    peak = None
    if on_tpu:
        if dev.device_kind not in PEAK_BF16_FLOPS:
            raise SystemExit(
                f"bench.py: no published peak for device kind "
                f"{dev.device_kind!r}; add it to PEAK_BF16_FLOPS with "
                f"its source")
        peak = PEAK_BF16_FLOPS[dev.device_kind]

    # warmup (compile). The barrier is a device_get of the scalar loss:
    # dispatch is async, so timing without it measures the enqueue.
    loss = trainer.train_step(ids, labels)
    float(jax.device_get(loss))
    loss = trainer.train_step(ids, labels)
    float(jax.device_get(loss))

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.train_step(ids, labels)
    float(jax.device_get(loss))  # drains the whole dispatched pipeline
    dt = time.perf_counter() - t0

    # step-budget decomposition (round 6): bucket a profiled step via
    # benchmarks/step_budget.py and print the schema-stable line next
    # to the tokens/s JSON, so BENCH carries the decomposition, not
    # just the headline. On by default on TPU; PTPU_STEP_BUDGET=1
    # forces the attempt elsewhere, =0 disables. A trace that cannot be
    # read fails the bench: a headline without its decomposition is not
    # a result.
    want_budget = os.environ.get("PTPU_STEP_BUDGET",
                                 "1" if on_tpu else "0")
    if want_budget not in ("0", "", "false"):
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
        from step_budget import capture, format_line
        budget = capture(lambda: trainer.train_step(ids, labels),
                         steps=3,
                         plane_filter="TPU" if on_tpu else "CPU")
        if budget is None:
            raise SystemExit("bench.py: step_budget found no device "
                             "plane in the trace")
        print(format_line(budget))
        out_path = os.environ.get("PTPU_STEP_BUDGET_OUT")
        if out_path:
            with open(out_path, "w") as f:
                json.dump(budget, f, sort_keys=True)
                f.write("\n")

    tokens_per_sec = batch * seq * steps / dt
    flops_per_token = 6 * trainer.n_params()  # fwd+bwd matmul estimate
    mfu = None if peak is None else \
        round(tokens_per_sec * flops_per_token / peak, 4)

    print(json.dumps({
        "metric": f"GPT-{size} pretrain tokens/sec/chip ({backend}, "
                  f"loss={float(jax.device_get(loss)):.3f})",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": mfu,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }))


if __name__ == "__main__":
    main()
