"""BASELINE configs[2]: GPT-1.3B hybrid parallel (TP+PP+DP+fsdp).

On one real chip: the flagship single-chip number (same as /bench.py).
On the virtual CPU mesh: one full hybrid step over pipe=2 x model=2 x
fsdp=2 — the allgather/reduce-scatter path the reference drives through
fleet; here one jitted program whose collectives GSPMD emits.
"""
import _path  # noqa: F401  (repo-root import shim)

import json
import time

import numpy as np


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                        num_heads=16, max_seq_len=1024, dtype=jnp.bfloat16)
        mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
        trainer = GPTSpmdTrainer(cfg, mesh, microbatches=1,
                                 remat="save_main",
                                 moment_dtype=jnp.bfloat16,
                                 master_dtype=jnp.bfloat16,
                                 quant8="wgrad",
                                 ce_chunks=1,
                                 moment8=True)
        B, T, steps = 6, 1024, 10
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=64, dtype=jnp.float32)
        mesh = build_mesh(n_devices=8, pipe=2, data=1, fsdp=2, sep=1,
                          model=2)
        trainer = GPTSpmdTrainer(cfg, mesh, microbatches=4)
        B, T, steps = 8, 64, 3

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
    labels = np.roll(ids, -1, 1)
    float(jax.device_get(trainer.train_step(ids, labels)))
    float(jax.device_get(trainer.train_step(ids, labels)))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.train_step(ids, labels)
    lv = float(jax.device_get(loss))
    dt = (time.perf_counter() - t0) / steps
    tps = B * T / dt
    n = trainer.n_params()
    mfu = tps * 6 * n / (197e12 if on_tpu else 1e12)
    tag = ("1 chip" if on_tpu else
           f"virtual mesh {dict(trainer.mesh.shape)}")
    print(json.dumps({
        "metric": f"GPT hybrid train tokens/s ({tag}, N={n/1e6:.0f}M, "
                  f"loss={lv:.3f})",
        "value": round(tps, 1), "unit": "tokens/s",
        "vs_baseline": round(mfu, 4)}))


if __name__ == "__main__":
    main()
