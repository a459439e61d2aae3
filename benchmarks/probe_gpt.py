"""Perf probe for the GPT-1.3B flagship step: remat policy x batch size.

Usage (on the real chip):
  python benchmarks/probe_gpt.py --remat full|none|save_attn|save_attn_ffn|save_dots \
      --bs 6 --steps 10 [--seq 1024] [--layers 24] [--hidden 2048]

Prints one JSON line with tokens/s, MFU, and the compiler's peak-memory
estimate. One config per process (clean HBM).
"""
import argparse
import json
import time

import _path  # noqa: F401  (repo-root import shim)


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--remat", default="full")
    ap.add_argument("--bs", type=int, default=6)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--moments", default="bf16")
    ap.add_argument("--masters", default="fp32", choices=["fp32", "bf16"])
    ap.add_argument("--quant8", default="",
                    choices=["", "fwd", "dgrad", "wgrad"])
    ap.add_argument("--unroll", default="1",
                    help="int scan-unroll factor, or 'full' for the\n                    per-layer-pytree unrolled stage (round 6)")
    ap.add_argument("--ce-chunks", type=int, default=16)
    ap.add_argument("--ce-int8", action="store_true")
    ap.add_argument("--no-fused-opt", action="store_true")
    ap.add_argument("--moment8", action="store_true")
    ap.add_argument("--fuse-ln", default="off",
                    choices=["off", "both", "qkv", "ffn1"])
    ap.add_argument("--no-fuse-gelu", action="store_true")
    ap.add_argument("--compile-only", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh

    remat = {"full": True, "none": False}.get(args.remat, args.remat)
    cfg = GPTConfig(vocab_size=50304, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=args.heads,
                    max_seq_len=args.seq, dtype=jnp.bfloat16)
    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
    trainer = GPTSpmdTrainer(
        cfg, mesh, microbatches=1, remat=remat,
        moment_dtype=jnp.bfloat16 if args.moments == "bf16"
        else jnp.float32,
        master_dtype=jnp.bfloat16 if args.masters == "bf16"
        else jnp.float32,
        quant8={"": False, "fwd": True, "dgrad": "dgrad",
                "wgrad": "wgrad"}[args.quant8],
        layer_unroll=args.unroll if args.unroll == "full"
        else int(args.unroll),
        ce_chunks=args.ce_chunks,
        ce_int8=args.ce_int8,
        fused_optimizer=False if args.no_fused_opt else None,
        moment8=args.moment8,
        fuse_ln_quant={"off": False, "both": True, "qkv": "qkv",
                       "ffn1": "ffn1"}[args.fuse_ln],
        fuse_gelu_quant=False if args.no_fuse_gelu else None)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      (args.bs, args.seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)

    fn = trainer.build_step()
    with jax.set_mesh(mesh):
        lowered = fn.lower(trainer.params, trainer.opt_state, ids, labels)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    peak_gb = getattr(mem, "temp_size_in_bytes", 0) / 2**30
    arg_gb = getattr(mem, "argument_size_in_bytes", 0) / 2**30
    out = {"remat": args.remat, "bs": args.bs, "seq": args.seq,
           "masters": args.masters, "quant8": args.quant8,
           "temp_gb": round(peak_gb, 2), "arg_gb": round(arg_gb, 2)}
    if args.compile_only:
        print(json.dumps(out))
        return

    loss = trainer.train_step(ids, labels)
    float(jax.device_get(loss))
    loss = trainer.train_step(ids, labels)
    float(jax.device_get(loss))
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss = trainer.train_step(ids, labels)
    lv = float(jax.device_get(loss))
    dt = time.perf_counter() - t0
    tps = args.bs * args.seq * args.steps / dt
    n = trainer.n_params()
    mfu = tps * 6 * n / 197e12
    out.update({"tokens_per_s": round(tps, 1), "mfu": round(mfu, 4),
                "loss": round(lv, 3), "step_ms": round(1000 * dt / args.steps, 1)})
    print(json.dumps(out))


if __name__ == "__main__":
    main()
