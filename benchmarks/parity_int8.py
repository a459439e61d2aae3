"""500-step bf16 vs int8-dgrad training parity on the flagship config.

Earns (or demotes) the bench default quant8='dgrad': identical init,
identical per-step fresh batches, loss recorded every 10 steps, final
gap, plus a late-run gradient-SNR probe (int8 dgrad vs exact bf16
dgrad on the step-N parameters — drift compounds and gradients shrink
toward convergence, so early-step agreement alone is not evidence).

Usage: python benchmarks/parity_int8.py [--steps 500] [--layers 24] ...
Prints one JSON line; full curves to --out.
"""
import _path  # noqa: F401  (repo-root import shim)

import argparse
import json
import time


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--bs", type=int, default=6)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--every", type=int, default=10)
    ap.add_argument("--out", default="/tmp/parity_int8.json")
    ap.add_argument("--quant8", default="dgrad",
                    choices=["dgrad", "wgrad"])
    ap.add_argument("--decay", action="store_true",
                    help="cosine-decay lr to 10%% over the run: the "
                         "gradients shrink into the quantization "
                         "noise floor, the regime the fixed-lr runs "
                         "never test")
    ap.add_argument("--guard-period", type=int, default=0)
    ap.add_argument("--ce-int8", action="store_true")
    ap.add_argument("--remat", default="save_qkv_ffn",
                    help="remat policy for BOTH runs (save_main = the "
                         "committed bench recipe; numerics identical "
                         "modulo f32 reassociation)")
    ap.add_argument("--moment8", action="store_true",
                    help="int8 moment storage on the quantized run "
                         "(the bf16 reference run keeps bf16 moments)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, \
        build_mesh

    cfg = GPTConfig(vocab_size=50304, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=args.heads,
                    max_seq_len=args.seq, dtype=jnp.bfloat16)
    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)

    sched = None
    if args.decay:
        import jax.numpy as jnp2
        T = float(args.steps)
        sched = lambda t: 0.1 + 0.45 * (1 + jnp2.cos(
            jnp2.pi * jnp2.minimum(t / T, 1.0)))

    def make(quant8):
        return GPTSpmdTrainer(
            cfg, mesh, microbatches=1, remat=args.remat,
            moment_dtype=jnp.bfloat16, master_dtype=jnp.bfloat16,
            quant8=quant8, ce_chunks=4 if not args.ce_int8 else 1,
            ce_int8=bool(quant8) and args.ce_int8, seed=0,
            lr_schedule=sched,
            moment8=bool(quant8) and args.moment8,
            int8_guard_period=args.guard_period if quant8 else 0)

    def run(quant8):
        tr = make(quant8)
        r = np.random.RandomState(1234)
        losses = []
        t0 = time.time()
        for s in range(args.steps):
            ids = r.randint(0, cfg.vocab_size,
                            (args.bs, args.seq)).astype(np.int32)
            labels = np.roll(ids, -1, 1)
            loss = tr.train_step(ids, labels)
            if (s + 1) % args.every == 0:
                losses.append(round(float(jax.device_get(loss)), 4))
        dt = time.time() - t0
        return tr, losses, dt

    import gc
    tr8, l8, dt8 = run(args.quant8)
    tr8_events = tr8.guard_events()
    # only one 7.8 GB trainer fits: keep the curves, free the state
    del tr8
    gc.collect()
    trb, lb, dtb = run(False)

    # late-run gradient SNR: exact vs int8 dgrad on the bf16 run's
    # final params, same batch. Toggle quant8 on the SAME trainer so
    # no second parameter set is ever allocated.
    r = np.random.RandomState(99)
    ids = r.randint(0, cfg.vocab_size,
                    (args.bs, args.seq)).astype(np.int32)
    labels = np.roll(ids, -1, 1)

    def grads_of(quant8):
        trb.quant8 = quant8  # read at trace time by _mm()
        with jax.set_mesh(mesh):
            loss, g = jax.jit(jax.value_and_grad(trb._forward_loss))(
                trb.params, jnp.asarray(ids), jnp.asarray(labels))
        return jax.device_get(g)

    g_exact = grads_of(False)
    g_int8 = grads_of(args.quant8)
    snrs = {}
    for k in ("wqkv", "win", "wout", "wproj"):
        a = np.asarray(g_exact["blocks"][k], np.float32)
        b = np.asarray(g_int8["blocks"][k], np.float32)
        err = np.linalg.norm(a - b)
        sig = np.linalg.norm(a)
        snrs[k] = round(float(sig / (err + 1e-30)), 2)

    gaps = [round(abs(a - b), 4) for a, b in zip(l8, lb)]
    result = {
        "steps": args.steps,
        "loss_bf16_first3": lb[:3], "loss_bf16_last3": lb[-3:],
        "quant8": args.quant8, "loss_int8_first3": l8[:3], "loss_int8_last3": l8[-3:],
        "final_gap": round(abs(lb[-1] - l8[-1]), 4),
        "max_gap": max(gaps), "mean_gap": round(float(np.mean(gaps)), 5),
        "grad_snr_at_end": snrs,
        "decay": bool(args.decay),
        "guard_events": getattr(tr8_events, "copy", lambda: [])(),
        "minutes": round((dt8 + dtb) / 60, 1),
    }
    with open(args.out, "w") as f:
        json.dump({"bf16": lb, "int8_" + args.quant8: l8, **result}, f)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
