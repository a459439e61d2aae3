"""Chrome-trace (xplane) decomposition of a training step.

Runs N steps of the flagship GPT trainer (or ResNet-50 with --model
resnet) under jax.profiler, then prints the per-op device-time ledger
via the self-contained xplane parser — the tool behind the rounds-1-5 notes
(git history before PR 23)
step waterfalls.

  python benchmarks/probe_trace.py --steps 3 [--top 25]
  python benchmarks/probe_trace.py --model resnet --bs 256
"""
import argparse
import json
import tempfile

import _path  # noqa: F401

import xplane


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt",
                    choices=["gpt", "resnet", "bert"])
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--bs", type=int, default=0)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--raw", action="store_true",
                    help="dump every op, not just top-N + buckets")
    ap.add_argument("--fuse-ln", action="store_true",
                    help="enable the (default-off) LN->quantize fusion")
    ap.add_argument("--unroll", default="full",
                    help="layer_unroll: 'full' (per-layer pytree, the "
                         "round-6 default) or an int scan-unroll")
    args = ap.parse_args()

    import jax
    import numpy as np

    if args.model == "gpt":
        import jax.numpy as jnp

        from paddle_tpu.models.gpt import (GPTConfig, GPTSpmdTrainer,
                                           build_mesh)
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048,
                        num_layers=24, num_heads=16, max_seq_len=1024,
                        dtype=jnp.bfloat16)
        mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
        trainer = GPTSpmdTrainer(cfg, mesh, microbatches=1,
                                 remat="save_main",
                                 moment_dtype=jnp.bfloat16,
                                 master_dtype=jnp.bfloat16,
                                 quant8="wgrad", ce_chunks=1,
                                 moment8=True,
                                 layer_unroll=args.unroll
                                 if args.unroll == "full"
                                 else int(args.unroll),
                                 fuse_ln_quant=args.fuse_ln)
        bs = args.bs or 6
        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (bs, 1024)).astype(np.int32)
        labels = np.roll(ids, -1, 1)

        def step():
            return trainer.train_step(ids, labels)
    elif args.model == "resnet":
        from bench_resnet50 import build_train_step
        step = build_train_step(args.bs or 256)
    else:
        from bench_bert_dp import build_train_step
        step = build_train_step(args.bs or 32)

    def drain(out):
        # paddle Tensor or raw jax array/loss tuple
        arr = getattr(out, "_data", None)
        if arr is None:
            arr = jax.tree.leaves(out)[0]
        float(jax.device_get(arr).reshape(-1)[0])

    # warm up / compile outside the trace window
    for _ in range(2):
        out = step()
    drain(out)

    logdir = tempfile.mkdtemp(prefix="ptpu_trace_")
    jax.profiler.start_trace(logdir)
    for _ in range(args.steps):
        out = step()
    drain(out)
    jax.profiler.stop_trace()

    path = xplane.latest_xplane(logdir)
    per_line = xplane.op_self_times(path)
    if not per_line:
        print(f"# {path}: no TPU plane in trace (CPU run?) — nothing "
              f"to decompose")
        return
    ops_line = "XLA Ops" if "XLA Ops" in per_line else \
        max(per_line, key=lambda k: len(per_line[k]))
    per_step = {k: v / args.steps for k, v in per_line[ops_line].items()}
    print(f"# {path} (line {ops_line!r}; self-times)")
    print(f"# total device ms/step: "
          f"{sum(per_step.values()):.1f}")
    print("## buckets (ms/step)")
    for name, ms in xplane.bucketize(per_step):
        print(f"{ms:9.2f}  {name}")
    print(f"## top {args.top} ops (ms/step)")
    items = sorted(per_step.items(), key=lambda kv: -kv[1])
    for name, ms in (items if args.raw else items[:args.top]):
        print(f"{ms:9.3f}  {name[:110]}")
    print(json.dumps({"total_ms_per_step":
                      round(sum(per_step.values()), 1)}))
    # the machine-checked form of the bucket table above (round 6)
    import step_budget
    print(step_budget.format_line(step_budget.budget_from_times(
        per_line[ops_line], steps=args.steps, line=ops_line,
        plane="TPU")))


if __name__ == "__main__":
    main()
