"""Standalone probe for the Pallas flash-attention kernels.

Times the fwd kernel and the two bwd kernels (dkdv, dq) in isolation at
the flagship shape (B*H=96, S=1024, D=128 by default), across block
configurations, reporting achieved TF/s against the causal-attention
FLOP count.  Work is chained inside ONE jitted scan so the per-program
dispatch floor does not pollute per-kernel numbers.

Usage:
  python benchmarks/probe_flash.py --sweep            # block sweep
  python benchmarks/probe_flash.py --bq 512 --bk 512  # one config
"""
import argparse
import functools
import json
import time

import _path  # noqa: F401


def flops_fwd(BH, S, D, causal=True):
    # QK^T + PV, 2*S*S*D each, halved by causality
    f = 2 * 2 * BH * S * S * D
    return f / 2 if causal else f


def flops_bwd(BH, S, D, causal=True):
    # dkdv kernel: s, dv, dp, dk = 4 block matmuls; dq kernel: s, dp, dq
    # = 3. Each 2*S*S*D, halved by causality.
    f = 7 * 2 * BH * S * S * D
    return f / 2 if causal else f


def timed(fn, *args, iters=20):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    l = jax.tree.leaves(out)[0]
    float(jax.device_get(l.reshape(-1)[0]))
    t0 = time.perf_counter()
    out = fn(*args)
    l = jax.tree.leaves(out)[0]
    float(jax.device_get(l.reshape(-1)[0]))
    dt = time.perf_counter() - t0
    return dt / iters


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--bh", type=int, default=96)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--bq", type=int, default=512)
    ap.add_argument("--bk", type=int, default=512)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--int8", action="store_true",
                    help="probe the int8 fwd kernel variant too")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_ops as po

    BH, S, D = args.bh, args.seq, args.d
    key = jax.random.key(0)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (BH, S, D), jnp.bfloat16)
    k = jax.random.normal(kk, (BH, S, D), jnp.bfloat16)
    v = jax.random.normal(kv, (BH, S, D), jnp.bfloat16)
    g = jax.random.normal(kg, (BH, S, D), jnp.bfloat16)
    scale = 1.0 / (D ** 0.5)

    def make_fwd(bq, bk):
        @jax.jit
        def run(q, k, v):
            def body(c, _):
                out, lse = po._fa_forward(c, k, v, True, scale, bq, bk)
                return q + 0.0 * out, (out[0, 0, 0], lse[0, 0, 0])

            c, outs = jax.lax.scan(body, q, None, length=args.iters)
            return outs

        return run

    def make_bwd(bq, bk):
        @jax.jit
        def run(q, k, v, g):
            out, lse = po._fa_forward(q, k, v, True, scale, bq, bk)

            def body(c, _):
                dq, dk, dv = po._fa_backward(
                    (c, k, v, out, lse), g, True, scale, bq, bk)
                return q + 0.0 * dq, (dq[0, 0, 0], dk[0, 0, 0])

            c, outs = jax.lax.scan(body, q, None, length=args.iters)
            return outs

        return run

    ff, fb = flops_fwd(BH, S, D), flops_bwd(BH, S, D)
    configs = ([(bq, bk) for bq in (256, 512, 1024) for bk in (256, 512, 1024)
                if bq <= S and bk <= S]
               if args.sweep else [(args.bq, args.bk)])
    for bq, bk in configs:
        try:
            tf = timed(make_fwd(bq, bk), q, k, v, iters=args.iters)
            tb = timed(make_bwd(bq, bk), q, k, v, g, iters=args.iters)
        except Exception as e:  # noqa: BLE001 — report per-config failures
            print(json.dumps({"bq": bq, "bk": bk,
                              "error": str(e)[:120]}))
            continue
        print(json.dumps({
            "bq": bq, "bk": bk,
            "fwd_ms": round(tf * 1e3, 3),
            "bwd_ms": round(tb * 1e3, 3),
            "fwd_tfs": round(ff / tf / 1e12, 1),
            "bwd_tfs": round(fb / tb / 1e12, 1),
            "layer24_ms": round((tf + tb) * 24 * 1e3, 1),
        }))


if __name__ == "__main__":
    main()
