"""Cross-process DistModel serving: overhead vs a monolithic Predictor.

On a multi-core/multi-host deployment the two stage processes overlap
(stage k on micro-batch i while stage k+1 runs i-1). THIS host has one
core, so the honest number here is the pipelining TAX: per-batch
latency of the 2-process pipeline vs the same layers served by one
in-process Predictor — socket framing + pickle + process scheduling.
"""
import _path  # noqa: F401  (repo-root import shim)

import json
import os
import tempfile
import time

import numpy as np


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")  # serving-host benchmark
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.jit.static_function import InputSpec
    from paddle_tpu import inference
    from paddle_tpu.inference.dist_model_mp import (DistModelMP,
                                                    DistModelConfig)

    W, B, M = 1024, 32, 4
    paddle.seed(0)

    class Stage(nn.Layer):
        def __init__(self, din, dout):
            super().__init__()
            self.fc1 = nn.Linear(din, W)
            self.fc2 = nn.Linear(W, dout)

        def forward(self, x):
            return self.fc2(nn.functional.relu(self.fc1(x)))

    class Mono(nn.Layer):
        def __init__(self, a, b):
            super().__init__()
            self.a, self.b = a, b

        def forward(self, x):
            return self.b(self.a(x))

    s1, s2 = Stage(64, W), Stage(W, 64)
    s1.eval(), s2.eval()
    mono = Mono(s1, s2)
    mono.eval()
    d = tempfile.mkdtemp()
    p1, p2, pm = (os.path.join(d, n) for n in ("s1", "s2", "mono"))
    paddle.jit.save(s1, p1, input_spec=[
        InputSpec([B // M, 64], "float32", name="x")])
    paddle.jit.save(s2, p2, input_spec=[
        InputSpec([B // M, W], "float32", name="h")])
    paddle.jit.save(mono, pm, input_spec=[
        InputSpec([B // M, 64], "float32", name="x")])

    x = np.random.RandomState(0).randn(B, 64).astype(np.float32)
    micro = [x[i * (B // M):(i + 1) * (B // M)] for i in range(M)]

    pred = inference.create_predictor(inference.Config(pm))
    for mb in micro:
        pred.run([mb])  # compile
    t0 = time.perf_counter()
    runs = 20
    for _ in range(runs):
        for mb in micro:
            pred.run([mb])[0].copy_to_cpu()
    t_mono = (time.perf_counter() - t0) / runs

    with DistModelMP(DistModelConfig([p1, p2],
                                     num_micro_batches=M)) as dm:
        ref = dm.run([x])  # compile both stage programs
        t0 = time.perf_counter()
        for _ in range(runs):
            out = dm.run([x])
        t_mp = (time.perf_counter() - t0) / runs
    mono_out = np.concatenate(
        [pred.run([mb])[0].copy_to_cpu() for mb in micro])
    assert np.allclose(out[0], mono_out, rtol=1e-5, atol=1e-5)

    overhead = t_mp / t_mono - 1.0
    print(json.dumps({
        "metric": f"DistModelMP 2-process 2-stage serving, batch {B} "
                  f"x{M} micro-batches (1-core host: number is the "
                  f"pipeline TAX vs one Predictor; stages overlap on "
                  f"real multi-core/multi-host serving)",
        "value": round(t_mp * 1e3, 2), "unit": "ms/batch",
        "vs_baseline": round(overhead, 4)}))


if __name__ == "__main__":
    main()
