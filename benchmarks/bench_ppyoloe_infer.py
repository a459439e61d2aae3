"""BASELINE configs[4]: PP-YOLOE inference — static export (StableHLO)
through the serving Predictor, latency + throughput (the reference's
AnalysisPredictor/TensorRT path).
"""
import _path  # noqa: F401  (repo-root import shim)

import json
import os
import tempfile
import time

import numpy as np


def _bench_one(path, x, steps, precision=None):
    import jax
    from paddle_tpu import inference
    cfg = inference.Config(path)
    if precision is not None:
        cfg.set_precision(precision)
    predictor = inference.create_predictor(cfg)
    name = predictor.get_input_names()[0]
    h = predictor.get_input_handle(name)
    h.copy_from_cpu(x)
    predictor.run()
    # device-resident zero-copy path (reference ZeroCopyRun contract:
    # input/output handles stay on device between runs). Drain with a
    # device-side scalar: full-output host copies measure the link to
    # the chip, not the predictor.
    drain = lambda: float(jax.device_get(predictor.get_output_handle(  # noqa: E731
        predictor.get_output_names()[0])._value.sum()))
    drain()
    t0 = time.perf_counter()
    for _ in range(steps):
        predictor.run()
    drain()
    return (time.perf_counter() - t0) / steps


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.jit as jit
    from paddle_tpu import inference, nn
    from paddle_tpu.vision.models import ppyoloe_s

    on_tpu = jax.default_backend() == "tpu"
    size, bs, steps = ((640, 8, 10) if on_tpu else (64, 1, 2))

    model = ppyoloe_s()
    model.eval()
    x = np.random.rand(bs, 3, size, size).astype(np.float32)

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "ppyoloe")
        jit.save(jit.to_static(model), path,
                 input_spec=[jit.InputSpec([bs, 3, size, size],
                                           "float32")])
        dt = _bench_one(path, x, steps)

        # PTQ real-int8: calibrate on the bench input, convert the convs
        # and linears to int8-MXU layers, export the int8 program
        from paddle_tpu.quantization import PTQ, QuantConfig
        from paddle_tpu.quantization.observers import AbsmaxObserver
        qcfg = QuantConfig(activation=None, weight=None)
        qcfg.add_type_config([nn.Conv2D, nn.Linear],
                             activation=AbsmaxObserver, weight=None)
        ptq = PTQ(qcfg)
        observed = ptq.quantize(model)
        observed(paddle.to_tensor(x))
        qmodel = ptq.convert(observed, real=True)
        qpath = os.path.join(td, "ppyoloe_int8")
        jit.save(jit.to_static(qmodel), qpath,
                 input_spec=[jit.InputSpec([bs, 3, size, size],
                                           "float32")])
        dt8 = _bench_one(qpath, x, steps)
    print(json.dumps({
        "metric": f"PP-YOLOE-s infer latency (bs={bs}, {size}x{size}, "
                  f"StableHLO predictor)",
        "value": round(dt * 1000, 2), "unit": "ms",
        "vs_baseline": round(bs / dt, 1),
        "int8_ms": round(dt8 * 1000, 2),
        "int8_img_per_s": round(bs / dt8, 1)}))


if __name__ == "__main__":
    main()
