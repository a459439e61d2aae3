"""BASELINE configs[0]: ResNet-50 single-device — training (AMP-O2
bf16, jitted TrainStep) and inference images/sec on one chip.

Prints one JSON line per phase. CPU smoke mode uses a tiny batch.
"""
import _path  # noqa: F401  (repo-root import shim)

import json
import time

import numpy as np


def build_train_step(bs: int, img_hw: int = 224):
    """Zero-arg AMP-O2 train-step thunk over fixed random data (shared
    by main() and benchmarks/probe_trace.py)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import TrainStep
    from paddle_tpu.vision.models import resnet50

    model = resnet50()
    model.train()
    x = paddle.to_tensor(
        np.random.rand(bs, 3, img_hw, img_hw).astype(np.float32))
    labels = paddle.to_tensor(
        np.random.randint(0, 1000, (bs,)).astype(np.int64))
    opt = paddle.optimizer.Momentum(learning_rate=0.01,
                                    parameters=model.parameters())
    model, opt = paddle.amp.decorate(models=model, optimizers=opt,
                                     level="O2", dtype="bfloat16")
    step = TrainStep(model, opt, paddle.nn.CrossEntropyLoss())

    def amp_step():
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            return step(x, labels)

    return amp_step


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50
    import paddle_tpu.jit as jit

    on_tpu = jax.default_backend() == "tpu"
    bs, steps = (256, 10) if on_tpu else (4, 2)
    img = (bs, 3, 224, 224) if on_tpu else (bs, 3, 32, 32)

    model = resnet50()
    x = paddle.to_tensor(np.random.rand(*img).astype(np.float32))
    labels = paddle.to_tensor(
        np.random.randint(0, 1000, (bs,)).astype(np.int64))

    # -- inference ---------------------------------------------------------
    model.eval()
    fwd = jit.to_static(lambda t: model(t))
    out = fwd(x)
    float(out.sum().numpy())
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fwd(x)
    float(out.sum().numpy())
    dt = (time.perf_counter() - t0) / steps
    print(json.dumps({"metric": "ResNet-50 inference img/s "
                                f"(bs={bs}, fp32)",
                      "value": round(bs / dt, 1), "unit": "img/s",
                      "vs_baseline": None}))

    # -- training (AMP-O2) -------------------------------------------------
    amp_step = build_train_step(bs, img[-1])
    loss = amp_step()
    float(loss.numpy())
    loss = amp_step()
    float(loss.numpy())
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = amp_step()
    float(loss.numpy())
    dt = (time.perf_counter() - t0) / steps
    print(json.dumps({"metric": "ResNet-50 train img/s "
                                f"(bs={bs}, AMP-O2 bf16, "
                                f"loss={float(loss.numpy()):.3f})",
                      "value": round(bs / dt, 1), "unit": "img/s",
                      "vs_baseline": None}))


if __name__ == "__main__":
    main()
