"""BASELINE configs[3]: ERNIE-3.0 finetune — AMP-O2 + ZeRO-3 group
sharding (GroupShardedStage3 analog: param/grad/optimizer-state sharding
over the dp axis).
"""
import _path  # noqa: F401  (repo-root import shim)

import json
import time

import numpy as np


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.bert import ErnieForSequenceClassification

    on_tpu = jax.default_backend() == "tpu"
    n_dev = 1 if on_tpu else 4
    if on_tpu:
        kw = dict(vocab_size=18000, hidden_size=768, num_hidden_layers=12,
                  num_attention_heads=12, intermediate_size=3072,
                  max_position_embeddings=512)
        B, T, steps = 256, 128, 10
    else:
        kw = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                  num_attention_heads=4, intermediate_size=128,
                  max_position_embeddings=64)
        B, T, steps = 8, 16, 3

    mesh = dist.ProcessMesh(list(range(n_dev)), dim_names=["dp"])
    dist.set_mesh(mesh)
    try:
        paddle.seed(0)
        model = ErnieForSequenceClassification(cfg=None, num_classes=2,
                                               **kw)
        opt = paddle.optimizer.AdamW(learning_rate=2e-5,
                                     parameters=model.parameters())
        model, opt = paddle.amp.decorate(models=model, optimizers=opt,
                                         level="O2", dtype="bfloat16")
        scaler = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 10)
        model, opt, scaler = dist.sharding.group_sharded_parallel(
            model, opt, level="p_g_os", scaler=scaler)

        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(
            rng.randint(0, kw["vocab_size"], (B, T)).astype("int64"))
        y = paddle.to_tensor((np.arange(B) % 2).astype("int64"))

        if on_tpu:
            # one jitted step (eager per-op dispatch is host-latency
            # bound over a remote chip); bf16 needs no loss scaling
            from paddle_tpu.jit.functional import TrainStep
            tstep = TrainStep(model, opt, paddle.nn.CrossEntropyLoss())

            def step():
                with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
                    return tstep(ids, y)
        else:
            def step():
                with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
                    logits = model(ids)
                    loss = paddle.nn.functional.cross_entropy(logits, y)
                scaled = scaler.scale(loss)
                scaled.backward()
                scaler.step(opt)
                scaler.update()
                opt.clear_grad()
                return loss

        lv = float(step())
        lv = float(step())
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step()
        lv = float(loss)
        dt = (time.perf_counter() - t0) / steps
        print(json.dumps({
            "metric": f"ERNIE finetune samples/s (AMP-O2 + ZeRO-3 "
                      f"over {n_dev} dev, loss={lv:.3f})",
            "value": round(B / dt, 1), "unit": "samples/s",
            "vs_baseline": None}))
    finally:
        dist.set_mesh(None)


if __name__ == "__main__":
    main()
