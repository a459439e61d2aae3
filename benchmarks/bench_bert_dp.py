"""BASELINE configs[1]: BERT-base pretraining, data-parallel hot path.

On one real chip: absolute tokens/sec through the jitted DistModel step.
On the virtual CPU mesh (JAX_PLATFORMS=cpu): 1→8 device weak scaling of
the same step — the DP allreduce path the reference drives with
EagerReducer bucketed NCCL (here: GSPMD data-axis sharding; XLA fuses
the gradient allreduce into the backward).
"""
import _path  # noqa: F401  (repo-root import shim)

import json
import time

import numpy as np


def _setup(n_devices, cfg_kw, bs_per_dev, seq, amp=False):
    """(DistModel, ids, labels) — the one model/opt/data construction
    shared by run_dp and build_train_step."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    paddle.seed(0)
    cfg = BertConfig(**cfg_kw)
    model = BertForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    if amp:
        model, opt = paddle.amp.decorate(models=model, optimizers=opt,
                                         level="O2", dtype="bfloat16")

    def loss_fn(*args):
        # model outputs splat first (BertForPretraining returns
        # (mlm_logits, nsp_logits)), labels last
        pred, mlm_labels = args[0], args[-1]
        return paddle.nn.functional.cross_entropy(
            pred.reshape([-1, cfg.vocab_size]),
            mlm_labels.reshape([-1]))

    dm = dist.to_static(model, loss=loss_fn, optimizer=opt)
    B = bs_per_dev * n_devices
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, seq)).astype("int64"))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, seq)).astype("int64"))
    return dm, ids, labels


def run_dp(n_devices, bs_per_dev, seq, cfg_kw, steps):
    import paddle_tpu.distributed as dist

    mesh = dist.ProcessMesh(list(range(n_devices)), dim_names=["dp"])
    dist.set_mesh(mesh)
    try:
        dm, ids, labels = _setup(n_devices, cfg_kw, bs_per_dev, seq)
        float(dm(ids, labels))
        float(dm(ids, labels))
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = dm(ids, labels)
        lv = float(loss)
        dt = (time.perf_counter() - t0) / steps
        return bs_per_dev * n_devices * seq / dt, lv
    finally:
        dist.set_mesh(None)


def build_train_step(bs: int = 32, seq: int = 128, cfg_kw=None,
                     amp: bool = False):
    """Zero-arg single-chip BERT train-step thunk (probe_trace.py);
    ``amp=True`` = AMP-O2 bf16 via amp.decorate + auto_cast (the
    reference BERT pretraining recipe). Single-chip: no global mesh is
    left behind."""
    import paddle_tpu as paddle

    dm, ids, labels = _setup(1, cfg_kw or {}, bs, seq, amp=amp)
    if not amp:
        return lambda: dm(ids, labels)

    def step():
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            return dm(ids, labels)
    return step


def main():
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        # single real chip: absolute number, bert-base. AMP-O2 bf16 at
        # bs 128 is the round-5 recipe (+32% over the r4 f32/bs32
        # number — the rounds-1-5 notes (git history before PR 23) BERT probe)
        import numpy as np_
        bs, seq, steps = 128, 128, 10
        step = build_train_step(bs, seq, amp=True)
        out = step()
        float(np_.asarray(jax.device_get(out._data)))
        out = step()
        float(np_.asarray(jax.device_get(out._data)))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = step()
        lv = float(np_.asarray(jax.device_get(out._data)))
        dt = (time.perf_counter() - t0) / steps
        print(json.dumps({
            "metric": f"BERT-base pretrain tokens/s/chip (AMP-O2 bf16, "
                      f"bs {bs}, loss={lv:.2f})",
            "value": round(bs * seq / dt, 1), "unit": "tokens/s",
            "vs_baseline": None}))
        return
    # virtual 8-device weak scaling
    cfg_kw = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                  num_attention_heads=4, intermediate_size=128,
                  max_position_embeddings=64)
    tps1, _ = run_dp(1, 4, 32, cfg_kw, steps=3)
    tps8, _ = run_dp(8, 4, 32, cfg_kw, steps=3)
    eff = tps8 / (8 * tps1)
    print(json.dumps({
        "metric": "BERT DP weak-scaling 1->8 (virtual mesh: 8 devices "
                  "share one CPU, so this checks the sharded path "
                  "compiles+runs, not true efficiency)",
        "value": round(eff, 3), "unit": "ratio",
        "vs_baseline": round(tps8, 1)}))


if __name__ == "__main__":
    main()
