"""int8 drift guard + dynamic lr schedule (round 4; the rounds-1-5 notes (git
history before PR 23) wqkv
SNR ~1 finding is why the default is watched, not assumed)."""
import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh


def _setup(**kw):
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=32, dtype=jnp.float32)
    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
    tr = GPTSpmdTrainer(cfg, mesh, microbatches=1, remat=False,
                        use_flash=False, **kw)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 256, (2, 32)).astype(np.int32)
    return tr, ids, np.roll(ids, -1, 1)


def test_guard_quiet_on_healthy_weights():
    tr, ids, labels = _setup(quant8="wgrad", int8_guard_period=2)
    for _ in range(4):
        tr.train_step(ids, labels)
    assert tr.guard_events() == []
    assert tr.quant8 == "wgrad"


def test_guard_walks_fallback_ladder():
    # threshold below any real quantization error: wgrad -> dgrad ->
    # exact, recompiling the step each time, training uninterrupted
    tr, ids, labels = _setup(quant8="wgrad", int8_guard_period=1,
                             int8_guard_threshold=1e-9)
    for _ in range(3):
        loss = tr.train_step(ids, labels)
    steps = [(e["from"], e["to"]) for e in tr.guard_events()]
    assert steps == [("wgrad", "dgrad"), ("dgrad", False)]
    assert tr.quant8 is False
    assert np.isfinite(float(jax.device_get(loss)))
    # once exact, the guard has nothing to watch: no more events
    tr.train_step(ids, labels)
    assert len(tr.guard_events()) == 2


def test_guard_measures_sane_magnitude():
    tr, ids, _ = _setup(quant8="dgrad", int8_guard_period=1)
    r = tr._run_guard(jnp.asarray(ids))
    # int8 per-matmul relative error is a few percent, never zero
    assert 1e-4 < r < 0.2
    assert tr.guard_events() == []


def test_lr_schedule_decays_update():
    sched = lambda t: 0.5 * (1 + jnp.cos(
        jnp.pi * jnp.minimum(t / 8.0, 1.0)))
    tr, ids, labels = _setup(lr_schedule=sched)
    p0 = np.asarray(jax.device_get(tr.params["blocks"]["wqkv"]))
    tr.train_step(ids, labels)
    d_early = float(np.abs(p0 - np.asarray(
        jax.device_get(tr.params["blocks"]["wqkv"]))).mean())
    for _ in range(9):
        tr.train_step(ids, labels)   # cosine reaches 0 at t=8
    p_late = np.asarray(jax.device_get(tr.params["blocks"]["wqkv"]))
    tr.train_step(ids, labels)
    d_late = float(np.abs(p_late - np.asarray(
        jax.device_get(tr.params["blocks"]["wqkv"]))).mean())
    # weight-decay term also scales with the multiplier, so the late
    # update must be far smaller than the first step's
    assert d_late < d_early * 0.2
