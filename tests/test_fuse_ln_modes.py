"""fuse_ln_quant trainer modes: 3-step loss parity across off/both/
per-site against the shipping default, plus the bad-value guard.
(On CPU every mode runs the shared XLA fallback quantizers, so the
losses must agree to float tolerance — the TPU perf A/B lives in
the rounds-1-5 notes (git history before PR 23).)"""
import numpy as np
import jax.numpy as jnp
import pytest

from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh


def _losses(mode, ids, labels, cfg):
    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
    tr = GPTSpmdTrainer(cfg, mesh, microbatches=1, remat="save_qkv_ffn",
                        quant8="wgrad", ce_chunks=1, seed=0,
                        fuse_ln_quant=mode)
    return [float(tr.train_step(ids, labels)) for _ in range(3)]


def test_fuse_ln_mode_parity():
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=64, dtype=jnp.float32)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 512, (2, 64)).astype(np.int32)
    labels = np.roll(ids, -1, 1)
    base = np.array(_losses(False, ids, labels, cfg))
    for mode in (True, "qkv", "ffn1"):
        got = np.array(_losses(mode, ids, labels, cfg))
        np.testing.assert_allclose(got, base, rtol=0, atol=0.05,
                                   err_msg=str(mode))


def test_fuse_ln_bad_value_raises():
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=1,
                    num_heads=2, max_seq_len=32, dtype=jnp.float32)
    mesh = build_mesh(1, 1, 1, 1, 1)
    with pytest.raises(ValueError, match="fuse_ln_quant"):
        GPTSpmdTrainer(cfg, mesh, quant8="wgrad", fuse_ln_quant="FFN1")
    with pytest.raises(ValueError, match="all-int8"):
        GPTSpmdTrainer(cfg, mesh, quant8="dgrad", fuse_ln_quant=True)
