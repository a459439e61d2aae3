"""Parity of the one-pass Pallas AdamW kernel (ops/fused_adamw.py)
against the trainer's reference update math
(models/gpt.py:GPTSpmdTrainer._adamw), run in interpret mode on CPU.

Reference analog: paddle/phi/kernels/gpu/fused_adam_kernel.cu
(multi-tensor fused Adam) — numerics contract is the plain AdamW
recurrence with decoupled weight decay and bias correction.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.fused_adamw import (fused_adamw_update,
                                        fused_adamw_eligible)

LR, WD, B1, B2, EPS = 3e-4, 0.1, 0.9, 0.95, 1e-8


def _ref_update(p, g, m, v, scale, ib1, ib2):
    gf = g.astype(jnp.float32) * scale
    m2 = B1 * m.astype(jnp.float32) + (1 - B1) * gf
    v2 = B2 * v.astype(jnp.float32) + (1 - B2) * gf * gf
    p2 = p.astype(jnp.float32) * (1 - LR * WD) - \
        LR * (m2 * ib1) / (jnp.sqrt(v2 * ib2) + EPS)
    return p2, m2, v2


def test_eligibility():
    z = jnp.zeros
    assert fused_adamw_eligible(z((512, 1024)))
    assert fused_adamw_eligible(z((1, 24, 2048, 6144)))
    assert not fused_adamw_eligible(z((2048,)))          # rank 1
    assert not fused_adamw_eligible(z((100, 100)))       # lanes % 128
    assert not fused_adamw_eligible(z((8, 128)))         # too small


def test_fp32_parity_exact():
    k = jax.random.key(0)
    R, C = 64, 384  # non-power-of-two lane tile (vocab-remainder case)
    p = jax.random.normal(k, (R, C), jnp.float32)
    g = jax.random.normal(jax.random.fold_in(k, 1), (R, C), jnp.float32)
    m = 0.1 * jax.random.normal(jax.random.fold_in(k, 2), (R, C),
                                jnp.float32)
    v = 0.01 * jnp.abs(jax.random.normal(jax.random.fold_in(k, 3),
                                         (R, C), jnp.float32))
    t = 7
    scale = jnp.float32(0.5)
    ib1 = 1.0 / (1.0 - B1 ** t)
    ib2 = 1.0 / (1.0 - B2 ** t)
    po, mo, vo = fused_adamw_update(
        p, g, m, v, scale, ib1, ib2, 0, lr=LR, wd=WD, b1=B1, b2=B2,
        eps=EPS, stoch_round=False, interpret=True)
    pr, mr, vr = _ref_update(p, g, m, v, scale, ib1, ib2)
    # interpret mode may associate fp32 ops differently: 1-2 ulp
    np.testing.assert_allclose(np.asarray(po), np.asarray(pr),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(mr),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr),
                               rtol=1e-6, atol=1e-7)


def test_bf16_moments_and_grads():
    """Mixed dtypes as the trainer uses them: bf16 p/g/m/v in, bf16
    out, fp32 math inside."""
    k = jax.random.key(1)
    R, C = 32, 256
    p = jax.random.normal(k, (R, C), jnp.bfloat16)
    g = jax.random.normal(jax.random.fold_in(k, 1), (R, C),
                          jnp.bfloat16)
    m = jnp.zeros((R, C), jnp.bfloat16)
    v = jnp.zeros((R, C), jnp.bfloat16)
    po, mo, vo = fused_adamw_update(
        p, g, m, v, 1.0, 1.0 / (1 - B1), 1.0 / (1 - B2), 0,
        lr=LR, wd=WD, b1=B1, b2=B2, eps=EPS, stoch_round=False,
        interpret=True)
    pr, mr, vr = _ref_update(p, g, m, v, jnp.float32(1.0),
                             1.0 / (1 - B1), 1.0 / (1 - B2))
    assert po.dtype == jnp.bfloat16
    for got, want in ((po, pr), (mo, mr), (vo, vr)):
        # fp32 math may differ by ~1 ulp pre-rounding: allow 1 bf16 ulp
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(want.astype(jnp.bfloat16), np.float32),
            rtol=2 ** -7, atol=1e-9)


def test_stochastic_rounding_neighbors_and_unbiased():
    """SR output must be one of the two bf16 neighbors of the fp32
    target, and the mean over seeds must approach the fp32 value."""
    k = jax.random.key(2)
    R, C = 16, 128
    p = jax.random.normal(k, (R, C), jnp.bfloat16)
    g = jax.random.normal(jax.random.fold_in(k, 1), (R, C),
                          jnp.bfloat16)
    m = jnp.zeros((R, C), jnp.bfloat16)
    v = jnp.zeros((R, C), jnp.bfloat16)
    ib1, ib2 = 1.0 / (1 - B1), 1.0 / (1 - B2)
    p_t, _, _ = _ref_update(p, g, m, v, jnp.float32(1.0), ib1, ib2)
    try:
        outs = []
        for s in range(32):
            ps, _, _ = fused_adamw_update(
                p, g, m, v, 1.0, ib1, ib2, s, lr=LR, wd=WD, b1=B1,
                b2=B2, eps=EPS, stoch_round=True, interpret=True)
            outs.append(np.asarray(ps, np.float32))
    except Exception as e:  # pragma: no cover
        pytest.skip(f"pltpu.prng_* unsupported in interpret mode: {e}")
    pt = np.asarray(p_t)
    ulp = np.abs(pt.astype(np.float32)) * 2 ** -7 + 1e-30
    for o in outs:
        assert np.all(np.abs(o - pt) <= ulp * 1.001)
    bias = (np.mean(outs, axis=0) - pt) / ulp
    assert abs(float(np.mean(bias))) < 0.05
    # determinism: same seed -> same bits
    a, _, _ = fused_adamw_update(p, g, m, v, 1.0, ib1, ib2, 5, lr=LR,
                                 wd=WD, b1=B1, b2=B2, eps=EPS,
                                 stoch_round=True, interpret=True)
    b, _, _ = fused_adamw_update(p, g, m, v, 1.0, ib1, ib2, 5, lr=LR,
                                 wd=WD, b1=B1, b2=B2, eps=EPS,
                                 stoch_round=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- int8 moment storage (round-5) --------------------------------------

def test_moment8_eligibility_and_init():
    from paddle_tpu.ops.fused_adamw import (moment8_eligible,
                                            moment8_init)
    z = jnp.zeros
    assert moment8_eligible(z((512, 1024)))
    assert moment8_eligible(z((24, 2048, 6144)))
    # vocab-head rows too wide for a full-row VMEM block -> bf16 path
    assert not moment8_eligible(z((2048, 50304)))
    assert not moment8_eligible(z((2048,)))
    mq, msc, vq, vsc = moment8_init(z((24, 2048, 6144)))
    assert mq.shape == (24 * 2048, 6144) and mq.dtype == jnp.int8
    assert msc.shape == (24 * 2048, 1) and msc.dtype == jnp.float32
    assert vq.shape == mq.shape and vsc.shape == msc.shape


def test_moment8_unpack_roundtrip():
    from paddle_tpu.ops.fused_adamw import moment8_unpack
    rng = np.random.RandomState(0)
    R, C = 16, 256
    m = rng.randn(R, C).astype(np.float32)
    v = np.abs(rng.randn(R, C)).astype(np.float32) * 1e-4
    # quantize by the kernel's rule (RTN here; kernel uses SR)
    ms = np.abs(m).max(1, keepdims=True) / 127.0
    mq = np.clip(np.round(m / ms), -127, 127).astype(np.int8)
    s = np.sqrt(v)
    vs = s.max(1, keepdims=True) / 127.0
    vq = np.clip(np.round(s / vs), 0, 127).astype(np.int8)
    m2, v2 = moment8_unpack(jnp.asarray(mq), jnp.asarray(ms),
                            jnp.asarray(vq), jnp.asarray(vs), (R, C))
    np.testing.assert_allclose(np.asarray(m2), m, atol=float(ms.max()))
    # v reconstructs through sqrt-domain quantization: tolerance is
    # one sqrt-step around each value
    np.testing.assert_allclose(np.sqrt(np.asarray(v2)), s,
                               atol=float(vs.max()))


def test_moment8_kernel_interpret_or_skip():
    """The int8-moment kernel always draws SR bits, so it runs only
    where pltpu.prng_* exists (TPU); interpret mode documents the
    skip the same way the SR-master path does."""
    from paddle_tpu.ops.fused_adamw import (fused_adamw_update8,
                                            moment8_init)
    k = jax.random.key(0)
    R, C = 64, 256
    p = jax.random.normal(k, (R, C), jnp.float32)
    g = jax.random.normal(jax.random.fold_in(k, 1), (R, C), jnp.float32)
    mq, msc, vq, vsc = moment8_init(p)
    try:
        p2, mq2, ms2, vq2, vs2 = fused_adamw_update8(
            p, g, mq, msc, vq, vsc, 1.0, 1.0, 1.0, 3,
            lr=LR, wd=WD, b1=B1, b2=B2, interpret=True)
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"pltpu.prng_* unsupported in interpret mode: {e}")
    # from zero state: m2 = (1-b1) g, v2 = (1-b2) g^2 — check the
    # dequantized m is within one SR step of the reference
    from paddle_tpu.ops.fused_adamw import moment8_unpack
    m2, v2 = moment8_unpack(mq2, ms2, vq2, vs2, (R, C))
    ref = (1 - B1) * np.asarray(g, np.float32)
    step = np.asarray(ms2).max()
    assert np.abs(np.asarray(m2) - ref).max() <= step + 1e-6


def test_trainer_moment8_requires_fused():
    from paddle_tpu.models.gpt import (GPTConfig, GPTSpmdTrainer,
                                       build_mesh)
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=1,
                    num_heads=2, max_seq_len=32, dtype=jnp.float32)
    with pytest.raises(ValueError, match="moment8"):
        GPTSpmdTrainer(cfg, build_mesh(1, 1, 1, 1, 1),
                       fused_optimizer=False, moment8=True)


def test_moment8_state_checkpoint_roundtrip(tmp_path):
    """(q, scale) tuple leaves must survive paddle.save/load with their
    TUPLE-ness intact — _adamw dispatches on isinstance(leaf, tuple),
    so a serializer that returns lists would silently break resume.
    (Full TPU resume verified live on-chip; the rounds-1-5 notes (git history
    before PR 23) round-5.)"""
    import paddle_tpu as paddle
    from paddle_tpu.ops.fused_adamw import moment8_init
    mq, msc, vq, vsc = moment8_init(jnp.zeros((64, 256)))
    state = {"step": jnp.ones((), jnp.int32),
             "m": {"w": (mq, msc), "b": jnp.zeros((8,))},
             "v": {"w": (vq, vsc), "b": jnp.zeros((8,))}}
    p = str(tmp_path / "m8.pdparams")
    paddle.save(state, p)
    got = paddle.load(p)
    assert isinstance(got["m"]["w"], tuple) and len(got["m"]["w"]) == 2
    assert isinstance(got["v"]["w"], tuple)
    q2, s2 = got["m"]["w"]
    assert np.asarray(q2).dtype == np.int8
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(mq))
    np.testing.assert_allclose(np.asarray(s2), np.asarray(msc))


def test_moment8_state_without_fused_optimizer_diagnoses():
    """int8 (q, scale) moment pairs reaching a non-fused trainer must
    fail with the diagnosis, not an UnboundLocalError (e.g. a moment8
    checkpoint resumed on a CPU debug trainer)."""
    from paddle_tpu.models.gpt import (GPTConfig, GPTSpmdTrainer,
                                       build_mesh)
    from paddle_tpu.ops.fused_adamw import moment8_init
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=1,
                    num_heads=2, max_seq_len=32, dtype=jnp.float32)
    tr = GPTSpmdTrainer(cfg, build_mesh(1, 1, 1, 1, 1), microbatches=1,
                        fused_optimizer=False)
    mq, msc, vq, vsc = moment8_init(jnp.zeros((256, 128)))
    tr.opt_state["m"]["wte"] = (mq, msc)
    tr.opt_state["v"]["wte"] = (vq, vsc)
    ids = np.zeros((2, 32), np.int32)
    with pytest.raises(RuntimeError, match="int8 .q, scale."):
        tr.train_step(ids, ids)
