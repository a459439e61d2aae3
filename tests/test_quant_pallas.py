"""Single-pass Pallas quantize kernel vs the two-pass XLA reference.

The kernels (`ops/quant_matmul.py::_rowq_kernel/_colq_kernel`) must be
bit-identical to `quantize_rowwise`: same amax, same round-half-even,
same clip. Run under interpret=True on the CPU mesh; the real-TPU
engagement is exercised by bench_gpt_hybrid (quant8 defaults).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.quant_matmul import (quantize_rowwise,
                                         quantize_rowwise_fast)


def _check(x, axis):
    q0, s0 = quantize_rowwise(x, axis)
    q1, s1 = quantize_rowwise_fast(x, axis, interpret=True)
    # XLA may fold /127.0 to a reciprocal multiply on one path and not
    # the other — allow 1 ULP on the scale, which can shift a value
    # sitting exactly on a rounding boundary by one quantization step
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1),
                               rtol=1e-6)
    dq = np.abs(np.asarray(q0, np.int32) - np.asarray(q1, np.int32))
    assert dq.max() <= 1 and (dq != 0).mean() < 0.01
    assert q1.dtype == jnp.int8 and s1.shape == s0.shape


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_row_quantize_2d(dtype):
    x = jax.random.normal(jax.random.key(0), (64, 256), dtype)
    _check(x, axis=-1)
    _check(x, axis=1)


def test_row_quantize_3d():
    x = jax.random.normal(jax.random.key(1), (4, 16, 384), jnp.bfloat16)
    _check(x, axis=-1)


def test_col_quantize_weight():
    w = jax.random.normal(jax.random.key(2), (256, 384), jnp.bfloat16)
    _check(w, axis=0)


def test_zero_row_scale_is_one():
    x = jnp.zeros((16, 128), jnp.float32).at[0, 0].set(3.0)
    q, s = quantize_rowwise_fast(x, axis=-1, interpret=True)
    np.testing.assert_allclose(np.asarray(s[1:]),
                               np.full((15, 1), 1.0 / 127.0, np.float32),
                               rtol=0, atol=0)
    assert int(q[0, 0]) == 127


def test_unaligned_shapes_fall_back():
    # lane-unaligned K and odd row counts must route to the XLA path
    x = jax.random.normal(jax.random.key(3), (7, 100), jnp.float32)
    q0, s0 = quantize_rowwise(x, -1)
    q1, s1 = quantize_rowwise_fast(x, -1, interpret=True)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


# -- stochastic-rounding column quantize + int8 wgrad (round 4) ----------

def test_sr_colwise_unbiased_xla_path():
    from paddle_tpu.ops.quant_matmul import _sr_colq_xla
    x = jax.random.normal(jax.random.key(7), (64, 128), jnp.float32)
    acc = np.zeros(x.shape, np.float64)
    n = 96
    for s in range(n):
        q, sc = _sr_colq_xla(x, jnp.int32(s))
        assert q.dtype == jnp.int8 and sc.shape == (1, 128)
        acc += np.asarray(q.astype(jnp.float32) * sc, np.float64)
    acc /= n
    lsb = np.asarray(jnp.max(jnp.abs(x), axis=0) / 127.0).mean()
    bias = np.abs(acc - np.asarray(x)).mean()
    # SR noise is +-0.5 LSB uniform; averaging n draws leaves
    # ~LSB/sqrt(12 n) — assert within 4x of that
    assert bias < 4 * lsb / np.sqrt(12 * n)


def test_sr_colwise_zero_column_scale_is_one():
    from paddle_tpu.ops.quant_matmul import _sr_colq_xla
    x = jnp.zeros((16, 128), jnp.float32).at[3, 5].set(-2.0)
    q, s = _sr_colq_xla(x, jnp.int32(0))
    cols = np.asarray(s)[0]
    assert cols[5] == np.float32(2.0 / 127.0)
    others = np.delete(cols, 5)
    np.testing.assert_allclose(others, 1.0 / 127.0, rtol=1e-6)
    assert int(q[3, 5]) in (-127, -126)  # SR can round either way


def test_int8_linear_all8_grads_close_and_unbiased():
    from paddle_tpu.ops.quant_matmul import int8_linear_all8
    kx, kw, kg = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(kx, (4, 32, 128), jnp.float32)
    w = jax.random.normal(kw, (128, 256), jnp.float32) * 0.1
    g = jax.random.normal(kg, (4, 32, 256), jnp.float32)

    def f8(x, w, s):
        return jnp.sum(int8_linear_all8(x, w, s) * g)

    def fe(x, w):
        return jnp.sum(jnp.einsum("btd,df->btf", x, w) * g)

    dx8, dw8, ds = jax.grad(f8, argnums=(0, 1, 2), allow_int=True)(
        x, w, jnp.int32(5))
    dxe, dwe = jax.grad(fe, argnums=(0, 1))(x, w)
    assert float(jnp.linalg.norm(dw8 - dwe) / jnp.linalg.norm(dwe)) < 0.06
    assert float(jnp.linalg.norm(dx8 - dxe) / jnp.linalg.norm(dxe)) < 0.06
    assert ds.dtype == jax.dtypes.float0  # seed carries no gradient

    # unbiasedness: averaging wgrad over seeds converges to exact
    acc = np.zeros(dwe.shape, np.float64)
    n = 48
    for s in range(n):
        _, dws, _ = jax.grad(f8, argnums=(0, 1, 2), allow_int=True)(
            x, w, jnp.int32(s))
        acc += np.asarray(dws, np.float64)
    acc /= n
    bias = float(np.linalg.norm(acc - np.asarray(dwe)) /
                 np.linalg.norm(dwe))
    per_draw = float(jnp.linalg.norm(dw8 - dwe) / jnp.linalg.norm(dwe))
    assert bias < 3 * per_draw / np.sqrt(n)


def test_wgrad_trainer_smoke_cpu():
    # quant8="wgrad" end-to-end on the CPU mesh: runs, loss finite,
    # close to the exact-bf16 step at tiny scale
    from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=64, dtype=jnp.float32)
    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 512, (2, 64)).astype(np.int32)
    labels = np.roll(ids, -1, 1)
    losses = {}
    for q8 in (False, "wgrad"):
        tr = GPTSpmdTrainer(cfg, mesh, microbatches=1, remat=False,
                            quant8=q8, seed=0, use_flash=False)
        for _ in range(3):
            loss = tr.train_step(ids, labels)
        losses[q8] = float(jax.device_get(loss))
    assert np.isfinite(losses["wgrad"])
    assert abs(losses["wgrad"] - losses[False]) < 0.05


def test_wgrad_trainer_no_tracer_leak():
    # Tracing the step must not leave traced state on the trainer: a
    # later direct _forward_loss trace (the parity harness pattern)
    # would hit UnexpectedTracerError if step() mutated self with a
    # tracer (round-4 review finding).
    from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=32, dtype=jnp.float32)
    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
    tr = GPTSpmdTrainer(cfg, mesh, microbatches=1, remat=False,
                        quant8="wgrad", seed=0, use_flash=False)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 256, (2, 32)).astype(np.int32)
    labels = np.roll(ids, -1, 1)
    tr.train_step(ids, labels)
    with jax.set_mesh(mesh):
        loss, g = jax.jit(jax.value_and_grad(tr._forward_loss))(
            tr.params, jnp.asarray(ids), jnp.asarray(labels))
    assert np.isfinite(float(jax.device_get(loss)))


def test_wgrad_microbatches_fold_seed():
    # M>1 path: runs, and distinct microbatch streams change nothing
    # about correctness (loss finite, near exact)
    from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh
    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=32, dtype=jnp.float32)
    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
    tr = GPTSpmdTrainer(cfg, mesh, microbatches=2, remat=False,
                        quant8="wgrad", seed=0, use_flash=False)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 256, (4, 32)).astype(np.int32)
    labels = np.roll(ids, -1, 1)
    for _ in range(2):
        loss = tr.train_step(ids, labels)
    assert np.isfinite(float(jax.device_get(loss)))


# -- round-5 producer-fused gelu->quantize (lever d) -------------------

def test_act_fused_rowq_matches_gelu_then_quant():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.quant_matmul import (quantize_rowwise,
                                             quantize_rowwise_fast)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 256).astype(np.float32))
    q1, s1 = quantize_rowwise_fast(x, axis=-1, act="gelu",
                                   interpret=True)
    q2, s2 = quantize_rowwise(jax.nn.gelu(x, approximate=True), -1)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5)
    # rounding at +-0.5 boundaries may flip the odd value
    assert (np.asarray(q1) == np.asarray(q2)).mean() > 0.999


def test_int8_gelu_linear_all8_matches_unfused():
    """Fused gelu+int8 matmul == int8_linear_all8(gelu(x)) in fwd and
    grads (same seeds -> same SR streams on the wgrad side; dgrad adds
    the gelu' chain)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.quant_matmul import (int8_gelu_linear_all8,
                                             int8_linear_all8)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(32, 128).astype(np.float32))
    w = jnp.asarray(rng.randn(128, 192).astype(np.float32) * 0.1)
    seed = jnp.int32(17)

    def fused(x, w):
        return (int8_gelu_linear_all8(x, w, seed) ** 2).sum()

    def unfused(x, w):
        a = jax.nn.gelu(x, approximate=True)
        return (int8_linear_all8(a, w, seed) ** 2).sum()

    f1, (gx1, gw1) = jax.value_and_grad(fused, argnums=(0, 1))(x, w)
    f2, (gx2, gw2) = jax.value_and_grad(unfused, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(f1), float(f2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-3, atol=1e-4)


# -- round-5 producer-fused LayerNorm->quantize (lever a) ---------------

def _ref_ln(x, g, b, eps=1e-5):
    xf = np.asarray(x, np.float32)
    m = xf.mean(-1, keepdims=True)
    v = xf.var(-1, keepdims=True)
    return (xf - m) / np.sqrt(v + eps) * np.asarray(g, np.float32) \
        + np.asarray(b, np.float32)


def test_ln_fused_rowq_matches_ln_then_quant():
    from paddle_tpu.ops.quant_matmul import (ln_quantize_rowwise,
                                             quantize_rowwise)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 256).astype(np.float32) * 3 + 0.5)
    g = jnp.asarray(rng.rand(256).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(256).astype(np.float32) * 0.1)
    q1, s1, m1, r1 = ln_quantize_rowwise(x, g, b, interpret=True)
    href = _ref_ln(x, g, b)
    q2, s2 = quantize_rowwise(jnp.asarray(href), -1)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5)
    assert (np.asarray(q1) == np.asarray(q2)).mean() > 0.999
    np.testing.assert_allclose(np.asarray(m1)[:, 0],
                               np.asarray(x, np.float32).mean(-1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(r1)[:, 0],
        1.0 / np.sqrt(np.asarray(x, np.float32).var(-1) + 1e-5),
        rtol=1e-4)


def test_int8_ln_linear_all8_matches_unfused():
    """Fused LN+int8 matmul == int8_linear_all8(layer_norm(x)) in fwd
    and all four grads (x, ln gamma/beta, w); same seeds -> same SR
    streams on the wgrad side."""
    from paddle_tpu.ops.quant_matmul import (int8_ln_linear_all8,
                                             int8_linear_all8)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(32, 128).astype(np.float32))
    g = jnp.asarray(rng.rand(128).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(128).astype(np.float32) * 0.1)
    w = jnp.asarray(rng.randn(128, 192).astype(np.float32) * 0.1)
    seed = jnp.int32(17)

    def _ln(x, g, b, eps=1e-5):
        m = x.mean(-1, keepdims=True)
        v = ((x - m) ** 2).mean(-1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + eps) * g + b

    def fused(x, g, b, w):
        return (int8_ln_linear_all8(x, g, b, w, seed) ** 2).sum()

    def unfused(x, g, b, w):
        return (int8_linear_all8(_ln(x, g, b), w, seed) ** 2).sum()

    f1, g1 = jax.value_and_grad(fused, argnums=(0, 1, 2, 3))(x, g, b, w)
    f2, g2 = jax.value_and_grad(unfused, argnums=(0, 1, 2, 3))(x, g, b, w)
    np.testing.assert_allclose(float(f1), float(f2), rtol=1e-5)
    for a1, a2 in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                                   rtol=1e-3, atol=1e-3)


def test_sr_colq_ln_matches_ln_then_colq():
    from paddle_tpu.ops.quant_matmul import (sr_quantize_colwise,
                                             sr_quantize_colwise_ln)
    if jax.default_backend() == "tpu":
        pytest.skip("the fused/unfused SR kernels derive per-tile PRNG "
                    "seeds differently on TPU; the identical-stream "
                    "premise only holds on the shared XLA fallback")
    rng = np.random.RandomState(2)
    x = rng.randn(24, 128).astype(np.float32)
    g = rng.rand(128).astype(np.float32) + 0.5
    b = rng.randn(128).astype(np.float32) * 0.1
    m = x.mean(-1, keepdims=True)
    r = 1.0 / np.sqrt(x.var(-1, keepdims=True) + 1e-5)
    h = (x - m) * r * g + b
    seed = jnp.int32(23)
    q1, s1 = sr_quantize_colwise_ln(jnp.asarray(x), jnp.asarray(m),
                                    jnp.asarray(r), jnp.asarray(g),
                                    jnp.asarray(b), seed)
    q2, s2 = sr_quantize_colwise(jnp.asarray(h), seed)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5)
    # identical SR streams + near-identical inputs: stray one-step
    # differences only at float boundaries
    dq = np.abs(np.asarray(q1, np.int32) - np.asarray(q2, np.int32))
    assert dq.max() <= 1 and (dq != 0).mean() < 0.01


# -- PR 28: the form of the int8 weight-gradient contraction --------------

def _wgrad_count(form):
    from paddle_tpu.observability.registry import default_registry
    fam = default_registry().get("ptpu_int8_wgrad_sites_total")
    return 0.0 if fam is None else fam.labels(form=form).value


@pytest.mark.parametrize("form", ["rule", "km"])
@pytest.mark.parametrize("K,N", [(128, 384), (128, 512), (512, 128)],
                         ids=["K<N", "K<<N", "K>>N"])
@pytest.mark.parametrize("site", ["linear", "gelu_linear", "ln_linear"])
def test_int8_wgrad_form_bit_identical(site, K, N, form, monkeypatch):
    """In either form the weight gradient of every all-int8 site is the
    reference ``dot_general(xq, gq, contract (0),(0))`` dequantised as
    before PR 28, bit for bit, and the counter reports the form taken:
    the one the shape rule promises (``rule``; on this backend ``kn``),
    or ``km`` with the left operand handed over as [K, M] (the XLA
    quantizer transposed stands in for the kernel, which needs the
    chip's PRNG; tests/test_chip_compile.py compiles the kernel)."""
    from paddle_tpu.ops import quant_matmul as qm
    M = 64
    if form == "rule":
        form = qm._wgrad_form(M, K)
    else:
        monkeypatch.setattr(qm, "_wgrad_form", lambda M, K: form)
    seen = []
    inner = qm._wgrad_int8

    def recording(xq, xs, gq, gs, out_dtype, form):
        dw = inner(xq, xs, gq, gs, out_dtype, form)
        seen.append((xq, xs, gq, gs, form, dw))
        return dw

    monkeypatch.setattr(qm, "_wgrad_int8", recording)
    rng = np.random.RandomState(K + N)
    x = jnp.asarray(rng.randn(2, M // 2, K).astype(np.float32))
    w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1)
    g_ln = jnp.asarray(rng.rand(K).astype(np.float32) + 0.5)
    b_ln = jnp.asarray(rng.randn(K).astype(np.float32) * 0.1)
    seed = jnp.int32(11)
    fn = {"linear": lambda w: qm.int8_linear_all8(x, w, seed),
          "gelu_linear": lambda w: qm.int8_gelu_linear_all8(x, w, seed),
          "ln_linear": lambda w: qm.int8_ln_linear_all8(
              x, g_ln, b_ln, w, seed)}[site]
    before = _wgrad_count(form)
    dw = jax.grad(lambda w: (fn(w) ** 2).sum())(w)
    assert _wgrad_count(form) == before + 1
    (xq, xs, gq, gs, taken, got), = seen
    assert taken == form
    assert xq.shape == ((K, M) if form == "km" else (M, K))
    assert gq.shape == (M, N)
    y = jax.lax.dot_general(xq.T if form == "km" else xq, gq,
                            (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.int32)
    ref = (y.astype(jnp.float32) * xs.reshape(K, 1) * gs).astype(w.dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(ref))


def test_int8_wgrad_form_rule(monkeypatch):
    """``km`` exactly where the Pallas SR quantizer runs on the left
    operand [M, K] and M fills whole lanes."""
    from paddle_tpu.ops import quant_matmul as qm
    assert {qm._wgrad_form(6144, K) for K in (2048, 8192)} == {"kn"}
    monkeypatch.setattr(qm, "single_device_tpu", lambda: True)
    assert {qm._wgrad_form(6144, K) for K in (2048, 8192)} == {"km"}
    assert qm._wgrad_form(6144 + 8, 2048) == "kn"    # M % 128
    assert qm._wgrad_form(6144, 2048 + 64) == "kn"   # K % 128
    assert qm._wgrad_form(16384, 2048) == "kn"       # block > VMEM
