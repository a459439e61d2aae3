"""Fused vocab-chunked cross-entropy (ops/fused_ce.py) vs dense reference,
and the GPTSpmdTrainer mixed-precision / moment-dtype knobs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.fused_ce import fused_softmax_cross_entropy


def _dense(x, head, labels):
    logits = jnp.einsum("btd,dv->btv", x, head,
                        preferred_element_type=jnp.float32)
    lp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1)[..., 0])


@pytest.fixture
def data():
    k = jax.random.key(0)
    D, V, B, T = 64, 512, 2, 16
    x = jax.random.normal(k, (B, T, D), jnp.float32)
    head = jax.random.normal(jax.random.fold_in(k, 1), (D, V)) * 0.05
    labels = jax.random.randint(jax.random.fold_in(k, 2), (B, T), 0, V)
    return x, head, labels


def test_matches_dense_forward(data):
    x, head, labels = data
    a = float(_dense(x, head, labels))
    b = float(fused_softmax_cross_entropy(x, head, labels, 8))
    assert abs(a - b) < 1e-5


def test_matches_dense_gradients(data):
    x, head, labels = data
    ga = jax.grad(lambda x_, h_: _dense(x_, h_, labels), (0, 1))(x, head)
    gb = jax.grad(lambda x_, h_: fused_softmax_cross_entropy(
        x_, h_, labels, 8), (0, 1))(x, head)
    np.testing.assert_allclose(ga[0], gb[0], atol=1e-5)
    np.testing.assert_allclose(ga[1], gb[1], atol=1e-5)


def test_chunk_counts_equivalent(data):
    x, head, labels = data
    ref = float(fused_softmax_cross_entropy(x, head, labels, 1))
    for nc in (2, 4, 16):
        assert abs(float(fused_softmax_cross_entropy(
            x, head, labels, nc)) - ref) < 1e-5


def test_bf16_activations(data):
    x, head, labels = data
    a = float(_dense(x.astype(jnp.bfloat16), head.astype(jnp.bfloat16),
                     labels))
    b = float(fused_softmax_cross_entropy(
        x.astype(jnp.bfloat16), head.astype(jnp.bfloat16), labels, 8))
    assert abs(a - b) < 2e-2


def test_jit_and_labels_out_of_chunk(data):
    x, head, labels = data
    f = jax.jit(lambda x_, h_, l_: fused_softmax_cross_entropy(
        x_, h_, l_, 4))
    assert np.isfinite(float(f(x, head, labels)))


# -- trainer knobs ---------------------------------------------------------

def _tiny_trainer(**kw):
    from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=32, dtype=jnp.float32)
    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
    return GPTSpmdTrainer(cfg, mesh, microbatches=1, **kw)


@pytest.mark.parametrize("kw", [
    dict(moment_dtype=jnp.bfloat16),
    dict(mixed_precision=False),
    dict(remat="save_attn"),
    dict(remat="save_attn_ffn"),
])
def test_trainer_variants_step(kw):
    tr = _tiny_trainer(**kw)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 256, (2, 32)).astype(np.int32)
    lab = np.roll(ids, -1, 1)
    l0 = float(jax.device_get(tr.train_step(ids, lab)))
    for _ in range(3):
        l1 = float(jax.device_get(tr.train_step(ids, lab)))
    assert np.isfinite(l0) and np.isfinite(l1)
    assert l1 < l0  # loss decreases on the overfit batch
    if "moment_dtype" in kw:
        assert tr.opt_state["m"]["wte"].dtype == jnp.bfloat16


def test_fused_loss_used_when_unsharded():
    """With model==sep==1 the trainer takes the fused-CE path; loss must
    equal the dense computation it replaces."""
    tr = _tiny_trainer()
    rng = np.random.RandomState(1)
    ids = rng.randint(0, 256, (2, 32)).astype(np.int32)
    lab = np.roll(ids, -1, 1)
    with jax.set_mesh(tr.mesh):
        loss = float(tr._forward_loss(tr.params, ids, lab))
        x_loss = float(_dense_forward_of_trainer(tr, ids, lab))
    assert abs(loss - x_loss) < 1e-4


def _dense_forward_of_trainer(tr, ids, labels):
    import paddle_tpu.models.gpt as G
    params, cfg = tr.params, tr.cfg
    T = ids.shape[1]
    x = params["wte"].astype(cfg.dtype)[ids] + \
        params["wpe"].astype(cfg.dtype)[jnp.arange(T)][None]
    stage = jax.tree.map(lambda a: a[0], params["blocks"])
    x = tr._stage_fn(stage, x)
    x = G._layer_norm(x, params["ln_f_g"], params["ln_f_b"])
    return _dense(x, params["wte"].T.astype(cfg.dtype), jnp.asarray(labels))


def test_ce_int8_mechanism_close_but_not_default():
    # ce_int8 exists as an OPTION (rejected as a training default:
    # 300-step parity diverges — the rounds-1-5 notes (git history before PR
    # 23) round 4). The
    # mechanism itself must stay numerically sane at one-shot scale.
    import numpy as np
    from paddle_tpu.ops.fused_ce import fused_softmax_cross_entropy
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 16, 64), jnp.float32)
    head = jnp.asarray(rng.randn(64, 256) * 0.5, jnp.float32)
    labels = jnp.asarray(rng.randint(0, 256, (2, 16)))
    le = fused_softmax_cross_entropy(x, head, labels, n_chunks=1)
    li = fused_softmax_cross_entropy(x, head, labels, n_chunks=1,
                                     int8=True)
    assert abs(float(le - li)) < 0.05
    from paddle_tpu.models.gpt import GPTSpmdTrainer
    assert GPTSpmdTrainer.__init__.__defaults__ is not None
    import inspect
    sig = inspect.signature(GPTSpmdTrainer.__init__)
    assert sig.parameters["ce_int8"].default is False


def test_vocab_major_matches_head_major():
    """Tied-embedding layout: head [V, D] with vocab_major=True must
    match head.T-as-[D, V] exactly, loss and grads both."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.fused_ce import fused_softmax_cross_entropy

    rng = np.random.RandomState(0)
    B, T, D, V = 2, 8, 16, 32
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
    wte = jnp.asarray(rng.randn(V, D).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, V, (B, T)))

    def lm(head_dv):
        return fused_softmax_cross_entropy(x, head_dv, labels,
                                           n_chunks=4)

    def lv(head_vd):
        return fused_softmax_cross_entropy(x, head_vd, labels,
                                           n_chunks=4,
                                           vocab_major=True)

    l1, g1 = jax.value_and_grad(lm)(wte.T)
    l2, g2 = jax.value_and_grad(lv)(wte)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g1.T), np.asarray(g2),
                               rtol=1e-5, atol=1e-6)
    # dx parity too
    gx1 = jax.grad(lambda x_: fused_softmax_cross_entropy(
        x_, wte.T, labels, n_chunks=4))(x)
    gx2 = jax.grad(lambda x_: fused_softmax_cross_entropy(
        x_, wte, labels, n_chunks=4, vocab_major=True))(x)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-5, atol=1e-6)


def test_vocab_major_int8_nonsquare():
    """int8 + vocab_major with T != Vc (the GPT shape class): the head
    scales must broadcast on the LAST axis (review r5 finding)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.fused_ce import fused_softmax_cross_entropy

    rng = np.random.RandomState(1)
    B, T, D, V = 2, 6, 16, 32          # T=6 != Vc=8
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
    wte = jnp.asarray(rng.randn(V, D).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, V, (B, T)))
    l_vm = fused_softmax_cross_entropy(x, wte, labels, n_chunks=4,
                                       int8=True, vocab_major=True)
    l_hm = fused_softmax_cross_entropy(x, wte.T, labels, n_chunks=4,
                                       int8=True)
    np.testing.assert_allclose(float(l_vm), float(l_hm), rtol=5e-3)
    # grads run too
    g = jax.grad(lambda w: fused_softmax_cross_entropy(
        x, w, labels, n_chunks=4, int8=True, vocab_major=True))(wte)
    assert np.isfinite(np.asarray(g)).all()
