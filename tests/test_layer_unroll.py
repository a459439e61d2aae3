"""Round-6 tentpole coverage: the per-layer-pytree unrolled stage
(layer_unroll="full") must be arithmetically IDENTICAL to the rolled
scan — same forward, same grads, same SR streams — while storing blocks
params as per-layer leaves (no [S, L, ...] stacking anywhere, which is
what kills the DUS residual-stacking copy traffic on TPU). Plus the
fuse_bwd_colq knob (ADVICE r5) and the dtype-discipline helpers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.gpt import GPTConfig, GPTSpmdTrainer, build_mesh

CFG = dict(vocab_size=256, hidden_size=32, num_layers=4, num_heads=4,
           max_seq_len=32, dtype=jnp.float32)


def _data(bs=4, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, CFG["vocab_size"], (bs, seq)).astype(np.int32)
    return ids, np.roll(ids, -1, 1)


def _trainer(unroll, layers=None, **kw):
    cfg = GPTConfig(**dict(CFG, **({"num_layers": layers}
                                   if layers else {})))
    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
    kw.setdefault("remat", True)
    return GPTSpmdTrainer(cfg, mesh, microbatches=1, seed=0,
                          layer_unroll=unroll, **kw)


def _losses(tr, steps, ids, labels):
    return [float(jax.device_get(tr.train_step(ids, labels)))
            for _ in range(steps)]


def test_unrolled_loss_bit_identical_to_rolled_scan():
    """The headline parity: same init (identical RNG draws), same data
    -> the same loss trajectory to the last ulp. Step 1 (a pure forward
    on identical params) is bit-identical. From step 2 on the losses
    may differ in the last f32 ulp: on the installed XLA (jax 0.9.0)
    the scan body and the unrolled layers fuse their backward
    differently, so grads — and with them every param, embeddings
    included, clipping or not — differ by f32 reassociation
    (~1 ulp/step), and bit identity of the later losses cannot be
    restored from the model's side (PR 23 measured 1 ulp at step 2,
    0 at step 3). Doubles as the trace-count
    assertion: the unrolled step fn must compile no more than the
    rolled one (ONE executable + the shared donated-output-sharding
    retrace on step 2), and stay flat after."""
    ids, labels = _data()
    tr_r = _trainer(1)
    tr_u = _trainer("full")
    lr = _losses(tr_r, 3, ids, labels)
    lu = _losses(tr_u, 3, ids, labels)
    assert lr[0] == lu[0], (lr, lu)
    np.testing.assert_array_max_ulp(np.float32(lr), np.float32(lu),
                                    maxulp=2)
    pr = np.asarray(jax.device_get(tr_r.params["blocks"]["wqkv"]))[0]
    pu = np.stack([np.asarray(jax.device_get(
        tr_u.params["blocks"][k]["wqkv"]))
        for k in sorted(tr_u.params["blocks"])])
    np.testing.assert_allclose(pr, pu, rtol=0, atol=1e-5)
    n_u = tr_u._step_fn._cache_size()
    n_r = tr_r._step_fn._cache_size()
    assert n_u <= n_r <= 2, (n_u, n_r)
    _losses(tr_u, 1, ids, labels)
    assert tr_u._step_fn._cache_size() == n_u  # flat: no per-step




def test_unrolled_param_layout_is_per_layer():
    """blocks is a dict of per-layer "layer_NNN" subtrees with the
    [S, L] leading dims gone — the structural property the copy
    elimination rides on — and optimizer state mirrors it
    leaf-for-leaf. Dict-shaped (not a list) so
    distributed/checkpoint's dict-recursing flatten can save it."""
    tr = _trainer("full")
    blocks = tr.params["blocks"]
    assert isinstance(blocks, dict)
    assert sorted(blocks) == [f"layer_{i:03d}" for i in range(4)]
    D = CFG["hidden_size"]
    assert blocks["layer_000"]["wqkv"].shape == (D, 3 * D)
    assert blocks["layer_000"]["ln1_g"].shape == (D,)
    assert jax.tree.structure(tr.opt_state["m"]) == \
        jax.tree.structure(tr.params)
    # rolled keeps the stacked layout
    tr_r = _trainer(1)
    assert tr_r.params["blocks"]["wqkv"].shape == (1, 4, D, 3 * D)


def test_unrolled_state_checkpoints_and_resumes(tmp_path):
    """The per-layer layout must round-trip through the distributed
    checkpoint (dict-only flatten) — regression: a list-of-dicts
    layout made save_state_dict unserializable, which silently
    disabled ResilientTrainLoop's periodic checkpoints."""
    from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                   save_state_dict)
    ids, labels = _data()
    tr = _trainer("full", layers=2)
    _losses(tr, 1, ids, labels)
    state = {"params": tr.params, "opt": tr.opt_state}
    h = save_state_dict(jax.device_get(state), str(tmp_path))
    if h is not None and hasattr(h, "wait"):
        h.wait()
    tmpl = jax.tree.map(np.zeros_like, jax.device_get(state))
    restored = load_state_dict(tmpl, str(tmp_path))
    if restored is None:
        restored = tmpl  # in-place API
    got = restored["params"]["blocks"]["layer_001"]["wqkv"]
    want = jax.device_get(tr.params["blocks"]["layer_001"]["wqkv"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.full
def test_unrolled_matches_rolled_under_wgrad_sr():
    """quant8='wgrad': the unrolled per-layer seeds must reproduce the
    scan's _layer_seeds derivation exactly, or SR streams (and losses)
    diverge. The derivation is compared exactly; the losses are
    bit-identical at step 1 and within 3e-6 relative at step 2. Bit
    identity there is gone on the installed XLA (jax 0.9.0): the
    last-ulp grad differences of the test above flip a few stochastic
    roundings, which PR 23 measured as 1.4e-6 relative, while a wrong
    seed stream measured 6.0e-6."""
    ids, labels = _data()
    tr_r = _trainer(1, layers=2, quant8="wgrad")
    tr_u = _trainer("full", layers=2, quant8="wgrad")
    seed = jnp.int32(7)
    np.testing.assert_array_equal(
        np.asarray(tr_r._layer_seeds(seed)),
        np.asarray([tr_u._layer_seed(seed, li)
                    for li in range(tr_u.Lps)]))
    lr = _losses(tr_r, 2, ids, labels)
    lu = _losses(tr_u, 2, ids, labels)
    assert lr[0] == lu[0], (lr, lu)
    np.testing.assert_allclose(lr, lu, rtol=3e-6, atol=0)


@pytest.mark.full
def test_unrolled_matches_rolled_moe():
    ids, labels = _data()
    lr = _losses(_trainer(1, layers=2, moe_experts=2), 2, ids, labels)
    lu = _losses(_trainer("full", layers=2, moe_experts=2), 2,
                 ids, labels)
    assert lr == lu, (lr, lu)


def test_unrolled_rejects_pipeline_mesh():
    cfg = GPTConfig(**CFG)
    mesh = build_mesh(n_devices=8, pipe=2, model=1, fsdp=1, sep=1)
    with pytest.raises(ValueError, match="pipe=1"):
        GPTSpmdTrainer(cfg, mesh, layer_unroll="full")


def test_int8_guard_probe_handles_per_layer_layout():
    """The drift guard indexes layer 0's weights; it must work on both
    layouts (it reads params['blocks'][0] when unrolled)."""
    ids, labels = _data()
    tr = _trainer("full", layers=2, remat=False, quant8=True,
                  int8_guard_period=1)
    _losses(tr, 1, ids, labels)
    assert tr.guard_events() == []  # exact-ish tiny config: no drift


# -- fuse_bwd_colq knob (ADVICE r5: the dead _FUSE_BWD_COLQ constant) --

def test_fuse_bwd_colq_skips_stat_residuals_when_off():
    from paddle_tpu.ops.quant_matmul import _fwd_ln_all8
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(16, 64).astype(np.float32))
    g = jnp.asarray(rng.rand(64).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(64).astype(np.float32) * 0.1)
    w = jnp.asarray(rng.randn(64, 96).astype(np.float32) * 0.1)
    seed = jnp.int32(5)
    _, res_off = _fwd_ln_all8(False, x, g, b, w, seed)
    _, res_on = _fwd_ln_all8(True, x, g, b, w, seed)
    assert res_off[5] is None          # [M,1] mean/rstd NOT saved
    m, r = res_on[5]
    assert m.shape == (16, 1) and r.shape == (16, 1)


@pytest.mark.parametrize("fuse_bwd_colq", [False, True])
def test_int8_ln_linear_all8_knob_matches_unfused(fuse_bwd_colq):
    """Both knob settings must match the unfused LN + int8_linear_all8
    composition in value and all four gradients (shared XLA SR path on
    CPU -> identical streams)."""
    from paddle_tpu.ops.quant_matmul import (int8_ln_linear_all8,
                                             int8_linear_all8)
    rng = np.random.RandomState(11)
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
        return (int8_ln_linear_all8(
            x, g, b, w, seed, fuse_bwd_colq=fuse_bwd_colq) ** 2).sum()

    def unfused(x, g, b, w):
        return (int8_linear_all8(_ln(x, g, b), w, seed) ** 2).sum()

    f1, g1 = jax.value_and_grad(fused, argnums=(0, 1, 2, 3))(x, g, b, w)
    f2, g2 = jax.value_and_grad(unfused, argnums=(0, 1, 2, 3))(
        x, g, b, w)
    np.testing.assert_allclose(float(f1), float(f2), rtol=1e-5)
    for a1, a2 in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                                   rtol=1e-3, atol=1e-3)


def test_trainer_fuse_bwd_colq_env_default(monkeypatch):
    monkeypatch.delenv("PTPU_FUSE_BWD_COLQ", raising=False)
    assert _trainer(1).fuse_bwd_colq is False
    monkeypatch.setenv("PTPU_FUSE_BWD_COLQ", "1")
    assert _trainer(1).fuse_bwd_colq is True
    monkeypatch.setenv("PTPU_FUSE_BWD_COLQ", "0")
    assert _trainer(1, fuse_bwd_colq=True).fuse_bwd_colq is True


# -- dtype-discipline pass (round 6) -----------------------------------

def test_int8_dot_dequant_out_dtype_folds_cast():
    from paddle_tpu.ops.quant_matmul import (int8_dot_dequant,
                                             quantize_rowwise)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(8, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(64, 32).astype(np.float32))
    xq, xs = quantize_rowwise(x, -1)
    wq, ws = quantize_rowwise(w, 0)
    y32 = int8_dot_dequant(xq, xs, wq, ws, ((1,), (0,)))
    y16 = int8_dot_dequant(xq, xs, wq, ws, ((1,), (0,)),
                           out_dtype=jnp.bfloat16)
    assert y32.dtype == jnp.float32 and y16.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(y32.astype(jnp.bfloat16), np.float32),
        np.asarray(y16, np.float32))
