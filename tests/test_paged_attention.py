"""The live-pages decode kernel (``paddle_tpu/ops/paged_attention.py``)
against the einsum of ``models/_decode_cache.paged_cache_attend``, which
stays the reference: the kernel interpreted on the CPU (``kernel=True``)
on float32 and bfloat16 pools over ragged lengths, slots that are not
active, GQA groups and page sizes; which inputs take the kernel and
which keep the einsum; and one greedy engine run, token-identical."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models._decode_cache import (paged_cache_attend,
                                             quantize_kv_page)
from paddle_tpu.ops import pallas_ops

F32, BF16 = jnp.float32, jnp.bfloat16
B, KV, D, PER_SEQ = 6, 2, 16, 4


@functools.lru_cache(maxsize=None)
def _attend(kernel, dtype, rep, page):
    """One compile a (path, pool dtype, group, page size): positions
    and tables are run-time values."""
    return jax.jit(lambda q, k, v, kp, vp, table, pos: paged_cache_attend(
        q, k, v, kp, vp, None, None, table, pos, dtype, kernel=kernel)[:3])


def _case(dtype, rep, page, seed=0):
    rng = np.random.default_rng(seed)
    n = B * PER_SEQ + 1
    draw = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    return dict(
        q=draw(B, 1, KV * rep, D), k=draw(B, 1, KV, D).astype(dtype),
        v=draw(B, 1, KV, D).astype(dtype),
        kp=draw(n, page, KV, D).astype(dtype),
        vp=draw(n, page, KV, D).astype(dtype),
        table=1 + rng.permutation(B * PER_SEQ).reshape(B, PER_SEQ))


def _both(dtype, rep, page, pos, table=None, seed=0):
    c = _case(dtype, rep, page, seed)
    table = c["table"] if table is None else table
    args = (c["q"], c["k"], c["v"], c["kp"], c["vp"],
            jnp.asarray(table, jnp.int32), jnp.asarray(pos, jnp.int32))
    ref = _attend(False, dtype, rep, page)(*args)
    got = _attend(True, dtype, rep, page)(*args)
    # the new token's write is the same scatter on both paths
    for a, b in zip(ref[1:], got[1:]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    return (np.asarray(ref[0], np.float32),
            np.asarray(got[0], np.float32))


# float32 pools: float32 rounding; bfloat16 pools: the output's own
# rounding and that of a probability (module docstring of _decode_cache)
TOL = {F32: dict(rtol=2e-5, atol=2e-6), BF16: dict(rtol=2e-2, atol=2e-2)}
POSITIONS = {"position-0": lambda page: 0,
             "a-page's-last": lambda page: 2 * page - 1,
             "a-page's-first": lambda page: 2 * page,
             "the-table's-last": lambda page: PER_SEQ * page - 1}


@pytest.mark.parametrize("where", list(POSITIONS))
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_kernel_matches_einsum_at_the_edges_of_a_page(dtype, where):
    """Every slot at the named position but two, which stay ragged:
    the last live page is masked beyond ``pos`` and no page that holds
    a live position is skipped."""
    page = 8
    pos = np.full(B, POSITIONS[where](page))
    pos[1], pos[4] = 5, 3 * page + 2
    ref, got = _both(dtype, 4, page, pos)
    np.testing.assert_allclose(got, ref, **TOL[dtype])


@pytest.mark.parametrize("page", [8, 128])
@pytest.mark.parametrize("rep", [1, 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_kernel_matches_einsum_ragged(dtype, rep, page):
    rng = np.random.default_rng(page + rep)
    for seed in range(2):
        pos = rng.integers(0, PER_SEQ * page, size=B)
        ref, got = _both(dtype, rep, page, pos, seed=seed)
        np.testing.assert_allclose(got, ref, **TOL[dtype])
        assert np.abs(ref).max() > 0.1


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_slots_that_are_not_active_read_the_trash_page(dtype):
    """The engine pins such a slot to position 0 and a table of page 0:
    the kernel reads that one page (whatever the other slots wrote
    there) and the active slots' rows do not change."""
    page = 8
    pos = np.array([0, 11, 0, 31, 0, 8])
    c = _case(dtype, 4, page)
    table = np.where((pos > 0)[:, None], c["table"], 0)
    ref, got = _both(dtype, 4, page, pos, table=table)
    on = pos > 0
    np.testing.assert_allclose(got[on], ref[on], **TOL[dtype])
    assert np.isfinite(got).all()
    # and they are the rows of a run where every slot is active
    ref_all, got_all = _both(dtype, 4, page, np.where(on, pos, 7))
    np.testing.assert_allclose(got[on], got_all[on], **TOL[dtype])


def test_bf16_pool_keeps_the_query_at_float32():
    """Against a bfloat16 cache the query is split into three bfloat16
    parts, so the scores are those of the float32 query: the kernel on
    bfloat16 pools agrees with the float32 einsum on the same (exactly
    representable) pools far inside bfloat16's rounding of a query."""
    page, rep = 8, 4
    c = _case(BF16, rep, page)
    pos = jnp.asarray([0, 7, 8, 31, 13, 22], jnp.int32)
    table = jnp.asarray(c["table"], jnp.int32)
    up = lambda x: x.astype(F32)
    ref = paged_cache_attend(c["q"], up(c["k"]), up(c["v"]), up(c["kp"]),
                             up(c["vp"]), None, None, table, pos, F32,
                             kernel=False)[0]
    got = paged_cache_attend(c["q"], c["k"], c["v"], c["kp"], c["vp"],
                             None, None, table, pos, F32, kernel=True)[0]
    # what is left is the value product's bfloat16 probabilities
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=6e-3)
    rounded = paged_cache_attend(
        up(c["q"].astype(BF16)), up(c["k"]), up(c["v"]), up(c["kp"]),
        up(c["vp"]), None, None, table, pos, F32, kernel=False)[0]
    assert np.abs(np.asarray(got) - np.asarray(ref)).max() \
        < np.abs(np.asarray(rounded) - np.asarray(ref)).max()


# -- which inputs take the kernel ---------------------------------------

def _traces_kernel(monkeypatch, *, t=1, wlen=False, int8=False, d=128,
                   mesh=False, backend="tpu"):
    """Whether ``paged_cache_attend`` traces a ``pallas_call`` by
    default (``kernel=None``) where the backend says ``backend``."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    b, kv, page = 2, 2, 8
    pool_dt = jnp.int8 if int8 else BF16
    z = lambda *s, dt=BF16: jnp.zeros(s, dt)
    q, k, v = z(b, t, 2 * kv, d, dt=F32), z(b, t, kv, d), z(b, t, kv, d)
    kp = z(b * PER_SEQ + 1, page, kv, d, dt=pool_dt)
    sc = z(b * PER_SEQ + 1, page, kv, dt=F32) if int8 else None
    table = jnp.zeros((b, PER_SEQ), jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    wl = jnp.ones((b,), jnp.int32) if wlen else None
    fn = lambda q, k, v, kp, vp: paged_cache_attend(
        q, k, v, kp, vp, sc, sc, table, pos, BF16, wlen=wl)[0]
    if mesh:
        devs = np.array(jax.devices()[:2])
        with jax.set_mesh(jax.sharding.Mesh(devs, ("model",))):
            jaxpr = jax.make_jaxpr(fn)(q, k, v, kp, kp)
    else:
        jaxpr = jax.make_jaxpr(fn)(q, k, v, kp, kp)
    return "pallas_call" in str(jaxpr)


@pytest.mark.parametrize("case,want", [
    (dict(), True),                         # the engine's decode program
    (dict(t=2), False),                     # extend, prefill, chunk
    (dict(wlen=True), False),               # speculative verify
    (dict(int8=True), False),               # int8 pages
    (dict(mesh=True), False),               # traced under a mesh
    (dict(d=64), False),                    # a head Mosaic refuses
    (dict(backend="cpu"), False),           # every CPU run
], ids=["decode", "t>1", "wlen", "int8", "mesh", "head-64", "cpu"])
def test_only_the_decode_form_on_one_tpu_takes_the_kernel(
        monkeypatch, case, want):
    assert _traces_kernel(monkeypatch, **case) is want


def test_int8_pages_keep_the_einsum_even_when_asked():
    """``kernel=True`` on int8 pages is not a third implementation: the
    scales are dequantized by the einsum."""
    kv, page, d = 2, 8, 16
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(3, page, kv, d)).astype(np.float32))
    kq, ks = quantize_kv_page(x)
    table = jnp.asarray([[1, 2]], jnp.int32)
    args = (jnp.ones((1, 1, kv, d), F32), jnp.ones((1, 1, kv, d), F32),
            jnp.ones((1, 1, kv, d), F32), kq, kq, ks, ks, table,
            jnp.asarray([9], jnp.int32), F32)
    a = paged_cache_attend(*args, kernel=True)[0]
    b = paged_cache_attend(*args, kernel=False)[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the engine ------------------------------------------------------------

def _tiny(family):
    """Two heads of 128 (the narrowest head the kernel takes)."""
    paddle.seed(0)
    if family == "llama":
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        model = LlamaForCausalLM(llama_tiny_config(
            max_position_embeddings=64, num_hidden_layers=2,
            hidden_size=256, intermediate_size=64,
            num_attention_heads=2, num_key_value_heads=1))
    else:
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        model = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=256, num_layers=1, num_heads=2,
            max_seq_len=64, dropout=0.0))
    model.eval()
    return model


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_engine_with_the_kernel_is_token_identical(monkeypatch, family):
    """A greedy run of the paged float32 engine whose decode program
    traced the kernel (``single_device_tpu`` patched true, the kernel
    interpreted; the engine has no argument for it) against the same
    run on the einsum: the same tokens, through extends (a shared
    prefix) and page boundaries."""
    from paddle_tpu.serving import ServingEngine
    model = _tiny(family)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int64)
               for n in (3, 9, 17, 6)]
    prompts.append(np.concatenate([prompts[2][:16], [7, 8]]))
    outs, words = [], []
    for patched in (False, True):
        if patched:
            monkeypatch.setattr(pallas_ops, "single_device_tpu",
                                lambda: True)
        eng = ServingEngine(model, max_slots=3, max_len=64, min_bucket=8,
                            page_size=8)
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run()
        outs.append([r.output_ids for r in reqs])
        words.append(eng.decode_attend)
        assert eng.trace_counts["decode"] == 1
    assert words == ["einsum", "paged_kernel"]
    assert outs[0] == outs[1]


def test_engine_programs_write_without_a_pass_over_the_pool():
    """The framework's op dispatch traces every op under ``jax.vjp``
    while a parameter is trainable, and the JVP of a scatter whose
    indices may repeat (the trash page) computes even its primal by
    selects over the whole operand. The engine serves and never
    differentiates: its programs trace under ``no_grad``, whatever
    attention they take, and no pool-sized select is left in them."""
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(_tiny("llama"), max_slots=3, max_len=64,
                        min_bucket=8, page_size=8)
    seen = {}

    def spy(kind, prog):
        def call(*args):
            seen[kind] = prog, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
            return prog(*args)
        return call

    eng._decode_jit = spy("decode", eng._decode_fn())
    eng._extend_jit = spy("extend", eng._extend_fn())
    rng = np.random.RandomState(5)
    shared = rng.randint(1, 128, (18,)).astype(np.int64)
    for tail in ([3], [4, 5]):          # the second extends the first
        eng.submit(np.concatenate([shared, tail]), max_new_tokens=3)
        eng.run()
    assert sorted(seen) == ["decode", "extend"]
    pool = "f32[%d,8,1,128]" % eng.cache.num_pages
    for kind, (prog, args) in seen.items():
        jaxpr = str(prog.trace(*args).jaxpr)
        assert f":{pool} = scatter" in jaxpr, kind
        assert not [line for line in jaxpr.splitlines()
                    if "select_n" in line and f":{pool} =" in line], kind
