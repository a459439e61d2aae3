"""models/solar.py on the normal path: its forward against the plain
reference (models/solar_reference.py), and through ``ServingEngine``
with K/V pages for its GQA layer and state rows for its KDA layers in
one cache manager (prefill into both, then decode from both) against the
reference's full forward, in logits.

Four layers (GQA, KDA, KDA, KDA: one period), 4 query heads on 2 KV
heads, experts 5..9 of 20 held, 4 a token. Everything is float32 on the
CPU, so paths differ by rounding in another order: ``RTOL`` (relative to
the largest logit) is ten times what the engine's prefill-then-decode
reads against the reference (2e-6 to 2e-5). A KDA state held in bfloat16
reads over 1e-3 (``test_a_bfloat16_state_is_caught``).

One engine serves most tests; each test drains it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.serving.engine as engine_module
from paddle_tpu.framework.tensor import no_grad
from paddle_tpu.models.solar import SolarOpen2Config, SolarOpen2ForCausalLM
from paddle_tpu.models.solar_reference import solar_logits
from paddle_tpu.observability import (MetricRegistry, TraceBuffer,
                                      install_trace_buffer, tracing)
from paddle_tpu.serving import (FrontDoor, ReplicaRouter, ServingEngine,
                                SlotCache, StateCacheUnsupported)

RTOL = 2e-4
VOCAB = 96


def _config(**kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        moe_intermediate_size=16, n_routed_experts=5,
        experts_published=20, first_expert=5, num_experts_per_tok=4,
        max_position_embeddings=256, gqa_layers=(0, 4),
        linear_num_heads=4, linear_head_dim=8, kda_rank=8)
    base.update(kw)
    return SolarOpen2Config(**base)


def _model(seed=0, **kw):
    paddle.seed(seed)
    model = SolarOpen2ForCausalLM(_config(**kw))
    model.eval()
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.endswith("norm.weight") or name.endswith("layernorm.weight"):
            p._data = jnp.asarray(rng.uniform(0.5, 1.5, p._data.shape),
                                  jnp.float32)
    return model


@pytest.fixture(scope="module")
def model():
    return _model()


_REFERENCES = {}


def _reference(model, ids):
    """The plain reference over ``ids``, padded at its end to one length
    (what follows a position cannot reach it) and jitted a model, so
    that it compiles once."""
    ids = np.asarray(ids)
    fn = _REFERENCES.setdefault(id(model), jax.jit(
        lambda params, x: solar_logits(params, x, model.config)))
    padded = np.concatenate([ids, np.zeros(128 - len(ids), np.int64)])
    return np.asarray(fn(model.raw_state()[0], padded))[:len(ids)]


class Spy:
    """Every logits row the engine samples from, by request."""

    def __init__(self):
        self.rows, self.real = {}, engine_module.sample_token
        self._alive = []

    def __call__(self, logits, params, rng):
        if id(rng) not in self.rows:
            self._alive.append(rng)
        self.rows.setdefault(id(rng), []).append(np.array(logits))
        return self.real(logits, params, rng)

    def check(self, model, req, rtol=RTOL):
        """The request's rows against the reference's forward over its
        prompt and outputs: the row that gave output i is position
        ``len(prompt) - 1 + i``; and the tokens served are the
        reference's own greedy choices."""
        ref = _reference(model, req.full_ids)
        rows = self.rows[id(req._rng)]
        assert len(rows) == len(req.output_ids)
        first = req.prompt_len - 1
        err = max(np.abs(r - ref[first + i]).max()
                  for i, r in enumerate(rows)) / np.abs(ref).max()
        assert err < rtol, err
        if rtol == RTOL:
            assert req.output_ids == list(ref[first:-1].argmax(-1))
        return err


@pytest.fixture(scope="module")
def spy():
    spy = Spy()
    engine_module.sample_token = spy
    yield spy
    engine_module.sample_token = spy.real


@pytest.fixture(scope="module")
def eng(model):
    # one prefill bucket (64) for every prompt of this file: a compile
    # of a four-layer hybrid program is what a test here costs
    return ServingEngine(model, max_slots=3, max_len=96, min_bucket=64,
                         page_size=8, registry=MetricRegistry())


def _prompts(lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, n) for n in lens]


@pytest.mark.parametrize("first_expert,held", [(5, 5), (0, 20), (16, 4)])
def test_the_model_is_the_reference(first_expert, held):
    """Whole sequence, a share of the experts or all of them."""
    m = _model(seed=3, first_expert=first_expert, n_routed_experts=held)
    ids = _prompts([48], seed=9)[0]
    with no_grad():
        got = np.asarray(m(paddle.to_tensor(ids[None]))._data)[0]
    ref = _reference(m, ids)
    assert np.abs(got - ref).max() / np.abs(ref).max() < RTOL
    assert np.isfinite(got).all() and np.abs(ref).max() > 0.1


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One FFN layer of the model: the routed parts of four chips with
    five experts each, plus the shared expert once, are what the
    reference gives with all twenty (the shared expert is in every
    share's result, so three of them are taken off)."""
    from paddle_tpu.models.solar_reference import solar_ffn
    whole = _model(seed=4, first_expert=0, n_routed_experts=20)
    mlp = whole.solar.layers[1].mlp
    x = jnp.asarray(np.random.default_rng(5).standard_normal(
        (1, 24, 32)).astype(np.float32))
    p = {k[len("solar.layers.1."):]: v for k, v in
         whole.raw_state()[0].items() if k.startswith("solar.layers.1.")}
    want = np.asarray(solar_ffn(x[0], p, top_k=4))
    only_shared = np.asarray(solar_ffn(
        x[0], {**p, "mlp.experts_gate": p["mlp.experts_gate"][:1]},
        top_k=4, first_expert=10 ** 6))
    total = np.zeros_like(want)
    for chip in range(4):
        part = _model(seed=4, first_expert=5 * chip, n_routed_experts=5)
        pm = part.solar.layers[1].mlp
        sl = slice(5 * chip, 5 * chip + 5)
        pm.router.weight._data = mlp.router.weight._data
        for name in ("experts_gate", "experts_up", "experts_down"):
            getattr(pm, name)._data = getattr(mlp, name)._data[sl]
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(pm.shared_expert, name).weight._data = getattr(
                mlp.shared_expert, name).weight._data
        with no_grad():
            y, counts = pm(paddle.to_tensor(x))
        total += np.asarray(y._data)[0]
    total -= 3 * only_shared
    assert np.abs(total - want).max() < 1e-5 * max(1, np.abs(want).max())


def test_cache_spec_is_pages_for_one_layer_and_rows_for_three(model, eng):
    spec = model.cache_spec()
    assert spec.layers == ("kv", "state", "state", "state")
    assert spec.state == (("S", (4, 8, 8), jnp.float32),
                          ("conv", (3, 96), jnp.float32))
    c = eng.cache
    assert isinstance(c, SlotCache)
    assert (c.kv_layers, c.state_layers) == (1, 3)
    assert eng.paged and eng.stateful and not eng.prefix_sharing
    assert len(c.ks) == len(c.vs) == 1
    assert c.ks[0].shape == (3 * 12 + 1, 8, 2, 8)
    assert [a.shape for a in c.pools[0]] == [(3, 4, 8, 8)] * 3
    assert [a.shape for a in c.pools[1]] == [(3, 3, 96)] * 3
    assert c.slot_bytes == 3 * (4 * 8 * 8 + 3 * 96) * 4
    assert c.state_bytes() == 3 * c.slot_bytes
    assert c.kv_bytes() == 2 * 37 * 8 * 2 * 8 * 4


def test_slots_at_different_positions_match_the_reference(model, eng,
                                                          spy):
    """Four requests on three slots, each padded to its bucket, admitted
    at different steps, so every decode step has slots at different
    positions, in pages and in states, and a slot is reused."""
    resets = eng.cache.resets
    reqs = [eng.submit(p, n) for p, n in zip(
        _prompts([23, 32, 5, 41]), [9, 4, 14, 6])]
    eng.run()
    for r in reqs:
        assert r.finish_reason == "length"
        spy.check(model, r)
    assert eng.trace_counts["prefill"] == {64: 1}
    assert eng.trace_counts["decode"] == 1
    assert not eng.trace_counts["extend"]
    assert eng.cache.resets == resets + 4
    assert not eng.cache.active_slots()


def test_a_reused_slot_resets_its_state_and_frees_its_pages(model, eng,
                                                            spy):
    free = eng.cache.free_page_count()
    first = eng.submit(_prompts([60], seed=2)[0], 12)
    eng.step()
    assert eng.cache.free_page_count() < free
    eng.run()
    assert first.slot is None and eng.cache.free_slots()[0] == 0
    assert eng.cache.free_page_count() == free
    assert not eng.cache.page_table.any()
    left = [np.asarray(a)[0].copy() for a in eng.cache.pools[0]]
    assert all(np.abs(a).max() > 0 for a in left)    # rows wait
    second = eng.submit(_prompts([7], seed=3)[0], 8)
    eng.step()
    assert second.slot == 0        # the slot the long request left
    eng.run()
    spy.check(model, first)
    spy.check(model, second)


def test_recover_rebuilds_pages_and_states(model, eng, spy):
    reqs = [eng.submit(p, 10) for p in _prompts([19, 33], seed=4)]
    for _ in range(4):
        eng.step()
    old = eng.cache
    report = eng.recover()
    assert report["recovered_slots"] == 2
    assert report["replay_mismatches"] == 0 and eng.cache is not old
    assert eng.cache.active_page_count() > 0
    eng.run()
    for r in reqs:
        assert len(r.output_ids) == 10
        spy.check(model, r)
    assert eng.cache.active_page_count() == 0


def test_the_kernels_serve_the_same_logits(model, spy, monkeypatch):
    """The KDA decode kernel and the grouped product's (interpreted
    here) in the engine's programs, where a TPU would use them."""
    from paddle_tpu.ops import pallas_ops
    monkeypatch.setattr(pallas_ops, "single_device_tpu", lambda: True)
    kernel_eng = ServingEngine(model, max_slots=2, max_len=32,
                               min_bucket=16)
    reqs = [kernel_eng.submit(p, n) for p, n in zip(
        _prompts([11, 14], seed=5), [4, 2])]
    kernel_eng.run()
    for r in reqs:
        spy.check(model, r)


def test_a_bfloat16_state_is_caught(model, eng, spy):
    """The tolerance is tight enough: the same run with the slots'
    states rounded to bfloat16 after every step fails it."""
    req = eng.submit(_prompts([40], seed=6)[0], 12)
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    while eng.has_work():
        eng.step()
        eng.cache.pools = [[low(a) for a in p] for p in eng.cache.pools]
    with pytest.raises(AssertionError):
        spy.check(model, req)
    assert spy.check(model, req, rtol=1.0) > 5 * RTOL


def test_a_bfloat16_model_keeps_bfloat16_pages_and_the_reference_rounds_them(
        spy):
    """K and V are stored in the weights' dtype, and what is stored is
    defined (the float32 projection rounded once): a bfloat16 model
    through bfloat16 pages, prefill then decode, is the reference with
    its K and V rounded the same way, to float32 rounding and in its
    greedy tokens (K and V rounded on one side only read ~1e-3)."""
    m = _model(seed=11)
    m.to(dtype="bfloat16")
    e = ServingEngine(m, max_slots=2, max_len=96, min_bucket=64,
                      page_size=8, registry=MetricRegistry())
    assert e.cache.ks[0].dtype == jnp.bfloat16
    assert all(a.dtype == jnp.float32 for p in e.cache.pools for a in p)
    req = e.submit(_prompts([44], seed=12)[0], 10)
    e.run()
    spy.check(m, req)


def test_served_behind_the_front_door_and_the_router(model, eng, spy):
    reg = MetricRegistry()
    front = FrontDoor(ReplicaRouter([eng], registry=reg), registry=reg)
    prompts = _prompts([12, 30, 9], seed=7)
    handles = [front.submit(p, 5) for p in prompts]
    front.run_until_idle()
    for h in handles:
        assert h.req.finish_reason == "length"
        assert len(h.req.output_ids) == 5
        spy.check(model, h.req)


@pytest.mark.parametrize("option", [
    {"kv_layout": "state"}, {"kv_layout": "contiguous"},
    {"prefix_sharing": True}, {"kv_dtype": "int8"},
    {"speculative": True}, {"kv_host_tier": True},
    {"host_tier_pages": 4}, {"prefix_store_dir": "/nonexistent"},
    {"kv_transport": object()}, {"prefill_devices": 1},
    {"mesh": object()}, {"prefill_chunk": 16},
    {"draft_model": object()}])
def test_what_state_layers_cannot_do_yet_is_refused_by_name(model, option):
    with pytest.raises(StateCacheUnsupported) as e:
        ServingEngine(model, max_slots=2, max_len=64, **option)
    (name,) = option
    assert e.value.option == name and name in str(e.value)


def test_pages_are_sized_as_for_any_model_with_kv_layers(model):
    e = ServingEngine(model, max_slots=2, max_len=64, page_size=16,
                      num_pages=7, kv_layout="paged")
    assert e.cache.page_size == 16 and e.cache.num_pages == 7
    assert e.prefix_sharing is False


def test_spans_attributes_and_counters(model, eng):
    buf = TraceBuffer(tracing.DEFAULT_CAPACITY)
    prev = install_trace_buffer(buf)
    reg = eng.registry
    before = (reg.get("ptpu_serving_expert_tokens_total").value,
              reg.get("ptpu_serving_experts_hit_total").value)
    try:
        for p in _prompts([10, 18, 6, 25], seed=8):
            eng.submit(p, 3)
        eng.run()
        spans = tracing.query()["spans"]
    finally:
        install_trace_buffer(prev)
    steps = [s for s in spans if s["name"] == "serving.step"]
    assert steps
    for s in steps:
        a = s["attrs"]
        assert a["state_slots_total"] == 3 and a["pages_total"] == 36
        assert 0 <= a["state_slots_in_use"] <= 3
        assert a["pages_in_use"] >= a["state_slots_in_use"]
        assert a["state_bytes"] \
            == a["state_slots_in_use"] * eng.cache.slot_bytes
    decodes = [s for s in spans if s["name"] == "serving.decode"]
    assert decodes
    tokens = hit = 0
    for s in decodes:
        a = s["attrs"]
        assert a["experts_held"] == 4 * 5 and a["live_pages"] >= 1
        assert 0 <= a["experts_hit"] <= min(20, a["expert_tokens"])
        # a slot's token chooses 4 of 20 experts a layer, 5 held here
        assert a["expert_tokens"] <= a["batch"] * 4 * 4
        tokens += a["expert_tokens"]
        hit += a["experts_hit"]
    assert tokens > 0
    assert reg.get("ptpu_serving_expert_tokens_total").value \
        == before[0] + tokens
    assert reg.get("ptpu_serving_experts_hit_total").value \
        == before[1] + hit
    pre = [s for s in spans if s["name"] == "serving.prefill"]
    assert len(pre) == 4
    assert all(s["attrs"]["program"] == "prefill"
               and s["attrs"]["state_reset"] is True for s in pre)
    assert len([s for s in spans
                if s["name"] == "serving.state.reset"]) == 4
