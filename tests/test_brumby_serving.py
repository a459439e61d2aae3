"""models/brumby.py on the normal path: its forward against the plain
reference (models/brumby_reference.py), and through ``ServingEngine``
(prefill into a slot's state, then decode from the slots' states)
against the full forward, in logits.

Tolerances. Everything here is float32 on the CPU, so paths differ by
rounding in another order. ``SERVE_RTOL`` (relative to the largest
logit): the engine's prefill-then-decode against the model's own full
forward reads 1e-6 to 5e-6. ``REF_RTOL``: the model (chunked form)
against the plain reference (whole ``A`` matrices) reads 1e-5 to 3e-5,
a sum in another order under a division by a normaliser that can be
small at a sequence's first positions. A state held in bfloat16 reads
over 1e-3 against either (``test_a_bfloat16_state_is_caught``). The
gate projection is scaled by 3, gates in about (0.05, 0.95): at 8 a
gate can close to 1e-7, the output is a quotient of two numbers near
zero and single positions read 1e-2 in any form.

One engine serves most tests (a compile of its programs is seconds);
each test drains it.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.serving.engine as engine_module
from paddle_tpu.models.brumby import BrumbyForCausalLM
from paddle_tpu.models.brumby_reference import brumby_logits
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import (MetricRegistry, TraceBuffer,
                                      install_trace_buffer, tracing)
from paddle_tpu.serving import (FrontDoor, ReplicaRouter, ServingEngine,
                                SlotCache, StateCacheUnsupported)

SERVE_RTOL = 2e-5
REF_RTOL = 1e-4


def _model(seed=0):
    """Two layers, 10 query heads on 2 KV heads (the published ratio 5),
    head size 8; norm gains drawn off 1 and the gate projection scaled
    up so that gates spread over (0, 1) and memory is not uniform."""
    paddle.seed(seed)
    model = BrumbyForCausalLM(llama_tiny_config(
        hidden_size=80, num_attention_heads=10, num_key_value_heads=2,
        max_position_embeddings=128))
    model.eval()
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if p._data.ndim == 1:
            p._data = jnp.asarray(rng.uniform(0.5, 1.5, p._data.shape),
                                  jnp.float32)
        elif "g_proj" in name:
            p._data = p._data * 3.0
    return model


@pytest.fixture(scope="module")
def model():
    return _model()


def _full_logits(model, ids):
    """The whole-sequence forward; padded at its end to one length (what
    follows a position cannot reach it), so it compiles once."""
    ids = np.asarray(ids)
    padded = np.concatenate([ids, np.zeros(128 - len(ids), np.int64)])
    return np.asarray(model(paddle.to_tensor(
        padded[None]))._data)[0][:len(ids)]


class Spy:
    """Every logits row the engine samples from, by request."""

    def __init__(self):
        self.rows, self.real = {}, engine_module.sample_token
        self._alive = []        # an id is a key only while its owner lives

    def __call__(self, logits, params, rng):
        if id(rng) not in self.rows:
            self._alive.append(rng)
        self.rows.setdefault(id(rng), []).append(np.array(logits))
        return self.real(logits, params, rng)

    def check(self, model, req, rtol=SERVE_RTOL):
        """The request's rows against the full forward over its prompt
        and outputs: the row that gave output i is position
        ``len(prompt) - 1 + i``."""
        ref = _full_logits(model, req.full_ids)
        rows = self.rows[id(req._rng)]
        assert len(rows) == len(req.output_ids)
        first = req.prompt_len - 1
        err = max(np.abs(r - ref[first + i]).max()
                  for i, r in enumerate(rows)) / np.abs(ref).max()
        assert err < rtol, err
        return err


@pytest.fixture(scope="module")
def spy():
    spy = Spy()
    engine_module.sample_token = spy
    yield spy
    engine_module.sample_token = spy.real


@pytest.fixture(scope="module")
def eng(model):
    return ServingEngine(model, max_slots=3, max_len=96, min_bucket=16,
                         registry=MetricRegistry())


def _prompts(lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 128, n) for n in lens]


def test_forward_matches_the_plain_reference(model):
    ids = _prompts([96])[0]
    c = model.config
    ref = np.asarray(brumby_logits(
        model.raw_state()[0], ids, layers=c.num_hidden_layers,
        heads=c.num_attention_heads, kv_heads=c.kv_heads,
        eps=c.rms_norm_eps, theta=c.rope_theta))
    got = _full_logits(model, ids)
    assert np.abs(got - ref).max() / np.abs(ref).max() < REF_RTOL
    assert np.isfinite(got).all() and np.abs(ref).max() > 0.1


def test_cache_spec_is_a_state_a_slot(model, eng):
    spec = model.cache_spec()
    assert spec.layers == ("state", "state") and spec.num_layers == 2
    assert spec.state == (("S", (2, 36, 8), jnp.float32),
                          ("z", (2, 36), jnp.float32))
    assert isinstance(eng.cache, SlotCache)
    assert (eng.cache.kv_layers, eng.cache.state_layers) == (0, 2)
    assert eng.cache.kv_bytes() == 0
    assert not eng.paged and not eng.prefix_sharing
    assert [a.shape for a in eng.cache.pools[0]] == [(3, 2, 36, 8)] * 2
    assert [a.shape for a in eng.cache.pools[1]] == [(3, 2, 36)] * 2
    assert eng.cache.slot_bytes == 2 * (2 * 36 * 8 + 2 * 36) * 4
    assert eng.cache.state_bytes() == 3 * eng.cache.slot_bytes


def test_kv_models_state_their_cache_too():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    llama = LlamaForCausalLM(llama_tiny_config(num_key_value_heads=2))
    spec = llama.cache_spec()
    assert (spec.layers, spec.num_layers, spec.kv_heads, spec.head_dim,
            spec.max_positions, spec.state) == (("kv", "kv"), 2, 2, 16, 64, ())
    gpt = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                   num_layers=1, num_heads=4,
                                   max_seq_len=32))
    spec = gpt.cache_spec()
    assert (spec.layers, spec.kv_heads, spec.head_dim,
            spec.max_positions) == (("kv",), 4, 8, 32)
    with pytest.raises(ValueError, match="recurrent state"):
        ServingEngine(llama, kv_layout="state")
    with pytest.raises(TypeError, match="cache_spec"):
        ServingEngine(llama.llama)


def test_slots_at_different_positions_match_the_full_forward(
        model, eng, spy):
    """Four requests on three slots: lengths on and off their bucket,
    admitted at different steps, so every decode step has slots at
    different positions and a slot is reused."""
    resets = eng.cache.resets
    reqs = [eng.submit(p, n) for p, n in zip(
        _prompts([23, 32, 5, 41]), [9, 4, 14, 6])]
    eng.run()
    for r in reqs:
        assert r.finish_reason == "length"
        spy.check(model, r)
    # 23 and 32 share the bucket 32, 5 pads to 16, 41 to 64: a program
    # a bucket and one decode program, whatever the mix
    assert {32: 1, 16: 1, 64: 1}.items() \
        <= eng.trace_counts["prefill"].items()
    assert eng.trace_counts["decode"] == 1
    assert eng.cache.resets == resets + 4
    assert not eng.cache.active_slots()


def test_a_reused_slot_keeps_no_trace_of_the_earlier_request(
        model, eng, spy):
    first = eng.submit(_prompts([60], seed=2)[0], 12)
    eng.run()
    assert first.slot is None and eng.cache.free_slots()[0] == 0
    left = [np.asarray(a)[0].copy() for a in eng.cache.pools[0]]
    assert all(np.abs(a).max() > 0 for a in left)
    second = eng.submit(_prompts([7], seed=3)[0], 8)
    eng.step()
    assert second.slot == 0        # the slot the long request left
    eng.run()
    spy.check(model, first)
    spy.check(model, second)


def test_recover_rebuilds_the_states(model, eng, spy):
    reqs = [eng.submit(p, 10) for p in _prompts([19, 33], seed=4)]
    for _ in range(4):
        eng.step()
    old = eng.cache
    report = eng.recover()
    assert report["recovered_slots"] == 2
    assert report["replay_mismatches"] == 0 and eng.cache is not old
    eng.run()
    for r in reqs:
        assert len(r.output_ids) == 10
        spy.check(model, r)


def test_the_decode_kernel_serves_the_same_logits(model, spy,
                                                  monkeypatch):
    """The Pallas kernel (interpreted here) in the engine's decode
    program, where a TPU would use it."""
    from paddle_tpu.ops import pallas_ops
    monkeypatch.setattr(pallas_ops, "single_device_tpu", lambda: True)
    kernel_eng = ServingEngine(model, max_slots=2, max_len=32,
                               min_bucket=16)
    reqs = [kernel_eng.submit(p, n) for p, n in zip(
        _prompts([11, 14], seed=5), [7, 3])]
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
    assert spy.check(model, req, rtol=1.0) > 10 * SERVE_RTOL


def test_served_behind_the_front_door_and_the_router(model, eng, spy):
    reg = MetricRegistry()
    front = FrontDoor(ReplicaRouter([eng], registry=reg), registry=reg)
    prompts = _prompts([12, 20, 9, 30], seed=7)
    handles = [front.submit(p, 5) for p in prompts]
    front.run_until_idle()
    for h, p in zip(handles, prompts):
        assert h.req.finish_reason == "length"
        assert len(h.req.output_ids) == 5
        spy.check(model, h.req)
        ref = _full_logits(model, h.req.full_ids)
        assert h.req.output_ids == list(ref[len(p) - 1:-1].argmax(-1))


@pytest.mark.parametrize("option", [
    {"kv_layout": "paged"}, {"kv_layout": "contiguous"},
    {"prefix_sharing": True}, {"kv_dtype": "int8"}, {"page_size": 16},
    {"num_pages": 8}, {"speculative": True}, {"kv_host_tier": True},
    {"host_tier_pages": 4}, {"prefix_store_dir": "/nonexistent"},
    {"kv_transport": object()}, {"prefill_devices": 1},
    {"mesh": object()}, {"prefill_chunk": 16},
    {"draft_model": object()}])
def test_what_a_state_cannot_do_yet_is_refused_by_name(model, option):
    with pytest.raises(StateCacheUnsupported) as e:
        ServingEngine(model, max_slots=2, max_len=64, **option)
    (name,) = option
    assert e.value.option == name and name in str(e.value)


def test_spans_attributes_and_gauges(model, eng):
    buf = TraceBuffer(tracing.DEFAULT_CAPACITY)
    prev = install_trace_buffer(buf)
    try:
        for p in _prompts([10, 18, 6, 25], seed=8):
            eng.submit(p, 3)
        eng.run()
        spans = tracing.query()["spans"]
    finally:
        install_trace_buffer(prev)
    by_id = {s["id"]: s for s in spans}
    steps = [s for s in spans if s["name"] == "serving.step"]
    assert steps
    for s in steps:
        a = s["attrs"]
        assert a["state_slots_total"] == 3
        assert 0 <= a["state_slots_in_use"] <= 3
        assert a["state_bytes"] \
            == a["state_slots_in_use"] * eng.cache.slot_bytes
        assert "pages_in_use" not in a
    assert max(s["attrs"]["state_slots_in_use"] for s in steps) == 3
    pre = [s for s in spans if s["name"] == "serving.prefill"]
    assert len(pre) == 4
    assert all(s["attrs"]["program"] == "prefill"
               and s["attrs"]["state_reset"] is True for s in pre)
    resets = [s for s in spans if s["name"] == "serving.state.reset"]
    assert [by_id[s["parent"]]["name"] for s in resets] \
        == ["serving.prefill"] * 4
    assert all(s["attrs"]["state_bytes"] == eng.cache.slot_bytes
               and s["attrs"]["slot"] in (0, 1, 2) for s in resets)
    reg = eng.registry
    assert reg.get("ptpu_serving_state_bytes").value \
        == eng.cache.state_bytes()
    assert reg.get("ptpu_serving_state_slots_in_use").value == 0
