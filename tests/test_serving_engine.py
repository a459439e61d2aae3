"""Continuous-batching serving engine (paddle_tpu/serving): slot
admission/eviction, prefill bucketing (compile-count contract via
trace counting), masked per-slot decode parity vs the synchronized
whole-batch decode path, and metrics accounting on a fake clock."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (FIFOScheduler, PagedKVCache, Request,
                                SamplingParams, ServingEngine,
                                SlotCache, bucket_for,
                                prefill_buckets, sample_token)


def _tiny_llama(**kw):
    paddle.seed(0)
    kw.setdefault("max_position_embeddings", 128)
    model = LlamaForCausalLM(llama_tiny_config(**kw))
    model.eval()
    return model


@pytest.fixture(autouse=True)
def _clean_faults():
    from paddle_tpu.resilience import faults
    faults.clear()
    faults.reset_counts()
    yield
    faults.clear()


def _prompts(rng, lens, vocab=128):
    return [rng.randint(0, vocab, (n,)).astype(np.int64) for n in lens]


# -- policy / bookkeeping units ----------------------------------------

def test_bucket_policy():
    assert bucket_for(1, 4, 64) == 4          # min_bucket floor
    assert bucket_for(4, 4, 64) == 4
    assert bucket_for(5, 4, 64) == 8          # next power of 2
    assert bucket_for(33, 4, 64) == 64
    assert bucket_for(50, 4, 48) == 48        # capped at max_len
    with pytest.raises(ValueError):
        bucket_for(0, 4, 64)
    # the compile-count budget: O(log max_len) buckets, max_len included
    assert prefill_buckets(4, 64) == [4, 8, 16, 32, 64]
    assert prefill_buckets(16, 48) == [16, 32, 48]
    # non-power-of-2 min_bucket normalizes the same way in BOTH, so
    # every bucket_for result stays inside the published budget
    assert prefill_buckets(24, 100) == [32, 64, 100]
    assert bucket_for(30, 24, 100) in set(prefill_buckets(24, 100))


def test_slot_cache_lease_cycle():
    import jax.numpy as jnp
    c = SlotCache(("state", "state"), (("S", (2, 4, 4), jnp.float32),
                                       ("z", (2, 4), jnp.float32)),
                  3, 16, 2, 4, jnp.float32, page_size=16)
    assert c.free_slots() == [0, 1, 2] and c.occupancy == 0.0
    from types import SimpleNamespace
    c.assign(1, SimpleNamespace(rid=7))
    assert c.free_slots() == [0, 2] and c.active_slots() == [1]
    with pytest.raises(RuntimeError):
        c.assign(1, SimpleNamespace(rid=8))
    c.release(1)
    with pytest.raises(RuntimeError):
        c.release(1)
    assert c.free_slots() == [0, 1, 2]
    S, z = c.pools                  # a list a layer, a row a slot
    assert len(S) == len(z) == 2
    assert S[0].shape == (3, 2, 4, 4) and z[0].shape == (3, 2, 4)


def test_kv_layout_follows_from_the_model():
    """The layout is no choice: a model that caches K and V is served
    from pages. The contiguous pool's old value is refused by a
    message that says so, any other foreign value by what this model's
    kind gives; None and the matching value build the same engine."""
    model = _tiny_llama()
    with pytest.raises(ValueError, match="contiguous slot pool is gone, "
                                         "pages serve K and V"):
        ServingEngine(model, max_slots=2, max_len=32,
                      kv_layout="contiguous")
    with pytest.raises(ValueError, match="'paged' for LlamaForCausalLM"):
        ServingEngine(model, max_slots=2, max_len=32, kv_layout="rows")
    with pytest.raises(ValueError, match="recurrent state"):
        ServingEngine(model, max_slots=2, max_len=32, kv_layout="state")
    for layout in (None, "paged"):
        eng = ServingEngine(model, max_slots=2, max_len=32,
                            kv_layout=layout)
        assert eng.paged and eng.page_size == 32
        assert isinstance(eng.cache, PagedKVCache)


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_per_row_positions_need_pages_or_a_write_length(family):
    """The fixed-buffer 3-tuple is ``generate()``'s: one position for
    the whole batch. A per-row position without ``wlen`` (the tuple of
    the contiguous pool's decode step) is refused by name, not
    mis-sliced."""
    import jax.numpy as jnp
    if family == "llama":
        model = _tiny_llama()
    else:
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
            max_seq_len=16, dropout=0.0))
        model.eval()
    spec = model.cache_spec()
    buf = jnp.zeros((2, 16, spec.kv_heads, spec.head_dim), spec.dtype)
    caches = [(buf, buf, jnp.asarray([3, 5], jnp.int32))] \
        * spec.num_layers
    ids = paddle.to_tensor(np.ones((2, 1), np.int64))
    with pytest.raises(ValueError, match="4-tuple cache"):
        model.cached_forward(ids, caches)


def test_scheduler_fifo_admission():
    s = FIFOScheduler()
    reqs = [Request(rid=i, prompt=np.zeros(2, np.int64),
                    max_new_tokens=1, sampling=SamplingParams())
            for i in range(3)]
    for r in reqs:
        s.add(r)
    # two free slots -> first two requests, FCFS, one per slot
    got = s.admissions([5, 7])
    assert [(slot, r.rid) for slot, r in got] == [(5, 0), (7, 1)]
    assert s.depth == 1 and s.has_pending()
    assert s.admissions([]) == []
    assert [(sl, r.rid) for sl, r in s.admissions([0, 1])] == [(0, 2)]
    assert not s.has_pending()


def test_sample_token_top_k_truncates():
    logits = np.array([0.0, 5.0, 4.0, 3.0, -1.0])
    rng = np.random.RandomState(0)
    p = SamplingParams(temperature=1.0, top_k=3)
    draws = {sample_token(logits, p, rng) for _ in range(60)}
    assert draws <= {1, 2, 3}
    # greedy and top_k=1 agree
    g = SamplingParams()
    one = SamplingParams(temperature=0.7, top_k=1)
    assert sample_token(logits, g, rng) == 1
    assert sample_token(logits, one, rng) == 1
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1).validate()


# -- decode parity vs the synchronized whole-batch path ----------------

def test_engine_matches_synchronized_batch_greedy():
    """The acceptance bar: token-identical greedy outputs to the
    synchronized-batch static decode on a fixed trace."""
    model = _tiny_llama()
    rng = np.random.RandomState(0)
    prompts = _prompts(rng, [6, 6, 6])
    ids = paddle.to_tensor(np.stack(prompts))
    ref = model.generate(ids, max_new_tokens=8).numpy()[:, 6:]

    eng = ServingEngine(model, max_slots=3, max_len=64, min_bucket=8)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run()
    for row, req in zip(ref, reqs):
        np.testing.assert_array_equal(row, np.asarray(req.output_ids))


def test_engine_ragged_parity_and_gqa():
    """Mixed prompt lengths through the slot pool must reproduce each
    request's own bs=1 generate() tokens (per-row positions + per-slot
    mask do not leak across slots); GQA folds through the same path."""
    model = _tiny_llama(num_key_value_heads=2)
    rng = np.random.RandomState(1)
    prompts = _prompts(rng, [3, 9, 5, 12, 7])
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=4)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    for p, req in zip(prompts, reqs):
        ref = model.generate(paddle.to_tensor(p[None]),
                             max_new_tokens=6).numpy()[0, len(p):]
        np.testing.assert_array_equal(ref, np.asarray(req.output_ids))


def test_engine_serves_gpt_family():
    """The engine is model-agnostic: GPT's cache-aware forward (learned
    positions instead of RoPE) rides the same slot pool."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(2)
    prompts = _prompts(rng, [4, 7, 11])
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8)
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run()
    for p, req in zip(prompts, reqs):
        ids = p[None].copy()
        for _ in range(5):  # reference: full-context greedy recompute
            logits = model(paddle.to_tensor(ids)).numpy()[0, -1]
            ids = np.concatenate(
                [ids, [[int(np.argmax(logits))]]], axis=1)
        np.testing.assert_array_equal(ids[0, len(p):],
                                      np.asarray(req.output_ids))


# -- compile-count contract --------------------------------------------

def test_compile_counts_stay_bucketed():
    """1 decode program + one prefill program per power-of-2 bucket, no
    matter how many distinct prompt lengths arrive (trace counting:
    the counters bump inside the traced python, once per compile)."""
    model = _tiny_llama()
    rng = np.random.RandomState(3)
    lens = [3, 4, 5, 6, 7, 9, 12, 17, 18, 23, 31]
    eng = ServingEngine(model, max_slots=4, max_len=64, min_bucket=4)
    for p in _prompts(rng, lens):
        eng.submit(p, max_new_tokens=3)
    eng.run()
    assert eng.trace_counts["decode"] == 1
    budget = set(prefill_buckets(4, 64))
    assert set(eng.trace_counts["prefill"]) <= budget
    # every bucket compiled AT MOST once (17/18/23/31 share the 32s)
    assert all(n == 1 for n in eng.trace_counts["prefill"].values())
    assert eng.trace_counts["prefill"] == {4: 1, 8: 1, 16: 1, 32: 1}


# -- slot admission / eviction -----------------------------------------

def test_iteration_level_admission_and_eviction():
    """Short requests finish, free their slot, and the queue refills it
    while a long request keeps decoding — the continuous-batching
    property itself (no synchronized-batch drain between requests)."""
    model = _tiny_llama()
    rng = np.random.RandomState(4)
    prompts = _prompts(rng, [5, 5, 5, 5, 5])
    news = [3, 12, 3, 3, 3]
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8)
    reqs = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, news)]
    holders = []           # which request ids sit in slots, per step
    while eng.has_work():
        eng.step()
        holders.append({r.rid for r in eng.cache.slots
                        if r is not None})
    long_rid = reqs[1].rid
    # while the long request was mid-flight, its companion slot turned
    # over through the OTHER requests (iteration-level refill)
    companions = set()
    for h in holders:
        if long_rid in h:
            companions |= h - {long_rid}
    assert len(companions) >= 3, holders
    assert all(r.finished for r in reqs)
    assert [r.finish_reason for r in reqs] == ["length"] * 5
    assert eng.cache.free_slots() == [0, 1]          # all evicted
    # continuous batching bounds the step count by the LONG pole (+
    # admission tail), far under the 2-at-a-time synchronized drain
    assert eng.metrics.summary()["steps"] <= 14


def test_eos_evicts_early():
    model = _tiny_llama()
    rng = np.random.RandomState(5)
    prompt = _prompts(rng, [6])[0]
    probe = ServingEngine(model, max_slots=1, max_len=64)
    r0 = probe.submit(prompt, max_new_tokens=8)
    probe.run()
    assert len(r0.output_ids) == 8 and r0.finish_reason == "length"
    eos = r0.output_ids[2]
    eng = ServingEngine(model, max_slots=1, max_len=64, eos_id=eos)
    r1 = eng.submit(prompt, max_new_tokens=8)
    eng.run()
    assert r1.finish_reason == "eos"
    assert r1.output_ids == r0.output_ids[:3]        # stops AT the EOS
    assert eng.cache.free_slots() == [0]


def test_typed_admission_errors():
    """Flow-control failures are TYPED: a full bounded queue raises
    QueueFull (not silent unbounded growth), step() on an empty engine
    raises EngineIdle (not a silent no-op)."""
    from paddle_tpu.serving import EngineIdle, QueueFull, ServingError
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=1, max_len=32, max_queue=2)
    with pytest.raises(EngineIdle):
        eng.step()
    prompt = np.arange(1, 5)
    eng.submit(prompt, 2)
    eng.submit(prompt, 2)
    with pytest.raises(QueueFull) as ei:
        eng.submit(prompt, 2)
    assert ei.value.max_queue == 2 and ei.value.depth == 2
    assert isinstance(ei.value, ServingError)     # catchable as base
    eng.step()                  # one admitted: a slot frees queue room
    eng.submit(prompt, 2)       # accepted again
    eng.run()
    with pytest.raises(EngineIdle):
        eng.step()


def test_broken_recover_token_identical_replay():
    """The poisoned -> recover() -> token-identical-replay path: after
    a step fails with donated pools, recover() rebuilds the KV pools by
    re-prefilling prompt + delivered tokens, and the remaining greedy
    decode matches an unbroken engine token-for-token."""
    from paddle_tpu.serving import EngineBroken
    model = _tiny_llama()
    rng = np.random.RandomState(8)
    prompts = _prompts(rng, [6, 9, 4])

    ref = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8)
    refs = [ref.submit(p, max_new_tokens=8) for p in prompts]
    ref.run()

    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8)
    eng._donate = lambda: (5, 6)          # simulate the TPU path
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    eng.step()

    def boom(n):
        raise RuntimeError("device fault mid-step")

    orig_on_step, eng.metrics.on_step = eng.metrics.on_step, boom
    with pytest.raises(RuntimeError, match="device fault"):
        eng.step()
    eng.metrics.on_step = orig_on_step
    with pytest.raises(EngineBroken, match="recover"):
        eng.step()
    report = eng.recover()
    assert report["recovered_slots"] >= 1
    assert report["replay_mismatches"] == 0   # greedy replay verified
    eng.run()
    for r_ref, r in zip(refs, reqs):
        assert r_ref.output_ids == r.output_ids, (r_ref.rid, r.rid)
    assert eng.cache.free_slots() == [0, 1]


def test_finished_in_failed_step_delivered_once_via_recover():
    """Deferred PR-3 bug (a): a deadline-cancel sweep and a decode
    fault land in the SAME step (donated pools). The expired request
    reached its terminal state inside the failed step — it must
    surface exactly once, through the recover() report, never lost
    and never duplicated."""
    from paddle_tpu.resilience import faults
    model = _tiny_llama()
    clock = {"t": 0.0}
    eng = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8,
                        time_fn=lambda: clock["t"])
    eng._donate = lambda: (5, 6)          # simulate the TPU path
    a = eng.submit(np.arange(1, 6), max_new_tokens=6)
    b = eng.submit(np.arange(1, 6), max_new_tokens=6, deadline_s=1.0)
    eng.step()                            # a takes the slot; b queued
    faults.inject("serving.step.decode", times=1)
    clock["t"] = 5.0                      # b expires at the sweep...
    with pytest.raises(faults.InjectedFault):
        eng.step()                        # ...then the decode dies
    assert b.finished and b.finish_reason == "deadline"
    report = eng.recover()
    assert [r.rid for r in report["finished"]] == [b.rid]
    done = eng.run()
    assert b not in done                  # exactly once
    assert a in done and a.finish_reason == "length"


def test_finished_in_failed_step_delivered_once_via_next_step():
    """Bug (a), undonated (CPU) flavor: the engine is not broken after
    the failed step, so the stranded terminal request rides the next
    SUCCESSFUL step() return instead."""
    from paddle_tpu.resilience import faults
    model = _tiny_llama()
    clock = {"t": 0.0}
    eng = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8,
                        time_fn=lambda: clock["t"])
    a = eng.submit(np.arange(1, 6), max_new_tokens=6)
    b = eng.submit(np.arange(1, 6), max_new_tokens=6, deadline_s=1.0)
    eng.step()
    faults.inject("serving.step.decode", times=1)
    clock["t"] = 5.0
    with pytest.raises(faults.InjectedFault):
        eng.step()
    finished = eng.step()                 # first successful step
    assert b in finished
    rest = eng.run()
    assert b not in rest and a in rest


def test_drain_preserves_done_across_mid_drain_failure():
    """Deferred PR-3 bug (b): a transient step failure inside drain()
    must not discard the already-finished `done` list — the drain
    retries and returns every result."""
    from paddle_tpu.resilience import faults
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8)
    r1 = eng.submit(np.arange(1, 6), max_new_tokens=2)
    r2 = eng.submit(np.arange(1, 6), max_new_tokens=4)
    # r1 finishes on the 1st decode; the fault fires on the 3rd, well
    # after r1 already sits in drain()'s done list
    faults.inject("serving.step.decode", times=1, after=2)
    done = eng.drain()
    assert faults.fired("serving.step.decode") == 1
    assert {r.rid for r in done} == {r1.rid, r2.rid}
    assert r1.finish_reason == "length"
    assert r2.finish_reason == "length"   # transient fault retried


def test_drain_broken_mid_drain_returns_done_and_cancels_rest():
    """Bug (b), donated flavor: the engine BREAKS mid-drain; drain()
    keeps the finished results and cancels the remainder instead of
    raising them away."""
    from paddle_tpu.resilience import faults
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8)
    eng._donate = lambda: (5, 6)
    r1 = eng.submit(np.arange(1, 6), max_new_tokens=2)
    r2 = eng.submit(np.arange(1, 6), max_new_tokens=6)
    faults.inject("serving.step.decode", times=1, after=2)
    done = eng.drain()
    assert {r.rid for r in done} == {r1.rid, r2.rid}
    assert r1.finish_reason == "length"
    assert r2.finish_reason == "cancelled"
    assert "broken" in str(r2.error)


def test_drain_gives_up_after_repeated_transient_failures():
    """A drain that cannot make progress (every step fails, engine not
    broken) cancels the backlog after a bounded number of consecutive
    failures instead of looping or raising."""
    from paddle_tpu.resilience import faults
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8)
    r1 = eng.submit(np.arange(1, 6), max_new_tokens=2)
    faults.inject("serving.step.prefill", times=10)
    done = eng.drain()
    assert done == [r1]
    assert r1.finish_reason == "cancelled"
    assert "consecutive step failures" in str(r1.error)
    assert not eng.has_work()


def test_raising_auditor_never_loses_requests():
    """Review rider: delivery is consumed only when the return
    actually happens — a caller-supplied auditor that raises leaves
    the debt owed, and the next call (here: drain) flushes it instead
    of losing the finished request."""

    class BoomAuditor:
        def __init__(self):
            self.fail = 1
            self.seen = []

        def on_submitted(self, req):
            pass

        def on_delivered(self, req, via):
            if self.fail:
                self.fail -= 1
                raise RuntimeError("audit boom")
            self.seen.append((req.rid, via))

    model = _tiny_llama()
    aud = BoomAuditor()
    eng = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8,
                        auditor=aud)
    r = eng.submit(np.arange(1, 6), max_new_tokens=1)
    with pytest.raises(RuntimeError, match="audit boom"):
        eng.step()
    assert r.finished and eng._undelivered == [r]   # owed, not lost
    done = eng.drain()
    assert done == [r] and r.finish_reason == "length"
    assert aud.seen == [(r.rid, "drain")]
    assert eng._undelivered == []


def test_submit_validation():
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=1, max_len=32)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros((0,), np.int64))
    with pytest.raises(ValueError, match="single prompt"):
        eng.submit(np.zeros((2, 4), np.int64))   # a batch is NOT one req
    assert eng.submit(np.zeros((1, 4), np.int64)).prompt_len == 4
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.zeros((4,), np.int64), max_new_tokens=0)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(np.zeros((20,), np.int64), max_new_tokens=20)
    with pytest.raises(ValueError, match="position range"):
        ServingEngine(model, max_slots=1, max_len=4096)


def test_sampling_seeded_replay():
    model = _tiny_llama()
    rng = np.random.RandomState(6)
    prompt = _prompts(rng, [5])[0]
    outs = []
    for _ in range(2):
        eng = ServingEngine(model, max_slots=1, max_len=64)
        r = eng.submit(prompt, max_new_tokens=6,
                       sampling=SamplingParams(temperature=0.8,
                                               top_k=20, seed=11))
        eng.run()
        outs.append(r.output_ids)
    assert outs[0] == outs[1]


# -- metrics accounting ------------------------------------------------

def test_metrics_accounting_fake_clock():
    """Exact accounting on a driven clock: submit at t=0, step at
    t=1,2,3 with max_new_tokens=4 (prefill token + first decode token
    land together at t=1)."""
    model = _tiny_llama()
    clock = {"t": 0.0}
    eng = ServingEngine(model, max_slots=1, max_len=64,
                        time_fn=lambda: clock["t"])
    prompt = _prompts(np.random.RandomState(7), [5])[0]
    eng.submit(prompt, max_new_tokens=4)
    t = 0.0
    while eng.has_work():
        t += 1.0
        clock["t"] = t
        eng.step()
    m = eng.metrics.summary()
    assert m["requests"] == 1
    assert m["total_tokens"] == 4
    assert m["steps"] == 3
    assert m["wall_s"] == pytest.approx(3.0)
    assert m["tokens_per_s"] == pytest.approx(4.0 / 3.0)
    assert m["ttft_p50_s"] == pytest.approx(1.0)
    # token gaps [0, 1, 1]: two tokens at t=1, then one per step
    assert m["tok_latency_p50_s"] == pytest.approx(1.0)
    assert m["occupancy_mean"] == pytest.approx(1.0)


# -- one step ahead ----------------------------------------------------
# A page engine enqueues the next decode step, fed this step's argmax
# on the device, before it reads this step. Each case runs the same
# traffic on an engine that runs ahead and on one drained (every step
# read before the next is built: its run-ahead taken away), with the
# same clock, and compares what the requests got.

@pytest.fixture(scope="module")
def ahead_model():
    return _tiny_llama()


def _ahead_engine(model, drained=False, **kw):
    from paddle_tpu.observability import MetricRegistry
    kw = dict(dict(max_slots=3, max_len=64, min_bucket=8, page_size=8,
                   registry=MetricRegistry()), **kw)
    eng = ServingEngine(model, **kw)
    if drained:
        eng._ahead_rows = lambda step, active: []
    return eng


def _ahead_counts(eng):
    """(steps enqueued ahead, {reason: rows thrown away})."""
    reg = eng.registry
    disc = reg.get("ptpu_serving_decode_discarded_rows_total")
    return (int(reg.get("ptpu_serving_decode_ahead_total").value),
            {r: int(disc.labels(reason=r).value)
             for r in ("finished", "resampled")})


def _mixed_traffic():
    """Four requests at step 0 on three slots (one waits), two more
    admitted between later steps: the first shares 12 tokens of the
    first prompt's 20, into the middle of its second page (a COW copy).
    One request of step 0 carries a deadline, another is cancelled."""
    rng = np.random.RandomState(12)
    base = rng.randint(1, 128, (20,)).astype(np.int64)
    first = [(base, 12, None), (_prompts(rng, [7])[0], 16, 4.5),
             (_prompts(rng, [11])[0], 14, None),
             (_prompts(rng, [5])[0], 9, None)]
    later = {2: (np.concatenate([base[:12], _prompts(rng, [6])[0]]), 8),
             4: (_prompts(rng, [9])[0], 10)}
    return first, later


def _drive(eng, clock, traffic, cancel_at=8, max_steps=80):
    first, later = traffic
    reqs = [eng.submit(p, n, deadline_s=d) for p, n, d in first]
    steps = 0
    while eng.has_work() and steps < max_steps:
        clock["t"] = float(steps)
        eng.step()
        steps += 1
        if steps in later:
            reqs.append(eng.submit(*later[steps]))
        if steps == cancel_at:
            eng.cancel(reqs[2])
    return [(r.output_ids, r.finish_reason) for r in reqs]


def test_running_ahead_serves_the_drained_engines_tokens(ahead_model):
    """(a) Greedy outputs and finish reasons, run ahead and drained,
    over EOS mid-stream, length, admissions between steps, a shared
    prefix with a COW page, a cancel and a deadline."""
    traffic = _mixed_traffic()
    clock = {"t": 0.0}
    probe = _drive(_ahead_engine(ahead_model, drained=True,
                                 time_fn=lambda: clock["t"]),
                   clock, traffic)
    eos = probe[5][0][3]      # the fourth token of a request let in late
    got = {}
    for drained in (False, True):
        clock["t"] = 0.0
        eng = _ahead_engine(ahead_model, drained=drained, eos_id=eos,
                            time_fn=lambda: clock["t"])
        got[drained] = _drive(eng, clock, traffic)
        assert eng.cache.cow_copies >= 1
        assert not eng.has_work()
        if not drained:
            ahead, thrown = _ahead_counts(eng)
    assert got[False] == got[True]
    reasons = [why for _, why in got[False]]
    assert {"eos", "length", "cancelled", "deadline"} <= set(reasons)
    assert any(why == "eos" and len(out) > 1 for out, why in got[False])
    # the deadline, the cancel and the EOS each threw away a row of a
    # step enqueued ahead
    assert ahead > 10
    assert thrown == {"finished": 3, "resampled": 0}


def _altered_every_fifth_of_each_request():
    """``chipbench.serving_loop.altered_tokens``'s fault counted per
    request (a request's own sampling stream names it), so that which
    tokens are altered does not depend on how rows interleave."""
    import contextlib
    import paddle_tpu.serving.engine as engine_module
    real, calls = engine_module.sample_token, {}

    def altered(logits, params, rng):
        n = calls[id(rng)] = calls.get(id(rng), 0) + 1
        tok = real(logits, params, rng)
        return (tok + 1) % len(logits) if n % 5 == 0 else tok

    @contextlib.contextmanager
    def patched():
        engine_module.sample_token = altered
        try:
            yield calls
        finally:
            engine_module.sample_token = real
    return patched()


@pytest.mark.parametrize("traffic", ["one_request", "a_batch"])
def test_a_resampled_row_runs_its_position_again(ahead_model, traffic):
    """(b) ``sample_token`` alters every fifth token, through the seam
    the benchmark's planted fault uses: a row whose sampled token is
    not the argmax it was fed ahead is thrown away and run again from
    the sampled token, so the outputs equal the drained engine's under
    the same fault, and ``discarded{resampled}`` counts those rows."""
    from chipbench.serving_loop import altered_tokens
    rng = np.random.RandomState(13)
    if traffic == "one_request":
        work = [(_prompts(rng, [9])[0], 17)]
        fault = lambda: altered_tokens(every=5)
    else:
        work = list(zip(_prompts(rng, [6, 10, 4, 8]), [15, 12, 18, 9]))
        fault = _altered_every_fifth_of_each_request
    got = {}
    for drained in (False, True):
        eng = _ahead_engine(ahead_model, drained=drained)
        with fault():
            reqs = [eng.submit(p, n) for p, n in work]
            eng.run(max_steps=100)
        got[drained] = [r.output_ids for r in reqs]
        if not drained:
            ahead, thrown = _ahead_counts(eng)
    assert got[False] == got[True]
    assert got[False] != [model_out for model_out in
                          _greedy_outputs(ahead_model, work)]
    # the altered tokens past each request's first (its prefill's)
    # that are not its last: a step had been enqueued behind each
    altered = sum(len(range(5, n, 5)) for _, n in work)
    assert thrown["finished"] == 0 and ahead > 0
    if traffic == "one_request":
        assert thrown["resampled"] == altered == 3
    else:
        assert 0 < thrown["resampled"] <= altered


def _greedy_outputs(model, work):
    eng = _ahead_engine(model, max_slots=len(work))
    reqs = [eng.submit(p, n) for p, n in work]
    eng.run(max_steps=100)
    return [r.output_ids for r in reqs]


def test_host_and_device_fed_steps_share_one_decode_program(ahead_model):
    """(c) The decode program is traced once over a run that mixes
    steps fed from the host's tokens with steps fed the argmax on the
    device: both reach it through the one token program, with one
    dtype, shape and placement."""
    eng = _ahead_engine(ahead_model)
    real, seen = eng._decode_fn(), []

    def spy(*args):
        toks = args[2]
        seen.append((type(toks), toks.dtype, toks.shape,
                     str(toks.sharding), toks.committed))
        return real(*args)

    eng._decode_jit = spy
    rng = np.random.RandomState(14)
    for p, n in zip(_prompts(rng, [5, 9, 3]), [8, 5, 11]):
        eng.submit(p, n)
    for _ in range(3):
        eng.step()
    eng.submit(_prompts(rng, [7])[0], 6)      # admitted while ahead
    eng.run(max_steps=100)
    ahead, _ = _ahead_counts(eng)
    assert 0 < ahead < len(seen)              # host-fed steps too
    assert len(set(seen)) == 1
    assert eng.trace_counts["decode"] == 1
    assert eng.trace_counts["tokens"] == 1


@pytest.mark.parametrize("case", ["sampled_row", "state_engine"])
def test_what_cannot_run_ahead_stays_synchronous(ahead_model, case):
    """(d) A batch with a ``temperature > 0`` row fetches its logits and
    samples on the host, and an engine with a recurrent state folds a
    token into it for good: neither runs a step ahead."""
    from paddle_tpu.observability import (TraceBuffer,
                                          install_trace_buffer, tracing)
    rng = np.random.RandomState(15)
    if case == "state_engine":
        from test_brumby_serving import _model as brumby_model
        eng = _ahead_engine(brumby_model(), page_size=None)
    else:
        eng = _ahead_engine(ahead_model)
        eng.submit(_prompts(rng, [4])[0], 12,
                   sampling=SamplingParams(temperature=0.9, seed=3))
    for p, n in zip(_prompts(rng, [6, 3]), [7, 10]):
        eng.submit(p, n)
    buf = TraceBuffer(tracing.DEFAULT_CAPACITY)
    prev = install_trace_buffer(buf)
    try:
        eng.run(max_steps=100)
        spans = tracing.query("serving.decode")["spans"]
    finally:
        install_trace_buffer(prev)
    assert not eng.has_work() and spans
    assert {s["attrs"]["ahead"] for s in spans} == {0}
    assert _ahead_counts(eng) == (0, {"finished": 0, "resampled": 0})
