"""Paged KV cache (paddle_tpu/serving/slot_cache.PagedKVCache +
engine paged path): token identity with generate() over ragged
request mixes, copy-on-write prefix sharing (page-boundary and
mid-page divergence), refcount conservation across eviction, deadline
cancel and drain, int8-KV measured-parity gate, page-gated admission
under an oversubscribed pool, and the compile-count contract (paging
adds ZERO decode compiles)."""
import numpy as np
from types import SimpleNamespace
import pytest

import paddle_tpu as paddle
from conftest import model_greedy
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.resilience.invariants import page_leak_violations
from paddle_tpu.serving import (PagedKVCache, ServingEngine,
                                SlotCache)


def _tiny_llama(**kw):
    # deliberately minuscule (1 layer, d=32): every test compiles its
    # own engine programs, and the value here is in page bookkeeping
    # and identity, not the matmuls
    paddle.seed(0)
    kw.setdefault("max_position_embeddings", 128)
    kw.setdefault("num_hidden_layers", 1)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("intermediate_size", 64)
    kw.setdefault("num_attention_heads", 2)
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
    return [rng.randint(1, vocab, (n,)).astype(np.int64) for n in lens]


def _quiesced_ok(eng):
    v = page_leak_violations(eng)
    assert v == [], "\n".join(v)


# -- pool construction / bookkeeping (satellites 1 + 2) ----------------

def test_cache_geometry_validation():
    import jax.numpy as jnp
    for bad in [dict(num_layers=-1), dict(max_slots=0),
                dict(max_len=0), dict(kv_heads=0), dict(head_dim=0)]:
        kw = dict(num_layers=2, max_slots=2, max_len=16, kv_heads=2,
                  head_dim=4)
        kw.update(bad)
        with pytest.raises(ValueError):
            PagedKVCache(kw["num_layers"], kw["max_slots"],
                         kw["max_len"], kw["kv_heads"],
                         kw["head_dim"], jnp.float32, page_size=8)
    with pytest.raises(ValueError, match="multiple of page_size"):
        PagedKVCache(1, 2, 20, 2, 4, jnp.float32, page_size=8)
    with pytest.raises(ValueError, match="page_size"):
        PagedKVCache(1, 2, 16, 2, 4, jnp.float32, page_size=0)
    with pytest.raises(ValueError, match="num_pages"):
        PagedKVCache(1, 2, 16, 2, 4, jnp.float32, page_size=8,
                     num_pages=2)
    state = (("S", (2, 4), jnp.float32),)
    rows = lambda layers, slots, state: SlotCache(
        layers, state, slots, 16, 2, 4, jnp.float32, page_size=8)
    for bad in [((), 2, state), (("state",), 0, state),
                (("state",), 2, ()), (("kv",), 2, state),
                (("state", "window"), 2, state)]:
        with pytest.raises(ValueError):
            rows(*bad)


def test_slot_bookkeeping_is_maintained_not_scanned():
    """free/active come from maintained sets: correct through an
    arbitrary assign/release interleaving, and release returns slots
    in O(1) (no O(max_slots) list scans on the per-step path)."""
    import jax.numpy as jnp
    c = SlotCache(("state",), (("S", (2, 4), jnp.float32),), 5, 16, 2,
                  4, jnp.float32, page_size=16)
    rng = np.random.RandomState(0)
    held = set()
    for _ in range(200):
        assert c.free_slots() == sorted(set(range(5)) - held)
        assert c.active_slots() == sorted(held)
        assert c.occupancy == len(held) / 5
        if held and rng.rand() < 0.5:
            s = rng.choice(sorted(held))
            c.release(int(s))
            held.discard(int(s))
        elif len(held) < 5:
            s = rng.choice(sorted(set(range(5)) - held))
            c.assign(int(s), SimpleNamespace(rid=int(s)))
            held.add(int(s))
    for s in range(5):                  # misuse stays loud
        if s in held:
            with pytest.raises(RuntimeError):
                c.assign(s, "again")
        else:
            with pytest.raises(RuntimeError):
                c.release(s)


def test_page_span_and_reservation_accounting():
    import jax.numpy as jnp

    class R:
        def __init__(self, rid):
            self.rid = rid

    c = PagedKVCache(1, 2, 32, 2, 4, jnp.float32, page_size=8,
                     num_pages=5, prefix_sharing=False)
    assert c.page_span(2) == 1          # 1 prompt tok + 1 new
    assert c.page_span(9) == 1          # last write at pos 7
    assert c.page_span(10) == 2
    assert c.page_span(32) == 4
    assert c.usable_pages() == 4        # trash page excluded
    ids = np.arange(1, 10)              # 9 tokens -> 2 pages
    assert c.try_reserve(R(0), ids, 9 + 8)    # span(17) = 2 pages
    assert c.committed_pages == 2
    assert c.try_reserve(R(1), ids, 9 + 8)
    assert not c.try_reserve(R(2), ids, 9 + 8)  # 4th+5th page short
    assert not c.try_reserve(R(3), ids, 32)     # span 4 > remaining
    # consume one reservation into a slot and release it
    req = R(0)
    m, copies = c.begin_sequence(0, req, ids)
    assert m == 0 and copies == []
    assert c.free_page_count() == 2             # 2 allocated
    c.assign(0, req)
    c.release(0)
    assert c.free_page_count() == 4 and c.committed_pages == 2
    assert (c.page_table[0] == 0).all()


# -- token identity of the paged path with generate() ------------------

def test_paged_matches_generate_ragged_llama():
    """Acceptance bar: greedy outputs on the f32 paged path are
    token-identical to generate() over a ragged mix, for MHA and GQA,
    with and without the prefix index."""
    for kv_kw in ({}, {"num_key_value_heads": 1}):
        model = _tiny_llama(**kv_kw)
        rng = np.random.RandomState(1)
        prompts = _prompts(rng, [3, 9, 5, 12, 7, 17])
        ref = [model_greedy(model, p, 6) for p in prompts]
        for share in (True, False):
            eng = ServingEngine(model, max_slots=2, max_len=64,
                                min_bucket=4, page_size=8,
                                prefix_sharing=share)
            reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            eng.run()
            assert [r.output_ids for r in reqs] == ref, (kv_kw, share)


def test_paged_serves_gpt_family():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(2)
    prompts = _prompts(rng, [4, 7, 11])
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        page_size=8)
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run()
    assert [r.output_ids for r in reqs] == [
        model_greedy(model, p, 5) for p in prompts]


# -- copy-on-write prefix sharing --------------------------------------

def _share_trio(P=8):
    rng = np.random.RandomState(3)
    A = rng.randint(1, 128, (17,)).astype(np.int64)
    B = np.concatenate([A[:16], [5]])   # diverges AT a page boundary
    C = np.concatenate([A[:12], [9]])   # diverges mid-page (pos 12)
    return A, B, C


def _run_serial(model, prompts, share, quant=None, P=8, new=6):
    eng = ServingEngine(model, max_slots=3, max_len=64, min_bucket=8,
                        page_size=P, prefix_sharing=share,
                        kv_dtype=quant)
    out = []
    for p in prompts:
        r = eng.submit(p, max_new_tokens=new)
        eng.run()                  # serial: earlier prompts register
        out.append(r.output_ids)
    return out, eng


def test_cow_divergence_page_boundary_and_mid_page():
    model = _tiny_llama()
    A, B, C = _share_trio()
    ref, _ = _run_serial(model, (A, B, C), share=False)
    got, eng = _run_serial(model, (A, B, C), share=True)
    assert got == ref                       # token-identical
    s = eng.paged_stats()
    # A: 16 lookup 0 hit; B: matches A's both full pages (16);
    # C: full page 0 (8) + mid-page partial (4) = 12
    assert s["prefix_hit_tokens"] == 28, s
    # only C's mid-page divergence copies; B's boundary divergence
    # starts a fresh page with NO copy
    assert s["cow_copies"] == 1, s
    assert eng.trace_counts["copy"] == 1    # copy program compiled once
    assert eng.trace_counts["decode"] == 1
    _quiesced_ok(eng)


def test_shared_pages_are_refcounted_and_cached_after_release():
    model = _tiny_llama()
    A, B, _ = _share_trio()
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        page_size=8)
    ra = eng.submit(A, max_new_tokens=4)
    eng.run()
    cache = eng.cache
    cached_after_a = cache.cached_page_count()
    assert cached_after_a == 2              # A's two full prompt pages
    rb = eng.submit(B, max_new_tokens=4)
    eng.step()                              # B admitted, references A's
    shared = [int(p) for p in cache.page_table[rb.slot][:2]]
    assert all(cache.refcnt[p] == 1 for p in shared)
    assert cache.cached_page_count() == 0   # both pinned by B
    eng.run()
    assert all(cache.refcnt[p] == 0 for p in shared)
    assert cache.cached_page_count() >= 2   # back to cached
    _quiesced_ok(eng)


def test_refcounts_release_on_deadline_and_cancel():
    model = _tiny_llama()
    clock = {"t": 0.0}
    rng = np.random.RandomState(4)
    prompts = _prompts(rng, [9, 9, 9])
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        page_size=8, time_fn=lambda: clock["t"])
    r0 = eng.submit(prompts[0], max_new_tokens=30, deadline_s=2.0)
    r1 = eng.submit(prompts[1], max_new_tokens=30)
    r2 = eng.submit(prompts[2], max_new_tokens=30)   # queued
    eng.step()
    assert eng.cache.active_page_count() > 0
    clock["t"] = 5.0                  # r0 expires at the next sweep
    eng.step()
    assert r0.finished and r0.finish_reason == "deadline"
    eng.cancel(r1)
    eng.cancel(r2)
    eng.drain()
    _quiesced_ok(eng)


def test_prefill_fault_unwinds_claimed_pages():
    """Mid-prefill fault AFTER pages are claimed: the abort path must
    return every page and the reservation (chaos pins the same law
    over random schedules)."""
    from paddle_tpu.resilience import faults
    model = _tiny_llama()
    A, B, _ = _share_trio()
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        page_size=8)
    eng.submit(A, max_new_tokens=2)
    eng.run()
    faults.inject("serving.prefill.paged", times=1)
    rb = eng.submit(B, max_new_tokens=2)       # shared-prefix request
    with pytest.raises(faults.InjectedFault):
        eng.step()
    assert faults.fired("serving.prefill.paged") == 1
    assert eng.cache.active_page_count() == 0  # unwound
    assert eng.cache.committed_pages == 0
    hit_after_abort = eng.cache.prefix_hit_tokens
    done = eng.run()                           # requeued, retried
    assert rb in done and rb.finish_reason == "length"
    # the aborted attempt's counter bump rolled back: the retry
    # counts B's shared tokens exactly once
    assert eng.cache.prefix_hit_tokens == hit_after_abort + 16
    _quiesced_ok(eng)


def test_recover_rebuilds_paged_pool_token_identical():
    """Donated-pool step failure -> recover() re-prefills into a FRESH
    paged pool (empty prefix index) and greedy decode resumes
    token-identically."""
    from paddle_tpu.serving import EngineBroken
    model = _tiny_llama()
    rng = np.random.RandomState(8)
    prompts = _prompts(rng, [6, 9, 4])
    ref = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        page_size=8)
    refs = [ref.submit(p, max_new_tokens=8) for p in prompts]
    ref.run()

    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        page_size=8)
    eng._donate = lambda: (5, 6)
    reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    eng.step()

    def boom(n):
        raise RuntimeError("device fault mid-step")

    orig, eng.metrics.on_step = eng.metrics.on_step, boom
    with pytest.raises(RuntimeError, match="device fault"):
        eng.step()
    eng.metrics.on_step = orig
    with pytest.raises(EngineBroken):
        eng.step()
    report = eng.recover()
    assert report["replay_mismatches"] == 0
    eng.run()
    for r_ref, r in zip(refs, reqs):
        assert r_ref.output_ids == r.output_ids
    _quiesced_ok(eng)


def test_mid_prompt_content_divergence_still_shares():
    """Partial sharing must also fire when the prompt CONTENT diverges
    mid-page with a long tail still to come (not only when the prompt
    runs out mid-page): the common prefix of the divergent page is
    referenced and COW'd on the first tail write."""
    model = _tiny_llama()
    rng = np.random.RandomState(10)
    A = rng.randint(1, 128, (20,)).astype(np.int64)
    B = np.concatenate(
        [A[:10], rng.randint(1, 128, (10,))]).astype(np.int64)
    ref, _ = _run_serial(model, (A, B), share=False)
    got, eng = _run_serial(model, (A, B), share=True)
    assert got == ref
    s = eng.paged_stats()
    # B matches A's full page 0 (8) + 2 tokens into the divergent
    # page 1 -> 10 hit tokens, one COW copy
    assert s["prefix_hit_tokens"] == 10, s
    assert s["cow_copies"] == 1, s
    _quiesced_ok(eng)


def test_extend_bucket_overrunning_rope_table_stays_identical():
    """Regression: when the shared-tail extend's bucket padding runs
    past the rope table (max_len == max_position_embeddings, start +
    min_bucket > max_len), the REAL tail tokens must still rotate at
    their true positions — a clamped dynamic_slice start used to
    shift them silently."""
    model = _tiny_llama(max_position_embeddings=64)
    rng = np.random.RandomState(11)
    A = rng.randint(1, 128, (60,)).astype(np.int64)
    B = np.concatenate([A[:59], [7]])   # matched 56, tail 4 ->
    outs = []                           # bucket 16, 56+16 > 64
    for share in (False, True):
        eng = ServingEngine(model, max_slots=2, max_len=64,
                            min_bucket=16, page_size=8,
                            prefix_sharing=share)
        got = []
        for p in (A, B):
            r = eng.submit(p, max_new_tokens=4)
            eng.run()
            got.append(r.output_ids)
        outs.append(got)
        if share:
            assert eng.trace_counts["extend"], eng.trace_counts
    assert outs[0] == outs[1]


def test_prefix_hit_counters_count_commits_not_retries():
    """A blocked FCFS head is re-claimed every step; the prefix
    hit/lookup counters must count each request ONCE (at reservation
    commit), or the PAGED_KV hit-rate artifact inflates."""
    model = _tiny_llama()
    rng = np.random.RandomState(12)
    prompts = _prompts(rng, [9, 9, 9])
    # pool fits two 2-page requests at a time -> the third blocks
    eng = ServingEngine(model, max_slots=3, max_len=32, min_bucket=8,
                        page_size=8, num_pages=5)
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    eng.run()
    # 3 commits x 8 matchable tokens each, however many steps the
    # heads spent blocked
    assert eng.cache.prefix_lookup_tokens == 24
    _quiesced_ok(eng)


# -- int8 KV parity gate ------------------------------------------------

def test_int8_kv_greedy_parity_gate():
    """Measured-parity gate: int8 KV (per-page scales) greedy tokens
    must agree with the model-dtype path at >= 90% on a ragged mix —
    and the logits path stays finite. (Token identity is pinned for
    the non-quantized path only; int8 is a measured trade.)"""
    model = _tiny_llama()
    rng = np.random.RandomState(5)
    prompts = _prompts(rng, [5, 11, 8, 14])
    ref, _ = _run_serial(model, prompts, share=False)
    got, eng = _run_serial(model, prompts, share=False, quant="int8")
    total = sum(len(x) for x in ref)
    agree = sum(int(a == b) for x, y in zip(got, ref)
                for a, b in zip(x, y))
    assert agree / total >= 0.9, (agree, total, got, ref)
    assert eng.kv_quant and eng.cache.quant
    import jax.numpy as jnp
    assert eng.cache.ks[0].dtype == jnp.int8
    assert eng.cache.kss[0].dtype == jnp.float32
    _quiesced_ok(eng)


def test_int8_kv_with_prefix_sharing_and_cow():
    model = _tiny_llama()
    A, B, C = _share_trio()
    ref, _ = _run_serial(model, (A, B, C), share=True)
    got, eng = _run_serial(model, (A, B, C), share=True, quant="int8")
    total = sum(len(x) for x in ref)
    agree = sum(int(a == b) for x, y in zip(got, ref)
                for a, b in zip(x, y))
    assert agree / total >= 0.9
    assert eng.paged_stats()["cow_copies"] == 1
    _quiesced_ok(eng)


# -- compile-count contract ---------------------------------------------

def test_paging_adds_zero_decode_compiles():
    """One decode program across admission, shared-prefix extends,
    COW copies, eviction and refill — paging must not add a single
    decode compile (the repo's compile-once serving contract)."""
    model = _tiny_llama()
    A, B, C = _share_trio()
    rng = np.random.RandomState(6)
    extra = _prompts(rng, [3, 4, 5, 6, 7, 9, 12, 18])
    eng = ServingEngine(model, max_slots=3, max_len=64, min_bucket=4,
                        page_size=8)
    for p in [A, B, C] + extra:
        eng.submit(p, max_new_tokens=3)
    eng.run()
    assert eng.trace_counts["decode"] == 1
    # full-prefill buckets stay inside the O(log max_len) budget and
    # extend buckets reuse the same bucket set
    from paddle_tpu.serving import prefill_buckets
    budget = set(prefill_buckets(4, 64))
    assert set(eng.trace_counts["prefill"]) <= budget
    assert set(eng.trace_counts["extend"]) <= budget
    assert all(n == 1 for n in eng.trace_counts["prefill"].values())
    _quiesced_ok(eng)


# -- page-gated admission / oversubscription ----------------------------

def test_admission_gated_by_free_pages_not_slots():
    """A pool with fewer pages than slots admits by PAGES: concurrency
    is bounded by the page budget, every request still completes, and
    the budget is returned."""
    model = _tiny_llama()
    rng = np.random.RandomState(7)
    prompts = _prompts(rng, [9] * 6)
    # span(9+6) = 2 pages per request; 4 usable pages -> 2 in flight
    eng = ServingEngine(model, max_slots=6, max_len=32, min_bucket=8,
                        page_size=8, num_pages=5,
                        prefix_sharing=False)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    peak = 0
    while eng.has_work():
        eng.step()
        peak = max(peak, len(eng.cache.active_slots()))
    assert peak <= 2                    # page-bounded, not slot-bounded
    assert all(r.finish_reason == "length" for r in reqs)
    assert [r.output_ids for r in reqs] == [
        model_greedy(model, p, 6) for p in prompts]
    _quiesced_ok(eng)


def test_cached_prefix_pages_are_reclaimed_under_pressure():
    """Refcount-0 cached prefix pages are the reclaim pool: admission
    that needs their pages drops the LRU index entries instead of
    refusing."""
    model = _tiny_llama()
    rng = np.random.RandomState(9)
    eng = ServingEngine(model, max_slots=2, max_len=32, min_bucket=8,
                        page_size=8, num_pages=6)
    a = rng.randint(1, 128, (17,)).astype(np.int64)
    eng.submit(a, max_new_tokens=2)
    eng.run()
    assert eng.cache.cached_page_count() == 2
    # a disjoint prompt needing more pages than the free list holds
    b = rng.randint(1, 128, (17,)).astype(np.int64)
    c = rng.randint(1, 128, (17,)).astype(np.int64)
    for p in (b, c):
        r = eng.submit(p, max_new_tokens=4)
        eng.run()
        assert r.finish_reason == "length"
    assert eng.cache.pages_reclaimed > 0
    _quiesced_ok(eng)
