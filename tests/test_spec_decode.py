"""Speculative decoding (serving/spec_decode.py + the engine's
widened verify program): draft-proposer units (determinism, edge
cases, hit-rate floor, state lifecycle) and the ISSUE-8 acceptance
band — greedy speculative output TOKEN-IDENTICAL to the
non-speculative engine and to generate(), for llama and GPT, with
and without COW-shared prefixes, across a >= 25-seed property
band — with the compile-once contract held (exactly ONE verify
program per engine, k=1 fallback inside it, trace-count asserted)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from conftest import model_greedy
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import (NgramProposer, SamplingParams,
                                ServingEngine)


@pytest.fixture(autouse=True)
def _clean_faults():
    from paddle_tpu.resilience import faults
    faults.clear()
    faults.reset_counts()
    yield
    faults.clear()


def _tiny_llama(**kw):
    paddle.seed(0)
    kw.setdefault("max_position_embeddings", 128)
    model = LlamaForCausalLM(llama_tiny_config(**kw))
    model.eval()
    return model


def _tiny_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _mixed_prompts(rng, n, lo=3, hi=14, shared_prefix=None):
    """Half repetitive (periodic — the traffic self-speculation pays
    on), half random (the k=1 fallback regime); optionally all
    sharing a common prefix (paged COW coverage)."""
    out = []
    for _ in range(n):
        L = int(rng.randint(lo, hi))
        if rng.random() < 0.5:
            pat = rng.randint(1, 100, (int(rng.randint(1, 4)),))
            p = np.tile(pat, (L // len(pat)) + 1)[:L]
        else:
            p = rng.randint(1, 100, (L,))
        if shared_prefix is not None:
            p = np.concatenate([shared_prefix, p])
        out.append(p.astype(np.int64))
    return out


# -- proposer units ----------------------------------------------------

def test_proposer_validation():
    with pytest.raises(ValueError, match="ngram"):
        NgramProposer(ngram=0)
    with pytest.raises(ValueError, match="min_ngram"):
        NgramProposer(ngram=2, min_ngram=3)
    with pytest.raises(ValueError, match="max_draft"):
        NgramProposer(max_draft=-1)


def test_proposer_deterministic_and_incremental():
    """Proposals are a pure function of the token history: a fresh
    proposer and one fed the same history incrementally agree, and
    repeated calls are stable."""
    ids = np.array([7, 8, 9, 7, 8, 9, 7, 8], np.int64)
    a = NgramProposer(ngram=2, max_draft=3)
    b = NgramProposer(ngram=2, max_draft=3)
    d1 = a.propose(0, ids)
    d2 = a.propose(0, ids)              # same history, same answer
    np.testing.assert_array_equal(d1, d2)
    for cut in range(4, len(ids) + 1):  # incremental feed
        d3 = b.propose(1, ids[:cut])
    np.testing.assert_array_equal(d1, d3)
    # the suffix (7, 8) last recurred at positions 3-4 -> the draft is
    # what followed: 9, 7, 8
    assert list(d1) == [9, 7, 8]


def test_proposer_empty_short_and_no_match():
    p = NgramProposer(ngram=2, max_draft=3)
    assert p.propose(0, np.zeros((0,), np.int64)).size == 0
    assert p.propose(0, np.array([5], np.int64)).size == 0  # too short
    # strictly non-repeating history: nothing to look up -> k=1
    assert p.propose(0, np.arange(1, 12, dtype=np.int64)).size == 0
    # max_tokens=0: never drafts
    rep = np.array([3, 3, 3, 3], np.int64)
    assert p.propose(0, rep, max_tokens=0).size == 0
    assert p.propose(0, rep).size > 0


def test_proposer_backoff_to_shorter_ngram():
    """A single repeated token (period 1) has no repeated 2-gram
    prefix early on — the min_ngram backoff still drafts it."""
    p = NgramProposer(ngram=2, max_draft=2, min_ngram=1)
    d = p.propose(0, np.array([9, 4, 4], np.int64))
    assert list(d) == [4]               # 1-gram hit on the repeat


def test_proposer_repeated_suffix_hit_rate_floor():
    """On a periodic sequence the proposer's next-token prediction
    must be right nearly always once the period has been seen — the
    floor that makes self-speculation worth running."""
    rng = np.random.RandomState(0)
    pat = rng.randint(1, 100, (4,))
    seq = np.tile(pat, 16).astype(np.int64)       # 64 tokens, period 4
    p = NgramProposer(ngram=2, max_draft=3)
    hits = total = 0
    for cut in range(10, len(seq)):
        d = p.propose(0, seq[:cut])
        if len(d):
            total += 1
            hits += int(d[0] == seq[cut])
    assert total >= 40                  # drafts actually fire
    assert hits / total >= 0.95, (hits, total)


def test_proposer_state_release_and_retain():
    p = NgramProposer(ngram=2, max_draft=2)
    rep = np.array([1, 2, 1, 2, 1], np.int64)
    for rid in (3, 4, 5):
        p.propose(rid, rep)
    assert p.tracked() == [3, 4, 5]
    p.release(4)
    assert p.tracked() == [3, 5]
    p.release(4)                        # idempotent
    p.retain([5])
    assert p.tracked() == [5]
    p.retain(())
    assert p.tracked() == []


def test_proposer_rebuilds_on_shrunk_history():
    """A history that SHRANK for a known rid (failover replay edge)
    must not poison the index — the proposer rebuilds from scratch."""
    p = NgramProposer(ngram=2, max_draft=2)
    long = np.array([1, 2, 3, 1, 2, 3, 1, 2], np.int64)
    p.propose(0, long)
    short = np.array([7, 8, 7, 8, 7], np.int64)
    d = p.propose(0, short)
    assert list(d) == [8, 7]            # indexed from the NEW history


# -- engine verify: the >= 25-seed token-identity property band --------

def _run_band(model, seeds, *, max_len=64, shared=False,
              spec_k=4, max_new=8):
    """One spec + one base engine (programs compile once), driven over
    ``seeds`` request mixes; every request's greedy output must be
    token-identical across the two."""
    spec = ServingEngine(model, max_slots=3, max_len=max_len,
                         min_bucket=8, page_size=8, speculative=True,
                         spec_k=spec_k)
    base = ServingEngine(model, max_slots=3, max_len=max_len,
                         min_bucket=8, page_size=8)
    accepted = 0
    for seed in seeds:
        rng = np.random.RandomState(seed)
        prefix = rng.randint(1, 100, (9,)).astype(np.int64) \
            if shared else None
        prompts = _mixed_prompts(rng, int(rng.randint(2, 5)),
                                 shared_prefix=prefix)
        news = [int(rng.randint(2, max_new + 1)) for _ in prompts]
        rs = [spec.submit(p, n) for p, n in zip(prompts, news)]
        rb = [base.submit(p, n) for p, n in zip(prompts, news)]
        spec.run()
        base.run()
        for a, b in zip(rs, rb):
            assert a.output_ids == b.output_ids, \
                (seed, a.rid, a.output_ids, b.output_ids)
    accepted = spec._spec["accepted_draft_tokens"]
    # compile-once contract across every ragged mix in the band:
    # exactly ONE verify program, and at most one k=1 decode program
    # (the ISSUE-9 verify GATE routes draft-less steps through it
    # instead of paying the k-wide program)
    assert spec.trace_counts["verify"] == 1
    assert spec.trace_counts["decode"] <= 1
    return spec, accepted


def test_llama_paged_identity_band_25_seeds_with_shared_prefixes():
    """Prefix sharing: every seed's prompts share a 9-token prefix
    (full page + mid-page partial -> COW on first write), so
    accepted/rejected speculative writes land in pages that started
    life shared."""
    model = _tiny_llama()
    spec, accepted = _run_band(model, range(25), shared=True)
    assert accepted >= 20       # the band really speculated
    assert spec.proposer.tracked() == []      # state all released
    assert spec.cache.prefix_hit_tokens > 0   # sharing really engaged
    assert spec.cache.cow_copies >= 1
    from paddle_tpu.resilience.invariants import page_leak_violations
    assert page_leak_violations(spec) == []   # spec rollback leak-free


def test_gpt_identity_band_unshared_and_shared():
    model = _tiny_gpt()
    _run_band(model, range(8))
    _run_band(model, range(8, 16), shared=True)


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_speculative_matches_generate(family):
    """End to end vs the model's own greedy decode (llama: the fused
    static-cache generate(); GPT: a cache-free loop): the spec
    engine's greedy output equals it."""
    model = _tiny_llama() if family == "llama" else _tiny_gpt()
    rng = np.random.RandomState(3)
    prompts = _mixed_prompts(rng, 4, lo=5, hi=10)
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        speculative=True, spec_k=4)
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run()
    for p, req in zip(prompts, reqs):
        assert req.output_ids == model_greedy(model, p, 10)
    assert eng._spec["accepted_draft_tokens"] >= 1    # it speculated


def test_speculative_eos_stops_inside_accepted_run():
    """An EOS inside an accepted multi-token run must terminate the
    request AT the EOS — the tokens the verifier accepted beyond it
    must never surface (sequential decode would have stopped)."""
    model = _tiny_llama()
    rng = np.random.RandomState(5)
    prompt = np.tile(rng.randint(1, 100, (2,)), 5).astype(np.int64)
    probe = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8)
    r0 = probe.submit(prompt, max_new_tokens=10)
    probe.run()
    for cut in range(2, len(r0.output_ids)):
        eos = r0.output_ids[cut]
        eng = ServingEngine(model, max_slots=1, max_len=64,
                            min_bucket=8, speculative=True, spec_k=4,
                            eos_id=eos)
        r1 = eng.submit(prompt, max_new_tokens=10)
        eng.run()
        stop = r0.output_ids.index(eos)
        assert r1.output_ids == r0.output_ids[:stop + 1], cut
        assert r1.finish_reason == "eos"


def test_sampled_requests_fall_back_to_k1_in_same_program():
    """Non-greedy rows run at per-row length 1 INSIDE the verify
    program (host sampling rides position-0 logits): same seeded
    output as the non-speculative engine, one verify compile. With
    the default GATE (ISSUE 9), all-sampled traffic never drafts, so
    the k-wide program is never even compiled — the k=1 decode
    program serves every step; ``spec_gate=False`` pins the original
    in-program fallback."""
    model = _tiny_llama()
    rng = np.random.RandomState(7)
    prompt = rng.randint(1, 100, (6,)).astype(np.int64)
    outs = []
    for mode in ("base", "gated", "ungated"):
        kw = {}
        if mode != "base":
            kw = {"speculative": True, "spec_k": 4,
                  "spec_gate": mode == "gated"}
        eng = ServingEngine(model, max_slots=2, max_len=64,
                            min_bucket=8, **kw)
        r = eng.submit(prompt, max_new_tokens=8,
                       sampling=SamplingParams(temperature=0.8,
                                               top_k=20, seed=11))
        eng.run()
        outs.append(r.output_ids)
        if mode == "gated":
            assert eng.trace_counts["verify"] == 0
            assert eng.trace_counts["decode"] == 1
            assert eng._spec["gated_steps"] > 0
        elif mode == "ungated":
            assert eng.trace_counts["verify"] == 1
            assert eng.trace_counts["decode"] == 0
        if mode != "base":
            # sampled rows never consumed a draft either way
            assert eng._spec["draft_tokens"] == 0
    assert outs[0] == outs[1] == outs[2]


def test_spec_config_validation():
    model = _tiny_llama()
    with pytest.raises(ValueError, match="spec_k"):
        ServingEngine(model, max_slots=1, max_len=32,
                      speculative=True, spec_k=1)
    with pytest.raises(ValueError, match="speculative=True"):
        ServingEngine(model, max_slots=1, max_len=32, spec_k=8)


# -- proposer state lifecycle through the ENGINE -----------------------

def test_proposer_state_cleanup_on_eviction_and_cancel():
    model = _tiny_llama()
    rng = np.random.RandomState(9)
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        speculative=True, spec_k=4)
    pat = np.tile(rng.randint(1, 100, (2,)), 4).astype(np.int64)
    a = eng.submit(pat, max_new_tokens=6)
    b = eng.submit(pat, max_new_tokens=12)
    eng.step()
    eng.step()                          # both drafted at least once
    assert set(eng.proposer.tracked()) <= {a.rid, b.rid}
    eng.cancel(b)
    assert b.rid not in eng.proposer.tracked()
    eng.run()
    assert a.finished
    assert eng.proposer.tracked() == []       # eviction released a


def test_proposer_state_cleanup_on_deadline():
    model = _tiny_llama()
    clock = {"t": 0.0}
    eng = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8,
                        speculative=True, spec_k=4,
                        time_fn=lambda: clock["t"])
    pat = np.tile(np.array([3, 5], np.int64), 4)
    r = eng.submit(pat, max_new_tokens=12, deadline_s=5.0)
    eng.step()
    assert r.rid in eng.proposer.tracked() or not r.finished
    clock["t"] = 99.0
    done = eng.step()                   # deadline sweep evicts r
    assert r in done and r.finish_reason == "deadline"
    assert eng.proposer.tracked() == []


def test_proposer_state_pruned_and_identity_held_through_recover():
    """A verify-step fault with donated pools breaks the engine;
    recover() re-prefills and decoding resumes — outputs stay
    token-identical to an unbroken non-speculative engine, and the
    proposer tracks only the surviving in-flight set."""
    from paddle_tpu.resilience import faults
    model = _tiny_llama()
    rng = np.random.RandomState(11)
    prompts = _mixed_prompts(rng, 3, lo=4, hi=10)

    base = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8)
    rb = [base.submit(p, max_new_tokens=8) for p in prompts]
    base.run()

    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        speculative=True, spec_k=4)
    eng._donate = lambda: (5, 6)          # simulate the TPU path
    rs = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.step()
    faults.inject("serving.decode.verify", times=1)
    with pytest.raises(faults.InjectedFault):
        eng.run()
    report = eng.recover()
    assert report["replay_mismatches"] == 0
    live = {r.rid for r in eng.cache.slots if r is not None}
    assert set(eng.proposer.tracked()) <= live
    eng.run()
    for a, b in zip(rs, rb):
        assert a.output_ids == b.output_ids
    assert eng.proposer.tracked() == []


def test_verify_fault_point_is_wired():
    """serving.decode.verify fires inside the speculative step (and
    ONLY there — a non-speculative engine never evaluates it)."""
    from paddle_tpu.resilience import faults
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8,
                        speculative=True, spec_k=4)
    eng.submit(np.arange(1, 7), max_new_tokens=4)
    faults.inject("serving.decode.verify", times=1)
    with pytest.raises(faults.InjectedFault):
        eng.run()
    assert faults.fired("serving.decode.verify") == 1
    eng.run()                            # CPU pools: step just retries
    faults.clear()


def test_faulted_verify_returns_overclaimed_pages():
    """Regression (ptpu-lint PTL301 on the verify step): a paged
    verify step claims the FULL k-wide write window up front
    (ensure_decode_range), then hits the mid-step kill point. Before
    the unwind existed, a faulted-but-retryable step stranded every
    page past the one holding next_pos — each faulted step silently
    shrank the admission pool until the request finished. The handler
    must rollback_speculation() so the pool (free pages AND the
    reservation budget) is byte-identical to the pre-step snapshot,
    and the retried step must still produce base-identical output."""
    from paddle_tpu.observability import MetricRegistry
    from paddle_tpu.resilience import faults
    from paddle_tpu.resilience.invariants import page_leak_violations
    model = _tiny_llama()
    kw = dict(max_slots=1, max_len=64, min_bucket=8, page_size=8)
    # own registries: spec_k=8 buckets must not collide with the
    # default registry's spec_k=4 histograms from earlier tests
    eng = ServingEngine(model, speculative=True, spec_k=8,
                        registry=MetricRegistry(), **kw)
    base = ServingEngine(model, registry=MetricRegistry(), **kw)
    prompt = np.arange(1, 13).astype(np.int64)   # 12 = 2 full pages
    h = eng.submit(prompt, max_new_tokens=10)
    hb = base.submit(prompt, max_new_tokens=10)

    # phase 1 — draft-less steps walk next_pos just past the page
    # boundary, deterministically
    eng.proposer.propose = \
        lambda rid, ids, k: np.empty((0,), np.int64)
    for _ in range(4):
        eng.step()
        if len(h.output_ids) >= 2:
            break
    req = eng.cache.slots[0]
    assert req is not None and req.rid == h.rid

    # phase 2 — force a full-width draft: the 8-wide verify window
    # crosses into a page the row does not hold yet, so the faulted
    # step REALLY claims a fresh page before it dies
    eng.proposer.propose = \
        lambda rid, ids, k: np.arange(1, 1 + k, dtype=np.int64)
    last_page = (req.next_pos + eng.spec_k - 1) // 8
    assert last_page > req.next_pos // 8
    assert int(eng.cache.page_table[0][last_page]) == 0

    free0 = eng.cache.free_page_count()
    comm0 = eng.cache._committed
    faults.inject("serving.decode.verify", times=1)
    with pytest.raises(faults.InjectedFault):
        eng.step()
    assert faults.fired("serving.decode.verify") == 1
    # the unwind returned the over-claimed window page(s); pre-fix
    # this reads free0 - 1 and the stranded page never comes back
    assert eng.cache.free_page_count() == free0
    assert eng.cache._committed == comm0
    assert int(eng.cache.page_table[0][last_page]) == 0

    faults.clear()
    eng.run()                            # retry replays the step
    base.run()
    assert h.output_ids == hb.output_ids
    assert page_leak_violations(eng) == []
