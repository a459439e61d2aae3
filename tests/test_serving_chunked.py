"""Chunked prefill (ISSUE 14): long prompts split into fixed-budget
chunks interleaved with decode, without changing a single emitted
token.

Acceptance band: the ``prefill_chunk`` engine is greedy
TOKEN-IDENTICAL to the unchunked engine and to ``generate()`` across
a >= 25-seed property band — llama (GQA) and GPT, COW-shared
prefixes included, chunk sizes including
the chunk >= prompt degenerate case — with the compile contract
intact: ONE decode program, chunk programs bounded by the prefill
bucket set. Mid-prefill terminal paths (cancel / deadline /
disconnect between chunks) must free the PREFILLING slot and every
claimed page, and an injected ``serving.prefill.chunk`` fault must
unwind + requeue + replay token-identically. The bounded-lookahead
admission knob (``admission_lookahead``) is pinned here too: it
relieves page-gated head-of-line blocking without starving the head.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from conftest import model_greedy, serving_model_mesh
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.invariants import (engine_leak_violations,
                                              page_leak_violations)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.scheduler import prefill_buckets

pytestmark = pytest.mark.chaos  # fast, CPU-only


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_counts()
    yield
    faults.clear()


def _tiny_llama():
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=2, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64))
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


_MODELS = {}


def _model(family):
    if family not in _MODELS:
        _MODELS[family] = (_tiny_llama() if family == "llama"
                           else _tiny_gpt())
    return _MODELS[family]


def _wave(rng, n=4, shared=None):
    """One seeded traffic wave: ragged prompts (some LONG, so most
    waves really chunk), optionally sharing a prefix (paged COW)."""
    out = []
    for i in range(n):
        L = int(rng.randint(3, 40))
        p = rng.randint(1, 100, (L,)).astype(np.int64)
        if shared is not None and i % 2 == 0:
            p = np.concatenate([shared, p[:30]]).astype(np.int64)
        out.append(p)
    return out


def _drive(eng, prompts, max_new=6):
    reqs = [eng.submit(p, max_new) for p in prompts]
    while eng.has_work():
        eng.step()
    return [list(r.out_tokens) for r in reqs]


def _engine(family, **kw):
    eng_kw = dict(max_slots=3, max_len=64, min_bucket=8, page_size=8)
    eng_kw.update(kw)
    return ServingEngine(_model(family), **eng_kw)


# ---------------------------------------------------------------------------
# the >= 25-seed identity band (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["llama", "gpt"],
                         ids=["llama-paged", "gpt-paged"])
def test_chunked_identity_band_25_seeds(family):
    """Chunked greedy outputs == unchunked engine outputs, bitwise,
    for 25 seeded traffic waves per family — the waves share a
    prompt prefix so COW/prefix-index admissions chunk too.
    ONE engine per chunk size serves the whole band, so it also
    proves the compile contract: one decode program and chunk
    programs bounded by the prefill bucket set across all waves.
    chunk=64 == max_len is the degenerate case: every prompt fits one
    chunk and the engine must behave exactly like the unchunked one."""
    shared = np.arange(1, 11, dtype=np.int64)  # > 1 page of 8
    ref_eng = _engine(family)
    chunk_engines = {c: _engine(family, prefill_chunk=c)
                     for c in (8, 16, 64)}
    for seed in range(25):
        rng = np.random.RandomState(1400 + seed)
        prompts = _wave(rng, shared=shared)
        ref = _drive(ref_eng, prompts)
        sizes = (8, 16, 64) if seed % 5 == 0 \
            else ((8, 16, 64)[seed % 3],)
        for c in sizes:
            got = _drive(chunk_engines[c], prompts)
            assert got == ref, (family, seed, c)
    budget = set(prefill_buckets(8, 64))
    for c, eng in chunk_engines.items():
        assert eng.trace_counts["decode"] == 1, (family, c)
        assert set(eng.trace_counts["chunk"]) <= budget, \
            (family, c, eng.trace_counts["chunk"])
    assert ref_eng.trace_counts["decode"] == 1
    # the degenerate engine (chunk >= every prompt) prefills each
    # prompt as ONE whole-prompt chunk: its compiled chunk shapes are
    # exactly the bucketed prompt lengths the unchunked engine
    # compiled as monolithic prefills — no extra shapes
    assert set(chunk_engines[64].trace_counts["chunk"]) \
        <= set(ref_eng.trace_counts["prefill"])


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_chunked_matches_generate(family):
    """Transitive anchor: chunked engine == the model's own greedy
    decode directly (not just == the unchunked engine). llama pins
    against its public generate(); GPT (no generate()) against a
    cache-free full-forward greedy loop."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 100, (L,)).astype(np.int64)
               for L in (5, 23, 37)]
    got = _drive(_engine(family, prefill_chunk=8), prompts)
    assert got == [model_greedy(_model(family), p, 6) for p in prompts]


def test_chunk_trace_counts_pinned():
    """Exact compile accounting: prompts of 20 and 35 tokens at
    chunk=8 produce 8-token chunks only (finals are 4 and 3 tokens,
    bucketed back to 8) — ONE chunk program, one decode program, and
    no monolithic prefill at all."""
    eng = _engine("llama", prefill_chunk=8)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 100, (L,)).astype(np.int64)
               for L in (20, 35)]
    assert _drive(eng, prompts) == _drive(_engine("llama"), prompts)
    assert eng.trace_counts["chunk"] == {8: 1}
    assert eng.trace_counts["decode"] == 1
    assert eng.trace_counts["prefill"] == {}


def test_chunk_budget_caps_tokens_per_step():
    """The per-step prefill token budget: while a chunked prefill is
    in flight, a step admits no monolithic prefill past the budget
    and advances at most ONE chunk — so no step ever carries more
    than ``chunk + max_slots`` tokens of work."""
    eng = _engine("llama", max_slots=3, prefill_chunk=8)
    rng = np.random.RandomState(9)
    long1 = rng.randint(1, 100, (30,)).astype(np.int64)
    long2 = rng.randint(1, 100, (25,)).astype(np.int64)
    r1 = eng.submit(long1, 4)
    r2 = eng.submit(long2, 4)
    eng.step()
    # both admitted into PREFILLING, neither finished a prompt in one
    # step, and only the fifo HEAD advanced
    assert r1.prefill_pos is not None and r1.prefill_pos <= 8
    assert r2.prefill_pos == 0
    assert len(eng._chunk_fifo) == 2
    steps = 1
    while eng.has_work():
        eng.step()
        steps += 1
    # 30 tokens + 25 tokens at one 8-token chunk per step, then the
    # decode tail: the prefill phase alone needs >= 7 steps
    assert steps >= 8
    assert not engine_leak_violations(eng)


# ---------------------------------------------------------------------------
# mid-chunk terminal paths: cancel / deadline / disconnect / fault
# ---------------------------------------------------------------------------

def _start_chunked(eng, prompt, max_new=4, **submit_kw):
    """Submit + step once: the request is admitted into PREFILLING
    (some chunks written, more to go)."""
    req = eng.submit(prompt, max_new, **submit_kw)
    eng.step()
    assert req.prefill_pos is not None, "request did not chunk"
    assert not req.finished
    return req


def test_mid_chunk_cancel_frees_slot_and_pages():
    eng = _engine("llama", prefill_chunk=8)
    rng = np.random.RandomState(11)
    req = _start_chunked(eng, rng.randint(1, 100, (40,)).astype(np.int64))
    assert eng.cancel(req)
    assert req.finished and req.finish_reason == "cancelled"
    assert eng._chunk_fifo == [] and req.slot is None
    while eng.has_work():
        eng.step()
    assert not engine_leak_violations(eng)
    assert not page_leak_violations(eng)


def test_mid_chunk_deadline_frees_slot_and_pages():
    clock = {"t": 0.0}
    eng = _engine("llama", prefill_chunk=8,
                  time_fn=lambda: clock["t"])
    rng = np.random.RandomState(12)
    req = _start_chunked(eng, rng.randint(1, 100, (40,)).astype(np.int64),
                         deadline_s=1.0)
    clock["t"] = 5.0            # expire mid-prefill
    while eng.has_work():
        eng.step()
    assert req.finished and req.finish_reason == "deadline"
    assert req.out_tokens == []          # never reached decode
    assert not engine_leak_violations(eng)
    assert not page_leak_violations(eng)


def test_mid_chunk_disconnect_frees_slot_and_pages():
    eng = _engine("llama", prefill_chunk=8)
    rng = np.random.RandomState(13)
    req = _start_chunked(eng, rng.randint(1, 100, (40,)).astype(np.int64))
    req.cancel_requested = True          # client went away
    while eng.has_work():
        eng.step()
    assert req.finished and req.finish_reason == "disconnect"
    assert not engine_leak_violations(eng)
    assert not page_leak_violations(eng)


def test_chunk_fault_unwinds_requeues_and_replays_identically():
    """An injected ``serving.prefill.chunk`` fault between chunks
    unwinds the PREFILLING request (slot + pages freed), requeues it,
    and the re-chunked replay emits EXACTLY the unfaulted tokens."""
    rng = np.random.RandomState(21)
    prompts = [rng.randint(1, 100, (L,)).astype(np.int64)
               for L in (35, 20)]
    ref = _drive(_engine("llama", prefill_chunk=8), prompts)

    eng = _engine("llama", prefill_chunk=8)
    reqs = [eng.submit(p, 6) for p in prompts]
    faults.inject("serving.prefill.chunk", times=1, after=2)
    fired = 0
    while eng.has_work():
        try:
            eng.step()
        except faults.InjectedFault:
            fired += 1
            # the unwind already ran: the FAULTED request is out of
            # the fifo and back in the queue (the other PREFILLING
            # request keeps its slot), and the engine is not broken
            assert eng.scheduler.pending()
            pending = {r.rid for r in eng.scheduler.pending()}
            fifo_rids = {eng.cache.slots[s].rid
                         for s in eng._chunk_fifo}
            assert not (pending & fifo_rids)
            assert not eng._broken
    assert fired == 1
    assert [list(r.out_tokens) for r in reqs] == ref
    assert not engine_leak_violations(eng)
    assert not page_leak_violations(eng)


def test_chunked_recover_replays_token_identically():
    """recover() with a PREFILLING request in flight: device pools are
    rebuilt and the replay (which re-prefills monolithically — the
    degenerate chunking) lands on the same tokens."""
    rng = np.random.RandomState(23)
    prompts = [rng.randint(1, 100, (L,)).astype(np.int64)
               for L in (30, 12)]
    ref = _drive(_engine("llama", prefill_chunk=8), prompts)

    eng = _engine("llama", prefill_chunk=8)
    reqs = [eng.submit(p, 6) for p in prompts]
    eng.step()
    assert eng._chunk_fifo          # someone is mid-prefill
    eng._broken = "test: forced break mid-chunked-prefill"
    eng.recover()
    assert eng._chunk_fifo == [] and eng._chunk_local == {}
    while eng.has_work():
        eng.step()
    assert [list(r.out_tokens) for r in reqs] == ref
    assert not engine_leak_violations(eng)
    assert not page_leak_violations(eng)


# ---------------------------------------------------------------------------
# composition: speculative decoding and the disaggregated mesh
# ---------------------------------------------------------------------------

def test_chunked_composes_with_speculative():
    """Chunked prefill + speculative decode in ONE engine: greedy
    outputs still match the plain k=1 unchunked engine, and the
    PREFILLING slot is skipped by the verify program until its final
    chunk."""
    rng = np.random.RandomState(31)
    pat = rng.randint(1, 100, (3,)).astype(np.int64)
    prompts = [np.tile(pat, 12)[:30].astype(np.int64),
               rng.randint(1, 100, (20,)).astype(np.int64)]
    ref = _drive(_engine("llama"), prompts, max_new=10)
    eng = _engine("llama", prefill_chunk=8,
                  speculative=True, spec_k=4)
    got = _drive(eng, prompts, max_new=10)
    assert got == ref
    assert eng.trace_counts["verify"] == 1
    assert set(eng.trace_counts["chunk"]) <= set(prefill_buckets(8, 64))


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_chunked_disaggregated_identity(family):
    """Disaggregated mesh engines chunk on the PREFILL group (local
    per-layer buffers, final-span handoff to the decode pool) and
    stay token-identical to the single-chip unchunked engine and to
    the model's own greedy decode."""
    mesh = serving_model_mesh(tp=2, prefill=2)
    rng = np.random.RandomState(41)
    prompts = [rng.randint(1, 100, (L,)).astype(np.int64)
               for L in (35, 20, 9)]
    ref = _drive(_engine(family), prompts)
    eng = _engine(family, mesh=mesh, prefill_devices=2,
                  prefill_chunk=8)
    got = _drive(eng, prompts)
    assert got == ref, family
    assert got == [model_greedy(_model(family), p, 6) for p in prompts]
    assert eng.trace_counts["decode"] == 1
    assert eng._chunk_local == {}        # every handoff completed
    assert not engine_leak_violations(eng)


# ---------------------------------------------------------------------------
# knob validation + bounded-lookahead admission (HOL fix)
# ---------------------------------------------------------------------------

def test_prefill_chunk_validation():
    with pytest.raises(ValueError, match="power of 2"):
        _engine("llama", prefill_chunk=12)
    with pytest.raises(ValueError, match="bucket"):
        _engine("llama", prefill_chunk=4)   # < min_bucket
    with pytest.raises(ValueError, match="admission_lookahead"):
        _engine("llama", admission_lookahead=-1)


def test_admission_lookahead_relieves_head_of_line():
    """FCFS head-of-line fix: with the page pool too small for the
    queue HEAD, strict FCFS (lookahead=0) idles the engine even
    though a smaller request behind it would fit;
    ``admission_lookahead=1`` admits the small request WITHOUT losing
    the head's queue position."""
    rng = np.random.RandomState(51)
    occ_p = rng.randint(1, 100, (33,)).astype(np.int64)
    big_p = rng.randint(1, 100, (40,)).astype(np.int64)
    small_p = rng.randint(1, 100, (5,)).astype(np.int64)

    def build(lookahead):
        # 8 data pages + trash. The occupier (33 + 16 -> 6 pages)
        # holds the pool for many steps; while it runs, the big head
        # (40 + 4 -> 6 pages) cannot reserve but the small request
        # (5 + 2 -> 1 page) can.
        eng = ServingEngine(
            _model("llama"), max_slots=3, max_len=64, min_bucket=8,
            page_size=8, num_pages=9, prefix_sharing=False,
            admission_lookahead=lookahead)
        occ = eng.submit(occ_p, 16)
        eng.step()                       # occupier admitted + running
        big = eng.submit(big_p, 4)
        small = eng.submit(small_p, 2)
        return eng, occ, big, small

    eng0, occ0, b0, s0 = build(0)
    for _ in range(5):                   # occupier still mid-decode
        eng0.step()
    assert not occ0.finished
    assert s0.out_tokens == []           # strict FCFS: stuck behind
    assert not b0.finished               # the page-blocked head
    while eng0.has_work():
        eng0.step()
    assert b0.finished and s0.finished   # ...but NOT starved forever

    eng1, occ1, b1, s1 = build(1)
    for _ in range(5):
        eng1.step()
    assert not occ1.finished
    assert s1.finished                   # admitted past the stuck
    assert len(s1.out_tokens) == 2       # head while it was blocked
    assert not b1.finished               # head kept its queue spot
    assert eng1.scheduler.pending()[0] is b1
    while eng1.has_work():
        eng1.step()
    assert b1.finished
    assert not engine_leak_violations(eng1)
    assert not page_leak_violations(eng1)


def test_lookahead_zero_is_strict_fcfs_bit_identical():
    """The default admission order with lookahead=0 is byte-identical
    to the historical policy: the claim-gated scan never skips."""
    from paddle_tpu.serving.scheduler import FIFOScheduler, Request
    from paddle_tpu.serving.sampling import SamplingParams

    def mk(rid, L):
        return Request(rid=rid, prompt=np.ones((L,), np.int64),
                       max_new_tokens=1, sampling=SamplingParams())

    sched = FIFOScheduler()
    for rid, L in enumerate((10, 3, 4)):
        sched.add(mk(rid, L))
    # head blocked, lookahead=0: NOTHING admitted (strict FCFS)
    picked = sched.admissions([0, 1], claim=lambda r: r.prompt_len < 5)
    assert picked == []
    assert [r.rid for r in sched.pending()] == [0, 1, 2]
    # lookahead=2: the two small ones pair with the free slots, the
    # blocked head stays put
    picked = sched.admissions([0, 1], claim=lambda r: r.prompt_len < 5,
                              lookahead=2)
    assert [(s, r.rid) for s, r in picked] == [(0, 1), (1, 2)]
    assert [r.rid for r in sched.pending()] == [0]
