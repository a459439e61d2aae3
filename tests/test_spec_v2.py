"""ISSUE-19 speculation v2 (serving/spec_decode.DraftModelProposer +
sampled rejection-sampling acceptance + serving/spec_tune.SpecTuner):

- DraftModelProposer units: config validation, the ONE-compiled-draft-
  program contract, slot-pool lifecycle (release/retain/reset, the
  no-leak audit surface) and degrade-to-k=1 when the pool is full.
- The greedy token-identity property band with a draft MODEL behind
  the verify program — an INDEPENDENT draft (disagrees with the
  target constantly) and a self-draft oracle (agrees constantly, the
  acceptance-floor regime) — across llama + GPT, with and without
  COW-shared prefixes, >= 25 seeds total.
- Sampled acceptance: distribution parity vs the k=1 engine
  (aggregate histograms under fixed sampling seeds), bitwise parity
  for sampled rows when spec_sampled is OFF, and the residual
  resample really firing under an independent draft.
- SpecTuner units: hysteresis dead band, dwell gating, probe cadence,
  proposer switching with margin — plus the tuner-driven GATING law
  through the engine: a no-draft regime provably runs the k=1 decode
  program (trace-counted), never the k-wide verify program.
- Lifecycle under failure: recover() replay with live draft-pool
  state, adopt() of a mid-flight request, and the serving.spec.draft
  containment law (a killed draft proposal costs one row's window,
  never the step, and output stays identical).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving.spec_decode import DraftModelProposer
from paddle_tpu.serving.spec_tune import SpecTuner


@pytest.fixture(autouse=True)
def _clean_faults():
    from paddle_tpu.resilience import faults
    faults.clear()
    faults.reset_counts()
    yield
    faults.clear()


def _tiny_llama(seed=0, **kw):
    paddle.seed(seed)
    kw.setdefault("max_position_embeddings", 128)
    model = LlamaForCausalLM(llama_tiny_config(**kw))
    model.eval()
    return model


def _tiny_draft(seed=7):
    """An INDEPENDENT draft model: same vocab/positions, different
    width and different weights — it disagrees with the target often,
    which is exactly the regime the identity law must survive."""
    return _tiny_llama(seed=seed, hidden_size=32, intermediate_size=64,
                       num_hidden_layers=1, num_attention_heads=2)


def _tiny_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def _prompts(rng, n, lo=3, hi=14, shared_prefix=None):
    out = []
    for _ in range(n):
        L = int(rng.randint(lo, hi))
        p = rng.randint(1, 100, (L,))
        if shared_prefix is not None:
            p = np.concatenate([shared_prefix, p])
        out.append(p.astype(np.int64))
    return out


# -- DraftModelProposer units ------------------------------------------

def test_draft_proposer_validation():
    model = _tiny_llama()
    with pytest.raises(ValueError, match="max_slots"):
        DraftModelProposer(model, max_slots=0, max_len=32)
    with pytest.raises(ValueError, match="max_draft"):
        DraftModelProposer(model, max_slots=1, max_len=32,
                           max_draft=-1)
    # the draft model must cover the TARGET horizon: positions past
    # its embedding table would draft garbage silently
    small = _tiny_llama(seed=1, max_position_embeddings=16)
    with pytest.raises(ValueError, match="positions"):
        DraftModelProposer(small, max_slots=1, max_len=64)


def test_engine_spec_v2_config_validation():
    model = _tiny_llama()
    with pytest.raises(ValueError, match="spec_proposer"):
        ServingEngine(model, max_slots=1, max_len=32,
                      speculative=True, spec_proposer="medusa")
    with pytest.raises(ValueError, match="draft_model="):
        ServingEngine(model, max_slots=1, max_len=32,
                      speculative=True, spec_proposer="draft")
    # every v2 knob is refused without speculative=True
    for kw in ({"spec_proposer": "draft"}, {"draft_model": model},
               {"spec_sampled": True}, {"spec_tune": True}):
        with pytest.raises(ValueError, match="speculative=True"):
            ServingEngine(model, max_slots=1, max_len=32, **kw)


def test_draft_proposer_deterministic_and_compile_once():
    """Greedy proposals are a pure function of (weights, history) —
    two proposers over the same history agree, incremental feeding
    agrees — and EVERY forward (catch-up at any width, wlen=1 chain)
    runs the ONE compiled draft program."""
    model = _tiny_llama()
    a = DraftModelProposer(model, max_slots=2, max_len=64, max_draft=3)
    b = DraftModelProposer(model, max_slots=2, max_len=64, max_draft=3)
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 100, (11,)).astype(np.int64)
    d1 = a.propose(0, ids)
    d2 = a.propose(0, ids)              # idempotent re-proposal
    np.testing.assert_array_equal(d1, d2)
    grow = ids
    for _ in range(3):                  # incremental confirmed growth
        d3 = b.propose(1, grow)
        grow = np.concatenate([grow, d3[:1]]) if len(d3) else \
            np.concatenate([grow, [5]])
    d4 = b.propose(1, ids)              # history SHRANK: rebuilds
    np.testing.assert_array_equal(d1, d4)
    assert len(d1) == 3
    assert a.trace_counts["draft"] == 1
    assert b.trace_counts["draft"] == 1


def test_draft_proposer_pool_lifecycle_and_degrade():
    model = _tiny_llama()
    p = DraftModelProposer(model, max_slots=2, max_len=64, max_draft=2)
    rng = np.random.RandomState(1)
    ids = [rng.randint(1, 100, (6,)).astype(np.int64) for _ in range(3)]
    assert p.free_slots() == 2
    assert len(p.propose(10, ids[0])) > 0
    assert len(p.propose(11, ids[1])) > 0
    assert p.tracked() == [10, 11]
    assert p.free_slots() == 0
    # pool full: the third request degrades to k=1, no eviction
    assert p.propose(12, ids[2]).size == 0
    assert p.tracked() == [10, 11]
    p.release(10)
    p.release(10)                       # idempotent
    assert p.free_slots() == 1
    assert len(p.propose(12, ids[2])) > 0
    p.retain([12])
    assert p.tracked() == [12]
    p.reset()
    assert p.tracked() == [] and p.free_slots() == 2
    assert p._ks is None                # pools dropped with the state


def test_draft_proposer_short_and_full_histories():
    model = _tiny_llama()
    p = DraftModelProposer(model, max_slots=1, max_len=16, max_draft=3)
    assert p.propose(0, np.zeros((0,), np.int64)).size == 0
    assert p.propose(0, np.array([5], np.int64), max_tokens=0).size == 0
    # history at the pool horizon: nothing left to draft into
    full = np.arange(1, 17, dtype=np.int64)
    assert p.propose(0, full).size == 0


# -- greedy identity band with a draft model ---------------------------

def _run_band(model, draft, seeds, *, max_len=64, shared=False,
              spec_k=4, max_new=8, **extra):
    """One draft-spec + one base engine over ``seeds`` request mixes;
    every greedy output must be token-identical, under the compile-
    once contract: ONE verify program, ONE draft program, at most one
    k=1 decode program (the gate serves draft-less steps)."""
    kw = dict(page_size=8, **extra)
    spec = ServingEngine(model, max_slots=3, max_len=max_len,
                         min_bucket=8, speculative=True, spec_k=spec_k,
                         spec_proposer="draft", draft_model=draft,
                         **kw)
    base = ServingEngine(model, max_slots=3, max_len=max_len,
                         min_bucket=8, **kw)
    for seed in seeds:
        rng = np.random.RandomState(seed)
        prefix = rng.randint(1, 100, (9,)).astype(np.int64) \
            if shared else None
        prompts = _prompts(rng, int(rng.randint(2, 5)),
                           shared_prefix=prefix)
        news = [int(rng.randint(2, max_new + 1)) for _ in prompts]
        rs = [spec.submit(p, n) for p, n in zip(prompts, news)]
        rb = [base.submit(p, n) for p, n in zip(prompts, news)]
        spec.run()
        base.run()
        for a, b in zip(rs, rb):
            assert a.output_ids == b.output_ids, \
                (seed, a.rid, a.output_ids, b.output_ids)
    assert spec.trace_counts["verify"] == 1
    assert spec.trace_counts["draft"] == 1
    assert spec.trace_counts["decode"] <= 1
    return spec


def test_independent_draft_identity_band_25_seeds():
    """Identity under DISAGREEMENT: an independent draft model is
    wrong about the target constantly — the k-wide verify program
    must still emit exactly the target's greedy chain, every seed."""
    model = _tiny_llama()
    draft = _tiny_draft()
    spec = _run_band(model, draft, range(13))
    _run_band(model, draft, range(13, 25), shared=True)
    st = spec.spec_stats()
    assert st["proposer"] == "draft"
    assert st["draft_tokens"] > 0       # it really drafted
    # all draft state released with the band's evictions
    for p in spec._proposers.values():
        assert p.tracked() == []


def test_self_draft_acceptance_floor_band():
    """The oracle regime: the draft model IS the target, so its
    greedy chain always matches and the verify program should accept
    (nearly) every drafted token — the acceptance-rate floor that
    proves the k-wide program actually consumes drafts instead of
    silently running k=1."""
    model = _tiny_llama()
    spec = _run_band(model, model, range(8))
    st = spec.spec_stats()
    assert st["draft_hit_rate"] >= 0.95, st
    assert st["accepted_per_step"] >= 2.0, st
    from paddle_tpu.resilience.invariants import engine_leak_violations
    assert engine_leak_violations(spec) == []


def test_gpt_draft_identity_band():
    """Draft speculation is model-family-agnostic: a GPT target behind
    a GPT self-draft holds the same identity law, with and without
    a shared prefix."""
    model = _tiny_gpt()
    _run_band(model, model, range(4))
    _run_band(model, model, range(4, 8), shared=True)


def test_paged_shared_prefix_draft_band_leak_free():
    model = _tiny_llama()
    spec = _run_band(model, _tiny_draft(), range(6), shared=True)
    assert spec.cache.prefix_hit_tokens > 0
    from paddle_tpu.resilience.invariants import page_leak_violations
    assert page_leak_violations(spec) == []


def test_int8_kv_draft_identity_band():
    """int8 KV composes with draft speculation: scales are
    per-(position, kv-head), so a drafted-but-rejected write only
    touches its OWN positions (overwritten before ever read) and the
    spec engine's quantized pool stays write-identical to the base
    engine's — output token-identical between the two int8 engines."""
    model = _tiny_llama()
    _run_band(model, _tiny_draft(), range(5), kv_dtype="int8")


# -- sampled acceptance ------------------------------------------------

def _sampled_tokens(model, n_req, max_new, seed0=1000, **kw):
    """Pooled token histogram over seeded sampled requests."""
    eng = ServingEngine(model, max_slots=3, max_len=64, min_bucket=8,
                        **kw)
    rng = np.random.RandomState(5)
    prompts = _prompts(rng, n_req, lo=4, hi=9)
    reqs = [eng.submit(p, max_new_tokens=max_new,
                       sampling=SamplingParams(temperature=0.8,
                                               top_k=8,
                                               seed=seed0 + i))
            for i, p in enumerate(prompts)]
    eng.run()
    toks = [t for r in reqs for t in r.output_ids]
    return np.bincount(np.asarray(toks, np.int64), minlength=128), eng


def test_sampled_acceptance_distribution_parity():
    """The Leviathan correctness law, measured: tokens emitted through
    rejection-sampling acceptance (draft q vs target p, residual on
    first rejection) are distributed as sequential sampling from p.
    Exact per-token identity is NOT expected (acceptance consumes the
    RNG stream differently); the aggregate histograms over a pooled
    seeded workload must agree within a total-variation tolerance
    sized for the sample count (two empirical histograms of ~750
    draws each over a top_k=8-per-position support sit near TV~0.12
    when the laws match; a broken acceptance rule lands far past the
    0.25 gate)."""
    model = _tiny_llama()
    base_h, _ = _sampled_tokens(model, 64, 12)
    spec_h, eng = _sampled_tokens(
        model, 64, 12, speculative=True, spec_k=4,
        spec_proposer="draft", draft_model=_tiny_draft(),
        spec_sampled=True)
    a = base_h / max(1, base_h.sum())
    b = spec_h / max(1, spec_h.sum())
    tv = 0.5 * float(np.abs(a - b).sum())
    assert tv < 0.25, tv
    st = eng.spec_stats()
    assert st["accepted_draft_tokens"] > 0      # drafts really land
    # an independent draft disagrees: the residual path really runs
    assert st["resamples"] > 0, st


def test_sampled_rows_bitwise_identical_without_spec_sampled():
    """With spec_sampled OFF (the default), sampled rows never consume
    a draft — they ride position-0 logits on the same per-request RNG
    stream, so output is BITWISE identical to the k=1 engine even
    with a draft proposer configured for the greedy rows."""
    model = _tiny_llama()
    base_h, _ = _sampled_tokens(model, 6, 8)
    spec_h, eng = _sampled_tokens(
        model, 6, 8, speculative=True, spec_k=4,
        spec_proposer="draft", draft_model=_tiny_draft())
    np.testing.assert_array_equal(base_h, spec_h)
    assert eng._spec["draft_tokens"] == 0


# -- SpecTuner units ---------------------------------------------------

def test_tuner_validation():
    with pytest.raises(ValueError, match="k_max"):
        SpecTuner(k_max=1)
    with pytest.raises(ValueError, match="proposer"):
        SpecTuner(k_max=4, proposers=())
    with pytest.raises(ValueError, match="alpha"):
        SpecTuner(k_max=4, alpha=0.0)
    with pytest.raises(ValueError, match="dead band"):
        SpecTuner(k_max=4, enable_at=1.2, disable_at=1.4)


def test_tuner_disables_after_dwell_and_probes_while_off():
    t = SpecTuner(k_max=4, dwell=4, probe_every=8)
    assert t.decide("greedy") == (4, "ngram")   # optimistic start
    # acceptance collapses to 1 (every draft rejected)
    for _ in range(3):
        t.observe("greedy", "ngram", 1)
        t.on_step()
        # dwell gate: no flip before `dwell` steps have passed
        assert t.decide("greedy")[1] == "ngram"
    t.observe("greedy", "ngram", 1)
    t.on_step()                                 # step 4: dwell expired
    assert t.flips == 1
    k, kind = t.decide("greedy")
    assert (k, kind) == (1, None)
    snap = t.snapshot()["classes"]["greedy"]
    assert snap["on"] is False and snap["k"] == 1 and snap["kind"] is None
    # while off: k=2 probe exactly on the probe cadence, k=1 otherwise
    probed = []
    for step in range(t._step, t._step + 16):
        k, kind = t.decide("greedy")
        if step % 8 == 0:
            assert (k, kind) == (2, "ngram")
            probed.append(step)
        else:
            assert (k, kind) == (1, None)
        t.on_step()
    assert len(probed) == 2


def test_tuner_reenables_on_good_probe_and_scales_k():
    t = SpecTuner(k_max=6, dwell=2, probe_every=4)
    for _ in range(4):                          # drive it off
        t.observe("greedy", "ngram", 1)
        t.on_step()
    assert not t.snapshot()["classes"]["greedy"]["on"]
    # probe steps observe long accepted runs: EWMA climbs back over
    # enable_at and the tuner re-enables at k = ceil(ewma) + 1
    while not t.snapshot()["classes"]["greedy"]["on"]:
        if t.decide("greedy")[0] == 2:
            t.observe("greedy", "ngram", 4)
        t.on_step()
        assert t._step < 200, "tuner never re-enabled"
    st = t.snapshot()["classes"]["greedy"]
    assert st["kind"] == "ngram"
    assert 2 <= st["k"] <= 6
    assert t.flips == 2                          # off once, on once


def test_tuner_switches_proposer_only_past_margin():
    t = SpecTuner(k_max=4, proposers=("ngram", "draft"), dwell=1,
                  switch_margin=0.5)
    # rival within the margin: incumbent keeps the seat (no flap on
    # measurement noise)
    t.observe("greedy", "ngram", 2)
    t.observe("greedy", "draft", 2)
    t.on_step()
    assert t.snapshot()["classes"]["greedy"]["kind"] == "ngram"
    assert t.flips == 0
    # rival clears the margin: the tuner switches kinds
    for _ in range(3):
        t.observe("greedy", "draft", 4)
        t.on_step()
    assert t.snapshot()["classes"]["greedy"]["kind"] == "draft"
    assert t.flips >= 1


def test_tuner_classes_are_independent():
    t = SpecTuner(k_max=4, dwell=1)
    for _ in range(4):
        t.observe("greedy", "ngram", 4)         # greedy pays
        t.observe("sampled", "ngram", 1)        # sampled does not
        t.on_step()
    s = t.snapshot()["classes"]
    assert s["greedy"]["on"] is True
    assert s["sampled"]["on"] is False


# -- tuner-driven gating through the ENGINE ----------------------------

def test_tuned_no_draft_regime_runs_k1_program():
    """Satellite (b): when the tuner turns speculation off, the
    no-draft steps must provably run the cheap k=1 decode program —
    not the k-wide verify program at wlen=1. Random prompts give the
    n-gram proposer nothing to draft, acceptance sits at 1.0, the
    EWMA crosses the dead band, and from then on every step is gated.
    Output stays identical to the base engine throughout."""
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        speculative=True, spec_k=4, spec_tune=True)
    base = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8)
    rng = np.random.RandomState(17)
    prompts = _prompts(rng, 4, lo=5, hi=10)
    rs = [eng.submit(p, max_new_tokens=24) for p in prompts]
    rb = [base.submit(p, max_new_tokens=24) for p in prompts]
    eng.run()
    base.run()
    for a, b in zip(rs, rb):
        assert a.output_ids == b.output_ids
    st = eng.spec_stats()
    assert st["tuner"]["classes"]["greedy"]["on"] is False
    assert st["tuner"]["classes"]["greedy"]["k"] == 1
    assert st["tuner"]["flips"] >= 1
    assert st["gated_steps"] > 0
    # the k=1 program really compiled and served the gated steps; the
    # verify program compiled at most once (the optimistic prefix —
    # ngram on random prompts may never draft at all)
    assert eng.trace_counts["decode"] == 1
    assert eng.trace_counts["verify"] <= 1


def test_tuned_draftable_regime_keeps_speculating():
    """The other half of the gating law: traffic the draft model
    predicts well (self-draft oracle) keeps the tuner ON, accepted
    length stays at the window, and k never collapses to 1."""
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        speculative=True, spec_k=4, spec_tune=True,
                        spec_proposer="draft", draft_model=model)
    rng = np.random.RandomState(19)
    for p in _prompts(rng, 3, lo=5, hi=10):
        eng.submit(p, max_new_tokens=16)
    eng.run()
    st = eng.spec_stats()
    assert st["tuner"]["classes"]["greedy"]["on"] is True
    assert st["tuner"]["classes"]["greedy"]["kind"] == "draft"
    assert st["tuner"]["classes"]["greedy"]["k"] >= 2
    assert st["accepted_per_step"] >= 2.0, st


# -- lifecycle under failure -------------------------------------------

def test_draft_fault_contained_to_one_row():
    """serving.spec.draft (or a real draft-model error) costs ONE
    row's draft window: the step completes, output is identical to an
    unfaulted run, and speculation resumes the very next step."""
    from paddle_tpu.resilience import faults
    model = _tiny_llama()
    kw = dict(max_slots=1, max_len=64, min_bucket=8, speculative=True,
              spec_k=4, spec_proposer="draft", draft_model=model)
    ref_eng = ServingEngine(model, **kw)
    ref = ref_eng.submit(np.arange(1, 8), max_new_tokens=10)
    ref_eng.run()

    eng = ServingEngine(model, **kw)
    r = eng.submit(np.arange(1, 8), max_new_tokens=10)
    eng.step()                                   # prefill + first tok
    faults.inject("serving.spec.draft", times=1)
    done = eng.step()                            # fault INSIDE this step
    assert faults.fired("serving.spec.draft") == 1
    assert done == [] or r in done
    assert eng._spec["draft_faults"] == 1
    faults.clear()
    acc0 = eng._spec["accepted_draft_tokens"]
    eng.run()
    assert r.output_ids == ref.output_ids
    assert eng._spec["accepted_draft_tokens"] > acc0  # drafting resumed
    for p in eng._proposers.values():
        assert p.tracked() == []


def test_recover_replays_with_live_draft_state():
    """A verify-step fault with donated pools breaks the engine mid-
    flight while the draft pool holds live per-request state;
    recover() re-prefills, the proposers prune to the surviving set,
    and the finished outputs stay token-identical to the base."""
    from paddle_tpu.resilience import faults
    model = _tiny_llama()
    rng = np.random.RandomState(23)
    prompts = _prompts(rng, 3, lo=4, hi=10)
    base = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8)
    rb = [base.submit(p, max_new_tokens=12) for p in prompts]
    base.run()

    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        speculative=True, spec_k=4,
                        spec_proposer="draft", draft_model=model)
    eng._donate = lambda: (5, 6)          # simulate the TPU path
    rs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.step()                            # draft state now live (the
    # oracle draft accepts whole windows, so don't step further —
    # requests would finish and release the state under test)
    assert any(p.tracked() for p in eng._proposers.values())
    faults.inject("serving.decode.verify", times=1)
    with pytest.raises(faults.InjectedFault):
        eng.run()
    report = eng.recover()
    assert report["replay_mismatches"] == 0
    live = {r.rid for r in eng.cache.slots if r is not None}
    for p in eng._proposers.values():
        assert set(p.tracked()) <= live
    eng.run()
    for a, b in zip(rs, rb):
        assert a.output_ids == b.output_ids
    for p in eng._proposers.values():
        assert p.tracked() == []


def test_adopted_request_replays_under_draft_speculation():
    """Router failover into a draft-spec engine: adopt() re-prefills
    prompt + already-delivered tokens, the draft pool admits the rid
    fresh, and the continuation is token-identical to an uninterrupted
    greedy run."""
    model = _tiny_llama()
    prompt = np.arange(3, 12, dtype=np.int64)
    ref_eng = ServingEngine(model, max_slots=1, max_len=64,
                            min_bucket=8)
    ref = ref_eng.submit(prompt, max_new_tokens=10)
    ref_eng.run()

    first = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8)
    r = first.submit(prompt, max_new_tokens=10)
    first.step()
    first.step()                          # a few tokens delivered
    assert 0 < len(r.output_ids) < 10

    second = ServingEngine(model, max_slots=1, max_len=64, min_bucket=8,
                           speculative=True, spec_k=4,
                           spec_proposer="draft", draft_model=model)
    second.adopt(r)
    second.run()
    assert r.output_ids == ref.output_ids
    for p in second._proposers.values():
        assert p.tracked() == []
