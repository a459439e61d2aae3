"""Tensor-parallel continuous batching (ISSUE 9): the serving engine
under a `model`-axis mesh on the emulated 8-device CPU mesh.

Acceptance band: sharded decode (TP=2) is greedy TOKEN-IDENTICAL to
the single-chip engine and to ``generate()`` across a >= 25-seed
property band — llama (GQA) and GPT, COW-shared prefixes
included — with decode/verify trace counts == 1
per mesh shape (the compile-once contract survives sharding).

Disaggregated prefill/decode: full prefills run on the prefill chip
group and hand their KV spans to the decode group through the explicit
``device_put`` + install handoff; identity holds, installs stay inside
the prefill-bucket compile budget, and every handoff failure path —
injected ``serving.kv.handoff`` faults, client-disconnect flags and
deadline expiry observed MID-handoff, a silently dropped install —
unwinds pages on both groups or is detected by the identity law.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from conftest import (model_greedy, require_devices,
                      serving_model_mesh)
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import ServingEngine

pytestmark = pytest.mark.chaos  # fast, CPU-only, fault-injection heavy


@pytest.fixture(autouse=True)
def _clean_faults():
    from paddle_tpu.resilience import faults
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
    """One seeded traffic wave: ragged prompts, some sharing a prefix
    (paged COW coverage when ``shared`` is given)."""
    out = []
    for i in range(n):
        L = int(rng.randint(3, 14))
        p = rng.randint(1, 100, (L,)).astype(np.int64)
        if shared is not None and i % 2 == 0:
            p = np.concatenate([shared, p]).astype(np.int64)
        out.append(p)
    return out


def _drive(eng, prompts, max_new=8):
    reqs = [eng.submit(p, max_new) for p in prompts]
    while eng.has_work():
        eng.step()
    return [list(r.out_tokens) for r in reqs]


def _engine(family, mesh=None, prefill=0, **kw):
    eng_kw = dict(max_slots=4, max_len=64, min_bucket=8, page_size=8)
    if mesh is not None:
        eng_kw["mesh"] = mesh
        if prefill:
            eng_kw["prefill_devices"] = prefill
    eng_kw.update(kw)
    return ServingEngine(_model(family), **eng_kw)


# ---------------------------------------------------------------------------
# the >= 25-seed identity band (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["llama", "gpt"],
                         ids=["llama-paged", "gpt-paged"])
def test_tp2_token_identity_band_25_seeds(family):
    """TP=2 greedy outputs == single-chip engine outputs, bitwise, for
    25 seeded traffic waves per family — the waves share a prompt
    prefix so COW/prefix-index paths run sharded too. ONE
    engine pair serves all 25 waves, so the band also proves the
    compile-once contract: exactly one decode program per mesh shape
    across the whole band."""
    mesh = serving_model_mesh(tp=2)
    shared = np.arange(1, 11, dtype=np.int64)  # > 1 page of 8
    ref_eng = _engine(family)
    tp_eng = _engine(family, mesh=mesh)
    for seed in range(25):
        rng = np.random.RandomState(1000 + seed)
        prompts = _wave(rng, shared=shared)
        ref = _drive(ref_eng, prompts)
        got = _drive(tp_eng, prompts)
        assert got == ref, (family, seed)
    assert tp_eng.trace_counts["decode"] == 1
    assert tp_eng.trace_counts["verify"] == 0
    assert ref_eng.trace_counts["decode"] == 1


@pytest.mark.parametrize("family", ["llama", "gpt"])
def test_tp2_matches_generate(family):
    """The sharded engine's greedy output equals the model's own
    greedy decode (transitively pinned through the single-chip engine
    in the band above; direct here for one wave)."""
    mesh = serving_model_mesh(tp=2)
    rng = np.random.RandomState(0)
    prompts = _wave(rng)
    got = _drive(_engine(family, mesh=mesh), prompts, max_new=8)
    assert got == [model_greedy(_model(family), p, 8) for p in prompts]


def test_tp2_speculative_identity_and_one_verify_program():
    """Speculative TP=2: the widened verify program jits under the
    mesh too — token identity vs the single-chip k=1 engine holds and
    verify trace count == 1 per mesh shape."""
    mesh = serving_model_mesh(tp=2)
    rng = np.random.RandomState(3)
    pat = rng.randint(1, 100, (3,))
    prompts = [np.tile(pat, 5)[:int(n)].astype(np.int64)
               for n in (9, 12, 14)]
    ref = _drive(_engine("llama"), prompts, max_new=10)
    spec = _engine("llama", mesh=mesh, speculative=True,
                   spec_k=4)
    got = _drive(spec, prompts, max_new=10)
    assert got == ref
    assert spec.trace_counts["verify"] == 1
    assert spec.trace_counts["decode"] <= 1   # the gated k=1 fallback
    st = spec.spec_stats()
    assert st["accepted_draft_tokens"] >= 1   # really speculated


def test_tp2_int8_kv_matches_single_chip_int8():
    """int8 pools + per-page scales shard over the mesh: the sharded
    int8 engine is token-identical to the SINGLE-CHIP int8 engine
    (quantization math is replicated work, so the int8 flavor keeps
    bitwise identity with its own single-chip counterpart even where
    it diverges from the fp reference)."""
    mesh = serving_model_mesh(tp=2)
    rng = np.random.RandomState(5)
    prompts = _wave(rng, shared=np.arange(1, 11, dtype=np.int64))
    ref = _drive(_engine("llama", kv_dtype="int8"), prompts)
    got = _drive(_engine("llama", kv_dtype="int8",
                         mesh=mesh), prompts)
    assert got == ref


def test_tp2_recover_replays_token_identically():
    """A decode fault with donated pools on the MESH engine: recover()
    rebuilds the SHARDED pools and replays token-identically."""
    from paddle_tpu.resilience import faults
    mesh = serving_model_mesh(tp=2)
    rng = np.random.RandomState(11)
    prompts = _wave(rng)
    ref = _drive(_engine("llama"), prompts)
    eng = _engine("llama", mesh=mesh)
    eng._donate = lambda: (5, 6)          # TPU-like donated pools
    reqs = [eng.submit(p, 8) for p in prompts]
    faults.inject("serving.decode.sharded", times=1, after=2)
    recovered = False
    while eng.has_work():
        try:
            eng.step()
        except faults.InjectedFault:
            eng.recover()
            recovered = True
    assert recovered
    assert [list(r.out_tokens) for r in reqs] == ref


# ---------------------------------------------------------------------------
# disaggregated prefill/decode + the KV handoff failure surface
# ---------------------------------------------------------------------------

def _quiesced_pool_clean(eng):
    from paddle_tpu.resilience.invariants import (
        engine_leak_violations, page_leak_violations)
    return engine_leak_violations(eng) + page_leak_violations(eng)


@pytest.mark.parametrize("family,split,against", [
    ("llama", 2, "engine"), ("llama", 1, "engine"),
    ("gpt", 2, "engine"),
    ("llama", 2, "generate"), ("gpt", 2, "generate"),
])
def test_disaggregated_token_identity(family, split, against):
    """Disaggregated prefill/decode (prefill group = ``split``
    devices, decode group TP=2 or 1): outputs identical to the
    single-chip engine over five waves, or (``against="generate"``)
    to the model's own greedy decode over one; installs bounded by
    the prefill bucket set, no staged handoff survives quiesce."""
    mesh = serving_model_mesh(tp=2 if split == 2 else 1,
                              prefill=split)
    shared = np.arange(1, 11, dtype=np.int64)
    dis = _engine(family, mesh=mesh, prefill=split)
    if against == "generate":
        prompts = _wave(np.random.RandomState(2000), shared=shared)
        assert _drive(dis, prompts) == [
            model_greedy(_model(family), p, 8) for p in prompts]
    else:
        ref_eng = _engine(family)
        for seed in range(5):
            rng = np.random.RandomState(2000 + seed)
            prompts = _wave(rng, shared=shared)
            assert _drive(dis, prompts) == _drive(ref_eng, prompts), \
                seed
    assert dis.trace_counts["decode"] == 1
    # one install compile per distinct prefill block shape — the same
    # O(log max_len) budget as the prefill buckets themselves
    assert 1 <= len(dis.trace_counts["install"]) <= 4
    assert all(n == 1 for n in dis.trace_counts["install"].values())
    assert _quiesced_pool_clean(dis) == []


def test_handoff_fault_requeues_and_stays_identical():
    """An injected serving.kv.handoff fault (span computed on the
    prefill group, install never ran): the abort path unwinds the
    decode-side page claims, the request requeues at the FCFS head,
    and the retried handoff produces the identical output."""
    from paddle_tpu.resilience import faults
    mesh = serving_model_mesh(tp=2, prefill=2)
    rng = np.random.RandomState(21)
    prompts = _wave(rng)
    ref = _drive(_engine("llama"), prompts)
    eng = _engine("llama", mesh=mesh, prefill=2)
    reqs = [eng.submit(p, 8) for p in prompts]
    faults.inject("serving.kv.handoff", times=2)
    while eng.has_work():
        try:
            eng.step()
        except faults.InjectedFault as e:
            assert e.point == "serving.kv.handoff"
    assert faults.fired("serving.kv.handoff") == 2
    assert [list(r.out_tokens) for r in reqs] == ref
    assert _quiesced_pool_clean(eng) == []


@pytest.mark.parametrize("arm", ["flag", "deadline"])
def test_cancel_mid_handoff_frees_pages_on_both_groups(arm):
    """Regression (ISSUE-9 satellite): a request whose client
    disconnects (flag probe) or whose deadline expires MID-handoff —
    KV computed prefill-side, nothing installed decode-side — must
    free its decode-group page claims and leave no staged span on the
    prefill group. The disconnect flag is checked AT the handoff
    point, so the abort path is what runs; deadline expiry is swept at
    the next step boundary after the fault-triggered requeue."""
    from paddle_tpu.resilience import faults
    mesh = serving_model_mesh(tp=2, prefill=2)
    clock = {"t": 0.0}
    gone = set()
    eng = ServingEngine(_model("llama"), max_slots=2, max_len=64,
                        min_bucket=8, page_size=8, mesh=mesh,
                        prefill_devices=2,
                        time_fn=lambda: clock["t"],
                        cancel_probe=lambda r: r.rid in gone)
    rng = np.random.RandomState(33)
    victim = eng.submit(rng.randint(1, 100, (9,)).astype(np.int64), 8,
                        deadline_s=(5.0 if arm == "deadline"
                                    else None))
    other = eng.submit(rng.randint(1, 100, (5,)).astype(np.int64), 4)
    if arm == "flag":
        # the probe turns true while the victim's span is staged: the
        # mid-handoff cancel check routes through the abort path
        gone.add(victim.rid)
    else:
        # a handoff fault requeues the victim; its deadline then
        # expires before the retry — swept at the step boundary
        faults.inject("serving.kv.handoff", times=1)
        clock["t"] = 10.0
    while eng.has_work():
        try:
            eng.step()
        except faults.InjectedFault:
            pass
        clock["t"] += 1.0
    assert victim.finished
    assert victim.finish_reason == ("disconnect" if arm == "flag"
                                    else "deadline")
    assert other.finish_reason == "length"
    assert eng._staged_handoffs == {}
    assert _quiesced_pool_clean(eng) == []


def test_stranded_staged_handoff_is_reported_by_leak_audit():
    """The cross-group leak law's engine half is REACHABLE: staging is
    popped by the install/abort paths (not a blanket finally), so a
    regression that strands a handoff mid-flight shows up in
    engine_leak_violations rather than passing vacuously."""
    from paddle_tpu.resilience.invariants import engine_leak_violations
    mesh = serving_model_mesh(tp=2, prefill=2)
    eng = _engine("llama", mesh=mesh, prefill=2)
    assert engine_leak_violations(eng) == []
    eng._staged_handoffs[7] = 0           # simulate a forgotten unwind
    v = engine_leak_violations(eng)
    assert any("staged KV handoff" in s for s in v), v
    eng._staged_handoffs.clear()


def test_dropped_handoff_is_detected_by_token_identity():
    """A handoff that silently DROPS the span (install patched out —
    pages claimed, logits returned, KV never arrives on the decode
    pool) must surface as token divergence: decode then attends trash
    pages instead of the prompt. This is the engine-level half of the
    pinned chaos red seed (test_chaos.py: dropped handoff goes
    LOST)."""
    mesh = serving_model_mesh(tp=2, prefill=2)
    rng = np.random.RandomState(44)
    prompts = _wave(rng)
    ref = _drive(_engine("llama"), prompts)
    eng = _engine("llama", mesh=mesh, prefill=2)
    real_install = eng._install_fn

    def skip_install(key):
        fn = real_install(key)
        return lambda page_ids, kb, vb, ksb, vsb, ks, vs, kss, vss: \
            (ks, vs, kss, vss)

    eng._install_fn = skip_install
    got = _drive(eng, prompts)
    assert got != ref          # the drop is DETECTED, not silent


# ---------------------------------------------------------------------------
# the verify gate (ISSUE-9 satellite: no-draft steps skip the k-wide
# program)
# ---------------------------------------------------------------------------

def test_spec_gate_skips_widened_program_and_keeps_outputs():
    """On steps where no row has a draft, the gated engine runs the
    k=1 decode program instead of the k-wide verify program — outputs
    are identical either way, the gate really engages on random
    (draft-less) traffic, and trace counts stay bounded at <= 1
    decode + <= 1 verify program."""
    rng = np.random.RandomState(9)
    # random prompts: the n-gram proposer finds few/no drafts early,
    # so gated steps occur; periodic prompts keep real verify steps in
    # the mix too
    prompts = [rng.randint(1, 100, (6,)).astype(np.int64),
               np.tile(rng.randint(1, 100, (2,)), 6).astype(np.int64)]
    gated = ServingEngine(_model("llama"), max_slots=2, max_len=64,
                          min_bucket=8, page_size=8,
                          speculative=True, spec_k=4)
    plain = ServingEngine(_model("llama"), max_slots=2, max_len=64,
                          min_bucket=8, page_size=8,
                          speculative=True, spec_k=4,
                          spec_gate=False)
    out_g = _drive(gated, prompts, max_new=10)
    out_p = _drive(plain, prompts, max_new=10)
    assert out_g == out_p
    assert gated._spec["gated_steps"] >= 1      # the gate engaged
    assert plain._spec["gated_steps"] == 0
    assert gated.trace_counts["verify"] == 1
    assert gated.trace_counts["decode"] <= 1
    assert plain.trace_counts["decode"] == 0    # ungated never needs it
    # the per-row accounting is flavor-independent
    assert gated._spec["rows"] == plain._spec["rows"]
    assert gated._spec["emitted"] == plain._spec["emitted"]


def test_spec_gate_param_validation():
    with pytest.raises(ValueError, match="spec_gate"):
        ServingEngine(_model("llama"), max_slots=2, max_len=64,
                      spec_gate=False)


# ---------------------------------------------------------------------------
# mesh validation + bookkeeping
# ---------------------------------------------------------------------------

def test_mesh_validation_errors():
    require_devices(2)
    from paddle_tpu.distributed import ProcessMesh
    model = _model("llama")                    # kv_heads == 2
    with pytest.raises(ValueError, match="axis"):
        ServingEngine(model, max_slots=2,
                      mesh=ProcessMesh(np.arange(2), ["data"]))
    with pytest.raises(ValueError, match="kv_heads"):
        require_devices(3)
        ServingEngine(model, max_slots=2,
                      mesh=ProcessMesh(np.arange(3), ["model"]))
    with pytest.raises(ValueError, match="prefill_devices"):
        ServingEngine(model, max_slots=2, prefill_devices=1)
    with pytest.raises(ValueError, match="decode group"):
        ServingEngine(model, max_slots=2,
                      mesh=ProcessMesh(np.arange(2), ["model"]),
                      prefill_devices=2)


def test_mesh_engine_picks_up_live_weight_swap():
    """The per-group placement cache is keyed by param NAME with the
    source array's identity checked against the live entry — a weight
    swapped on the live model (checkpoint load, quantization) must be
    re-placed on the next step, not served stale from the cache
    (regression: an id()-keyed cache could alias a freed array's
    reused address and silently decode with the old weights)."""
    mesh = serving_model_mesh(tp=2)
    model = _tiny_llama()             # private instance: we mutate it
    prompts = _wave(np.random.RandomState(8))
    kw = dict(max_slots=4, max_len=64, min_bucket=8, page_size=8)
    ref_eng = ServingEngine(model, **kw)
    tp_eng = ServingEngine(model, mesh=mesh, **kw)
    a0, b0 = _drive(ref_eng, prompts), _drive(tp_eng, prompts)
    assert a0 == b0
    name, p = next((n, t) for n, t in model.named_parameters()
                   if n.endswith("q_proj.weight"))
    p._data = -p._data                # live swap -> new device array
    a1, b1 = _drive(ref_eng, prompts), _drive(tp_eng, prompts)
    assert a1 == b1, "mesh engine served stale weights after swap"
    assert a1 != a0                   # the swap really changed decode


def test_pools_and_params_actually_sharded():
    """The mesh engine's KV pools and the family's shardable params
    really live split over the model axis (not silently replicated) —
    pinned so a sharding-spec regression cannot hide behind the
    identity tests."""
    mesh = serving_model_mesh(tp=2)
    eng = _engine("llama", mesh=mesh)
    prompts = _wave(np.random.RandomState(1))
    _drive(eng, prompts)
    import jax
    pool = eng.cache.ks[0]
    assert len(pool.sharding.device_set) == 2
    # per-device shard holds HALF the kv_heads
    shard = pool.addressable_shards[0].data
    assert shard.shape[2] * 2 == pool.shape[2]
    kproj = next(v for k, v in eng._params.items()
                 if k.endswith("k_proj.weight"))
    assert len(kproj.sharding.device_set) == 2
    assert kproj.addressable_shards[0].data.shape[-1] * 2 \
        == kproj.shape[-1]
    # norms replicate (the rule set is output-dim-only by design)
    norm = next(v for k, v in eng._params.items() if "norm" in k)
    assert norm.sharding.is_fully_replicated
