"""Chaos-soak harness (resilience/chaos.py + invariants.py): the
deterministic seed matrix asserted in tier-1, the conservation-ledger
and invariant-checker units, and the pinned seeds that demonstrably
catch the PR-3 deferred failure-path bug classes — each pinned test
re-introduces the pre-fix code path via monkeypatch and asserts the
harness goes red on that exact seed, then green on the fixed code.
Everything runs on CPU with virtual clocks and seeded RNG: a red
episode is reproducible from its seed alone."""
import threading
import types

import numpy as np
import pytest

from paddle_tpu.resilience import chaos, faults, invariants
from paddle_tpu.resilience.invariants import (ConservationLedger,
                                              InvariantViolation)

pytestmark = [pytest.mark.chaos,
              pytest.mark.usefixtures("worker_compile_cache")]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_counts()
    yield
    faults.clear()


# -- the sweep covers the whole fault-point catalogue ------------------

def test_sweep_covers_registered_fault_points():
    """Adding a fault point to faults.KNOWN_POINTS without enrolling
    it in an episode kind silently shrinks the soak — fail loudly."""
    sweeps = {"serving": set(chaos.SERVING_SWEEP),
              "training": set(chaos.TRAINING_SWEEP),
              "frontdoor": set(chaos.FRONTDOOR_SWEEP),
              "cluster": set(chaos.CLUSTER_SWEEP),
              "control": set(chaos.CONTROL_SWEEP)}
    swept = set().union(*sweeps.values())
    assert swept == set(faults.KNOWN_POINTS)
    # coverage ownership is a partition (front-door episodes also
    # SAMPLE the serving points — the full stack includes the
    # engines — but each point is owned by exactly one sweep)
    names = sorted(sweeps)
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            assert not sweeps[a] & sweeps[b], (a, b)


# -- conservation ledger units (no engine, injected state) -------------

def _req(rid, finished=True, reason="length", toks=(), max_new=4):
    return types.SimpleNamespace(
        rid=rid, finished=finished, finish_reason=reason,
        out_tokens=list(toks), max_new_tokens=max_new)


def test_ledger_exactly_once_accounting():
    led = ConservationLedger()
    a, b, c = _req(0), _req(1), _req(2)
    for r in (a, b, c):
        led.on_submitted(r)
    led.on_delivered(a, via="step")
    led.on_delivered(b, via="recover")
    led.on_delivered(c, via="drain")
    assert led.violations() == []
    led.check()                                  # no raise


def test_ledger_catches_lost_duplicate_phantom_nonterminal():
    led = ConservationLedger()
    lost = _req(0)                               # never delivered
    dup = _req(1)
    nonterm = _req(2, finished=False, reason=None)
    noreason = _req(3, finished=True, reason=None)
    for r in (lost, dup, nonterm, noreason):
        led.on_submitted(r)
    led.on_delivered(dup, via="step")
    led.on_delivered(dup, via="recover")         # double delivery
    led.on_delivered(nonterm, via="step")        # not terminal
    led.on_delivered(noreason, via="step")       # no finish_reason
    phantom = _req(9)
    led.on_delivered(phantom, via="step")        # never submitted
    v = "\n".join(led.violations())
    assert "request 0 LOST" in v
    assert "request 1 DELIVERED 2 times" in v
    assert "not in a terminal state" in v
    assert "without a finish_reason" in v
    assert "phantom" in v
    with pytest.raises(InvariantViolation, match="LOST"):
        led.check()


def test_ledger_frontdoor_attempt_law():
    """Mounted at the front door, the ledger also audits admission:
    every attempt gets exactly one outcome (accept | typed reject) —
    an attempt that produced neither is a vanished request."""
    led = ConservationLedger()
    a, b = _req(0), _req(1)
    led.on_attempt()
    led.on_submitted(a)
    led.on_attempt()
    led.on_rejected(tenant="t", reason="rate_limited")
    led.on_delivered(a, via="stream")
    led.on_delivered(b, via="stream")   # phantom — never submitted
    v = "\n".join(led.violations())
    assert "phantom" in v
    led2 = ConservationLedger()
    led2.on_attempt()
    led2.on_attempt()                   # outcome never recorded
    led2.on_submitted(a)
    led2.on_delivered(a, via="stream")
    assert any("vanished at the boundary" in s
               for s in led2.violations())


def test_token_prefix_invariant():
    ref = [5, 6, 7, 8]
    ok_full = _req(0, reason="length", toks=[5, 6, 7, 8], max_new=4)
    ok_part = _req(1, reason="deadline", toks=[5, 6], max_new=4)
    bad_tok = _req(2, reason="length", toks=[5, 9], max_new=2)
    too_long = _req(3, reason="length", toks=[5, 6, 7, 8, 1],
                    max_new=5)
    short_len = _req(4, reason="length", toks=[5], max_new=3)
    v = invariants.token_prefix_violations(
        [(ok_full, ref), (ok_part, ref), (bad_tok, ref),
         (too_long, ref), (short_len, ref)])
    joined = "\n".join(v)
    assert "request 0" not in joined and "request 1" not in joined
    assert "request 2 tokens diverged" in joined
    assert "request 3" in joined            # longer than the replay
    assert "request 4 finished 'length' with 1/3" in joined


def test_loss_trajectory_invariant():
    base = [(0, 1.0), (1, 0.5), (2, 0.25)]
    ok = {"losses": [(0, 1.0), (1, 0.5), (2, 0.25)]}
    resumed = {"losses": [(2, 0.25)]}       # relaunch tail: still ok
    assert invariants.loss_trajectory_violations([ok, resumed],
                                                 base) == []
    diverged = {"losses": [(0, 1.0), (1, 0.75)]}
    dup_step = {"losses": [(0, 1.0), (0, 1.0)]}
    v = "\n".join(invariants.loss_trajectory_violations(
        [diverged, dup_step], base))
    assert "diverged from the uninjected baseline" in v
    assert "not strictly increasing" in v


def test_thread_leak_invariant():
    before = list(threading.enumerate())
    assert invariants.thread_leak_violations(before) == []
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="chaos-leak",
                         daemon=False)
    t.start()
    try:
        v = invariants.thread_leak_violations(before)
        assert v and "chaos-leak" in v[0]
    finally:
        stop.set()
        t.join()


# -- the deterministic seed matrix (acceptance criterion) --------------
# >= 25 seeded episodes spanning serving and training, every invariant
# asserted per episode. A red seed reproduces standalone:
#   python -c "from paddle_tpu.resilience import chaos; \
#              print(chaos.run_serving_episode(SEED).violations)"

SERVING_SEEDS = list(range(0, 13))
TRAINING_SEEDS = list(range(100, 112))
# the replica-kill + front-door arm (ISSUE 7): FrontDoor over a 2-3
# replica router, whole-replica kills (flag + mid-step, i.e. mid-
# prefill/mid-stream), audited END-TO-END at the front door. Across
# this band: >= 12 episodes with at least one replica death and
# >= 10 with requests failed over to a peer (pinned below so the
# band cannot silently go quiet).
FRONTDOOR_SEEDS = list(range(300, 325))
# the tensor-parallel + disaggregated arm (ISSUE 9): mesh engines over
# the emulated 8-device CPU mesh — TP=2 on odd seeds, disaggregated
# 2-prefill + 2-decode on even seeds — with the sharded-decode and
# mid-KV-handoff kill arms sampled on top of the usual serving faults.
# Every episode is audited against the SAME single-chip reference
# outputs (cross-flavor token identity) plus the page/slot/staged-
# handoff no-leak laws across both chip groups.
TP_SERVING_SEEDS = list(range(400, 425))
# the cross-process arm (ISSUE 11): the same ReplicaRouter, but each
# replica is a RemoteReplica proxy over a REAL worker subprocess —
# killed three ways per the sampled schedule: cooperative flag,
# mid-step SIGKILL (immediate, or armed at a serving fault point
# INSIDE the worker so it dies mid-prefill/mid-decode), and network
# partition (cluster.rpc.* wire faults outlasting the retry budget).
# Audited end to end at the front door, plus per-worker page/slot
# audits fetched over RPC from the survivors. Needs the native
# TCPStore extension for rendezvous; skipped (not silently green)
# where it can't build.
CLUSTER_SEEDS = list(range(500, 525))


def _have_cluster():
    try:
        from paddle_tpu.distributed.store import get_lib
        return get_lib() is not None
    except Exception:
        return False


_serving_spec_tally = {"episodes": 0, "speculative": 0,
                       "accepted_drafts": 0, "verify_kills": 0,
                       "chunked": 0, "chunk_kills": 0,
                       "tiered": 0, "demotions": 0, "promotions": 0,
                       "tier_kills": 0, "draft_proposed": 0,
                       "spec_sampled": 0, "spec_tuned": 0,
                       "draft_kills": 0, "draft_faults": 0}

# chunk-budget controller coverage, fed by BOTH serving matrices
# (single-chip and TP — the controller rides on any chunked engine)
_chunk_ctl_tally = {"bands": set(), "controlled": 0, "adaptations": 0}


@pytest.mark.parametrize("seed", SERVING_SEEDS)
def test_serving_episode_matrix(seed):
    res = chaos.run_serving_episode(seed)
    assert res.ok, "\n".join(res.violations)
    assert res.stats["requests"] >= 1
    _serving_spec_tally["episodes"] += 1
    _serving_spec_tally["speculative"] += \
        1 if res.stats["speculative"] else 0
    _serving_spec_tally["accepted_drafts"] += \
        res.stats["spec_accepted_drafts"]
    _serving_spec_tally["verify_kills"] += \
        res.fired.get("serving.decode.verify", 0)
    _serving_spec_tally["chunked"] += \
        1 if res.stats["prefill_chunk"] else 0
    _serving_spec_tally["chunk_kills"] += \
        res.fired.get("serving.prefill.chunk", 0)
    _serving_spec_tally["tiered"] += 1 if res.stats["kv_tiered"] else 0
    _serving_spec_tally["demotions"] += res.stats["demotions"]
    _serving_spec_tally["promotions"] += res.stats["promotions"]
    _serving_spec_tally["tier_kills"] += \
        res.fired.get("serving.kv.demote", 0) \
        + res.fired.get("serving.kv.promote", 0)
    _serving_spec_tally["draft_proposed"] += \
        1 if res.stats["spec_proposer"] == "draft" else 0
    _serving_spec_tally["spec_sampled"] += \
        1 if res.stats["spec_sampled"] else 0
    _serving_spec_tally["spec_tuned"] += \
        1 if res.stats["spec_tuned"] else 0
    _serving_spec_tally["draft_kills"] += \
        res.fired.get("serving.spec.draft", 0)
    _serving_spec_tally["draft_faults"] += \
        res.stats["spec_draft_faults"]
    _chunk_ctl_tally["controlled"] += 1 if res.stats["chunk_ctl"] else 0
    _chunk_ctl_tally["adaptations"] += res.stats["chunk_adaptations"]
    if _serving_spec_tally["episodes"] == len(SERVING_SEEDS):
        _chunk_ctl_tally["bands"].add("serving")


def test_serving_matrix_actually_speculates():
    """The speculative arm must stay LOADED: episodes that really run
    the verify program, really accept drafted tokens, and really get
    killed mid-verify-step — otherwise the speculative-mode soak goes
    green by vacuity."""
    if _serving_spec_tally["episodes"] < len(SERVING_SEEDS):
        pytest.skip("full serving matrix did not run")
    assert _serving_spec_tally["speculative"] >= 4, _serving_spec_tally
    assert _serving_spec_tally["accepted_drafts"] >= 3, \
        _serving_spec_tally
    assert _serving_spec_tally["verify_kills"] >= 2, _serving_spec_tally


def test_serving_matrix_actually_chunks():
    """The chunked-prefill arm must stay LOADED: episodes that really
    run with a ``prefill_chunk`` budget (sampled on its own rng stream
    so pre-chunk seeds stay bit-identical) and really get killed
    MID-CHUNK (between chunks of a PREFILLING request) — otherwise
    the ``serving.prefill.chunk`` coverage goes green by vacuity."""
    if _serving_spec_tally["episodes"] < len(SERVING_SEEDS):
        pytest.skip("full serving matrix did not run")
    assert _serving_spec_tally["chunked"] >= 3, _serving_spec_tally
    assert _serving_spec_tally["chunk_kills"] >= 1, _serving_spec_tally


def test_serving_matrix_actually_tiers():
    """The KV-tier arm must stay LOADED: episodes that really run with
    a host tier attached (sampled on its own rng stream so pre-tier
    seeds stay bit-identical), episodes that really demote cold pages
    to host RAM under the clamped pool, and at least one promotion
    genuinely installing a host page back on-device — otherwise the
    tier regime soaks green by vacuity. Kills ON the tier fault
    points are pinned separately (the dropped-promotion seed below
    fires ``serving.kv.promote`` on every run). Floors re-baselined
    for ISSUE-19: draft-model speculation accepts multi-token runs on
    two of the band's tiered seeds, finishing them in fewer decode
    steps and below the demotion-pressure threshold — band demotions
    dropped from 4 to 2; the pinned dropped-promotion seed still
    proves real demotions AND promotions on every run."""
    if _serving_spec_tally["episodes"] < len(SERVING_SEEDS):
        pytest.skip("full serving matrix did not run")
    assert _serving_spec_tally["tiered"] >= 3, _serving_spec_tally
    assert _serving_spec_tally["demotions"] >= 2, _serving_spec_tally
    assert _serving_spec_tally["promotions"] >= 1, _serving_spec_tally


def test_serving_matrix_actually_drafts():
    """The draft-model arm must stay LOADED: speculative episodes that
    really run a ``DraftModelProposer`` (sampled on its own rng stream
    so pre-spec-v2 seeds stay bit-identical), episodes that really
    submit sampled (temperature > 0) requests through the sampled
    acceptance rule, episodes that really attach the accept-rate
    tuner, and at least one kill genuinely fired ON a draft proposal
    with the fault contained (the row fell back to k=1, the episode
    stayed green) — otherwise the ISSUE-19 regimes soak green by
    vacuity. The resample kill point needs a sampled + draft + armed
    draw and is pinned separately below."""
    if _serving_spec_tally["episodes"] < len(SERVING_SEEDS):
        pytest.skip("full serving matrix did not run")
    assert _serving_spec_tally["draft_proposed"] >= 4, _serving_spec_tally
    assert _serving_spec_tally["spec_sampled"] >= 1, _serving_spec_tally
    assert _serving_spec_tally["spec_tuned"] >= 2, _serving_spec_tally
    assert _serving_spec_tally["draft_kills"] >= 1, _serving_spec_tally
    assert _serving_spec_tally["draft_faults"] >= 1, _serving_spec_tally


# ISSUE-17 chaos certification, the false-positive half: the SAME 25
# seeded serving workloads (identical rng schedules — every draw still
# happens; only the fault arming is skipped) with a watchtower mounted
# must raise ZERO incidents. Any page here is a detector that would
# cry wolf on healthy production traffic.
WATCHTOWER_CLEAN_SEEDS = list(range(25))


@pytest.mark.parametrize("seed", WATCHTOWER_CLEAN_SEEDS)
def test_watchtower_clean_band_raises_zero_incidents(seed):
    res = chaos.run_serving_episode(seed, watchtower=True,
                                    arm_faults=False)
    assert res.ok, "\n".join(res.violations)
    assert res.fired == {}                   # genuinely clean
    assert res.stats["incidents"] == 0, res.stats["incident_kinds"]


@pytest.mark.parametrize("seed", TRAINING_SEEDS)
def test_training_episode_matrix(seed, tmp_path):
    res = chaos.run_training_episode(seed, str(tmp_path))
    assert res.ok, "\n".join(res.violations)


_tp_tally = {"episodes": 0, "disagg": 0, "handoff_kills": 0,
             "sharded_kills": 0, "recoveries": 0, "chunked": 0,
             "chunk_kills": 0, "wired": 0, "wire_handoffs": 0,
             "wire_kills": 0}


@pytest.mark.parametrize("seed", TP_SERVING_SEEDS)
def test_tp_serving_episode_matrix(seed):
    import jax
    if jax.device_count() < 4:
        pytest.skip("mesh episodes need the 8-device emulation")
    flavor = "disagg" if seed % 2 == 0 else "tp"
    res = chaos.run_serving_episode(seed, mesh_flavor=flavor)
    assert res.ok, "\n".join(res.violations)
    assert res.stats["mesh"] == flavor
    assert res.stats["tp"] == 2          # both flavors decode at TP=2
    _tp_tally["episodes"] += 1
    _tp_tally["disagg"] += 1 if res.stats["mesh"] == "disagg" else 0
    _tp_tally["handoff_kills"] += \
        res.fired.get("serving.kv.handoff", 0)
    _tp_tally["sharded_kills"] += \
        res.fired.get("serving.decode.sharded", 0)
    _tp_tally["recoveries"] += res.stats["recoveries"]
    _tp_tally["chunked"] += 1 if res.stats["prefill_chunk"] else 0
    _tp_tally["chunk_kills"] += \
        res.fired.get("serving.prefill.chunk", 0)
    _tp_tally["wired"] += 1 if res.stats["kv_wired"] else 0
    _tp_tally["wire_handoffs"] += res.stats["wire_handoffs"]
    _tp_tally["wire_kills"] += res.fired.get("cluster.kv.wire", 0)
    _chunk_ctl_tally["controlled"] += 1 if res.stats["chunk_ctl"] else 0
    _chunk_ctl_tally["adaptations"] += res.stats["chunk_adaptations"]
    if _tp_tally["episodes"] == len(TP_SERVING_SEEDS):
        _chunk_ctl_tally["bands"].add("tp")


def test_serving_matrices_actually_adapt_chunk_budget():
    """ISSUE-20 coverage floor: the chunk-budget controller must stay
    LOADED across the chunked serving episodes (both bands feed it) —
    episodes that really run under the controller and budgets that
    really move. Otherwise the adaptive-chunk soak is vacuous."""
    if _chunk_ctl_tally["bands"] != {"serving", "tp"}:
        pytest.skip("both serving matrices did not run in full")
    assert _chunk_ctl_tally["controlled"] >= 3, _chunk_ctl_tally
    assert _chunk_ctl_tally["adaptations"] >= 3, _chunk_ctl_tally


def test_tp_matrix_actually_kills_handoffs_and_sharded_decodes():
    """The mesh arm must stay LOADED: episodes that really run
    disaggregated, really get killed MID-KV-HANDOFF (span computed on
    the prefill group, not yet installed on the decode pool) and
    mid-sharded-decode, and really recover — otherwise the
    tensor-parallel soak goes green by vacuity."""
    if _tp_tally["episodes"] < len(TP_SERVING_SEEDS):
        pytest.skip("full TP serving matrix did not run")
    assert _tp_tally["disagg"] >= 10, _tp_tally
    assert _tp_tally["handoff_kills"] >= 5, _tp_tally
    assert _tp_tally["sharded_kills"] >= 8, _tp_tally
    assert _tp_tally["recoveries"] >= 5, _tp_tally
    # chunked prefill composes with the mesh: episodes really chunk
    # on the mesh engines and really get killed mid-chunk there too
    assert _tp_tally["chunked"] >= 6, _tp_tally
    assert _tp_tally["chunk_kills"] >= 2, _tp_tally


def test_tp_matrix_actually_ships_kv_over_the_wire():
    """The wire-handoff arm (ISSUE 18) must stay LOADED: disaggregated
    episodes that really route every KV handoff through the
    authenticated socket transport (sampled on its own rng stream so
    pre-fabric seeds stay bit-identical), handoffs that really
    round-trip the wire, and ``cluster.kv.wire`` faults that really
    fire mid-transfer — otherwise the cross-host handoff soak goes
    green by vacuity."""
    if _tp_tally["episodes"] < len(TP_SERVING_SEEDS):
        pytest.skip("full TP serving matrix did not run")
    assert _tp_tally["wired"] >= 8, _tp_tally
    assert _tp_tally["wire_handoffs"] >= 10, _tp_tally
    assert _tp_tally["wire_kills"] >= 4, _tp_tally


_frontdoor_death_tally = {"episodes": 0, "deaths": 0,
                          "failover_requests": 0,
                          "control": 0, "sheds": 0, "tier0_sheds": 0,
                          "affinity_hits": 0, "scale_actions": 0,
                          "control_arms": 0}


@pytest.mark.parametrize("seed", FRONTDOOR_SEEDS)
def test_frontdoor_episode_matrix(seed):
    res = chaos.run_frontdoor_episode(seed)
    assert res.ok, "\n".join(res.violations)
    assert res.stats["requests"] >= 1
    _frontdoor_death_tally["episodes"] += 1
    _frontdoor_death_tally["deaths"] += \
        1 if res.stats["replica_deaths"] else 0
    _frontdoor_death_tally["failover_requests"] += \
        res.stats["failover_requests"]
    _frontdoor_death_tally["control"] += \
        1 if res.stats["control_on"] else 0
    _frontdoor_death_tally["sheds"] += res.stats["sheds"]
    _frontdoor_death_tally["tier0_sheds"] += \
        res.stats["sheds_by_tier"].get(0, 0)
    _frontdoor_death_tally["affinity_hits"] += \
        res.stats["affinity_hits"]
    _frontdoor_death_tally["scale_actions"] += \
        res.stats["scale_actions"]
    _frontdoor_death_tally["control_arms"] += sum(
        res.fired.get(p, 0) for p in ("control.shed",
                                      "control.affinity",
                                      "control.scale"))


def test_frontdoor_matrix_actually_controls():
    """ISSUE-20 coverage floors: the control arms must stay LOADED —
    across the band the brownout must actually shed (never tier 0),
    prefix affinity must actually route warm, the autoscaler must
    actually act, and the control.* actuator faults must actually
    fire. Otherwise the self-driving soak goes green by vacuity (the
    per-episode graceful-degradation law lives inside the episode)."""
    if _frontdoor_death_tally["episodes"] < len(FRONTDOOR_SEEDS):
        pytest.skip("full front-door matrix did not run")
    assert _frontdoor_death_tally["control"] >= 8, \
        _frontdoor_death_tally
    assert _frontdoor_death_tally["sheds"] >= 3, \
        _frontdoor_death_tally
    assert _frontdoor_death_tally["tier0_sheds"] == 0, \
        _frontdoor_death_tally
    assert _frontdoor_death_tally["affinity_hits"] >= 3, \
        _frontdoor_death_tally
    assert _frontdoor_death_tally["scale_actions"] >= 2, \
        _frontdoor_death_tally
    assert _frontdoor_death_tally["control_arms"] >= 2, \
        _frontdoor_death_tally


def test_frontdoor_matrix_actually_kills_replicas():
    """The replica-kill arm must stay LOADED: if sampling drift ever
    stops killing replicas (or failing requests over), the matrix
    would go green by vacuity — pin the coverage floor."""
    if _frontdoor_death_tally["episodes"] < len(FRONTDOOR_SEEDS):
        pytest.skip("full front-door matrix did not run")
    assert _frontdoor_death_tally["deaths"] >= 12, \
        _frontdoor_death_tally
    assert _frontdoor_death_tally["failover_requests"] >= 10, \
        _frontdoor_death_tally


_cluster_tally = {"episodes": 0, "requests": 0, "coop": 0,
                  "sigkill": 0, "partition": 0, "authpart": 0,
                  "deaths": 0, "failover_requests": 0, "respawns": 0,
                  "partition_incidents": 0, "death_incidents": 0,
                  "auth_blips": 0, "weights_arms": 0}


@pytest.mark.parametrize("seed", CLUSTER_SEEDS)
def test_cluster_episode_matrix(seed):
    if not _have_cluster():
        pytest.skip("native TCPStore extension unavailable")
    res = chaos.run_cluster_episode(seed)
    assert res.ok, "\n".join(res.violations)
    # every episode offers load; whether any request COMPLETES is
    # chaos-dependent (a seed may legitimately refuse every submit
    # with a typed error while both workers are down — e.g. seed
    # 519).  Completed-request coverage is floored band-wide below.
    assert res.stats["attempts"] >= 1
    _cluster_tally["episodes"] += 1
    _cluster_tally["requests"] += res.stats["requests"]
    for kind in ("coop", "sigkill", "partition", "authpart"):
        _cluster_tally[kind] += res.stats["kills"].get(kind, 0)
    _cluster_tally["auth_blips"] += 1 if res.stats["auth_blip"] else 0
    _cluster_tally["weights_arms"] += \
        1 if res.stats["weights_arm"] else 0
    _cluster_tally["deaths"] += 1 if res.stats["replica_deaths"] else 0
    _cluster_tally["failover_requests"] += \
        res.stats["failover_requests"]
    _cluster_tally["respawns"] += res.stats["respawns"]
    # watchtower attribution law, per episode: an episode where no
    # worker died must raise NO death-class incidents (the false-
    # positive bar under full chaos load)
    kinds = {tuple(k) for k in res.stats["incident_kinds"]}
    death_kinds = {k for k in kinds
                   if k[0] in ("partition", "worker_death")}
    if not res.stats["replica_deaths"]:
        assert not death_kinds, res.stats
    _cluster_tally["partition_incidents"] += \
        1 if ("partition", "dispatch") in kinds else 0
    _cluster_tally["death_incidents"] += \
        1 if ("worker_death", "failover") in kinds else 0


def test_cluster_matrix_actually_kills_workers():
    """The cross-process arm must stay LOADED, per kill KIND: across
    the band, real cooperative kills, real SIGKILLs, and real
    partitions must each fire, workers must actually die, requests
    must actually fail over, and the supervisor must actually respawn
    — otherwise the cluster soak goes green by vacuity."""
    if _cluster_tally["episodes"] < len(CLUSTER_SEEDS):
        pytest.skip("full cluster matrix did not run")
    assert _cluster_tally["requests"] >= 25, _cluster_tally
    assert _cluster_tally["coop"] >= 4, _cluster_tally
    assert _cluster_tally["sigkill"] >= 4, _cluster_tally
    assert _cluster_tally["partition"] >= 4, _cluster_tally
    assert _cluster_tally["deaths"] >= 8, _cluster_tally
    assert _cluster_tally["failover_requests"] >= 6, _cluster_tally
    assert _cluster_tally["respawns"] >= 6, _cluster_tally


def test_cluster_matrix_actually_exercises_the_fabric():
    """The serving-fabric arms (ISSUE 18) must stay LOADED across the
    band: auth blips (``cluster.rpc.auth`` under the handshake/frame
    retry budget, healed invisibly), auth partitions (exhausted auth =
    a fenced worker: respawned like any partition), and weight-store
    fetch faults (``cluster.weights.fetch`` armed inside the worker
    against its manifest fetch, absorbed by the digest-verified
    retry). All sampled on the fabric rng stream so the pre-fabric
    kill schedules stay bit-identical."""
    if _cluster_tally["episodes"] < len(CLUSTER_SEEDS):
        pytest.skip("full cluster matrix did not run")
    assert _cluster_tally["authpart"] >= 3, _cluster_tally
    assert _cluster_tally["auth_blips"] >= 6, _cluster_tally
    assert _cluster_tally["weights_arms"] >= 6, _cluster_tally


def test_cluster_matrix_watchtower_attributes_kills():
    """ISSUE-17 chaos certification, band-wide: the watchtower mounted
    on every cluster episode must raise correctly-attributed incidents
    for the REAL kills — network partitions as ``(partition,
    dispatch)`` (the wire died past the retry budget; the worker may
    be fine) and coop/SIGKILL deaths as ``(worker_death, failover)``.
    The per-episode false-positive law (no deaths -> no death-class
    incidents) is asserted inside the matrix itself."""
    if _cluster_tally["episodes"] < len(CLUSTER_SEEDS):
        pytest.skip("full cluster matrix did not run")
    assert _cluster_tally["partition_incidents"] >= 3, _cluster_tally
    assert _cluster_tally["death_incidents"] >= 3, _cluster_tally


def test_matrix_spans_all_kinds_and_enough_episodes():
    assert len(SERVING_SEEDS) + len(TRAINING_SEEDS) >= 25
    assert len(FRONTDOOR_SEEDS) >= 25      # ISSUE-7 acceptance bar
    assert len(TP_SERVING_SEEDS) >= 25     # ISSUE-9 acceptance bar
    assert len(CLUSTER_SEEDS) >= 25        # ISSUE-11 acceptance bar


def test_episodes_are_deterministic():
    """Same seed, same schedule, same faults fired, same verdict —
    the property that makes a red episode a one-line reproducer."""
    a = chaos.run_serving_episode(3)
    b = chaos.run_serving_episode(3)
    assert [(x.point, x.times, x.after) for x in a.schedule] \
        == [(x.point, x.times, x.after) for x in b.schedule]
    assert a.fired == b.fired
    assert a.violations == b.violations
    assert a.stats == b.stats


def test_frontdoor_episodes_are_deterministic():
    """Replica kills, failover adoption order, stream faults — all a
    function of the seed alone (virtual clocks, seeded RNG)."""
    a = chaos.run_frontdoor_episode(306)
    b = chaos.run_frontdoor_episode(306)
    assert [(x.point, x.times, x.after) for x in a.schedule] \
        == [(x.point, x.times, x.after) for x in b.schedule]
    assert a.fired == b.fired
    assert a.violations == b.violations
    assert a.stats == b.stats
    assert a.stats["replica_deaths"] >= 1     # the arm is loaded


def test_cluster_episodes_are_deterministic():
    """The kill schedule, workload, and verdict are a function of the
    seed alone even across the process boundary (every RPC carries the
    virtual clock). `fired` is deliberately NOT compared: when a
    worker is SIGKILLed the client may notice via proc.poll() before
    the next send or via a wire error after it — same outcome, but a
    kernel-timing race over whether one more client-side wire fault
    gets consumed."""
    if not _have_cluster():
        pytest.skip("native TCPStore extension unavailable")
    a = chaos.run_cluster_episode(502)
    b = chaos.run_cluster_episode(502)
    assert [(x.point, x.times, x.after) for x in a.schedule] \
        == [(x.point, x.times, x.after) for x in b.schedule]
    assert a.violations == b.violations
    assert a.stats["kills"] == b.stats["kills"]
    assert a.stats["requests"] == b.stats["requests"]
    assert a.stats["replica_deaths"] >= 1     # the arm is loaded


# -- open-ended soak (slow tier: excluded from smoke via `full`) -------

@pytest.mark.full
def test_open_ended_soak(tmp_path):
    """A wider randomized seed band than the tier-1 matrix — the
    `full`-tier soak; benchmarks/chaos_soak.py runs the same episodes
    under a wall/episode budget for longer hunts."""
    red = []
    for seed in range(200, 240):
        kind = "serving" if seed % 2 == 0 else "training"
        res = chaos.run_episode(seed, kind, workdir=str(tmp_path))
        if not res.ok:
            red.append((seed, kind, res.violations))
    assert not red, red


# -- pinned seeds: the harness catches the PR-3 deferred bug classes ---
# Each test re-introduces the PRE-FIX code path and asserts the pinned
# seed's fault schedule drives the ledger red (the bug class is
# DETECTED), while the fixed code stays green on the same seed.

PINNED_SEED_BUG_A = 4       # deadline expiry in the step a decode
PINNED_SEED_BUG_B = 7       # fault lands in / fault mid-drain
# (re-pinned for the SPECULATIVE episode flow — the speculative-engine
# sampling, verify fault arm and repetitive pool prompts shifted every
# seed's schedule; bug (a)'s seed again once plain decode steps ran one
# step ahead: the decode fault point fires where a step is enqueued,
# which moved the schedule's fault into another step)


def test_pinned_seed_catches_lost_finished_on_failed_step(monkeypatch):
    """Deferred bug (a): pre-fix, a request that reached a terminal
    state inside a step that then faulted (deadline-cancel sweep +
    decode fault in the same step) lived only in step()'s local
    `finished` list and vanished with the raise."""
    from paddle_tpu.serving import ServingEngine
    orig_step = ServingEngine.step

    def prefix_step(self):
        n = len(self._undelivered)
        try:
            return orig_step(self)
        except Exception:
            del self._undelivered[n:]   # pre-fix: the list was a local
            raise

    monkeypatch.setattr(ServingEngine, "step", prefix_step)
    red = chaos.run_serving_episode(PINNED_SEED_BUG_A)
    assert not red.ok
    assert any("LOST" in v for v in red.violations), red.violations
    monkeypatch.setattr(ServingEngine, "step", orig_step)
    green = chaos.run_serving_episode(PINNED_SEED_BUG_A)
    assert green.ok, "\n".join(green.violations)


PINNED_SEED_PAGE_LEAK = 15  # paged-prefill fault mid-admission
# (re-pinned from 14 for the CHUNKED episode flow — seed 14 now draws
# a prefill_chunk budget on the chunk rng stream, which routes its
# mid-prefill fault through the chunk unwind instead of the
# monolithic abort path this pin exercises; 15 stays unchunked)


def test_pinned_seed_catches_leaked_pages_on_aborted_prefill(
        monkeypatch):
    """No-leaked-pages law (paged KV): a prefill that faults AFTER
    claiming pages must unwind them (abort_sequence). With the unwind
    disabled, the pinned seed's mid-prefill fault strands refcounts
    and the page-leak audit goes red; the real code stays green."""
    from paddle_tpu.serving.slot_cache import PagedKVCache
    orig = PagedKVCache.abort_sequence
    monkeypatch.setattr(PagedKVCache, "abort_sequence",
                        lambda self, slot, req: None)
    red = chaos.run_serving_episode(PINNED_SEED_PAGE_LEAK)
    assert not red.ok
    assert any("leaked page" in v or "reservation" in v
               for v in red.violations), red.violations
    monkeypatch.setattr(PagedKVCache, "abort_sequence", orig)
    green = chaos.run_serving_episode(PINNED_SEED_PAGE_LEAK)
    assert green.ok, "\n".join(green.violations)


PINNED_SEED_CHUNK_LOST = 1   # chunk fault mid-prefill (chunk=8)


def test_pinned_seed_catches_swallowed_chunk_fault(monkeypatch):
    """ISSUE-14 pinned red seed: a fault BETWEEN chunks of a
    PREFILLING request must unwind the slot (paged claims aborted,
    lease freed) AND requeue the request for a token-identical
    replay. With the pre-fix semantics — the faulted request is
    silently dropped on the floor, its slot/page claims torn down but
    nobody re-queued — the conservation ledger goes RED with a LOST
    request; the real unwind+requeue path stays green on the same
    seed and really fires the ``serving.prefill.chunk`` fault."""
    from paddle_tpu.serving import ServingEngine
    orig = ServingEngine._unwind_chunk

    def dropped(self, slot, req, requeue):
        # pre-fix: swallow the unwind's requeue half — the request
        # vanishes mid-prefill
        self._clear_chunk_state(slot, req)
        self.cache.release(slot)
        req.slot = None

    monkeypatch.setattr(ServingEngine, "_unwind_chunk", dropped)
    red = chaos.run_serving_episode(PINNED_SEED_CHUNK_LOST)
    assert not red.ok
    assert any("LOST" in v for v in red.violations), red.violations
    monkeypatch.setattr(ServingEngine, "_unwind_chunk", orig)
    green = chaos.run_serving_episode(PINNED_SEED_CHUNK_LOST)
    assert green.ok, "\n".join(green.violations)
    assert green.stats["prefill_chunk"] == 8
    assert green.fired.get("serving.prefill.chunk", 0) >= 1


PINNED_SEED_SHED = 321   # control-on overload: the brownout sheds


def test_pinned_seed_unaudited_shed_goes_lost(monkeypatch):
    """ISSUE-20 pinned red seed: a shed request that skips its audited
    rejection (the client still gets the typed ``Shed``, but the
    ledger never hears about it) must trip the admission law as LOST
    — brownout is load SHEDDING, never load losing. The real path
    (every shed flows through ``_reject`` -> ``on_rejected``) stays
    green on the same seed, and really sheds."""
    from paddle_tpu.serving.frontdoor import FrontDoor
    orig = FrontDoor._reject

    def silent_shed(self, tenant, reason, tier=0):
        if reason == "shed":
            return       # pre-fix semantics: refusal without audit
        orig(self, tenant, reason, tier)

    monkeypatch.setattr(FrontDoor, "_reject", silent_shed)
    red = chaos.run_frontdoor_episode(PINNED_SEED_SHED)
    assert not red.ok
    assert any("LOST" in v for v in red.violations), red.violations
    monkeypatch.setattr(FrontDoor, "_reject", orig)
    green = chaos.run_frontdoor_episode(PINNED_SEED_SHED)
    assert green.ok, "\n".join(green.violations)
    assert green.stats["sheds"] >= 1


PINNED_SEED_NO_FAILOVER = 306   # replica death with requests aboard


def test_pinned_seed_catches_disabled_failover(monkeypatch):
    """ISSUE-7 pinned red seed: with the router's failover path
    DISABLED (a dead replica's requests die with it — the pre-router
    world, where a dead engine took its requests along), the
    front-door ledger must go RED with LOST violations THROUGH the
    router; the real failover path stays green on the same seed."""
    from paddle_tpu.serving.router import ReplicaRouter
    orig = ReplicaRouter._failover

    def no_failover(self, rep):
        # pre-fix semantics: the replica's host state is gone and the
        # router forgets everything it had dispatched there
        eng = rep.engine
        gone = list(eng._undelivered) + eng.scheduler.pending() \
            + [eng.cache.slots[s] for s in eng.cache.active_slots()]
        for req in gone:
            self._inflight.pop(req.rid, None)
            self._owner.pop(req.rid, None)

    monkeypatch.setattr(ReplicaRouter, "_failover", no_failover)
    red = chaos.run_frontdoor_episode(PINNED_SEED_NO_FAILOVER)
    assert not red.ok
    assert any("LOST" in v for v in red.violations), red.violations
    monkeypatch.setattr(ReplicaRouter, "_failover", orig)
    green = chaos.run_frontdoor_episode(PINNED_SEED_NO_FAILOVER)
    assert green.ok, "\n".join(green.violations)
    assert green.stats["replica_deaths"] >= 1
    assert green.stats["failover_requests"] >= 1


PINNED_SEED_CLUSTER_LOST = 502   # worker killed with requests aboard


def test_pinned_seed_catches_disabled_cluster_failover(monkeypatch):
    """ISSUE-11 pinned red seed: with respawn disabled AND the
    router's failover path disabled, a REAL worker-process death takes
    its in-flight requests with it and the ledger goes RED with LOST
    — proof the cluster band is exercising actual cross-process
    recovery, not an in-process simulation of it. The real path stays
    green on the same seed with real deaths and real failovers."""
    if not _have_cluster():
        pytest.skip("native TCPStore extension unavailable")
    from paddle_tpu.serving.router import ReplicaRouter
    orig = ReplicaRouter._failover

    def no_failover(self, rep):
        # pre-fix semantics: the worker process is gone and the router
        # forgets everything it had dispatched there (RemoteEngine's
        # host-side mirrors expose the same shape as a live engine)
        eng = rep.engine
        gone = list(eng._undelivered) + eng.scheduler.pending() \
            + [eng.cache.slots[s] for s in eng.cache.active_slots()]
        for req in gone:
            self._inflight.pop(req.rid, None)
            self._owner.pop(req.rid, None)

    monkeypatch.setattr(ReplicaRouter, "_failover", no_failover)
    red = chaos.run_cluster_episode(PINNED_SEED_CLUSTER_LOST,
                                    respawn=False)
    assert not red.ok
    assert any("LOST" in v for v in red.violations), red.violations
    monkeypatch.setattr(ReplicaRouter, "_failover", orig)
    green = chaos.run_cluster_episode(PINNED_SEED_CLUSTER_LOST)
    assert green.ok, "\n".join(green.violations)
    assert green.stats["replica_deaths"] >= 1
    assert green.stats["failover_requests"] >= 1


def test_pinned_seed_catches_drain_discarding_done(monkeypatch):
    """Deferred bug (b): pre-fix, drain()'s step loop let a mid-drain
    exception propagate, discarding the already-finished `done` list
    — the caller lost every result the drain had collected."""
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.errors import RequestCancelled
    orig_drain = ServingEngine.drain

    def prefix_drain(self, max_steps=None):
        self._closed = True
        done = []
        steps = 0
        self._in_drain = True
        try:
            while self.has_work():
                cutoff = "drain cutoff" if (
                    max_steps is not None and steps >= max_steps) \
                    else (f"drain on broken engine ({self._broken})"
                          if self._broken else None)
                if cutoff is not None:
                    for req in self.scheduler.drain():
                        req.finished, req.finish_reason = \
                            True, "cancelled"
                        req.error = RequestCancelled(req.rid, cutoff)
                        self.metrics.on_finished(req.rid)
                        done.append(req)
                    for s in self.cache.active_slots():
                        req = self.cache.slots[s]
                        req.finished, req.finish_reason = \
                            True, "cancelled"
                        req.error = RequestCancelled(req.rid, cutoff)
                        self._evict(s, req, done)
                    break
                done.extend(self.step())   # pre-fix: a raise here
                steps += 1                 # discards `done`
        finally:
            self._in_drain = False
        if self.auditor is not None:
            for r in done:
                self.auditor.on_delivered(r, via="drain")
        return done

    monkeypatch.setattr(ServingEngine, "drain", prefix_drain)
    red = chaos.run_serving_episode(PINNED_SEED_BUG_B)
    assert not red.ok
    assert any("LOST" in v for v in red.violations), red.violations
    monkeypatch.setattr(ServingEngine, "drain", orig_drain)
    green = chaos.run_serving_episode(PINNED_SEED_BUG_B)
    assert green.ok, "\n".join(green.violations)


PINNED_SEED_BROKEN_SPEC = 8   # speculative episode with real accepts
# (re-pinned 5 -> 6 for the ISSUE-9 verify GATE: no-draft steps now
# run the k=1 decode program, so the broken-acceptance patch only
# distorts steps that really carry drafts; re-pinned 6 -> 8 for the
# ISSUE-16 tier duty cycle: seed 6's tiered workload changed and its
# drafts now verify clean — seed 8 still has partially rejected
# drafts, which is exactly what the patch mis-emits)


def test_pinned_seed_catches_broken_speculative_acceptance(
        monkeypatch):
    """Speculative-mode pinned red seed (ISSUE 8): with the verify
    step's acceptance/rollback DELIBERATELY broken — the engine trusts
    the whole draft window instead of the in-program accepted length,
    i.e. rejected draft tokens are emitted as if verified — the token-
    identity audit must go RED (the stream carries tokens sequential
    greedy would never have produced). The real acceptance rule stays
    green on the same seed, with drafts genuinely accepted and the
    mid-verify kill arm genuinely fired — so the law is not green by
    vacuity."""
    from paddle_tpu.serving import ServingEngine
    orig = ServingEngine._emit_verified

    def trust_the_whole_draft(self, slot, req, greedy_row, acc,
                              logits_row, *a, **kw):
        return orig(self, slot, req, greedy_row, len(greedy_row),
                    logits_row, *a, **kw)

    monkeypatch.setattr(ServingEngine, "_emit_verified",
                        trust_the_whole_draft)
    red = chaos.run_serving_episode(PINNED_SEED_BROKEN_SPEC)
    assert not red.ok
    assert any("diverged" in v or "emitted" in v
               for v in red.violations), red.violations
    monkeypatch.setattr(ServingEngine, "_emit_verified", orig)
    green = chaos.run_serving_episode(PINNED_SEED_BROKEN_SPEC)
    assert green.ok, "\n".join(green.violations)
    assert green.stats["speculative"]
    assert green.stats["spec_accepted_drafts"] >= 1
    assert green.fired.get("serving.decode.verify", 0) >= 1


PINNED_SEED_DROPPED_HANDOFF = 412   # disagg episode, handoff kill


def test_pinned_seed_dropped_kv_handoff_goes_lost(monkeypatch):
    """ISSUE-9 pinned red seed: a DROPPED KV handoff must be detected.
    With the handoff failure SWALLOWED (the pre-fix shape: the engine
    eats the mid-handoff exception instead of routing it through the
    abort/requeue path, so the request is neither served nor
    returned), the conservation ledger must go RED with LOST on the
    pinned disaggregated seed; the real path — abort_sequence unwinds
    the decode-side page claims, the staged span dies with the frame,
    and the request requeues — stays green on the same seed, with the
    handoff kill arm genuinely fired (not green by vacuity)."""
    from paddle_tpu.resilience.faults import InjectedFault
    from paddle_tpu.serving import ServingEngine
    orig = ServingEngine._prefill

    def swallow_handoff_failure(self, slot, req):
        try:
            return orig(self, slot, req)
        except InjectedFault as e:
            if getattr(e, "point", "") != "serving.kv.handoff":
                raise
            return          # pre-fix: request dropped on the floor

    monkeypatch.setattr(ServingEngine, "_prefill",
                        swallow_handoff_failure)
    red = chaos.run_serving_episode(PINNED_SEED_DROPPED_HANDOFF,
                                    mesh_flavor="disagg")
    assert not red.ok
    assert any("LOST" in v for v in red.violations), red.violations
    monkeypatch.setattr(ServingEngine, "_prefill", orig)
    green = chaos.run_serving_episode(PINNED_SEED_DROPPED_HANDOFF,
                                      mesh_flavor="disagg")
    assert green.ok, "\n".join(green.violations)
    assert green.fired.get("serving.kv.handoff", 0) >= 1
    assert green.stats["mesh"] == "disagg"


PINNED_SEED_WIRE_LOST = 11   # disagg episode, wire arm past budget


def test_pinned_seed_swallowed_wire_handoff_goes_lost(monkeypatch):
    """ISSUE-18 pinned red seed: a wire KV handoff that fails PAST the
    retry budget must abort and requeue, never vanish. The pinned
    seed's ``cluster.kv.wire`` arm outlasts the transport's 3-attempt
    budget, so the typed :class:`KVWireError` surfaces mid-handoff
    (span staged, decode-side pages claimed). With that error
    SWALLOWED at the prefill boundary — the pre-fix shape: neither
    served nor requeued — the conservation ledger goes RED with LOST;
    the real path (staged span dropped, ``abort_sequence`` returns the
    page claims, request requeued and re-shipped on a fresh transfer
    id) stays green on the same seed, with the wire arm genuinely
    fired past budget and real handoffs genuinely round-tripping the
    socket (not green by vacuity)."""
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.kv_wire import KVWireError
    orig = ServingEngine._prefill

    def swallow_wire_failure(self, slot, req):
        try:
            return orig(self, slot, req)
        except KVWireError:
            return          # pre-fix: request dropped on the floor

    monkeypatch.setattr(ServingEngine, "_prefill",
                        swallow_wire_failure)
    red = chaos.run_serving_episode(PINNED_SEED_WIRE_LOST)
    assert not red.ok
    assert any("LOST" in v for v in red.violations), red.violations
    monkeypatch.setattr(ServingEngine, "_prefill", orig)
    green = chaos.run_serving_episode(PINNED_SEED_WIRE_LOST)
    assert green.ok, "\n".join(green.violations)
    assert green.stats["mesh"] == "disagg"
    assert green.stats["kv_wired"]
    assert green.stats["wire_handoffs"] >= 1
    # past-budget: more fires than one ship's 3-attempt budget
    assert green.fired.get("cluster.kv.wire", 0) >= 4


PINNED_SEED_DROPPED_PROMOTION = 696   # tiered episode, promote kill


def test_pinned_seed_dropped_kv_promotion_goes_lost(monkeypatch):
    """ISSUE-16 pinned red seed: a DROPPED KV promotion must be
    detected. With the mid-promotion failure SWALLOWED at the prefill
    boundary (the pre-fix shape: the engine eats the exception after
    the request was staged and its dst pages claimed, so the request
    is neither served nor returned), the conservation ledger must go
    RED with LOST on the pinned tiered seed; the real path — the
    staged-promotion unwind pops the staging entry, returns the dst
    pages and the tier pins through ``abort_sequence``, and the
    request requeues and retries — stays green on the same seed, with
    the promote kill arm genuinely fired and real demotions AND
    promotions behind it (not green by vacuity)."""
    from paddle_tpu.resilience.faults import InjectedFault
    from paddle_tpu.serving import ServingEngine
    orig = ServingEngine._prefill

    def swallow_promotion_failure(self, slot, req):
        try:
            return orig(self, slot, req)
        except InjectedFault as e:
            if getattr(e, "point", "") != "serving.kv.promote":
                raise
            return          # pre-fix: request dropped on the floor

    monkeypatch.setattr(ServingEngine, "_prefill",
                        swallow_promotion_failure)
    red = chaos.run_serving_episode(PINNED_SEED_DROPPED_PROMOTION,
                                    watchtower=True)
    assert not red.ok
    assert any("LOST" in v for v in red.violations), red.violations
    # ISSUE-17: the watchtower detects the same drop LIVE — the
    # request the metrics plane still tracks but the engine forgot is
    # an orphan, attributed to the phase it was last seen in
    # (kv_promotion: on_promotion_start fired at staging, before the
    # kill point)
    assert ("request_orphaned", "kv_promotion") \
        in red.stats["incident_kinds"], red.stats
    monkeypatch.setattr(ServingEngine, "_prefill", orig)
    green = chaos.run_serving_episode(PINNED_SEED_DROPPED_PROMOTION,
                                      watchtower=True)
    assert green.ok, "\n".join(green.violations)
    assert green.fired.get("serving.kv.promote", 0) >= 1
    # the real path unwinds and requeues: nothing orphaned, no page
    assert green.stats["incidents"] == 0, green.stats
    assert green.stats["kv_tiered"]
    assert green.stats["demotions"] >= 1
    assert green.stats["promotions"] >= 1


# -- disarmed maybe_fail is (nearly) free ------------------------------

def test_maybe_fail_disarmed_path_is_lock_free(monkeypatch):
    """The zero-cost contract for every instrumented hot path
    (per-sample dataloader, per-op store, per-step engines): with no
    rule armed and no PTPU_FAULTS, ``maybe_fail`` is ONE cached bool
    plus one env probe — it never touches ``_lock`` and never bumps a
    counter. Arming a rule flips it onto the locked slow path; an env
    arm set mid-process (forked workers, monkeypatch) must still take
    effect on the very next evaluation."""

    class _CountingLock:
        def __init__(self, inner):
            self.inner = inner
            self.acquisitions = 0

        def __enter__(self):
            self.acquisitions += 1
            return self.inner.__enter__()

        def __exit__(self, *exc):
            return self.inner.__exit__(*exc)

    monkeypatch.delenv("PTPU_FAULTS", raising=False)
    probe = _CountingLock(faults._lock)
    monkeypatch.setattr(faults, "_lock", probe)

    assert faults._disarmed is True
    for _ in range(1000):
        faults.maybe_fail("serving.step.decode")
    assert probe.acquisitions == 0
    assert faults.hits("serving.step.decode") == 0  # no bookkeeping

    faults.inject("serving.step.decode", times=1)
    assert faults._disarmed is False
    before = probe.acquisitions
    with pytest.raises(faults.InjectedFault):
        faults.maybe_fail("serving.step.decode")
    assert probe.acquisitions > before       # armed = the locked walk
    assert faults.fired("serving.step.decode") == 1
    faults.clear()
    assert faults._disarmed is True

    # the env probe is the one read that cannot be cached away
    monkeypatch.setenv("PTPU_FAULTS", "serving.step.decode:1")
    with pytest.raises(faults.InjectedFault):
        faults.maybe_fail("serving.step.decode")
    monkeypatch.delenv("PTPU_FAULTS")
    faults.maybe_fail("serving.step.decode")  # disarms lazily, no raise
    assert faults._disarmed is True


PINNED_SEED_SPEC_RESAMPLE = 44   # sampled + draft episode, both spec
# kill points armed (found by scanning the rng6 stream: needs a
# speculative draw, a draft-proposer draw with an INDEPENDENT draft
# model — an oracle self-draft never rejects, so the residual resample
# never runs — a sampled-acceptance draw, and both arm draws hot)


def test_pinned_seed_spec_kill_points_fire():
    """ISSUE-19 coverage pin: both new fault points must genuinely
    fire inside one episode and stay CONTAINED. ``serving.spec.draft``
    kills a draft proposal mid-step (the row falls back to k=1, the
    proposer state for that rid is unwound); ``serving.spec.resample``
    kills between the first rejection and the residual draw (the
    step's already-accepted prefix survives, the bonus token is
    dropped, the request continues next step). The episode must end
    green with real residual resamples besides the killed ones —
    proof the sampled acceptance rule actually rejects on this seed
    rather than the kill point being the only thing exercised."""
    res = chaos.run_serving_episode(PINNED_SEED_SPEC_RESAMPLE)
    assert res.ok, "\n".join(res.violations)
    assert res.stats["spec_proposer"] == "draft", res.stats
    assert res.stats["spec_sampled"], res.stats
    assert res.fired.get("serving.spec.draft", 0) >= 1, res.fired
    assert res.fired.get("serving.spec.resample", 0) >= 1, res.fired
    assert res.stats["spec_draft_faults"] >= 1, res.stats
    assert res.stats["spec_resamples"] >= 1, res.stats


PINNED_SEED_SWALLOWED_DRAFT = 5   # draft episode, draft kill armed


def test_pinned_seed_swallowed_draft_fault_goes_lost(monkeypatch):
    """ISSUE-19 pinned red seed: a draft-model failure must be
    CONTAINED, never escalated. With the containment broken in the
    tempting-but-wrong direction — the engine treats a failed draft
    proposal as fatal to the REQUEST and evicts it unfinished (the
    pre-fix shape: finish it with a synthetic reason and throw away
    the tokens) — the conservation ledger goes RED with LOST on the
    pinned seed. The real path — ``_on_draft_fault`` unwinds the
    proposer's per-rid state, the row falls back to k=1 for that step,
    and target decoding proceeds — stays green on the same seed with
    the kill arm genuinely fired and real accepted drafts behind it
    (not green by vacuity)."""
    from paddle_tpu.serving import ServingEngine
    orig = ServingEngine._on_draft_fault

    def escalate_draft_fault(self, slot, req, proposer, exc):
        req.finished = True
        req.finish_reason = "draft_fault"
        self._evict(slot, req, [])   # pre-fix: tokens dropped on floor

    monkeypatch.setattr(ServingEngine, "_on_draft_fault",
                        escalate_draft_fault)
    red = chaos.run_serving_episode(PINNED_SEED_SWALLOWED_DRAFT)
    assert not red.ok
    assert any("LOST" in v for v in red.violations), red.violations
    monkeypatch.setattr(ServingEngine, "_on_draft_fault", orig)
    green = chaos.run_serving_episode(PINNED_SEED_SWALLOWED_DRAFT)
    assert green.ok, "\n".join(green.violations)
    assert green.stats["spec_proposer"] == "draft", green.stats
    assert green.fired.get("serving.spec.draft", 0) >= 1, green.fired
    assert green.stats["spec_draft_faults"] >= 1, green.stats
    assert green.stats["spec_accepted_drafts"] >= 1, green.stats
