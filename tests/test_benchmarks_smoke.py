"""Smoke: every BASELINE-config benchmark script runs in CPU mode and
prints a well-formed JSON metric line, and the TrainStep AMP-O2 path they
depend on stays finite (regression: warm-init at step 0 used to divide
by 1-beta^0 and poison bf16 master weights with NaN)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the serving engine's metrics-summary schema is a STABLE contract:
# dashboards and the Prometheus bridge key on these — a key vanishing
# here is a breaking change, caught by the schema guard below
SERVING_SUMMARY_KEYS = {
    "requests", "total_tokens", "wall_s", "tokens_per_s",
    "ttft_p50_s", "ttft_p99_s", "queue_wait_p50_s", "queue_wait_p99_s",
    "tok_latency_p50_s", "tok_latency_p99_s", "occupancy_mean", "steps",
}


# the SERVING_SLO line (bench_serving_engine --frontdoor) is the
# ISSUE-7 acceptance artifact: a closed-loop load test against the
# front door with a replica KILLED mid-run — schema stable, exactly-
# once ledger green, SLO met, failover actually exercised
SERVING_SLO_KEYS = {
    "replicas", "clients", "requests", "completed", "rejected_noisy",
    "qps", "p99_ttft_s", "ttft_slo_s", "p99_ttft_steps", "slo_ok",
    "deadline_miss_rate", "failovers", "failover_requests",
    "lost", "duplicates", "ledger_green", "step_wall_ms",
}


# the SPEC_DECODE line (bench_serving_engine --speculative) is the
# ISSUE-8 acceptance artifact: self-drafted k-token verification on a
# repetitive-suffix trace — schema stable, > 1.5 accepted tokens per
# verify step, >= 25% fewer decode steps than the k=1 engine, greedy
# outputs token-identical, exactly one verify compile
SPEC_DECODE_KEYS = {
    "k", "requests", "tokens", "steps_speculative", "steps_k1",
    "step_reduction", "accepted_per_step", "draft_hit_rate",
    "draft_tokens", "accepted_draft_tokens", "acc_len_hist",
    "tok_latency_p50_s", "tok_latency_p99_s", "tok_latency_p50_s_k1",
    "tok_latency_p99_s_k1", "tokens_per_s_speculative",
    "tokens_per_s_k1", "verify_compiles", "token_identical",
}


# the SPEC_V2 line (bench_serving_engine --spec-v2) is the ISSUE-19
# acceptance artifact: draft-model speculation vs prompt-lookup on a
# LOW-self-similarity trace (where n-gram finds nothing), plus the
# sampled-acceptance distribution-parity bar and the tuner readout —
# schema stable, draft >= 1.3x the n-gram accepted tokens/step with
# greedy token identity, exactly one verify + one draft compile
SPEC_V2_KEYS = {
    "k", "requests", "accepted_per_step_ngram",
    "accepted_per_step_draft", "accepted_per_step_tuned",
    "draft_vs_ngram", "draft_overhead_frac", "draft_hit_rate_ngram",
    "draft_hit_rate_draft", "tuner_k", "tuner_kind", "tuner_flips",
    "token_identical", "sampled_requests", "sampled_tokens",
    "sampled_parity_tv", "sampled_parity_ok", "verify_compiles",
    "draft_compiles", "decode_compiles_ngram", "steps_k1",
    "steps_ngram", "steps_draft",
}


# the TP_SERVING line (bench_serving_engine --tensor-parallel) is the
# ISSUE-9 acceptance artifact: the same burst trace through the
# single-chip, TP=2 and disaggregated (2 prefill + 2 decode) engines
# on the emulated mesh — schema stable, greedy token-identical across
# all three, ONE decode compile per mesh shape, handoff installs
# bounded by the prefill-bucket shape set
TP_SERVING_KEYS = {
    "devices", "tp", "prefill_devices", "requests",
    "tokens_per_s_single", "tokens_per_s_tp", "tokens_per_s_disagg",
    "ttft_p99_s_single", "ttft_p99_s_tp", "ttft_p99_s_disagg",
    "token_identical", "decode_compiles_tp", "decode_compiles_disagg",
    "install_compiles", "install_shapes", "kv_shards",
}


# the CLUSTER_SLO line (bench_serving_engine --cluster) is the
# ISSUE-11 acceptance artifact: the closed-loop SLO run with worker
# PROCESSES behind RPC replicas and a real mid-run SIGKILL — schema
# stable, exactly-once ledger green through the process death,
# supervisor respawn exercised
CLUSTER_SLO_KEYS = {
    "workers", "clients", "requests", "completed", "rejected_noisy",
    "qps", "p99_ttft_s", "ttft_slo_s", "p99_ttft_steps", "slo_ok",
    "deadline_miss_rate", "worker_sigkills", "failovers",
    "failover_requests", "respawns", "lost", "duplicates",
    "ledger_green", "step_wall_ms",
}


# the CLUSTER_WAN line (bench_serving_engine --multihost) is the
# ISSUE-18 acceptance artifact: every disaggregated KV handoff shipped
# over the authenticated socket transport (token-identical, wire blips
# absorbed), then an authenticated worker cluster with a shared
# digest-verified weight store driven through a SIGKILL + a partition,
# with an unauthenticated raw client provably refused at the end
CLUSTER_WAN_KEYS = {
    "devices", "wire_requests", "wire_handoffs", "wire_bytes",
    "wire_faults_absorbed", "token_identical", "workers",
    "cluster_requests", "sigkills", "partitions", "failover_requests",
    "respawns", "unauth_client_rejected", "auth_failures",
    "weights_published", "weight_manifest", "ledger_green",
}


# the CHUNKED_PREFILL line (bench_serving_engine --chunked-prefill)
# is the ISSUE-14 acceptance artifact: mixed long-prompt/short-decode
# traffic through the unchunked and prefill_chunk engines — schema
# stable, max decode stall reduced >= 3x, greedy token-identical,
# exactly one decode compile, chunk compiles inside the prefill-
# bucket budget
CHUNKED_PREFILL_KEYS = {
    "chunk", "requests_short", "requests_long", "long_prompt_lens",
    "max_decode_stall_s_unchunked", "max_decode_stall_s_chunked",
    "stall_reduction", "tok_latency_p99_s_unchunked",
    "tok_latency_p99_s_chunked", "steps_unchunked", "steps_chunked",
    "chunk_steps", "token_identical", "decode_compiles",
    "chunk_compiles", "chunk_compile_shapes", "chunk_compile_budget",
}


# the CONTROL_PLANE line (bench_serving_engine --control-plane) is
# the ISSUE-20 acceptance artifact: the same virtual-clock overload
# burst replayed with the priority brownout OFF then ON — schema
# stable, low tiers really shed, tier 0 NEVER shed, tier-0 p99 TTFT
# (in pump-steps) no worse than the unshed run, zero LOST both ways
CONTROL_PLANE_KEYS = {
    "requests", "tiers", "completed_unshed", "completed_shed",
    "sheds", "sheds_by_tier", "tier0_sheds", "attempts_by_tier",
    "p99_ttft_steps_by_tier_unshed", "p99_ttft_steps_by_tier_shed",
    "brownout_level_max", "lost", "duplicates", "ledger_green",
}


# the PAGED_KV line (bench_serving_engine --prefix-share) is the
# artifact the paged-KV acceptance keys on: schema stable, gains over
# a full-length row a slot asserted at the ISSUE-6 bars (>= 4x paged,
# >= 10x with int8 + shared prefixes)
PAGED_KV_KEYS = {
    "budget_bytes", "page_size", "num_pages",
    "peak_concurrency_contiguous", "peak_concurrency_paged",
    "peak_concurrency_paged_int8", "concurrency_gain",
    "concurrency_gain_int8", "prefix_hit_rate", "pages_per_token",
    "cow_copies", "int8_greedy_agreement", "tokens_per_s_paged",
    "decode_compiles",
}


# the WATCHTOWER line (bench_serving_engine --watchtower) is the
# ISSUE-17 acceptance artifact: the same burst trace replayed clean
# (must raise ZERO incidents) and with an injected stall (must raise
# a ('stall', 'decode') incident and flip healthz red), detection
# read-only (token-identical outputs)
WATCHTOWER_KEYS = {
    "requests", "steps", "stall_after_s", "burn_objectives",
    "incidents_clean", "incidents_stalled", "incident_kinds_stalled",
    "healthz_ok_clean", "healthz_ok_stalled", "token_identical",
}


# the KV_TIERING line (bench_serving_engine --kv-tiering) is the
# ISSUE-16 acceptance artifact: shared-prompt waves under device-page
# pressure across untiered / host-tier / persistent-store engines —
# schema stable, tiered hit rate >= untiered, promotions actually
# exercised, restart wave warm from disk, token-identical, one decode
# compile
KV_TIERING_KEYS = {
    "device_pages", "page_size", "prefix_hit_rate_untiered",
    "prefix_hit_rate_tiered", "prefix_hit_rate_persistent",
    "restart_prefix_hit_rate", "hit_tokens_host", "hit_tokens_disk",
    "demotions", "promotions", "promotion_wait_p99_s",
    "token_identical", "tokens_per_s_untiered", "tokens_per_s_tiered",
    "decode_compiles",
}


@pytest.mark.parametrize("script", [
    "bench_resnet50.py", "bench_bert_dp.py", "bench_gpt_hybrid.py",
    "bench_ernie_zero3.py", "bench_ppyoloe_infer.py",
    "bench_llama_decode.py", "bench_serving_engine.py",
    "bench_serving_engine.py --prefix-share",
    "bench_serving_engine.py --speculative",
    "bench_serving_engine.py --spec-v2",
    "bench_serving_engine.py --kv-tiering",
    "bench_serving_engine.py --watchtower",
    "bench_serving_engine.py --chunked-prefill",
    "bench_serving_engine.py --frontdoor",
    "bench_serving_engine.py --control-plane",
    "bench_serving_engine.py --tensor-parallel",
    "bench_serving_engine.py --cluster",
    "bench_serving_engine.py --multihost",
    "chaos_soak.py",
])
def test_benchmark_script_smoke(script, tmp_path):
    if "--cluster" in script or "--multihost" in script:
        from paddle_tpu.distributed.store import get_lib
        if get_lib() is None:
            pytest.skip("native TCPStore extension unavailable")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               # the scripts turn the compile cache on: keep it (and
               # their cluster workers') out of the checkout
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.pathsep.join(
                   [HERE] + os.environ.get("PYTHONPATH", "")
                   .split(os.pathsep)))
    prom_path = tmp_path / "snapshot.prom"
    if script == "bench_serving_engine.py":
        env["PTPU_PROM_OUT"] = str(prom_path)
    trace_path = tmp_path / "cluster_trace.json"
    if "--cluster" in script:
        env["PTPU_TRACE_OUT"] = str(trace_path)
    if script == "chaos_soak.py":
        env["PTPU_CHAOS_EPISODES"] = "6"    # smoke budget
    argv = script.split()
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "benchmarks", argv[0])]
        + argv[1:],
        capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
    assert lines, r.stdout
    for line in lines:
        rec = json.loads(line)
        assert {"metric", "value", "unit", "vs_baseline"} <= set(rec)
        assert rec["value"] is not None and np.isfinite(rec["value"])
    if script == "bench_serving_engine.py":
        # schema guard: the METRICS line carries the engine summary
        # (stable key set) + the registry family list, and PTPU_PROM_OUT
        # produced a Prometheus snapshot with the serving families
        mlines = [l for l in r.stdout.splitlines()
                  if l.startswith("METRICS ")]
        assert mlines, r.stdout
        snap = json.loads(mlines[-1][len("METRICS "):])
        assert SERVING_SUMMARY_KEYS <= set(snap["engine_summary"]), \
            sorted(snap["engine_summary"])
        fams = set(snap["families"])
        assert {"ptpu_serving_ttft_seconds",
                "ptpu_serving_queue_wait_seconds",
                "ptpu_serving_step_seconds",
                "ptpu_serving_prefills_total"} <= fams, sorted(fams)
        prom = prom_path.read_text()
        assert "# TYPE ptpu_serving_ttft_seconds histogram" in prom
        assert "ptpu_serving_requests_total" in prom
    if script == "bench_serving_engine.py --prefix-share":
        plines = [l for l in r.stdout.splitlines()
                  if l.startswith("PAGED_KV ")]
        assert plines, r.stdout
        pk = json.loads(plines[-1][len("PAGED_KV "):])
        assert PAGED_KV_KEYS <= set(pk), sorted(pk)
        # ISSUE-6 acceptance bars, deterministic on the burst trace
        assert pk["concurrency_gain"] >= 4.0, pk
        assert pk["concurrency_gain_int8"] >= 10.0, pk
        assert pk["decode_compiles"] == 1, pk
        assert pk["prefix_hit_rate"] > 0.5, pk
        assert pk["int8_greedy_agreement"] >= 0.9, pk
    if script == "bench_serving_engine.py --speculative":
        slines = [l for l in r.stdout.splitlines()
                  if l.startswith("SPEC_DECODE ")]
        assert slines, r.stdout
        sd = json.loads(slines[-1][len("SPEC_DECODE "):])
        assert SPEC_DECODE_KEYS <= set(sd), sorted(sd)
        # ISSUE-8 acceptance bars, deterministic on the burst trace
        assert sd["accepted_per_step"] > 1.5, sd
        assert sd["step_reduction"] >= 0.25, sd
        assert sd["token_identical"] is True, sd
        assert sd["verify_compiles"] == 1, sd
        assert sd["draft_hit_rate"] > 0.2, sd
        # the accepted-length histogram really has multi-token accepts
        assert sum(sd["acc_len_hist"][2:]) > 0, sd
    if script == "bench_serving_engine.py --spec-v2":
        vlines = [l for l in r.stdout.splitlines()
                  if l.startswith("SPEC_V2 ")]
        assert vlines, r.stdout
        sv = json.loads(vlines[-1][len("SPEC_V2 "):])
        assert SPEC_V2_KEYS <= set(sv), sorted(sv)
        # ISSUE-19 acceptance bars, deterministic on the burst trace:
        # on the low-self-similarity trace the draft model must beat
        # the n-gram proposer by >= 1.3x accepted tokens/step with
        # greedy token identity, the sampled rejection-sampling path
        # must hold distribution parity vs k=1, and the one-program
        # discipline extends to the draft proposer
        assert sv["draft_vs_ngram"] >= 1.3, sv
        assert sv["accepted_per_step_draft"] > 1.5, sv
        assert sv["token_identical"] is True, sv
        assert sv["sampled_parity_ok"] is True, sv
        assert sv["verify_compiles"] == 1, sv
        assert sv["draft_compiles"] == 1, sv
        assert 0.0 <= sv["draft_overhead_frac"] < 1.0, sv
    if script == "bench_serving_engine.py --kv-tiering":
        klines = [l for l in r.stdout.splitlines()
                  if l.startswith("KV_TIERING ")]
        assert klines, r.stdout
        kt = json.loads(klines[-1][len("KV_TIERING "):])
        assert KV_TIERING_KEYS <= set(kt), sorted(kt)
        # ISSUE-16 acceptance bars, deterministic on the wave trace:
        # tiering beats destroy-on-reclaim under the same page budget,
        # the tier is actually exercised, a restart resumes warm from
        # disk on its first wave, and identity/compile contracts hold
        assert kt["prefix_hit_rate_tiered"] \
            >= kt["prefix_hit_rate_untiered"], kt
        assert kt["demotions"] > 0 and kt["promotions"] > 0, kt
        assert kt["restart_prefix_hit_rate"] > 0, kt
        assert kt["hit_tokens_disk"] > 0, kt
        assert kt["token_identical"] is True, kt
        assert kt["decode_compiles"] == 1, kt
    if script == "bench_serving_engine.py --watchtower":
        wlines = [l for l in r.stdout.splitlines()
                  if l.startswith("WATCHTOWER ")]
        assert wlines, r.stdout
        wt = json.loads(wlines[-1][len("WATCHTOWER "):])
        assert WATCHTOWER_KEYS <= set(wt), sorted(wt)
        # ISSUE-17 acceptance bars, deterministic on the burst trace:
        # no false positives clean, the injected outage detected and
        # attributed to the decode phase, detection read-only
        assert wt["incidents_clean"] == 0, wt
        assert wt["healthz_ok_clean"] is True, wt
        assert wt["incidents_stalled"] >= 1, wt
        assert ["stall", "decode"] in wt["incident_kinds_stalled"], wt
        assert wt["healthz_ok_stalled"] is False, wt
        assert wt["token_identical"] is True, wt
    if script == "bench_serving_engine.py --chunked-prefill":
        clines = [l for l in r.stdout.splitlines()
                  if l.startswith("CHUNKED_PREFILL ")]
        assert clines, r.stdout
        cp = json.loads(clines[-1][len("CHUNKED_PREFILL "):])
        assert CHUNKED_PREFILL_KEYS <= set(cp), sorted(cp)
        # ISSUE-14 acceptance bars, deterministic on the mixed trace:
        # stall bounded by the chunk budget, identity preserved, the
        # compile contract intact
        assert cp["stall_reduction"] >= 3.0, cp
        assert cp["max_decode_stall_s_chunked"] \
            < cp["max_decode_stall_s_unchunked"], cp
        assert cp["token_identical"] is True, cp
        assert cp["decode_compiles"] == 1, cp
        assert 1 <= cp["chunk_compile_shapes"] \
            <= cp["chunk_compile_budget"], cp
        assert cp["chunk_steps"] > 0, cp
    if script == "bench_serving_engine.py --frontdoor":
        slines = [l for l in r.stdout.splitlines()
                  if l.startswith("SERVING_SLO ")]
        assert slines, r.stdout
        slo = json.loads(slines[-1][len("SERVING_SLO "):])
        assert SERVING_SLO_KEYS <= set(slo), sorted(slo)
        assert slo["completed"] == slo["requests"], slo
        assert slo["slo_ok"] is True, slo
        assert slo["ledger_green"] is True, slo
        assert slo["lost"] == 0 and slo["duplicates"] == 0, slo
        # the run is not vacuous: a replica really died mid-run with
        # requests failed over, and the noisy tenant was really shed
        assert slo["failovers"] >= 1, slo
        assert slo["failover_requests"] >= 1, slo
        assert slo["rejected_noisy"] >= 1, slo
    if script == "bench_serving_engine.py --control-plane":
        clines = [l for l in r.stdout.splitlines()
                  if l.startswith("CONTROL_PLANE ")]
        assert clines, r.stdout
        cp = json.loads(clines[-1][len("CONTROL_PLANE "):])
        assert CONTROL_PLANE_KEYS <= set(cp), sorted(cp)
        # ISSUE-20 acceptance bars, deterministic on the virtual-clock
        # burst: brownout really engaged and shed the low tiers, the
        # top tier was never shed and its p99 TTFT did not regress
        # versus the unshed replay, and a shed is an audited rejection
        # — never a lost request — under the conservation ledger
        assert cp["completed_unshed"] == cp["requests"], cp
        assert cp["sheds"] >= 1, cp
        assert cp["tier0_sheds"] == 0, cp
        assert cp["brownout_level_max"] >= 1, cp
        assert cp["completed_shed"] + cp["sheds"] == cp["requests"], cp
        assert cp["p99_ttft_steps_by_tier_shed"]["0"] \
            <= cp["p99_ttft_steps_by_tier_unshed"]["0"], cp
        assert cp["lost"] == 0 and cp["duplicates"] == 0, cp
        assert cp["ledger_green"] is True, cp
    if script == "bench_serving_engine.py --cluster":
        clines = [l for l in r.stdout.splitlines()
                  if l.startswith("CLUSTER_SLO ")]
        assert clines, r.stdout
        slo = json.loads(clines[-1][len("CLUSTER_SLO "):])
        assert CLUSTER_SLO_KEYS <= set(slo), sorted(slo)
        assert slo["completed"] == slo["requests"], slo
        assert slo["slo_ok"] is True, slo
        assert slo["ledger_green"] is True, slo
        assert slo["lost"] == 0 and slo["duplicates"] == 0, slo
        # not vacuous: a worker PROCESS was really SIGKILLED mid-run,
        # its requests failed over, and the supervisor respawned it
        assert slo["worker_sigkills"] == 1, slo
        assert slo["failovers"] >= 1, slo
        assert slo["failover_requests"] >= 1, slo
        assert slo["respawns"] >= 1, slo
        assert slo["rejected_noisy"] >= 1, slo
        # ISSUE-13: the merged-timeline artifact + schema-guarded line
        tlines = [l for l in r.stdout.splitlines()
                  if l.startswith("TRACE_TIMELINE ")]
        assert tlines, r.stdout
        tt = json.loads(tlines[-1][len("TRACE_TIMELINE "):])
        assert {"artifact", "spans", "lanes", "worker_pids",
                "failover_flow_events", "scrape_losses",
                "slo_requests", "merged_metric_lines"} <= set(tt), \
            sorted(tt)
        # spans from >= 2 distinct worker pids in ONE merged trace
        assert len(set(tt["worker_pids"])) >= 2, tt
        assert tt["spans"] > 0 and tt["slo_requests"] > 0, tt
        assert tt["failover_flow_events"] >= 3, tt   # linked lanes
        art = json.loads(trace_path.read_text())
        evs = art["chrome_trace"]["traceEvents"]
        span_pids = {e["pid"] for e in evs if e.get("ph") == "X"}
        assert set(tt["worker_pids"]) <= span_pids, tt
        assert len(span_pids & set(tt["worker_pids"])) >= 2
        assert any(e.get("ph") == "s" for e in evs)   # flow start
        assert art["slo_attribution"], "empty SLO attribution"
        assert "# TYPE" in art["merged_metrics"]
    if script == "bench_serving_engine.py --multihost":
        wlines = [l for l in r.stdout.splitlines()
                  if l.startswith("CLUSTER_WAN ")]
        assert wlines, r.stdout
        wan = json.loads(wlines[-1][len("CLUSTER_WAN "):])
        assert CLUSTER_WAN_KEYS <= set(wan), sorted(wan)
        # ISSUE-18 acceptance bars: the wire path really carried the
        # handoffs and really healed injected blips token-identically
        assert wan["wire_handoffs"] >= 1, wan
        assert wan["wire_faults_absorbed"] >= 1, wan
        assert wan["token_identical"] is True, wan
        # the cluster half really survived a SIGKILL and a partition
        # on the authenticated, weight-store-backed fabric
        assert wan["sigkills"] == 1 and wan["partitions"] == 1, wan
        assert wan["failover_requests"] >= 1, wan
        assert wan["respawns"] >= 1, wan
        assert wan["weights_published"] is True, wan
        assert wan["ledger_green"] is True, wan
        # the trust boundary: a raw unauthenticated client got a
        # typed refusal and the rejection was counted
        assert wan["unauth_client_rejected"] is True, wan
        assert wan["auth_failures"] >= 1, wan
    if script == "bench_serving_engine.py --tensor-parallel":
        tlines = [l for l in r.stdout.splitlines()
                  if l.startswith("TP_SERVING ")]
        assert tlines, r.stdout
        tps = json.loads(tlines[-1][len("TP_SERVING "):])
        assert TP_SERVING_KEYS <= set(tps), sorted(tps)
        # ISSUE-9 acceptance bars, deterministic on the burst trace:
        # identity across all three flavors, compile-once per mesh
        # shape, handoff installs bounded by the prefill bucket set
        assert tps["token_identical"] is True, tps
        assert tps["decode_compiles_tp"] == 1, tps
        assert tps["decode_compiles_disagg"] == 1, tps
        assert tps["tp"] == 2 and tps["kv_shards"] == 2, tps
        assert 1 <= tps["install_shapes"] <= 5, tps
        assert tps["install_compiles"] == tps["install_shapes"], tps
        assert tps["tokens_per_s_tp"] > 0, tps
        assert tps["tokens_per_s_disagg"] > 0, tps
    if script == "chaos_soak.py":
        # the soak summary line is the artifact the CI budgeted run
        # keys on: every episode green, schema stable
        slines = [l for l in r.stdout.splitlines()
                  if l.startswith("CHAOS_SOAK ")]
        assert slines, r.stdout
        soak = json.loads(slines[-1][len("CHAOS_SOAK "):])
        assert {"episodes", "green", "red_seeds", "faults_fired",
                "recoveries", "relaunches", "cluster_episodes",
                "respawns"} <= set(soak)
        assert soak["episodes"] == 6 and soak["green"] == 6
        assert soak["red_seeds"] == []


def test_trainstep_amp_o2_master_weights_finite():
    """bf16-decorated AdamW through TrainStep must not NaN: the
    warm-init previously ran the update at _step_count=0 (bias
    correction 1-beta^0 == 0) and stored NaN master weights."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.jit.functional import TrainStep

    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 16),
                               paddle.nn.ReLU(),
                               paddle.nn.Linear(16, 2))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=net.parameters())
    net, opt = paddle.amp.decorate(models=net, optimizers=opt,
                                   level="O2", dtype="bfloat16")
    step = TrainStep(net, opt, paddle.nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.rand(4, 8).astype(np.float32))
    y = paddle.to_tensor(np.array([0, 1, 0, 1]))
    losses = []
    for _ in range(6):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            losses.append(float(step(x, y).numpy()))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0]
    for _, p in net.named_parameters():
        assert bool(jnp.isfinite(p._data).all())
    for slots in opt._accumulators.values():
        for name, arr in slots.items():
            assert bool(jnp.isfinite(arr).all()), name


def test_trainstep_preserves_nonzero_slot_inits():
    """Warm-init must not overwrite optimizer-defined slot inits (NAdam
    mu_prod starts at 1, Rprop step_size at lr, Adagrad moment at the
    initial accumulator value)."""
    import paddle_tpu as paddle

    def first_slots(opt_cls, **kw):
        from paddle_tpu.jit.functional import TrainStep
        paddle.seed(0)
        net = paddle.nn.Linear(4, 2)
        opt = opt_cls(parameters=net.parameters(), **kw)
        step = TrainStep(net, opt, paddle.nn.CrossEntropyLoss())
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        y = paddle.to_tensor(np.array([0, 1]))
        l0 = float(step(x, y).numpy())
        for _ in range(4):
            l1 = float(step(x, y).numpy())
        assert np.isfinite(l1) and l1 < l0, (opt_cls.__name__, l0, l1)
        return opt

    opt = first_slots(paddle.optimizer.NAdam, learning_rate=0.05)
    for slots in opt._accumulators.values():
        assert float(np.asarray(slots["mu_prod"])) > 0  # never zeroed
    first_slots(paddle.optimizer.Rprop, learning_rate=0.01)
    first_slots(paddle.optimizer.Adagrad, learning_rate=0.1,
                initial_accumulator_value=0.5)
