"""Cross-process serving cluster (paddle_tpu/serving/cluster.py +
worker.py): RemoteReplica proxies over real worker subprocesses behind
the unchanged ReplicaRouter. Covers the greedy token-identity band
(cluster vs in-process engine vs generate()), worker SIGKILL landing
MID-paged-prefill with clean failover and no page leaks in the
survivors, the typed respawn-budget exhaustion, the stalled-worker
probe contract (slow is SUSPECT, not DEAD), and the framing layer's
wire-fault regression (typed ConnectionError, never a partial-frame
hang). Everything here needs the native TCPStore extension for worker
rendezvous — skipped, not silently green, where it can't build."""
import os
import pickle
import signal
import socket
import struct
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.store import get_lib
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import (ClusterTelemetry, FlightRecorder,
                                      MetricRegistry)
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.train_loop import RestartLimitExceeded
from paddle_tpu.serving import ClusterSupervisor, ServingEngine

pytestmark = [
    pytest.mark.skipif(get_lib() is None,
                       reason="native TCPStore extension unavailable"),
    pytest.mark.usefixtures("worker_compile_cache")]

MODEL_KW = dict(num_hidden_layers=1, hidden_size=32,
                intermediate_size=64, num_attention_heads=2,
                max_position_embeddings=64)
ENGINE_KW = dict(max_slots=2, max_len=64, min_bucket=8)
SPEC = {"tiny": True, "model_seed": 0, "model_config": MODEL_KW,
        "engine": ENGINE_KW}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_counts()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def cluster():
    """One warm 2-worker pool for the whole module: each test re-arms
    it with new_episode() (a reset RPC per worker) instead of paying a
    process spawn per test."""
    sup = ClusterSupervisor(SPEC, n_workers=2, max_respawns=4,
                            registry=MetricRegistry(),
                            flight_recorder=FlightRecorder(capacity=16),
                            dump_on_death=False,
                            telemetry=ClusterTelemetry(),
                            scrape_interval=1)
    sup.start()
    yield sup
    sup.shutdown()


@pytest.fixture(scope="module")
def ref_model():
    """The same model the workers build: same seed, same config —
    the precondition for token identity across the process border."""
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny_config(**MODEL_KW))
    model.eval()
    return model


def _prompts(rng, lens, vocab=96):
    return [rng.randint(1, vocab, (n,)).astype(np.int64) for n in lens]


def _drive(sup, router):
    done = []
    while router.has_work():
        done.extend(router.step())
        sup.poll()
    return done


# -- token identity across the process border --------------------------

IDENTITY_SEEDS = list(range(25))


@pytest.mark.parametrize("seed", IDENTITY_SEEDS)
def test_cluster_identity_band(seed, cluster, ref_model):
    """ISSUE-11 acceptance bar: >= 25 seeded workloads where the
    cluster's greedy outputs are bit-identical to an in-process engine
    run of the same prompts — same model weights, different batching,
    different process."""
    rng = np.random.RandomState(1000 + seed)
    prompts = _prompts(rng, rng.randint(3, 15,
                                        size=int(rng.randint(2, 5))))
    max_new = [int(rng.randint(3, 8)) for _ in prompts]

    eng = ServingEngine(ref_model, registry=MetricRegistry(),
                        **ENGINE_KW)
    refs = [eng.submit(p, mn) for p, mn in zip(prompts, max_new)]
    eng.run()

    router = cluster.new_episode(ENGINE_KW)
    reqs = [router.submit(p, mn) for p, mn in zip(prompts, max_new)]
    _drive(cluster, router)
    for req, ref in zip(reqs, refs):
        assert req.output_ids == ref.output_ids
        assert req.finish_reason == ref.finish_reason


def test_cluster_matches_generate_bs1(cluster, ref_model):
    """The third leg of the identity triangle: cluster outputs equal
    the model's own bs=1 generate() tokens."""
    rng = np.random.RandomState(7)
    prompts = _prompts(rng, [5, 9, 13])
    router = cluster.new_episode(ENGINE_KW)
    reqs = [router.submit(p, 6) for p in prompts]
    _drive(cluster, router)
    for p, req in zip(prompts, reqs):
        ref = ref_model.generate(paddle.to_tensor(p[None]),
                                 max_new_tokens=6).numpy()[0, len(p):]
        assert req.output_ids == list(ref)


# -- real process death mid-paged-prefill ------------------------------

def test_worker_sigkill_mid_paged_prefill(cluster, ref_model):
    """A worker armed to SIGKILL ITSELF inside the paged-prefill fault
    point dies with pages claimed and the program not yet run. The
    router must fail its requests over with token identity intact, the
    supervisor must respawn the slot, and no survivor may leak a page
    (asserted IN the workers via the audit RPC — the host-side mirror
    cannot see the device pools)."""
    kw = dict(ENGINE_KW, page_size=8, num_pages=24)
    rng = np.random.RandomState(11)
    prompts = _prompts(rng, [9, 12, 10, 14])

    eng = ServingEngine(ref_model, registry=MetricRegistry(), **kw)
    refs = [eng.submit(p, 6) for p in prompts]
    eng.run()

    router = cluster.new_episode(kw)
    fail0 = int(router._m_failover.value)
    cluster.workers[0].client.arm_fault("serving.prefill.paged",
                                        times=1, kill=True)
    victim_pid = cluster.workers[0].pid
    reqs = [router.submit(p, 6) for p in prompts]
    _drive(cluster, router)

    for req, ref in zip(reqs, refs):
        assert req.finish_reason == ref.finish_reason
        assert req.output_ids == ref.output_ids
    # the kill was real: new pid in slot 0, a failover, a respawn
    assert int(router._m_failover.value) == fail0 + 1
    assert cluster.respawns_used >= 1
    assert cluster.workers[0].pid != victim_pid
    for slot in cluster.workers:
        assert slot.client.remote_audit() == []


# -- slow is not dead (the probe-timeout bugfix) -----------------------

def test_stalled_worker_is_suspect_not_dead(cluster):
    """A worker that answers — slowly — must be classified SUSPECT by
    the probe timeout and recover to HEALTHY once it speeds up. The
    pre-fix behavior (any probe exception → instant DEAD + failover)
    would kill a merely-overloaded worker and pay a pointless replay."""
    router = cluster.new_episode(ENGINE_KW)
    fail0 = int(router._m_failover.value)
    rng = np.random.RandomState(3)
    reqs = [router.submit(p, 4) for p in _prompts(rng, [4, 6])]
    router.step()                        # both replicas carry work
    rep0 = router.replicas[0]
    cluster.workers[0].client.stall(1.5)  # > probe_timeout_s=1.0
    router.step()                        # probe times out -> SUSPECT
    assert rep0.state == "suspect"
    assert rep0.probe_failures == 1
    # un-stall (this response itself is served at stalled speed)
    cluster.workers[0].client.stall(0.0, deadline=15.0)
    _drive(cluster, router)
    assert rep0.state == "healthy"       # clean probe resets SUSPECT
    assert rep0.probe_failures == 0
    assert int(router._m_failover.value) == fail0   # nobody failed over
    assert all(r.finish_reason == "length" for r in reqs)


# -- respawn budget is a typed contract --------------------------------

def test_respawn_exhaustion_is_typed(cluster):
    """Worker deaths beyond max_respawns raise RestartLimitExceeded
    from poll() — the operator hears 'this cluster is flapping' as a
    typed error, not as an infinite respawn loop."""
    router = cluster.new_episode(ENGINE_KW)
    budget = cluster.max_respawns
    cluster.max_respawns = 0
    try:
        os.kill(cluster.workers[0].pid, signal.SIGKILL)
        router.step()                    # probe -> ReplicaDead -> DEAD
        assert router.replicas[0].state == "dead"
        with pytest.raises(RestartLimitExceeded):
            cluster.poll()
    finally:
        cluster.max_respawns = budget
    # the dead slot stays fenced; the next episode respawns it
    # budget-free and the cluster is whole again
    router = cluster.new_episode(ENGINE_KW)
    assert all(s.alive() for s in cluster.workers)
    rng = np.random.RandomState(5)
    req = router.submit(_prompts(rng, [6])[0], 3)
    _drive(cluster, router)
    assert req.finish_reason == "length"


# -- framing-layer wire faults (no cluster needed) ---------------------

def test_framing_faults_are_typed_and_prompt():
    """The cluster.rpc.* fault points re-type ANY armed exception as
    ConnectionError at the framing layer — a network fault IS a broken
    connection — and a fault landing mid-frame (header consumed, body
    in flight) must raise, never resynchronize on a stale frame."""
    from paddle_tpu.distributed._framing import recv_msg, send_msg
    a, b = socket.socketpair()
    try:
        faults.inject("cluster.rpc.send", times=1)
        with pytest.raises(ConnectionError):
            send_msg(a, b"payload")
        send_msg(a, b"payload")          # next frame goes through
        assert recv_msg(b) == b"payload"
        # recv-side fault fires AFTER the header is consumed — the
        # worst spot: the body is already in the socket buffer
        send_msg(a, b"stale-frame-body")
        faults.inject("cluster.rpc.recv", times=1)
        with pytest.raises(ConnectionError):
            recv_msg(b)
    finally:
        a.close()
        b.close()


def test_framing_peer_close_mid_frame_raises():
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack("<Q", 64) + b"short")   # 64 promised
        a.close()
        from paddle_tpu.distributed._framing import recv_msg
        with pytest.raises(ConnectionError):
            recv_msg(b)                  # EOF mid-frame: typed, no hang
    finally:
        b.close()


# -- ISSUE-18: the cross-host trust boundary ---------------------------
# Authenticated framing must reject — typed, counted, never a hang or
# a desync — every malformed thing a hostile or broken peer can put on
# the wire: oversized length prefixes, truncated frames, tampered
# MACs, replayed frames, and clients that skip or fail the handshake.

def test_framing_rejects_oversized_length_prefix():
    """A corrupt or hostile header must not drive recv into a near-
    2^64 allocation: the length prefix is bounded BEFORE the body is
    read."""
    from paddle_tpu.distributed._framing import MAX_FRAME_BYTES, recv_msg
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack("<Q", MAX_FRAME_BYTES + 1))
        with pytest.raises(ConnectionError, match="MAX_FRAME_BYTES"):
            recv_msg(b)
    finally:
        a.close()
        b.close()


def _auth_pair():
    from paddle_tpu.distributed._framing import FrameAuth
    key = bytes(range(32))
    return FrameAuth(key, key), FrameAuth(key, key)


def test_framing_auth_rejects_truncated_and_tampered_frames():
    from paddle_tpu.distributed import _framing as fr
    tx, rx = _auth_pair()
    before = fr.auth_failures()
    a, b = socket.socketpair()
    try:
        # truncated: a frame shorter than its MAC (e.g. a peer that
        # never sealed it) is an auth rejection, not an index error
        fr.send_msg(a, b"xy")
        with pytest.raises(fr.AuthError, match="shorter than its MAC"):
            fr.recv_msg(b, auth=rx)
        # tampered: one flipped bit anywhere in MAC or payload
        frame = tx.seal_frame(b"payload")
        frame = bytes([frame[0] ^ 0xFF]) + frame[1:]
        a.sendall(struct.pack("<Q", len(frame)) + frame)
        with pytest.raises(fr.AuthError, match="bad frame MAC"):
            fr.recv_msg(b, auth=rx)
    finally:
        a.close()
        b.close()
    assert fr.auth_failures() >= before + 2    # every rejection counted


def test_framing_auth_rejects_replayed_frames():
    """The per-direction counter is mixed into every MAC: the same
    sealed bytes are valid exactly once, so capture-and-replay fails
    verification even though the MAC was once good."""
    from paddle_tpu.distributed import _framing as fr
    tx, rx = _auth_pair()
    a, b = socket.socketpair()
    try:
        frame = tx.seal_frame(b"hello")
        raw = struct.pack("<Q", len(frame)) + frame
        a.sendall(raw)
        assert fr.recv_msg(b, auth=rx) == b"hello"
        a.sendall(raw)                       # verbatim replay
        with pytest.raises(fr.AuthError, match="replayed"):
            fr.recv_msg(b, auth=rx)
    finally:
        a.close()
        b.close()


def test_handshake_rejects_unauthenticated_and_wrong_secret_peers():
    from paddle_tpu.distributed import _framing as fr
    before = fr.auth_failures()
    # an unauthenticated client: speaks pickled RPC where the hello
    # belongs (the pre-fabric wire format)
    a, b = socket.socketpair()
    try:
        fr.send_msg(a, pickle.dumps({"op": "step"}))
        with pytest.raises(fr.AuthError, match="unauthenticated"):
            fr.server_handshake(b, b"right-secret")
    finally:
        a.close()
        b.close()
    # a wrong-secret client: correctly-shaped hello, wrong MAC
    a, b = socket.socketpair()
    client_err = []

    def dial():
        try:
            fr.client_handshake(a, b"wrong-secret")
        except ConnectionError as e:
            client_err.append(e)

    t = threading.Thread(target=dial)
    t.start()
    try:
        with pytest.raises(fr.AuthError,
                           match="failed the shared-secret"):
            fr.server_handshake(b, b"right-secret")
    finally:
        b.close()
        # join BEFORE closing the dialer's own socket: closing it under
        # a blocked recv raises EBADF there instead of the typed error
        t.join(timeout=10)
        a.close()
    assert client_err                        # the dialer got a typed
    assert fr.auth_failures() >= before + 2  # refusal too, all counted


def test_unauthenticated_client_rejected_by_real_worker(cluster):
    """ISSUE-18 acceptance bar, end to end: a raw client that dials a
    REAL worker's RPC port and speaks pickled RPC without the
    handshake gets a typed refusal (connection dropped, no reply
    bytes, no unpickling on the worker), the worker's auth-failure
    counter ticks, and the worker keeps serving authenticated
    clients."""
    from paddle_tpu.distributed._framing import recv_msg, send_msg
    cluster.new_episode(ENGINE_KW)
    w = cluster.workers[0]
    base = int(w.client.probe().get("auth_failures", 0))
    # the worker serves one connection at a time: release the
    # supervisor's persistent one so the accept loop reaches ours
    w.client._close_sock()
    s = socket.create_connection((w.host, w.port), timeout=10)
    s.settimeout(10)
    try:
        send_msg(s, pickle.dumps({"op": "probe"}))
        with pytest.raises(ConnectionError):
            recv_msg(s)          # refusal, not a probe response
    finally:
        s.close()
    health = w.client.probe()    # the worker is still serving
    assert int(health.get("auth_failures", 0)) >= base + 1


# -- ISSUE-13: distributed tracing + cluster telemetry acceptance ------

def test_merged_trace_after_real_sigkill(cluster, ref_model):
    """THE acceptance artifact: a real SIGKILL + failover episode
    yields ONE merged chrome-trace containing the router's lane and
    engine spans from >= 2 distinct worker pids, with the re-homed
    request's two worker lanes linked through the host-side
    ``router.failover.rehome`` span (flow arrows in the trace)."""
    from paddle_tpu.resilience.invariants import timeline_violations
    rng = np.random.RandomState(23)
    prompts = _prompts(rng, [9, 12, 10, 14])
    router = cluster.new_episode(ENGINE_KW)
    tel = cluster.telemetry
    # let the victim decode a few steps first (its spans get scraped
    # by the per-step poll), THEN die mid-decode: the merged trace
    # holds the request's PRE-death lane on the old pid
    cluster.workers[0].client.arm_fault("serving.step.decode",
                                        times=1, after=3, kill=True)
    victim_pid = cluster.workers[0].pid
    reqs = [router.submit(p, 8) for p in prompts]
    _drive(cluster, router)
    cluster.scrape_all()
    assert all(r.finish_reason == "length" for r in reqs)
    assert cluster.workers[0].pid != victim_pid      # kill was real

    spans = tel.aligned_spans()
    all_pids = {int(s["pid"]) for s in spans}
    worker_pids = {int(s["pid"]) for s in spans
                   if s.get("proc") not in ("router", "frontdoor",
                                            "supervisor")}
    assert os.getpid() in all_pids           # the router's own lane
    assert victim_pid in worker_pids         # pre-death spans survive
    assert len(worker_pids) >= 2             # ... next to the peer's
    rehomed = [s for s in spans
               if s["name"] == "router.failover.rehome"
               and s.get("attrs", {}).get("to_replica")]
    assert rehomed                           # host-side, lossless
    rids = {s["attrs"]["request_id"] for s in rehomed}
    assert rids <= {r.rid for r in reqs}

    ct = tel.chrome_trace()
    flows = [e for e in ct["traceEvents"] if e.get("ph") in
             ("s", "t", "f")]
    assert flows                             # lanes ARE linked
    flow_tids = {e["tid"] for e in flows}
    assert flow_tids & rids                  # ... on the re-homed lane
    # every flow id resolves to a start/step/end triple
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], set()).add(e["ph"])
    assert all(phs == {"s", "t", "f"} for phs in by_id.values())
    # the law: complete timeline per delivered request, or the loss
    # (the victim's un-scraped dying step) explicitly DETECTED
    assert timeline_violations(tel, reqs) == []


def test_cluster_metrics_merge_is_sum_never_average(cluster):
    """The cluster exposition is the SUM of the per-worker snapshots:
    counters added, histograms merged bucket-by-bucket (never averaged
    percentiles), gauges labeled by worker instead of collapsed."""
    router = cluster.new_episode(ENGINE_KW)
    tel = cluster.telemetry
    rng = np.random.RandomState(31)
    reqs = [router.submit(p, 4) for p in _prompts(rng, [5, 8, 6])]
    _drive(cluster, router)
    cluster.scrape_all()
    assert all(r.finish_reason == "length" for r in reqs)

    snaps = tel.worker_snapshots()
    assert set(snaps) == {s.slot_label for s in cluster.workers}
    merged = tel.merged_snapshot()

    # counters: merged total == sum over workers, exactly
    per_worker = [snaps[w]["metrics"].get("ptpu_serving_prefills_total")
                  for w in snaps]
    per_worker = [f for f in per_worker if f]
    assert per_worker                        # the episode did prefills
    want = sum(s["value"] for f in per_worker for s in f["samples"])
    got_total = sum(
        merged["ptpu_serving_prefills_total"]["samples"].values())
    assert got_total == want
    assert want > 0

    # histograms: bucket counts added bucket-by-bucket
    hists = [snaps[w]["metrics"].get("ptpu_serving_step_seconds")
             for w in snaps]
    hists = [f for f in hists if f]
    assert hists
    got = merged["ptpu_serving_step_seconds"]["samples"][()]
    for le in got["buckets"]:
        assert got["buckets"][le] == sum(
            f["samples"][0]["buckets"][le] for f in hists)
    assert got["count"] == sum(f["samples"][0]["count"] for f in hists)

    # gauges: one sample per worker, disambiguated by a worker label
    g = merged["ptpu_serving_queue_depth"]
    assert g["label_names"][-1] == "worker"
    workers_seen = {key[-1] for key in g["samples"]}
    assert workers_seen == set(snaps)

    # the rendered exposition agrees with the merged snapshot
    text = tel.merged_prometheus()
    assert "ptpu_serving_prefills_total" in text
    assert 'worker="' in text


def test_dropped_scrape_is_detected_not_truncated(cluster):
    """A telemetry scrape that dies on the wire must surface as a
    RECORDED loss — never a silently truncated timeline. (The armed
    wire fault outlives the retry budget, so the scrape RPC fails for
    real against a live worker.)"""
    router = cluster.new_episode(ENGINE_KW)
    tel = cluster.telemetry
    rng = np.random.RandomState(37)
    req = router.submit(_prompts(rng, [6])[0], 3)
    _drive(cluster, router)
    assert req.finish_reason == "length"
    assert tel.scrape_losses() == []         # clean so far
    faults.inject("cluster.rpc.send", times=8)   # > retry budget
    cluster.scrape_all()
    faults.clear()
    losses = tel.scrape_losses()
    assert losses and any(l["kind"] == "scrape_failed" for l in losses)
    # detection degrades the law instead of inventing violations
    from paddle_tpu.resilience.invariants import timeline_violations
    assert timeline_violations(tel, [req]) == []
    # the pool heals for the next test: dead-marked clients respawn
    router = cluster.new_episode(ENGINE_KW)
    req2 = router.submit(_prompts(rng, [5])[0], 2)
    _drive(cluster, router)
    assert req2.finish_reason == "length"


# -- control-plane scaling machinery (ISSUE 20) ------------------------

def test_cluster_scale_up_then_down(ref_model):
    """The autoscaler's cluster seams: ``scale_up`` spawns a real
    worker process and registers it with the RUNNING router as a
    first-class replica (token-identical service through it),
    ``scale_down`` drains one and shuts its process down — and never
    drains the last dispatchable worker. A private 1-worker pool: the
    module's warm fixture must not lose workers to this test."""
    sup = ClusterSupervisor(SPEC, n_workers=1, max_respawns=2,
                            registry=MetricRegistry(),
                            flight_recorder=FlightRecorder(capacity=16),
                            dump_on_death=False,
                            telemetry=ClusterTelemetry(),
                            scrape_interval=1)
    sup.start()
    try:
        router = sup.router
        assert sup.scale_down() is None      # never the last worker
        rep = sup.scale_up()
        assert rep.dispatchable
        assert sum(1 for r in router.replicas
                   if r.dispatchable) == 2
        rng = np.random.RandomState(5)
        prompts = _prompts(rng, [5, 9, 7])
        reqs = [router.submit(p, 5) for p in prompts]
        _drive(sup, router)
        eng = ServingEngine(ref_model, registry=MetricRegistry(),
                            **ENGINE_KW)
        refs = [eng.submit(p, 5) for p in prompts]
        eng.run()
        for req, ref in zip(reqs, refs):
            assert req.output_ids == ref.output_ids
            assert req.finish_reason == ref.finish_reason
        rid = sup.scale_down()
        assert rid == rep.id
        assert sum(1 for r in router.replicas
                   if r.dispatchable) == 1
        # the shrunk pool still serves
        reqs2 = [router.submit(p, 3) for p in prompts[:2]]
        _drive(sup, router)
        for req in reqs2:
            assert req.finish_reason == "length"
    finally:
        sup.shutdown()
