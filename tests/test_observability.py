"""Observability layer (paddle_tpu/observability): metrics registry
(thread-safety, label cardinality guard, Prometheus exposition
round-trip), request-correlated spans in chrome traces, the crash
flight recorder (ring bound + dump-on-exception in a serving run),
jit capture telemetry's public snapshot/reset API, queue-wait
accounting, and the watchdog's gauge/counter/dump hooks — all on
injected clocks, no sleeps."""
import json
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import (FlightRecorder, MetricError,
                                      MetricRegistry, default_registry,
                                      span)


# -- registry units ----------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricRegistry(time_fn=lambda: 123.0)
    c = reg.counter("ptpu_t_events_total", "events")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(MetricError):
        c.inc(-1)
    g = reg.gauge("ptpu_t_depth", "depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0
    assert reg.to_json()["ts"] == 123.0       # injectable clock
    # get-or-create returns the SAME family; schema mismatch raises
    assert reg.counter("ptpu_t_events_total") is c
    with pytest.raises(MetricError):
        reg.gauge("ptpu_t_events_total")
    with pytest.raises(MetricError):
        reg.counter("ptpu_t_events_total", labels=("x",))
    with pytest.raises(MetricError):
        reg.counter("bad name!")


def test_labels_and_cardinality_guard():
    reg = MetricRegistry(max_label_sets=3)
    c = reg.counter("ptpu_t_breaks_total", "b", labels=("reason",))
    for r in ("a", "b", "c"):
        c.labels(reason=r).inc()
    assert c.labels(reason="a").value == 1.0   # existing set: no growth
    with pytest.raises(MetricError, match="cardinality"):
        c.labels(reason="d")
    with pytest.raises(MetricError):           # wrong label names
        c.labels(nope="x")
    with pytest.raises(MetricError):           # unlabeled use of labeled
        c.inc()


def test_histogram_buckets_and_percentile():
    reg = MetricRegistry()
    h = reg.histogram("ptpu_t_lat_seconds", "lat",
                      buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.count == 5
    assert abs(h.sum - 5.56) < 1e-9
    # p50 falls in the (0.01, 0.1] bucket; interpolated estimate
    assert 0.01 < h.percentile(50) <= 0.1
    assert h.percentile(99) >= 1.0             # open +Inf tail clamps


def test_nan_values_do_not_break_exposition():
    reg = MetricRegistry()
    h = reg.histogram("ptpu_t_nan_seconds", "n", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(float("nan"))
    g = reg.gauge("ptpu_t_nan_g")
    g.set(float("nan"))
    text = reg.to_prometheus()          # must not raise
    assert "ptpu_t_nan_g NaN" in text
    # the NaN parks in +Inf so bucket sums stay consistent with _count
    assert 'ptpu_t_nan_seconds_bucket{le="+Inf"} 2' in text
    assert h.count == 2
    reg.to_json()                       # must not raise either


def test_histogram_bucket_schema_conflict():
    reg = MetricRegistry()
    h = reg.histogram("ptpu_t_b_seconds", "b", buckets=(0.1, 1.0))
    # get-or-create without explicit buckets: same family
    assert reg.histogram("ptpu_t_b_seconds") is h
    assert reg.histogram("ptpu_t_b_seconds",
                         buckets=(1.0, 0.1)) is h    # order-insensitive
    with pytest.raises(MetricError, match="buckets"):
        reg.histogram("ptpu_t_b_seconds", buckets=(0.5,))


def test_concurrent_increments_exact():
    reg = MetricRegistry()
    c = reg.counter("ptpu_t_conc_total", "c", labels=("w",))
    h = reg.histogram("ptpu_t_conc_seconds", "h")
    N, T = 1000, 8

    def work(w):
        for _ in range(N):
            c.labels(w=w % 2).inc()
            h.observe(0.01)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(T)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.labels(w=0).value + c.labels(w=1).value == N * T
    assert h.count == N * T


def _parse_prom(text):
    """Minimal exposition-format parser: {sample_name{labels} -> float},
    plus the # TYPE map."""
    types, samples = {}, {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            types[name] = kind
        elif line and not line.startswith("#"):
            key, val = line.rsplit(" ", 1)
            samples[key] = float(val)
    return types, samples


def test_prometheus_exposition_round_trip():
    reg = MetricRegistry()
    c = reg.counter("ptpu_t_req_total", "requests", labels=("kind",))
    c.labels(kind="a").inc(3)
    c.labels(kind='we"ird\n').inc()            # label escaping
    g = reg.gauge("ptpu_t_occ", "occupancy")
    g.set(0.75)
    h = reg.histogram("ptpu_t_wait_seconds", "wait",
                      buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(50.0)
    types, samples = _parse_prom(reg.to_prometheus())
    assert types == {"ptpu_t_req_total": "counter",
                     "ptpu_t_occ": "gauge",
                     "ptpu_t_wait_seconds": "histogram"}
    assert samples['ptpu_t_req_total{kind="a"}'] == 3
    assert samples['ptpu_t_req_total{kind="we\\"ird\\n"}'] == 1
    assert samples["ptpu_t_occ"] == 0.75
    # histogram buckets are CUMULATIVE and end at +Inf == _count
    assert samples['ptpu_t_wait_seconds_bucket{le="0.1"}'] == 1
    assert samples['ptpu_t_wait_seconds_bucket{le="1"}'] == 2
    assert samples['ptpu_t_wait_seconds_bucket{le="+Inf"}'] == 3
    assert samples["ptpu_t_wait_seconds_count"] == 3
    assert abs(samples["ptpu_t_wait_seconds_sum"] - 50.55) < 1e-9
    # JSON exporter agrees
    js = reg.to_json()["metrics"]["ptpu_t_wait_seconds"]
    assert js["samples"][0]["buckets"]["+Inf"] == 3
    # reset zeroes values but keeps families AND label sets
    reg.reset()
    assert c.labels(kind="a").value == 0
    _, samples = _parse_prom(reg.to_prometheus())
    assert samples['ptpu_t_req_total{kind="a"}'] == 0


# -- spans -> chrome trace ---------------------------------------------

def test_span_request_id_in_chrome_trace(tmp_path):
    from paddle_tpu import profiler
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    with span("t.request", request_id=42, bucket=16) as sp:
        sp.set_attr("tokens", 3)
    prof.stop()
    path = str(tmp_path / "trace.json")
    prof.export_chrome_tracing(path)
    evs = [e for e in json.load(open(path))["traceEvents"]
           if e["name"] == "t.request"]
    assert evs and evs[-1]["args"] == {
        "request_id": 42, "bucket": 16, "tokens": 3}


def test_recording_flag_is_process_wide(tmp_path):
    """Satellite: Profiler.start() in the main thread must make
    RecordEvents from WORKER threads visible (was threading.local —
    worker-thread events were silently dropped)."""
    from paddle_tpu import profiler
    prof = profiler.Profiler(timer_only=True)
    prof.start()

    def worker():
        with profiler.RecordEvent("t.worker_side"):
            pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    prof.stop()
    path = str(tmp_path / "trace.json")
    prof.export_chrome_tracing(path)
    names = [e["name"] for e in json.load(open(path))["traceEvents"]]
    assert "t.worker_side" in names


def test_profiler_export_metrics(tmp_path):
    from paddle_tpu import profiler
    default_registry().counter("ptpu_t_export_total", "x").inc()
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    prof.stop()
    p1 = str(tmp_path / "m.prom")
    text = prof.export_metrics(p1)
    assert "ptpu_t_export_total" in text
    assert text == open(p1).read()
    handler = profiler.export_metrics(str(tmp_path), worker_name="w0")
    handler(prof)
    assert "ptpu_t_export_total" in open(tmp_path / "w0.prom").read()


# -- flight recorder ---------------------------------------------------

def test_flight_recorder_ring_and_dump(tmp_path):
    clock = {"t": 0.0}
    fr = FlightRecorder(capacity=4, time_fn=lambda: clock["t"],
                        dump_dir=str(tmp_path))
    for i in range(7):
        clock["t"] = float(i)
        fr.record("step", step=i)
    snap = fr.snapshot()
    assert len(snap) == 4 and len(fr) == 4          # ring bound
    assert [r["step"] for r in snap] == [3, 4, 5, 6]  # oldest->newest
    assert [r["seq"] for r in snap] == [3, 4, 5, 6]
    assert snap[-1]["t"] == 6.0                     # injected clock
    path = fr.dump(reason="test dump")
    payload = json.load(open(path))
    assert payload["reason"] == "test dump"
    assert [r["step"] for r in payload["records"]] == [3, 4, 5, 6]
    assert "metrics" in payload                     # registry snapshot
    fr.clear()
    assert len(fr) == 0


def test_flight_recorder_excepthook(tmp_path, capsys):
    import sys
    fr = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
    fr.record("step", step=0)
    prev = sys.excepthook
    fr.install_excepthook()
    try:
        # simulate an unhandled exception reaching the installed hook
        try:
            raise RuntimeError("boom")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("ptpu_flight_")]
        assert len(dumps) == 1
        payload = json.load(open(tmp_path / dumps[0]))
        assert "boom" in payload["reason"]
        assert payload["records"][0]["kind"] == "step"
    finally:
        fr.uninstall_excepthook()
    assert sys.excepthook is prev
    capsys.readouterr()        # swallow the chained traceback print


# -- jit capture telemetry (satellite: public snapshot/reset) ----------

def test_capture_telemetry_snapshot_reset():
    from paddle_tpu import jit
    jit.reset_capture_report()

    @paddle.jit.to_static
    def f(x):
        return x * 2

    x = paddle.to_tensor(np.ones((2, 2), np.float32))
    f(x)
    f(x)

    @paddle.jit.to_static
    def gen(x):          # generator: can never be a graph
        yield x

    list(gen(x))
    snap = jit.capture_telemetry.snapshot()
    assert snap["whole_graph_calls"] >= 2
    assert snap["compile_calls"] >= 1
    assert snap["cache_hit_calls"] >= 1
    assert snap["never_trace_calls"] == 1
    # same counters surface as registry families (no module globals)
    fams = default_registry().families()
    assert "ptpu_jit_whole_graph_calls_total" in fams
    assert "ptpu_jit_never_trace_calls_total" in fams
    # capture_report is an alias of the snapshot
    assert jit.capture_report() == snap
    jit.capture_telemetry.reset()
    z = jit.capture_telemetry.snapshot()
    assert z["whole_graph_calls"] == 0 and z["breaks"] == {}
    assert int(default_registry().get(
        "ptpu_jit_whole_graph_calls_total").value) == 0


def test_graph_break_reason_label_is_normalized():
    from paddle_tpu.jit.static_function import capture_telemetry
    capture_telemetry.reset()
    capture_telemetry.note_break(
        "unguardable arg: TypeError('secret payload 0x1234')")
    capture_telemetry.note_break(
        "unguardable arg: TypeError('other payload 0x9999')")
    snap = capture_telemetry.snapshot()
    assert snap["graph_break_calls"] == 2
    assert len(snap["breaks"]) == 2            # full detail kept
    fam = default_registry().get("ptpu_jit_graph_breaks_total")
    # ONE label set for both (payload stripped -> bounded cardinality)
    assert fam.labels(reason="unguardable arg").value == 2
    capture_telemetry.reset()


# -- serving metrics: queue wait (satellite) ---------------------------

def test_engine_metrics_queue_wait_fake_clock():
    from paddle_tpu.serving.metrics import EngineMetrics
    clock = {"t": 0.0}
    m = EngineMetrics(4, time_fn=lambda: clock["t"],
                      registry=MetricRegistry())
    m.on_submit(0)
    clock["t"] = 5.0                 # queued for 5s
    m.on_first_prefill(0)
    m.on_first_prefill(0)            # idempotent: first prefill only
    clock["t"] = 7.0                 # +2s prefill compute
    m.on_token(0)
    s = m.summary()
    assert s["queue_wait_p50_s"] == 5.0
    assert s["queue_wait_p99_s"] == 5.0
    assert s["ttft_p50_s"] == 7.0    # ttft = queue wait + compute


# -- watchdog gauges/counter/dump hook ---------------------------------

class _FakeStore:
    def __init__(self):
        self._d = {}

    def set(self, k, v):
        self._d[k] = v

    def get(self, k, timeout=None):
        if k not in self._d:
            raise KeyError(k)
        return self._d[k]


def test_watchdog_gauge_counter_and_dump(tmp_path):
    from paddle_tpu.distributed.watchdog import CommWatchdog
    store = _FakeStore()
    reg = MetricRegistry()
    fr = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
    w = CommWatchdog(store, rank=0, world_size=3, timeout=10.0,
                     flight_recorder=fr, registry=reg)
    w.beat()
    store.set("__watchdog__/hb/1", repr(time.time()).encode())
    store.set("__watchdog__/hb/2", repr(time.time() - 100).encode())
    assert w._sweep()                       # rank 2 is stale
    assert reg.get("ptpu_dist_heartbeat_age_seconds")
    assert reg.get(
        "ptpu_dist_heartbeat_age_seconds").labels(rank=1).value < 5
    assert reg.get(
        "ptpu_dist_heartbeat_age_seconds").labels(rank=2).value > 50
    assert reg.get("ptpu_dist_watchdog_failures_total").value == 1
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("ptpu_flight_")]
    assert len(dumps) == 1
    payload = json.load(open(tmp_path / dumps[0]))
    assert "stale" in payload["reason"]
    assert payload["records"][-1]["kind"] == "watchdog.failure"
    # repeat sweep: same failure is not re-counted, not re-dumped
    assert w._sweep()
    assert reg.get("ptpu_dist_watchdog_failures_total").value == 1
    assert len([f for f in os.listdir(tmp_path)
                if f.startswith("ptpu_flight_")]) == 1
    with pytest.raises(RuntimeError, match="stale"):
        w.check()


# -- speculative-decoding gauges (ISSUE-8 satellite) -------------------

def test_speculative_metrics_published():
    """A speculative engine publishes the accepted-length histogram,
    draft/accepted counters and the cumulative draft-hit-rate gauge in
    its registry — consistent with the engine's own spec_stats(), and
    present in the Prometheus exposition."""
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny_config(
        max_position_embeddings=128))
    model.eval()
    reg = MetricRegistry()
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        speculative=True, spec_k=4, registry=reg,
                        flight_recorder=FlightRecorder(capacity=4))
    rng = np.random.RandomState(0)
    pat = np.tile(rng.randint(1, 100, (2,)), 6).astype(np.int64)
    eng.submit(pat, max_new_tokens=16)
    eng.submit(rng.randint(1, 100, (7,)).astype(np.int64),
               max_new_tokens=6)
    eng.run()
    st = eng.spec_stats()
    assert st["rows"] > 0 and st["emitted"] >= st["rows"]
    hist = reg.get("ptpu_serving_spec_accepted_length")
    assert hist.label_names == ("proposer",)   # per-proposer since v19
    children = hist._sorted_children()
    assert sum(c.count for c in children) == st["rows"]
    assert sum(c.sum for c in children) == pytest.approx(st["emitted"])
    # draft totals and the hit rate are spec_stats()'s (PR 26 took the
    # registry copies that nothing but this test read)
    assert st["draft_tokens"] >= st["accepted_draft_tokens"] >= 0
    text = reg.to_prometheus()
    assert "# TYPE ptpu_serving_spec_accepted_length histogram" in text
    assert "ptpu_serving_spec_draft_hit_rate" not in text
    # non-speculative engines do not grow the spec families
    reg2 = MetricRegistry()
    ServingEngine(model, max_slots=1, max_len=64, registry=reg2,
                  flight_recorder=FlightRecorder(capacity=4))
    assert "ptpu_serving_spec_accepted_length" not in reg2.families()


# -- chunked-prefill metrics + spans (ISSUE-14 satellite) --------------

def test_chunked_prefill_metrics_and_spans(tmp_path):
    """A chunked engine publishes the chunk-step counter, the
    chunk-queue-depth gauge and the decode-stall histogram in its
    registry, and its chrome trace carries ``serving.chunk_prefill``
    spans with request ids. Unchunked engines do not grow the chunk
    families."""
    from paddle_tpu import profiler
    from paddle_tpu.serving import ServingEngine

    model = _tiny_llama()
    reg = MetricRegistry()
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        prefill_chunk=8, registry=reg,
                        flight_recorder=FlightRecorder(capacity=4))
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    rng = np.random.RandomState(0)
    # a long prompt chunks; the short request behind it decodes while
    # the chunks run — its first token is a measured decode stall
    long_req = eng.submit(rng.randint(1, 100, (40,)).astype(np.int64),
                          max_new_tokens=4)
    short = eng.submit(rng.randint(1, 100, (5,)).astype(np.int64),
                       max_new_tokens=8)
    while eng.has_work():
        eng.step()
    prof.stop()
    assert long_req.finished and short.finished

    chunk_steps = reg.counter("ptpu_serving_chunk_steps_total").value
    assert chunk_steps >= 5                    # ceil(40/8) chunks
    stall = reg.histogram("ptpu_serving_decode_stall_seconds")
    assert stall.count >= 1                    # the short request
    text = reg.to_prometheus()
    assert "# TYPE ptpu_serving_chunk_steps_total counter" in text
    assert "# TYPE ptpu_serving_decode_stall_seconds histogram" in text

    trace_path = str(tmp_path / "trace.json")
    prof.export_chrome_tracing(trace_path)
    evs = json.load(open(trace_path))["traceEvents"]
    chunks = [e for e in evs if e["name"] == "serving.chunk_prefill"]
    assert len(chunks) == chunk_steps
    # every admission chunks (the short prompt as ONE whole-prompt
    # chunk), and every span carries its request id
    assert {e["args"]["request_id"] for e in chunks} \
        == {long_req.rid, short.rid}
    assert all("chunk" in e["args"] and "pos" in e["args"]
               for e in chunks)
    assert sum(1 for e in chunks if e["args"]["final"]) == 2
    assert sum(1 for e in chunks
               if e["args"]["request_id"] == long_req.rid) >= 5

    # unchunked engines do not grow the chunk families
    reg2 = MetricRegistry()
    ServingEngine(model, max_slots=1, max_len=64, registry=reg2,
                  flight_recorder=FlightRecorder(capacity=4))
    assert not [f for f in reg2.families() if "chunk" in f]


# -- acceptance: one serving run, three artifacts ----------------------

def _tiny_llama():
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny_config(
        max_position_embeddings=128))
    model.eval()
    return model


def test_one_run_three_artifacts(tmp_path):
    """Acceptance criterion: from ONE process — a Prometheus snapshot
    with serving/jit/dataloader families, a chrome trace whose serving
    spans carry request ids, and (when a step raises) a flight-recorder
    dump with the last >= 32 step records. Injected clocks, no
    sleeps."""
    from paddle_tpu import profiler
    from paddle_tpu.serving import ServingEngine

    clock = {"t": 0.0}
    fr = FlightRecorder(capacity=48, time_fn=lambda: clock["t"],
                        dump_dir=str(tmp_path))
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        time_fn=lambda: clock["t"], flight_recorder=fr)
    # virtual timeline: the engine clock ticks exactly 0.01 per step
    # (inside the step, before its end-of-step timestamp), making step
    # latency and TTFT byte-exact assertions below
    orig_on_step = eng.metrics.on_step

    def ticking_on_step(n_active):
        clock["t"] += 0.01
        orig_on_step(n_active)

    eng.metrics.on_step = ticking_on_step

    # jit family activity (families exist from import; touch them)
    @paddle.jit.to_static
    def double(x):
        return x + x

    double(paddle.to_tensor(np.ones((2, 2), np.float32)))

    # dataloader family: one tiny epoch
    class DS(paddle.io.Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            return np.float32([i])

    for _ in paddle.io.DataLoader(DS(), batch_size=4):
        pass

    prof = profiler.Profiler(timer_only=True)
    prof.start()
    rids = [eng.submit(np.arange(1, 6), 40).rid,
            eng.submit(np.arange(1, 10), 40).rid]
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
    prof.stop()
    assert steps >= 39

    # artifact 1: Prometheus snapshot with all three layer families
    prom_path = str(tmp_path / "metrics.prom")
    text = prof.export_metrics(prom_path)
    for fam in ("ptpu_serving_ttft_seconds",
                "ptpu_serving_queue_wait_seconds",
                "ptpu_serving_step_seconds",
                "ptpu_jit_whole_graph_calls_total",
                "ptpu_io_batch_wait_seconds"):
        assert f"# TYPE {fam}" in text, fam
    _, samples = _parse_prom(text)
    assert samples["ptpu_serving_step_seconds_count"] >= steps
    # injected clock: every step advanced exactly 0.01 on the engine
    # clock, so the ttft histogram saw exact values (first token rides
    # the admission step => ttft == one 0.01 tick)
    assert samples["ptpu_serving_ttft_seconds_count"] >= 2

    # artifact 2: chrome trace, serving spans carry request ids
    trace_path = str(tmp_path / "trace.json")
    prof.export_chrome_tracing(trace_path)
    evs = json.load(open(trace_path))["traceEvents"]
    prefills = [e for e in evs if e["name"] == "serving.prefill"]
    assert {e["args"]["request_id"] for e in prefills} >= set(rids)
    decodes = [e for e in evs if e["name"] == "serving.decode"]
    # batch spans carry the batch size; request ids ride along only
    # where requests have trace contexts (cluster workers)
    assert decodes and decodes[0]["args"]["batch"] >= 1
    assert [e for e in evs if e["name"] == "serving.step"]

    # artifact 3: a raising step dumps the flight recorder
    ring_before = len(fr)
    assert ring_before >= 32
    eng.submit(np.arange(1, 4), 4)

    def boom(n):
        raise RuntimeError("injected step failure")

    eng.metrics.on_step = boom
    with pytest.raises(RuntimeError, match="injected step failure"):
        eng.step()
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("ptpu_flight_")]
    assert len(dumps) == 1
    payload = json.load(open(tmp_path / dumps[0]))
    assert "ServingEngine.step" in payload["reason"]
    step_recs = [r for r in payload["records"]
                 if r["kind"] == "serving.step"]
    assert len(step_recs) >= 32
    for r in step_recs:
        assert {"step", "step_latency_s", "active_slots",
                "queue_depth", "admitted", "evicted",
                "compiles"} <= set(r)
    # the virtual clock stamped the records: step latency is exactly
    # one 0.01 tick for every recorded step
    assert all(abs(r["step_latency_s"] - 0.01) < 1e-9
               for r in step_recs)
    assert payload["records"][-1]["kind"] == "serving.step_error"
    # on CPU nothing was donated, so the engine is NOT poisoned: the
    # next step (with the hook restored) runs fine
    eng.metrics.on_step = ticking_on_step
    eng.step()


def test_router_frontdoor_gauges_counters_and_spans(tmp_path):
    """ISSUE-7 observability satellite: serving through the front
    door over a 2-replica router (one replica killed mid-run) leaves
    — in ONE registry next to the existing serving families —
    per-replica health/inflight gauges, per-tenant queue-depth gauges
    and rejected{reason} counters, failover counters; and the chrome
    trace carries router.dispatch spans with request ids plus the
    router.failover span for the death."""
    from paddle_tpu import profiler
    from paddle_tpu.serving import (FrontDoor, ReplicaRouter,
                                    ServingEngine, TenantPolicy,
                                    TenantQueueFull)

    reg = MetricRegistry()
    model = _tiny_llama()
    engines = [ServingEngine(model, max_slots=2, max_len=64,
                             min_bucket=8, registry=reg,
                             flight_recorder=FlightRecorder(capacity=4))
               for _ in range(2)]
    router = ReplicaRouter(engines, registry=reg,
                           flight_recorder=FlightRecorder(capacity=4))
    front = FrontDoor(router, registry=reg,
                      tenants={"cap": TenantPolicy(max_inflight=1)})
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    hs = [front.submit(np.arange(1, 5 + i), 4, tenant="cap" if i == 0
                       else "default") for i in range(4)]
    with pytest.raises(TenantQueueFull):
        front.submit(np.arange(1, 5), 4, tenant="cap")
    for _ in range(2):
        front.pump()
    router.replicas[1].kill()               # death mid-run
    front.run_until_idle()
    prof.stop()
    assert all(h.req.finished for h in hs)

    # per-replica gauges, per-tenant gauge/counters, failover counters
    # — in the SAME exposition as the serving families
    text = reg.to_prometheus()
    _, samples = _parse_prom(text)
    assert samples['ptpu_router_replica_healthy{replica="0"}'] == 1
    assert samples['ptpu_router_replica_healthy{replica="1"}'] == 0
    assert samples['ptpu_router_replica_inflight{replica="0"}'] == 0
    assert samples['ptpu_router_dispatches_total{replica="0"}'] >= 1
    assert samples["ptpu_router_failovers_total"] == 1
    # replica 1 holds in-flight work when killed (2 pumps into 4
    # requests of 4 tokens), so the kill really re-homed requests
    assert samples["ptpu_router_failover_requests_total"] >= 1
    assert samples['ptpu_frontdoor_tenant_depth{tenant="cap"}'] == 0
    assert samples['ptpu_frontdoor_rejected_total'
                   '{reason="tenant_queue_full",tier="0"}'] == 1
    assert samples['ptpu_frontdoor_accepted_total{tenant="cap"}'] == 1
    assert "# TYPE ptpu_serving_step_seconds" in text  # same registry

    # chrome trace: dispatch spans carry request ids; the failover
    # span marks which replica died
    trace_path = str(tmp_path / "trace.json")
    prof.export_chrome_tracing(trace_path)
    evs = json.load(open(trace_path))["traceEvents"]
    dispatches = [e for e in evs if e["name"] == "router.dispatch"]
    assert {e["args"]["request_id"] for e in dispatches} \
        >= {h.req.rid for h in hs}
    assert all("replica" in e["args"] for e in dispatches)
    failovers = [e for e in evs if e["name"] == "router.failover"]
    assert [e["args"]["replica"] for e in failovers] == ["1"]


def test_dump_embeds_the_owning_registry(tmp_path):
    """An engine built on an INJECTED registry must produce crash
    dumps whose metrics section carries that registry's families, not
    the process default's."""
    from paddle_tpu.serving import ServingEngine
    reg = MetricRegistry()
    fr = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
    eng = ServingEngine(_tiny_llama(), max_slots=2, max_len=32,
                        min_bucket=8, registry=reg, flight_recorder=fr)
    eng.submit(np.arange(1, 5), 4)
    eng.metrics.on_step = lambda n: (_ for _ in ()).throw(
        RuntimeError("boom"))
    with pytest.raises(RuntimeError, match="boom"):
        eng.step()
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("ptpu_flight_")]
    payload = json.load(open(tmp_path / dumps[0]))
    assert "ptpu_serving_step_seconds" in payload["metrics"]["metrics"]
    assert payload["metrics"]["metrics"][
        "ptpu_serving_requests_total"]["samples"][0]["value"] == 1


def test_engine_broken_after_donating_step_failure(tmp_path):
    """When the failing step ran with DONATED cache pools (TPU path),
    the pools may reference deleted device buffers — the engine must
    refuse further use with a typed error until recover() rebuilds
    the pools from host-side request state (the full recovery contract
    is pinned in tests/test_serving_engine.py and
    tests/test_resilience.py)."""
    from paddle_tpu.serving import EngineBroken, ServingEngine
    fr = FlightRecorder(capacity=8, dump_dir=str(tmp_path))
    eng = ServingEngine(_tiny_llama(), max_slots=2, max_len=32,
                        min_bucket=8, flight_recorder=fr)
    eng._donate = lambda: (5, 6)           # simulate the TPU donation
    req = eng.submit(np.arange(1, 5), 4)

    def boom(n):
        raise RuntimeError("device OOM mid-step")

    orig_on_step, eng.metrics.on_step = eng.metrics.on_step, boom
    with pytest.raises(RuntimeError, match="device OOM"):
        eng.step()
    with pytest.raises(EngineBroken, match="recover"):
        eng.step()
    with pytest.raises(EngineBroken, match="recover"):
        eng.submit(np.arange(1, 5), 4)
    eng.metrics.on_step = orig_on_step
    eng.recover()
    eng.run()
    assert req.finished and len(req.output_ids) == 4


@pytest.mark.usefixtures("worker_compile_cache")
def test_cluster_metric_families_and_death_dump(tmp_path):
    """ISSUE-11 observability satellite: a cluster run leaves — in ONE
    registry — per-worker liveness/respawn gauges, respawn and kill
    counters, the per-op RPC latency histogram and per-worker inflight
    gauges; and a worker death dumps the flight recorder (the
    post-mortem) with the cluster's death/respawn records aboard."""
    import signal

    from paddle_tpu.distributed.store import get_lib
    if get_lib() is None:
        pytest.skip("native TCPStore extension unavailable")
    from paddle_tpu.serving import ClusterSupervisor

    reg = MetricRegistry()
    fr = FlightRecorder(capacity=32, dump_dir=str(tmp_path))
    sup = ClusterSupervisor(
        {"tiny": True, "model_seed": 0,
         "model_config": dict(num_hidden_layers=1, hidden_size=32,
                              intermediate_size=64,
                              num_attention_heads=2,
                              max_position_embeddings=64),
         "engine": {"max_slots": 2, "max_len": 64, "min_bucket": 8}},
        n_workers=2, max_respawns=2, registry=reg,
        flight_recorder=fr, dump_on_death=True,
        spill_dir=str(tmp_path), spill_every=1)
    try:
        router = sup.start()
        reqs = [router.submit(np.arange(1, 6 + i), 3)
                for i in range(3)]
        while router.has_work():
            router.step()
            sup.poll()
        victim_pid = sup.workers[0].pid
        os.kill(sup.workers[0].pid, signal.SIGKILL)   # a real death
        router.step()            # probe -> ReplicaDead -> failover
        sup.poll()               # reap: dump the post-mortem, respawn
        r2 = router.submit(np.arange(1, 4), 2)
        while router.has_work():
            router.step()
            sup.poll()
        assert all(r.finished for r in reqs) and r2.finished
        text = reg.to_prometheus()   # BEFORE shutdown zeroes liveness
    finally:
        sup.shutdown()

    _, samples = _parse_prom(text)
    assert samples['ptpu_cluster_worker_alive{worker="w0"}'] == 1
    assert samples['ptpu_cluster_worker_alive{worker="w1"}'] == 1
    assert samples['ptpu_cluster_worker_respawns{worker="w0"}'] == 1
    assert samples["ptpu_cluster_respawns_total"] == 1
    assert samples['ptpu_cluster_worker_kills_total'
                   '{kind="exited"}'] == 1
    assert samples['ptpu_cluster_worker_rpc_inflight'
                   '{worker="w0"}'] == 0
    assert samples['ptpu_cluster_rpc_latency_seconds_count'
                   '{op="step"}'] >= 1
    assert samples['ptpu_cluster_rpc_latency_seconds_count'
                   '{op="probe"}'] >= 1
    assert samples["ptpu_router_failovers_total"] == 1

    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("ptpu_flight_")]
    assert len(dumps) == 1       # exactly one death, one post-mortem
    payload = json.load(open(tmp_path / dumps[0]))
    assert "cluster worker" in payload["reason"]
    kinds = [r["kind"] for r in payload["records"]]
    assert "cluster.worker_dead" in kinds
    assert "ptpu_cluster_respawns_total" in payload["metrics"]["metrics"]
    # ISSUE-13: the victim's own last flight spill rides the dump —
    # the post-mortem shows what the WORKER saw, not just the host
    victim = payload["victim_flight"]
    assert victim["pid"] == victim_pid
    assert victim["records"]            # it recorded engine steps


# -- ISSUE-13: flight spill + label-cardinality normalizers ------------

def test_flight_recorder_spill_file(tmp_path):
    """The worker-side flight recorder spills its ring to a well-known
    path every N records (atomic rename, failures swallowed) so a
    SIGKILLed worker still leaves a post-mortem behind."""
    p = tmp_path / f"flight_{os.getpid()}.json"
    fr = FlightRecorder(capacity=8, spill_path=str(p), spill_every=2)
    fr.record("a", i=1)
    assert not p.exists()               # 1 record: not due yet
    fr.record("b", i=2)
    assert p.exists()                   # every 2nd record spills
    payload = json.load(open(p))
    assert payload["pid"] == os.getpid()
    assert [r["kind"] for r in payload["records"]] == ["a", "b"]
    fr.record("c", i=3)
    fr.record("d", i=4)
    payload = json.load(open(p))        # overwritten in place
    assert [r["kind"] for r in payload["records"]] == \
        ["a", "b", "c", "d"]
    # no leftover temp files from the atomic rename
    assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []
    # an unwritable spill path must never take the engine down
    fr2 = FlightRecorder(capacity=4, spill_path="/nonexistent/x.json",
                         spill_every=1)
    fr2.record("still", fine=True)
    assert fr2.spill() is None
    # explicit spill (the SIGTERM path) works without a cadence
    fr3 = FlightRecorder(capacity=4, spill_path=str(tmp_path / "s.json"))
    fr3.record("x")
    assert fr3.spill() == str(tmp_path / "s.json")


def test_rpc_op_label_cardinality_is_bounded():
    """Every RPC latency sample goes through normalize_op: known ops
    pass, anything else collapses to 'other' — a buggy or hostile op
    string can never mint a new Prometheus label value."""
    from paddle_tpu.serving.cluster import _RPC_OPS, normalize_op
    assert "telemetry" in _RPC_OPS      # the scrape op is first-class
    for op in _RPC_OPS:
        assert normalize_op(op) == op
    weird = ["", "probe2", "TELEMETRY", "step; DROP TABLE", "x" * 999,
             None, 42]
    assert {normalize_op(w) for w in weird} == {"other"}
    # the full image is the closed set — bounded cardinality by law
    assert {normalize_op(x) for x in
            list(_RPC_OPS) + weird} == set(_RPC_OPS) | {"other"}


def test_death_kind_label_cardinality_is_bounded():
    """Failover reasons are free-form prose; the death counter label
    must come from the closed death_kind vocabulary."""
    from paddle_tpu.serving.router import _DEATH_KINDS, death_kind
    vocab = {kind for _, kind in _DEATH_KINDS} | {"other"}
    cases = {
        "3 consecutive probe failures": "probe_failures",
        "2 step failures": "step_failures",
        "recover() failed: ConnectionError": "recover_failed",
        "worker died mid-step (ConnectionError)": "died_mid_step",
        "worker died during drain": "died_during_drain",
        "process gone (pid 123)": "process_gone",
        "process exited with rc=-9": "process_exited",
        "peer unreachable": "unreachable",
        "": "other",
        "novel alien failure mode": "other",
    }
    for reason, want in cases.items():
        got = death_kind(reason)
        assert got == want, (reason, got, want)
        assert got in vocab
    assert death_kind(None) == "other"


def test_frontdoor_metrics_is_cluster_merged_when_telemetry_attached():
    """ISSUE-13: with a telemetry plane attached, the front door's
    /metrics body is the CLUSTER exposition — host families pass
    through, worker-only counters appear, worker gauges come back
    labeled by worker — while a plain front door keeps serving its
    own registry untouched."""
    from paddle_tpu.observability import ClusterTelemetry
    from paddle_tpu.serving import (FrontDoor, ReplicaRouter,
                                    ServingEngine)

    reg = MetricRegistry()
    model = _tiny_llama()
    eng = ServingEngine(model, max_slots=2, max_len=64, min_bucket=8,
                        registry=reg,
                        flight_recorder=FlightRecorder(capacity=4))
    router = ReplicaRouter([eng], registry=reg,
                           flight_recorder=FlightRecorder(capacity=4))
    tel = ClusterTelemetry()
    front = FrontDoor(router, registry=reg, telemetry=tel)
    h = front.submit(np.arange(1, 6), 3)
    front.run_until_idle()
    assert h.req.finished

    snap = {"ts": 0.0, "metrics": {
        "ptpu_t_worker_only_total": {
            "type": "counter", "help": "", "label_names": [],
            "samples": [{"labels": {}, "value": 4.0}]},
        "ptpu_t_worker_depth": {
            "type": "gauge", "help": "", "label_names": [],
            "samples": [{"labels": {}, "value": 2.0}]}}}
    tel.ingest_worker("w0", {"pid": 999, "now": 0.0, "spans": [],
                             "drained_total": 0, "dropped_total": 0,
                             "recorded_total": 0, "registry": snap},
                      host_now=0.0)

    text = front.metrics_exposition()
    _, samples = _parse_prom(text)
    assert samples["ptpu_t_worker_only_total"] == 4.0
    assert samples['ptpu_t_worker_depth{worker="w0"}'] == 2.0
    # the host-side serving/frontdoor families ride the SAME body
    assert "# TYPE ptpu_serving_step_seconds" in text
    assert "ptpu_frontdoor_accepted_total" in text

    # no telemetry attached: /metrics is the plain process registry
    front2 = FrontDoor(ReplicaRouter([eng], registry=MetricRegistry()),
                       registry=MetricRegistry())
    assert "ptpu_t_worker_only_total" not in front2.metrics_exposition()
