"""Chaos suite (paddle_tpu/resilience): fault-injection framework,
RetryPolicy/RetryingStore, serving-engine recovery under injected
faults, checkpoint crash consistency at the commit point, and the
auto-resume training driver's loss-curve continuity across an injected
mid-run crash. Everything runs on CPU with injected clocks/sleeps —
marked ``chaos`` and deliberately tier-1-fast."""
import gc
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import FlightRecorder, MetricRegistry
from paddle_tpu.resilience import (InjectedFault, RetryError,
                                   RetryPolicy, RetryingStore, faults)

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_counts()
    yield
    faults.clear()


def test_lazy_package_exports():
    # the user-facing import path: the package __getattr__ must load
    # train_loop without re-entering itself (regression: `from . import
    # train_loop` inside the hook recursed via the fromlist machinery)
    from paddle_tpu.resilience import ResilientTrainLoop, train_loop
    assert train_loop.ResilientTrainLoop is ResilientTrainLoop
    with pytest.raises(AttributeError):
        paddle.resilience.nope


# -- fault-injection framework -----------------------------------------

def test_fault_point_times_and_after():
    faults.inject("t.p", times=2, after=1)
    faults.maybe_fail("t.p")                      # skipped (after=1)
    with pytest.raises(InjectedFault, match="t.p"):
        faults.maybe_fail("t.p")
    with pytest.raises(InjectedFault):
        faults.maybe_fail("t.p")
    faults.maybe_fail("t.p")                      # exhausted
    assert faults.hits("t.p") == 4
    assert faults.fired("t.p") == 2
    faults.clear("t.p")
    faults.maybe_fail("t.p")


def test_fault_env_spec_and_reload(monkeypatch):
    monkeypatch.setenv("PTPU_FAULTS", "env.p:1@1")
    faults.maybe_fail("env.p")                    # skip 1
    with pytest.raises(InjectedFault):
        faults.maybe_fail("env.p")
    faults.maybe_fail("env.p")
    # env change re-arms from the new spec (lazy reload on next hit)
    monkeypatch.setenv("PTPU_FAULTS", "env.p:1")
    with pytest.raises(InjectedFault):
        faults.maybe_fail("env.p")
    # malformed specs arm nothing instead of killing the hot path
    monkeypatch.setenv("PTPU_FAULTS", "no-colon-entry")
    faults.maybe_fail("env.p")
    monkeypatch.setenv("PTPU_FAULTS", "")
    faults.maybe_fail("env.p")


def test_fault_seeded_rate_is_deterministic():
    fires = []
    for _ in range(2):
        faults.inject("t.rate", rate=0.5, seed=7)
        got = []
        for i in range(20):
            try:
                faults.maybe_fail("t.rate")
                got.append(False)
            except InjectedFault:
                got.append(True)
        fires.append(got)
        faults.clear("t.rate")
    assert fires[0] == fires[1]
    assert any(fires[0]) and not all(fires[0])


def test_injected_scope_restores_and_custom_exc():
    faults.inject("t.s", times=100)
    with faults.injected("t.s", times=1, exc=ConnectionError):
        with pytest.raises(ConnectionError):
            faults.maybe_fail("t.s")
        faults.maybe_fail("t.s")                  # scoped rule spent
    with pytest.raises(InjectedFault):            # outer rule restored
        faults.maybe_fail("t.s")


def test_fired_bumps_observability_counter():
    from paddle_tpu.observability import default_registry
    fam = default_registry().counter(
        "ptpu_fault_injections_total",
        "deliberately injected faults (resilience.faults)",
        labels=("point",))
    before = fam.labels(point="t.obs").value
    faults.inject("t.obs", times=1)
    with pytest.raises(InjectedFault):
        faults.maybe_fail("t.obs")
    assert fam.labels(point="t.obs").value == before + 1


# -- RetryPolicy / RetryingStore ---------------------------------------

def _fake_clock_sleep():
    clock = {"t": 0.0}
    slept = []

    def sleep(d):
        slept.append(d)
        clock["t"] += d

    return clock, slept, sleep


def test_retry_backoff_jitter_and_success():
    clock, slept, sleep = _fake_clock_sleep()
    reg = MetricRegistry()
    pol = RetryPolicy(max_attempts=5, base_delay=0.1, multiplier=2.0,
                      jitter=0.25, seed=0, sleep_fn=sleep,
                      time_fn=lambda: clock["t"], registry=reg)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("transient")
        return "ok"

    assert pol.call(flaky, op="t.flaky") == "ok"
    assert calls["n"] == 3 and len(slept) == 2
    # exponential shape within the jitter band
    assert 0.075 <= slept[0] <= 0.125
    assert 0.15 <= slept[1] <= 0.25
    assert reg.get("ptpu_retry_attempts_total").labels(
        op="t.flaky").value == 3
    assert reg.get("ptpu_retry_failures_total").labels(
        op="t.flaky").value == 2


def test_retry_exhaustion_and_deadline():
    clock, slept, sleep = _fake_clock_sleep()
    reg = MetricRegistry()
    pol = RetryPolicy(max_attempts=3, base_delay=0.1, jitter=0.0,
                      sleep_fn=sleep, time_fn=lambda: clock["t"],
                      registry=reg)

    def dead():
        raise TimeoutError("never")

    with pytest.raises(RetryError, match="3 attempt") as ei:
        pol.call(dead, op="t.dead")
    assert isinstance(ei.value.last, TimeoutError)
    assert len(slept) == 2
    # deadline-aware: the first backoff would overrun the budget, so
    # it gives up after ONE attempt without sleeping
    slept.clear()
    with pytest.raises(RetryError, match="deadline"):
        pol.call(dead, op="t.dl", deadline=0.05)
    assert slept == []
    # non-retryable exceptions propagate untouched
    with pytest.raises(ValueError):
        pol.call(lambda: (_ for _ in ()).throw(ValueError("x")))


class _DictStore:
    """In-memory store with the TCPStore client surface."""

    def __init__(self):
        self._d = {}
        self.world_size = 1

    def set(self, k, v):
        self._d[k] = v if isinstance(v, bytes) else str(v).encode()

    def get(self, k, timeout=None):
        if k not in self._d:
            raise TimeoutError(f"no value for {k}")
        return self._d[k]

    def add(self, k, delta=1):
        cur = int(self._d.get(k, b"0")) + delta
        self._d[k] = str(cur).encode()
        return cur

    def wait(self, k, timeout=None):
        if k not in self._d:
            raise TimeoutError(k)


def test_retrying_store_retries_transport_not_timeout():
    store = _DictStore()
    store.set("k", b"v")
    boom = {"n": 2}
    orig_get = store.get

    def flaky_get(k, timeout=None):
        if boom["n"] > 0:
            boom["n"] -= 1
            raise ConnectionError("io error")
        return orig_get(k, timeout)

    store.get = flaky_get
    _, slept, sleep = _fake_clock_sleep()
    rs = RetryingStore(store, RetryPolicy(
        max_attempts=4, base_delay=0.01, jitter=0.0, sleep_fn=sleep,
        retry_on=(ConnectionError, OSError, InjectedFault),
        no_retry_on=(TimeoutError,), registry=MetricRegistry()))
    assert rs.get("k") == b"v"
    assert boom["n"] == 0 and len(slept) == 2
    # TimeoutError = "key not set yet", the legitimate answer: NOT
    # retried (a watchdog poll must not multiply its latency)
    slept.clear()
    with pytest.raises(TimeoutError):
        rs.get("missing")
    assert slept == []
    assert rs.world_size == 1                     # passthrough


def test_tcpstore_fault_points_wired():
    from paddle_tpu.distributed.store import TCPStore, get_lib
    if get_lib() is None:
        pytest.skip("native TCPStore library unavailable")
    store = TCPStore(is_master=True, world_size=1)
    try:
        store.set("k", b"v")
        faults.inject("store.get", times=1, exc=ConnectionError)
        rs = RetryingStore(store, RetryPolicy(
            max_attempts=3, base_delay=0.001, jitter=0.0,
            registry=MetricRegistry()))
        assert rs.get("k") == b"v"        # injected fault absorbed
        assert faults.fired("store.get") == 1
        with faults.injected("store.set", times=1):
            with pytest.raises(InjectedFault):
                store.set("k2", b"x")     # un-wrapped client: raw fault
    finally:
        store.close()


# -- serving: flow control (typed errors, deadlines, drain) ------------

def _tiny_llama(**kw):
    from paddle_tpu.models.llama import LlamaForCausalLM, \
        llama_tiny_config
    paddle.seed(0)
    kw.setdefault("max_position_embeddings", 128)
    model = LlamaForCausalLM(llama_tiny_config(**kw))
    model.eval()
    return model


def _engine(model, clock=None, **kw):
    from paddle_tpu.serving import ServingEngine
    kw.setdefault("max_slots", 1)
    kw.setdefault("max_len", 64)
    kw.setdefault("min_bucket", 8)
    if clock is not None:
        kw["time_fn"] = lambda: clock["t"]
    return ServingEngine(model, registry=MetricRegistry(),
                         flight_recorder=FlightRecorder(capacity=16),
                         **kw)


def test_deadline_cancellation_queued_and_inflight():
    from paddle_tpu.serving import DeadlineExceeded
    model = _tiny_llama()
    clock = {"t": 0.0}
    eng = _engine(model, clock=clock)
    rng = np.random.RandomState(0)
    a = eng.submit(rng.randint(0, 128, (5,)), max_new_tokens=20)
    b = eng.submit(rng.randint(0, 128, (5,)), max_new_tokens=4,
                   deadline_s=1.0)                # will expire queued
    clock["t"] = 2.0
    finished = eng.step()
    assert b in finished and b.finish_reason == "deadline"
    assert isinstance(b.error, DeadlineExceeded)
    assert not a.finished and a.slot is not None
    # in-flight deadline: a fresh request admitted, then expired
    c_pending = eng.submit(rng.randint(0, 128, (5,)),
                           max_new_tokens=20, deadline_s=50.0)
    while a in eng.cache.slots:                   # let a finish
        eng.step()
    eng.step()                                    # admits c
    assert c_pending.slot is not None
    clock["t"] = 60.0
    finished = eng.step()
    assert c_pending in finished
    assert c_pending.finish_reason == "deadline"
    assert len(c_pending.out_tokens) >= 1         # partial delivery
    assert not eng.has_work()
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(rng.randint(0, 128, (5,)), deadline_s=0.0)


def test_drain_serves_backlog_then_closes():
    from paddle_tpu.serving import EngineClosed, RequestCancelled
    model = _tiny_llama()
    eng = _engine(model, max_slots=2)
    rng = np.random.RandomState(1)
    reqs = [eng.submit(rng.randint(0, 128, (4,)), max_new_tokens=3)
            for _ in range(4)]
    done = eng.drain()
    assert sorted(r.rid for r in done) == [r.rid for r in reqs]
    assert all(r.finish_reason == "length" for r in reqs)
    with pytest.raises(EngineClosed):
        eng.submit(rng.randint(0, 128, (4,)))
    # cutoff drain cancels the remainder with the typed error
    eng2 = _engine(model, max_slots=1)
    r1 = eng2.submit(rng.randint(0, 128, (4,)), max_new_tokens=30)
    r2 = eng2.submit(rng.randint(0, 128, (4,)), max_new_tokens=30)
    done = eng2.drain(max_steps=2)
    assert {r.rid for r in done} == {r1.rid, r2.rid}
    assert r2.finish_reason == "cancelled"
    assert isinstance(r2.error, RequestCancelled)
    assert not eng2.has_work()


def test_drain_on_broken_engine_cancels_instead_of_raising():
    """A caller that chooses shutdown over recover() still gets its
    outstanding requests back (cancelled), not an EngineBroken from
    inside drain()."""
    model = _tiny_llama()
    eng = _engine(model)
    eng._donate = lambda: (5, 6)
    r1 = eng.submit(np.arange(1, 6), max_new_tokens=10)
    r2 = eng.submit(np.arange(1, 6), max_new_tokens=10)
    faults.inject("serving.step.decode", times=1)
    with pytest.raises(InjectedFault):
        eng.step()
    done = eng.drain()
    assert {r.rid for r in done} == {r1.rid, r2.rid}
    assert all(r.finish_reason == "cancelled" for r in done)
    assert all("broken" in str(r.error) for r in done)
    assert not eng.has_work()


# -- serving: fault-injected recovery (acceptance criterion a) ---------

def test_decode_fault_recover_finishes_token_identical():
    """A failed decode step (injected), recover(), and the trace
    finishes with greedy outputs token-identical to an uninjected
    run — on the donated-pool (TPU-like) path."""
    from paddle_tpu.serving import EngineBroken
    model = _tiny_llama()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 128, (n,)).astype(np.int64)
               for n in [5, 9, 3, 7]]

    ref_eng = _engine(model, max_slots=2)
    refs = [ref_eng.submit(p, max_new_tokens=6) for p in prompts]
    ref_eng.run()

    eng = _engine(model, max_slots=2)
    eng._donate = lambda: (5, 6)          # simulate the TPU path
    faults.inject("serving.step.decode", times=1, after=2)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    recovered = 0
    finished = []
    while eng.has_work():
        try:
            finished.extend(eng.step())
        except InjectedFault:
            with pytest.raises(EngineBroken, match="recover"):
                eng.step()
            rep = eng.recover()
            finished.extend(rep["finished"])
            assert rep["replay_mismatches"] == 0
            recovered += 1
    assert recovered == 1
    assert sorted(r.rid for r in finished) == [r.rid for r in reqs]
    for ref, req in zip(refs, reqs):
        assert ref.output_ids == req.output_ids
    # the recovery is in the flight recorder (the registry counter that
    # only this test read went with PR 26's prune)
    assert sum(r["kind"] == "serving.recover"
               for r in eng.recorder.snapshot()) == 1


def test_prefill_fault_requeues_request():
    """A fault inside prefill must not LOSE the popped request: it goes
    back to the queue head and the next step serves it."""
    model = _tiny_llama()
    eng = _engine(model)
    rng = np.random.RandomState(3)
    p = rng.randint(0, 128, (6,)).astype(np.int64)
    ref = model.generate(paddle.to_tensor(p[None]),
                         max_new_tokens=4).numpy()[0, 6:]
    faults.inject("serving.step.prefill", times=1)
    req = eng.submit(p, max_new_tokens=4)
    with pytest.raises(InjectedFault):
        eng.step()
    assert eng.scheduler.depth == 1       # requeued, not lost
    eng.run()                             # CPU: pools undonated, no
    np.testing.assert_array_equal(        # recover() needed
        ref, np.asarray(req.output_ids))


def test_prefill_fault_requeues_whole_admission_batch():
    """admissions() pops one request per free slot; a prefill fault on
    the FIRST must requeue the untouched remainder too (in FCFS
    order), not just the failing request."""
    model = _tiny_llama()
    eng = _engine(model, max_slots=3)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 128, (5,)).astype(np.int64)
               for _ in range(3)]
    reqs = [eng.submit(p, max_new_tokens=3) for p in prompts]
    faults.inject("serving.step.prefill", times=1)
    with pytest.raises(InjectedFault):
        eng.step()
    assert eng.scheduler.depth == 3       # ALL requeued
    assert list(eng.scheduler._queue) == reqs   # FCFS preserved
    done = eng.run()
    assert sorted(r.rid for r in done) == [r.rid for r in reqs]
    assert all(len(r.output_ids) == 3 for r in reqs)


# -- checkpoint: crash consistency at the commit point (criterion b) ---

def _ckpt_state(val):
    from paddle_tpu.framework.tensor import Tensor
    return {"w": Tensor(np.full((4, 4), val, np.float32)),
            "opt": {"m": np.full((4,), val * 2.0, np.float32)},
            "step": int(val)}


def _ckpt_values(state):
    return (float(np.asarray(state["w"].numpy())[0, 0]),
            float(state["opt"]["m"][0]), int(state["step"]))


def test_commit_point_crash_keeps_old_generation(tmp_path):
    from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                   save_state_dict)
    path = str(tmp_path / "ck")
    save_state_dict(_ckpt_state(1.0), path)            # gen 0, good
    # a save KILLED between shard writes and the metadata flip...
    with faults.injected("checkpoint.commit", times=1):
        with pytest.raises(InjectedFault):
            save_state_dict(_ckpt_state(2.0), path)
    # ...leaves torn gen-1 shard files on disk but the OLD metadata
    torn = [f for f in os.listdir(path) if ".g1." in f]
    assert torn, os.listdir(path)
    tmpl = _ckpt_state(0.0)
    load_state_dict(tmpl, path)                        # old gen loads
    assert _ckpt_values(tmpl) == (1.0, 2.0, 1)
    # the next save reuses gen 1's names: torn files are overwritten,
    # the flip commits, and the new generation loads
    save_state_dict(_ckpt_state(3.0), path)
    tmpl = _ckpt_state(0.0)
    load_state_dict(tmpl, path)
    assert _ckpt_values(tmpl) == (3.0, 6.0, 3)
    meta = json.load(open(os.path.join(path, "0.metadata.json")))
    assert meta["gen"] == 1


def test_shard_write_retry_absorbs_transient_io_fault(tmp_path):
    from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                   save_state_dict)
    path = str(tmp_path / "ck")
    faults.inject("checkpoint.shard_write", times=1)
    save_state_dict(_ckpt_state(5.0), path)   # retried inside, no raise
    assert faults.fired("checkpoint.shard_write") == 1
    tmpl = _ckpt_state(0.0)
    load_state_dict(tmpl, path)
    assert _ckpt_values(tmpl) == (5.0, 10.0, 5)


def test_async_save_error_surfaces_at_wait_and_load(tmp_path):
    from paddle_tpu.distributed.checkpoint import (
        load_state_dict, save_state_dict, wait_for_pending_saves)
    path = str(tmp_path / "ck")
    save_state_dict(_ckpt_state(1.0), path)
    # unobserved async failure: surfaces at the next load (old
    # daemon-thread behavior silently dropped it)
    faults.inject("checkpoint.commit", times=1)
    save_state_dict(_ckpt_state(2.0), path, async_save=True)
    with pytest.raises(InjectedFault):
        load_state_dict(_ckpt_state(0.0), path)
    # observed async failure: handle.wait() delivers it, and the drain
    # does NOT re-raise a handled error into later unrelated loads
    faults.inject("checkpoint.commit", times=1)
    handle = save_state_dict(_ckpt_state(2.0), path, async_save=True)
    with pytest.raises(InjectedFault):     # no more vanishing errors
        handle.wait(timeout=30.0)
    wait_for_pending_saves()               # handled -> clean
    tmpl = _ckpt_state(0.0)
    load_state_dict(tmpl, path)            # old generation intact
    assert _ckpt_values(tmpl) == (1.0, 2.0, 1)
    # a healthy async save completes and loads
    h = save_state_dict(_ckpt_state(4.0), path, async_save=True)
    h.wait(timeout=30.0)
    tmpl = _ckpt_state(0.0)
    load_state_dict(tmpl, path)
    assert _ckpt_values(tmpl) == (4.0, 8.0, 4)
    # TWO unobserved failures deliver one at a time — the second is
    # not silently swallowed behind the first
    faults.inject("checkpoint.commit", times=2)
    save_state_dict(_ckpt_state(5.0), path, async_save=True)
    save_state_dict(_ckpt_state(6.0), path, async_save=True)
    with pytest.raises(InjectedFault):
        wait_for_pending_saves()
    with pytest.raises(InjectedFault):
        wait_for_pending_saves()
    wait_for_pending_saves()               # both delivered -> clean


def test_wait_for_pending_saves_timeout_is_total_deadline():
    """Deferred PR-3 bug (c): ``timeout`` is ONE total deadline shared
    across every pending handle — N stuck saves block ~timeout
    seconds overall, not N x timeout."""
    import time as _time

    from paddle_tpu.distributed import checkpoint
    from paddle_tpu.distributed.checkpoint import (
        AsyncSaveHandle, wait_for_pending_saves)
    handles = [AsyncSaveHandle() for _ in range(4)]
    checkpoint._pending.extend(handles)
    try:
        t0 = _time.monotonic()
        with pytest.raises(TimeoutError):
            wait_for_pending_saves(timeout=0.2)
        elapsed = _time.monotonic() - t0
        assert elapsed < 0.6, \
            f"timeout applied per handle: {elapsed:.2f}s for 4 handles"
        # still-writing handles STAY pending for later drains
        assert all(h in checkpoint._pending for h in handles)
    finally:
        for h in handles:
            h._finish()
        wait_for_pending_saves()
    assert not any(h in checkpoint._pending for h in handles)


# -- watchdog satellites -----------------------------------------------

class _HbStore(_DictStore):
    pass


def test_peer_ages_distinguishes_unreachable_from_missing():
    from paddle_tpu.distributed.watchdog import (CommWatchdog,
                                                 StoreUnreachableError)
    store = _HbStore()
    reg = MetricRegistry()
    w = CommWatchdog(store, rank=0, world_size=2, timeout=10.0,
                     registry=reg,
                     flight_recorder=FlightRecorder(capacity=4))
    w.beat()
    # peer 1 never heartbeat: startup grace, small age, no failure
    ages = w.peer_ages()
    assert 0.0 <= ages[1] < 5.0
    assert not w._sweep()
    # store READ fails at the transport level: typed, not grace
    def broken_get(k, timeout=None):
        raise ConnectionError("connection refused")
    store.get = broken_get
    with pytest.raises(StoreUnreachableError, match="rank 1"):
        w.peer_ages()
    assert w.peer_ages(on_unreachable="grace")[1] >= 0.0
    assert w._sweep()
    assert any("store unreachable" in f for f in w._failed)
    with pytest.raises(RuntimeError, match="store unreachable"):
        w.check()
    assert reg.get("ptpu_dist_watchdog_failures_total").value == 1
    assert w._sweep()                       # counted once, not per sweep
    assert reg.get("ptpu_dist_watchdog_failures_total").value == 1
    # outage episodes count individually: recover, then a SECOND
    # outage bumps the counter again
    store.get = _HbStore.get.__get__(store)
    assert not w._sweep()
    store.get = broken_get
    assert w._sweep()
    assert reg.get("ptpu_dist_watchdog_failures_total").value == 2


def test_barrier_rounds_keyed_on_store_object():
    from paddle_tpu.distributed import watchdog
    s1, s2 = _DictStore(), _DictStore()
    watchdog.monitored_barrier(s1, 0, 1, timeout=1.0, tag="t")
    watchdog.monitored_barrier(s1, 0, 1, timeout=1.0, tag="t")
    watchdog.monitored_barrier(s2, 0, 1, timeout=1.0, tag="t")
    # per-object rounds: s1 advanced to round 2, s2 independently at 0
    assert "__watchdog__/barrier/t/1/release" in s1._d
    assert "__watchdog__/barrier/t/1/release" not in s2._d
    assert "__watchdog__/barrier/t/0/release" in s2._d
    # bookkeeping dies with the store (no id()-reuse collisions, no
    # leak): the WeakKeyDictionary entry disappears after GC
    n_before = len(watchdog._barrier_rounds)
    del s1, s2
    gc.collect()
    assert len(watchdog._barrier_rounds) <= max(0, n_before - 2)


# -- dataloader worker fault point -------------------------------------

class _RangeDS(paddle.io.Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        return np.float32([i])


def test_dataloader_fetch_fault_surfaces():
    faults.inject("io.dataloader.worker", times=1, after=1)
    loader = paddle.io.DataLoader(_RangeDS(), batch_size=2)
    it = iter(loader)
    next(it)
    with pytest.raises(InjectedFault):
        next(it)


def test_dataloader_process_worker_fault_via_env(monkeypatch):
    import multiprocessing as mp
    if "fork" not in mp.get_all_start_methods():
        pytest.skip("needs fork workers")
    monkeypatch.setenv("PTPU_FAULTS", "io.dataloader.worker:1")
    loader = paddle.io.DataLoader(_RangeDS(), batch_size=2,
                                  num_workers=1)
    with pytest.raises(RuntimeError, match="InjectedFault"):
        list(loader)


# -- auto-resume training driver (acceptance criterion c) --------------

def _make_train(tmp_path, name, n=4):
    rng = np.random.RandomState(42)
    data = rng.randn(64, n).astype(np.float32)
    state = {"w": np.zeros((n,), np.float32), "seen": 0}

    def step_fn(state, step):
        g = data[step % len(data)]
        state["w"] = state["w"] - 0.1 * (state["w"] - g)
        state["seen"] = int(state["seen"]) + 1
        return float(np.sum(state["w"] ** 2))

    from paddle_tpu.resilience.train_loop import ResilientTrainLoop
    return ResilientTrainLoop(
        step_fn, state, str(tmp_path / name), save_every=4,
        registry=MetricRegistry(),
        flight_recorder=FlightRecorder(capacity=32)), state


def test_train_loop_survives_injected_crash_with_continuity(tmp_path):
    base_loop, base_state = _make_train(tmp_path, "base")
    base_report = base_loop.run(12)
    assert base_report["recoveries"] == 0
    assert len(base_report["losses"]) == 12

    chaos_loop, chaos_state = _make_train(tmp_path, "chaos")
    faults.inject("train.step", times=1, after=9)   # dies at step 9
    report = chaos_loop.run(12)
    assert report["recoveries"] == 1
    assert report["restores"] and report["restores"][0] in (4, 8)
    # loss-curve continuity: ONE clean trajectory (pre-crash entries
    # past the restore point are dropped, replays re-record), every
    # step's loss matching the uninjected run exactly
    assert len(report["losses"]) == 12
    assert report["losses"] == base_report["losses"]
    np.testing.assert_array_equal(base_state["w"], chaos_state["w"])
    assert chaos_loop.latest_step() == 12


def test_train_loop_resumes_across_process_restart(tmp_path):
    base_loop, base_state = _make_train(tmp_path, "base")
    base_loop.run(12)

    first, _ = _make_train(tmp_path, "restart")
    first.run(6)
    # a NEW driver over the same dir (the relaunched process) resumes
    # from the published checkpoint instead of step 0
    second, state2 = _make_train(tmp_path, "restart")
    report = second.run(12)
    assert report["start_step"] == 6
    assert [s for s, _ in report["losses"]] == list(range(6, 12))
    np.testing.assert_array_equal(base_state["w"], state2["w"])


def test_train_loop_failure_policies(tmp_path):
    from paddle_tpu.resilience.train_loop import (RestartLimitExceeded,
                                                  TrainLoopError)
    # crash before the first published checkpoint: nothing to restore
    loop, _ = _make_train(tmp_path, "early")
    faults.inject("train.step", times=1, after=1)
    with pytest.raises(TrainLoopError, match="first checkpoint"):
        loop.run(12)
    # more failures than max_recoveries: typed give-up
    loop2, _ = _make_train(tmp_path, "limit")
    loop2.max_recoveries = 2
    faults.inject("train.step", times=10, after=5)
    with pytest.raises(RestartLimitExceeded):
        loop2.run(12)


def test_train_loop_failed_save_does_not_poison_restore(tmp_path):
    """A completely-failed periodic save (retries exhausted) is
    absorbed — LATEST keeps the previous good checkpoint — and its
    already-handled error must NOT resurface from the pending-save
    drain when a later crash triggers restore_latest()."""
    loop, state = _make_train(tmp_path, "ps")
    base_loop, base_state = _make_train(tmp_path, "ps_base")
    base_report = base_loop.run(12)
    # save at step 4 succeeds (1 shard-write hit); the save at step 8
    # burns all 3 retry attempts and fails; the crash lands at step 9
    faults.inject("checkpoint.shard_write", times=3, after=1)
    faults.inject("train.step", times=1, after=9)
    report = loop.run(12)
    assert report["recoveries"] == 1
    assert report["restores"] == [4]      # good checkpoint, not dead
    assert loop.registry.get(
        "ptpu_train_checkpoint_failures_total").value >= 1
    assert dict(report["losses"]) == dict(base_report["losses"])
    np.testing.assert_array_equal(base_state["w"], state["w"])
    assert loop.latest_step() == 12       # replayed save succeeded


def test_train_loop_watchdog_and_retried_beat(tmp_path):
    class _Watchdog:
        def __init__(self):
            self.beats = 0
            self.fail_beats = 2
            self.peer_dead = False

        def beat(self):
            if self.fail_beats > 0:
                self.fail_beats -= 1
                raise ConnectionError("store flake")
            self.beats += 1

        def check(self):
            if self.peer_dead:
                raise RuntimeError("distributed watchdog: rank 1 died")

    wd = _Watchdog()
    loop, _ = _make_train(tmp_path, "wd")
    loop.watchdog = wd
    loop.retry_policy = RetryPolicy(
        max_attempts=4, base_delay=0.001, jitter=0.0,
        registry=MetricRegistry())
    report = loop.run(4)               # transient beat flake absorbed
    assert wd.beats >= 1 and len(report["losses"]) == 4
    # a DEAD PEER propagates (in-process restore can't fix it; the
    # elastic relaunch loop owns it, and run() auto-resumes after)
    wd.peer_dead = True
    loop2, _ = _make_train(tmp_path, "wd")
    loop2.watchdog = wd
    with pytest.raises(RuntimeError, match="rank 1 died"):
        loop2.run(8)
