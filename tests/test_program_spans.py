"""The program's spans (PR 26): one path from the layer boundaries to
the JAX profiler's trace and to the process ring; the engine's, the
trainer's and the front door's phases on it; compile events; and the
benchmark's ``program_span`` reader over the ring."""
import logging
import os
import re
import sys
import types

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.observability import (MetricRegistry, TraceBuffer,
                                      install_trace_buffer, span, tracing)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import xplane  # noqa: E402

ENGINE_CHILDREN = ("serving.admit", "serving.decode.build",
                   "serving.decode.enqueue", "serving.decode.fetch",
                   "serving.sample", "serving.publish")


@pytest.fixture
def ring():
    """A fresh default-sized ring on the real clock for one test."""
    buf = TraceBuffer(tracing.DEFAULT_CAPACITY)
    prev = install_trace_buffer(buf)
    yield buf
    install_trace_buffer(prev)


@pytest.fixture
def fake_ring():
    t = {"t": 0.0}
    buf = TraceBuffer(capacity=64, time_fn=lambda: t["t"])
    prev = install_trace_buffer(buf)
    yield buf, t
    install_trace_buffer(prev)


def _tiny_llama():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=256, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=1,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return model


def _paged_engine(**kw):
    from paddle_tpu.serving import ServingEngine
    return ServingEngine(_tiny_llama(), max_slots=4, max_len=64,
                         page_size=8,
                         registry=MetricRegistry(),
                         **kw)


def _host_events(logdir):
    """{name: count} over every host line of the newest xplane, and the
    file's bytes (module names live in stats the parser skips)."""
    path = xplane.latest_xplane(logdir)
    names = {}
    for _, lines in xplane.planes_abs(path):
        for _, events in lines:
            for e in events:
                names[e[0]] = names.get(e[0], 0) + 1
    with open(path, "rb") as f:
        return names, f.read()


# -- the span itself ---------------------------------------------------

def test_span_reaches_a_plain_jax_profiler_session(tmp_path, ring):
    """No profiler.Profiler anywhere: jax.profiler.start_trace alone."""
    assert not profiler._is_recording()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("unit.plain_session", step=3) as sp:
            sp.set_attr("late", 1)
    finally:
        jax.profiler.stop_trace()
    names, _ = _host_events(str(tmp_path))
    assert names.get("unit.plain_session") == 1
    (rec,) = tracing.query("unit.plain_session")["spans"]
    assert rec["attrs"] == {"step": 3, "late": 1}


def test_span_lands_once_under_profiler(ring):
    prof = profiler.Profiler()
    prof.start()
    logdir = prof._jax_dir
    try:
        with span("unit.under_profiler", request_id=5):
            pass
    finally:
        prof.stop()
    if logdir is None:
        pytest.skip("the jax profiler did not start here")
    names, _ = _host_events(logdir)
    assert names.get("unit.under_profiler") == 1
    # and once in the chrome host timeline, with its args
    evs = [e for e in profiler._events
           if e["name"] == "unit.under_profiler"]
    assert len(evs) == 1 and evs[0]["args"] == {"request_id": 5}


def test_span_without_any_session_only_reaches_the_ring(ring):
    n = len(profiler._events)
    with span("unit.quiet"):
        pass
    assert len(profiler._events) == n
    assert [r["name"] for r in ring.snapshot()] == ["unit.quiet"]


def test_span_records_the_error_of_a_failed_body(ring):
    with pytest.raises(KeyError):
        with span("unit.fails", kind="f"):
            raise KeyError("boom")
    (rec,) = tracing.query("unit.fails")["spans"]
    assert rec["error"] == "KeyError" and rec["attrs"] == {"kind": "f"}
    assert tracing.active_context() is None     # the stack unwound
    with span("unit.after") as sp:
        assert sp._parent == 0


def test_ring_parent_and_self_time_on_a_nest(fake_ring):
    buf, t = fake_ring
    with span("nest.a"):
        t["t"] = 1.0
        with span("nest.b"):
            t["t"] = 2.0
            with span("nest.c", request_id=4):
                t["t"] = 4.5
            t["t"] = 5.0
        with span("nest.b"):
            t["t"] = 6.0
        t["t"] = 10.0
    by_name = {}
    for r in tracing.query("nest.*")["spans"]:
        by_name.setdefault(r["name"], []).append(r)
    (a,), (c,) = by_name["nest.a"], by_name["nest.c"]
    b1, b2 = by_name["nest.b"]
    assert a["parent"] == 0
    assert b1["parent"] == b2["parent"] == a["id"]
    assert c["parent"] == b1["id"]
    assert (a["dur"], b1["dur"], b2["dur"], c["dur"]) == \
        (10.0, 4.0, 1.0, 2.5)
    # self = duration minus what the children cover
    assert (a["self"], b1["self"], b2["self"], c["self"]) == \
        (5.0, 1.5, 1.0, 2.5)
    assert c["attrs"] == {"request_id": 4}


@pytest.mark.parametrize("name,t0,t1,want", [
    ("q.a", float("-inf"), float("inf"), ["q.a"]),
    ("q.*", float("-inf"), float("inf"), ["q.a", "q.b", "q.b"]),
    ("q.b", 2.0, 3.0, ["q.b"]),          # start in [t0, t1)
    ("q.b", 0.0, 2.0, []),
    (None, 3.0, 9.0, ["q.b"]),
])
def test_query_by_name_prefix_and_start_time(fake_ring, name, t0, t1,
                                             want):
    _, t = fake_ring
    for nm, start in (("q.a", 1.0), ("q.b", 2.0), ("q.b", 3.0)):
        t["t"] = start
        with span(nm):
            t["t"] = start + 0.5
    got = tracing.query(name, t0, t1)
    assert sorted(r["name"] for r in got["spans"]) == want
    assert got["dropped_total"] == 0 and got["recorded_total"] == 3


def test_ring_overflow_bumps_dropped_total_and_the_query_says_so():
    buf = TraceBuffer(capacity=3, time_fn=lambda: 0.0)
    prev = install_trace_buffer(buf)
    try:
        for i in range(5):
            with span("over.flow", i=i):
                pass
        got = tracing.query("over.flow")
    finally:
        install_trace_buffer(prev)
    assert buf.dropped_total == 2 and got["dropped_total"] == 2
    assert [r["attrs"]["i"] for r in got["spans"]] == [2, 3, 4]
    assert got["recorded_total"] == 5


def test_default_ring_keeps_setup_through_a_window_of_short_steps(ring):
    """A ``compile.*`` span of set-up is still in the default-sized
    ring after 3,500 engine steps' worth of spans (a 50 s window at a
    14 ms step, 13 spans a step): the benchmark's reader looks for it
    only when the window has closed."""
    tracing._record_span("compile.decode", tracing._now(), key=None)
    family = ("serving.admit", "serving.decode.build",
              "serving.decode.enqueue", "serving.decode.fetch",
              "serving.sample", "serving.publish", "frontdoor.deliver",
              "frontdoor.pump", "router.step", "serving.publish",
              "serving.prefill.fetch")
    for i in range(3500):
        with span("serving.step", step=i):
            with span("serving.decode"):
                for name in family:
                    with span(name):
                        pass
    got = tracing.query("compile.*")
    assert [r["name"] for r in got["spans"]] == ["compile.decode"]
    assert got["dropped_total"] == 0
    assert got["recorded_total"] == 1 + 3500 * 13 <= ring.capacity // 2


def test_default_ring_is_installed_on_perf_counter():
    buf = tracing.current_trace_buffer()
    assert buf is not None and buf.capacity >= 12_000
    import time
    assert abs(tracing._now() - time.perf_counter()) < 1.0


def test_record_span_is_a_child_of_the_open_span(fake_ring):
    # compile_cache.Watched's private way in (a compile is known after)
    _, t = fake_ring
    with span("rs.outer"):
        t0 = tracing._now()
        t["t"] = 2.0
        tracing._record_span("rs.after_the_fact", t0, key=7)
        t["t"] = 3.0
    inner, outer = tracing.query("rs.*")["spans"]
    assert inner["parent"] == outer["id"] and inner["dur"] == 2.0
    assert outer["self"] == 1.0 and inner["attrs"] == {"key": 7}


def test_context_for_skips_the_lock_without_bindings(monkeypatch):
    tracing.clear_bindings()

    class Boom:
        def __enter__(self):
            raise AssertionError("took the lock with no binding")

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(tracing, "_bind_lock", Boom())
    assert tracing.context_for(7) is None
    with span("unit.nolock", request_id=7):
        pass


# -- the engine's phases -----------------------------------------------

@pytest.fixture(scope="module")
def engine_run():
    """One tiny paged engine run into its own ring: three requests, the
    third sharing the first's prompt after it finished (a prefix hit)."""
    buf = TraceBuffer(tracing.DEFAULT_CAPACITY)
    prev = install_trace_buffer(buf)
    try:
        eng = _paged_engine()
        prompt = np.arange(1, 41, dtype=np.int64)
        eng.submit(prompt, 5)
        eng.submit(np.arange(50, 70, dtype=np.int64), 3)
        while eng.has_work():
            eng.step()
        eng.submit(np.concatenate([prompt[:32],
                                   np.arange(90, 99, dtype=np.int64)]), 3)
        while eng.has_work():
            eng.step()
        spans = tracing.query()["spans"]
    finally:
        install_trace_buffer(prev)
    return eng, spans


def test_each_step_has_its_six_children_inside_it(engine_run):
    _, spans = engine_run
    steps = [s for s in spans if s["name"] == "serving.step"]
    assert len(steps) >= 6
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent"], []).append(s)
    enqueues, ahead = 0, []
    for st in steps:
        kids = by_parent.get(st["id"], [])
        below = list(kids)
        for k in kids:                  # build/enqueue/fetch sit under
            below += by_parent.get(k["id"], [])    # serving.decode
        names = {k["name"] for k in below}
        (dec,) = [k for k in kids if k["name"] == "serving.decode"]
        # build and enqueue sit in the step that enqueued the decode it
        # reads: itself, or, where that decode ran one step ahead, the
        # step before, beside its own
        need = set(ENGINE_CHILDREN)
        if dec["attrs"]["ahead"]:
            need -= {"serving.decode.build", "serving.decode.enqueue"}
        assert need <= names, (st["attrs"], names)
        enqueues += sum(k["name"] == "serving.decode.enqueue"
                        for k in below)
        ahead.append(dec["attrs"]["ahead"])
        for k in below:
            assert st["t0"] <= k["t0"] <= k["t1"] <= st["t1"], k
        assert sum(k["dur"] for k in kids) <= st["dur"] + 1e-9
        assert st["self"] <= st["dur"]
    # every decode read was enqueued once; most here ran ahead
    assert enqueues == len(steps) and 0 < sum(ahead) < len(steps)
    dec = next(s for s in spans if s["name"] == "serving.decode")
    inside = {k["name"] for k in by_parent[dec["id"]]}
    assert inside >= {"serving.decode.build", "serving.decode.enqueue",
                      "serving.decode.fetch"}


def test_step_span_carries_the_counts_at_its_boundary(engine_run):
    _, spans = engine_run
    steps = [s for s in spans if s["name"] == "serving.step"]
    for st in steps:
        a = st["attrs"]
        assert {"step", "active_slots", "queue_depth", "pages_in_use",
                "pages_reserved", "pages_total", "prefix_hit_tokens",
                "prefix_lookup_tokens"} <= set(a)
        assert 0 <= a["pages_in_use"] <= a["pages_total"]
    assert sum(s["attrs"]["prefix_hit_tokens"] for s in steps) == 32
    admits = [s for s in spans if s["name"] == "serving.admit"]
    assert sum(s["attrs"]["admitted"] for s in admits) == 3
    assert all("refused_for_pages" in s["attrs"] for s in admits)
    fetch = next(s for s in spans if s["name"] == "serving.decode.fetch")
    # greedy requests: the step fetches the program's argmax, [slots]
    # int32, not the [slots, vocab] float32 logits (4 * 256 * 4)
    assert fetch["attrs"]["bytes"] == 4 * 4
    assert all("request_ids" not in s["attrs"] for s in spans
               if s["name"] == "serving.decode")


def test_prefill_spans_name_their_program(engine_run):
    _, spans = engine_run
    pre = [s for s in spans if s["name"] == "serving.prefill"]
    assert [s["attrs"]["program"] for s in pre] == \
        ["prefill", "prefill", "extend"]
    assert pre[2]["attrs"]["shared_prefix"] == 32
    assert all({"bucket", "prompt_tokens", "request_id"}
               <= set(s["attrs"]) for s in pre)
    kids = [s for s in spans if s["name"] == "serving.prefill.fetch"]
    assert [k["parent"] for k in kids] == [s["id"] for s in pre]


def test_compile_spans_equal_trace_counts_for_every_kind(engine_run):
    eng, spans = engine_run
    seen = {}
    for s in spans:
        if s["name"].startswith("compile."):
            kind = s["name"][len("compile."):]
            seen.setdefault(kind, []).append(s["attrs"]["key"])
    for kind, count in eng.trace_counts.items():
        n = sum(count.values()) if isinstance(count, dict) else count
        assert len(seen.get(kind, [])) == n, (kind, count, seen)
        if isinstance(count, dict):
            assert sorted(seen.get(kind, [])) == sorted(count)
    assert seen["extend"] == [16]        # the 17-token tail's bucket
    ext = next(s for s in spans if s["name"] == "compile.extend")
    assert ext["attrs"]["cache"] in ("hit", "miss", "off")
    assert ext["attrs"]["backend_s"] > 0 and ext["attrs"]["trace_s"] > 0
    assert ext["dur"] >= ext["attrs"]["backend_s"]
    fam = eng.registry.get("ptpu_compiles_total")
    assert fam.labels(program="extend").value == 1
    assert fam.labels(program="prefill").value == 2


def test_decode_spans_say_what_the_attention_read(engine_run):
    """``compile.decode`` names the attention the decode program traced
    as (the engine keeps the word beside ``trace_counts``), and every
    ``serving.decode`` counts the pages a length-aware attention reads:
    ``pos // page + 1`` over the active slots."""
    eng, spans = engine_run
    comp = [s for s in spans if s["name"] == "compile.decode"]
    assert [s["attrs"]["attend"] for s in comp] == ["einsum"]   # a CPU
    assert eng.decode_attend == "einsum"
    dec = [s for s in spans if s["name"] == "serving.decode"]
    assert dec
    for d in dec:
        live, batch = d["attrs"]["live_pages"], d["attrs"]["batch"]
        assert batch <= live <= batch * eng.cache.pages_per_slot
    # 40 and 20 prompt tokens in pages of 8: the first decode step
    # writes positions 40 and 20, in the sixth and the third page
    assert dec[0]["attrs"]["live_pages"] == 6 + 3


def test_compile_events_are_logged_and_kept_for_the_recorder(caplog):
    from paddle_tpu.observability import FlightRecorder
    rec = FlightRecorder(capacity=8)
    eng = _paged_engine(flight_recorder=rec)
    eng.submit(np.arange(1, 10, dtype=np.int64), 4)
    with caplog.at_level(logging.INFO, logger="paddle_tpu.compile"):
        eng.step()
    lines = [r.getMessage() for r in caplog.records
             if r.name == "paddle_tpu.compile"]
    assert any(re.match(r"kind=prefill key=16 total_s=\S+ cache=\w+ "
                        r"trace_s=\S+ backend_s=\S+", ln)
               for ln in lines), lines
    assert any(ln.startswith("kind=decode ") for ln in lines)
    (step,) = [r for r in rec.snapshot() if r["kind"] == "serving.step"]
    assert [(c["kind"], c["key"]) for c in step["compiles"]] == \
        [("prefill", 16), ("tokens", None), ("decode", None)]
    eng.step()                           # nothing compiles any more
    assert rec.snapshot()[-1]["compiles"] == []


@pytest.mark.parametrize("builder,name", [
    ("_decode_fn", "ptpu_decode"), ("_verify_fn", "ptpu_verify"),
    ("_prefill_fn", "ptpu_prefill"), ("_extend_fn", "ptpu_extend"),
    ("_chunk_fn", "ptpu_chunk"), ("_install_fn", "ptpu_install"),
    ("_copy_fn", "ptpu_copy"), ("_promote_fn", "ptpu_promote"),
])
def test_engine_programs_carry_stable_names(builder, name):
    from paddle_tpu.serving import ServingEngine
    code = getattr(ServingEngine, builder).__code__
    inner = {c.co_name for c in code.co_consts
             if isinstance(c, types.CodeType)}
    assert name in inner and "pure" not in inner


def test_engine_spans_and_module_names_in_a_plain_trace(tmp_path, ring):
    eng = _paged_engine()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit(np.arange(1, 20, dtype=np.int64), 3)
        while eng.has_work():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    names, raw = _host_events(str(tmp_path))
    for n in ("serving.step", "serving.decode", "serving.prefill") \
            + ENGINE_CHILDREN:
        assert names.get(n, 0) >= 1, n
    assert names["serving.step"] == \
        len(tracing.query("serving.step")["spans"])
    assert b"jit_ptpu_decode" in raw and b"jit_ptpu_prefill" in raw
    assert b"jit_pure" not in raw


def test_frontdoor_and_router_spans(ring):
    from paddle_tpu.serving import FrontDoor, ReplicaRouter
    reg = MetricRegistry()
    front = FrontDoor(ReplicaRouter([_paged_engine()], registry=reg),
                      registry=reg)

    class Stream:
        events = 0

        def write(self, event):
            Stream.events += 1

        def close(self):
            pass

    front.submit(np.arange(1, 12, dtype=np.int64), 3, stream=Stream())
    front.run_until_idle()
    spans = tracing.query()["spans"]
    pumps = [s for s in spans if s["name"] == "frontdoor.pump"]
    # admissions are the registry's (ptpu_frontdoor_accepted_total /
    # _rejected_total); the pump span carries no copy of them
    assert pumps and all("attrs" not in s for s in pumps)
    ids = {s["id"] for s in pumps}
    delivers = [s for s in spans if s["name"] == "frontdoor.deliver"]
    assert delivers and all(s["parent"] in ids for s in delivers)
    # three tokens and the done event
    assert sum(s["attrs"]["events"] for s in delivers) \
        == Stream.events == 4
    rsteps = [s for s in spans if s["name"] == "router.step"]
    assert rsteps and all(s["parent"] in ids
                          and s["attrs"]["replicas_stepped"] == 1
                          for s in rsteps)
    engine_steps = [s for s in spans if s["name"] == "serving.step"]
    assert {s["parent"] for s in engine_steps} == \
        {s["id"] for s in rsteps}


def test_batch_spans_list_request_ids_only_with_bindings(ring):
    from paddle_tpu.observability import (TraceContext, bind_request,
                                          clear_bindings)
    eng = _paged_engine()
    req = eng.submit(np.arange(1, 9, dtype=np.int64), 2)
    bind_request(req.rid, TraceContext.for_request(req.rid))
    try:
        while eng.has_work():
            eng.step()
    finally:
        clear_bindings()
    dec = [s for s in tracing.query("serving.decode")["spans"]]
    assert dec and dec[0]["attrs"]["request_ids"] == [req.rid]
    assert dec[0]["attrs"]["batch"] == 1


# -- the trainer's phases ----------------------------------------------

def _tiny_trainer():
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import (GPTConfig, GPTSpmdTrainer,
                                       build_mesh)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=16, dtype=jnp.float32)
    mesh = build_mesh(n_devices=1, pipe=1, model=1, fsdp=1, sep=1)
    return GPTSpmdTrainer(cfg, mesh, microbatches=1, remat=False)


def test_trainer_build_compile_and_step_spans(ring, caplog):
    tr = _tiny_trainer()
    ids = np.random.RandomState(0).randint(0, 128, (2, 16)) \
        .astype(np.int32)
    with caplog.at_level(logging.INFO, logger="paddle_tpu.compile"):
        for _ in range(3):
            loss = tr.train_step(ids, np.roll(ids, -1, axis=1))
    assert np.isfinite(float(jax.device_get(loss)))
    spans = tracing.query()["spans"]
    (build,) = [s for s in spans if s["name"] == "train.build"]
    (init,) = [s for s in spans if s["name"] == "train.init_state"]
    assert init["parent"] == build["parent"] == 0      # two phases
    assert init["attrs"]["n_params"] == tr.n_params()
    assert build["t0"] <= build["t1"] <= init["t0"] <= init["t1"]
    steps = [s for s in spans if s["name"] == "train.step"]
    assert len(steps) == 3
    assert all(s["attrs"] == {"tokens": 32} for s in steps)
    (comp,) = [s for s in spans if s["name"].startswith("compile.")]
    assert comp["name"] == "compile.train_step"
    assert comp["parent"] == steps[0]["id"]
    assert comp["attrs"]["key"] == "2x16"
    assert comp["attrs"]["backend_s"] > 0
    assert steps[0]["dur"] > 10 * max(s["dur"] for s in steps[1:])
    assert sum("kind=train_step key=2x16" in r.getMessage()
               for r in caplog.records) == 1
    # the watched step is still the jitted function to its callers
    assert tr.build_step()._cache_size() >= 1
    assert hasattr(tr.build_step(), "lower")


# -- the benchmark's reader --------------------------------------------

def _obs_and_marks(monkeypatch, marks, window_s):
    from chipbench import setup_marks
    from chipbench.readers import program_span
    monkeypatch.setattr(setup_marks, "T0", 100.0)
    monkeypatch.setattr(program_span, "T0", 100.0)
    monkeypatch.setattr(program_span, "MARKS", marks)
    return program_span, {"host": {"window_s": window_s}}


def _spans_at(t, *specs):
    for name, start, dur, attrs in specs:
        t["t"] = start
        with span(name, **attrs):
            t["t"] = start + dur


@pytest.mark.parametrize("args,want", [
    ({"span": "train.step", "phase": "window", "field": "dur",
      "stat": "mean", "scale": 1000.0}, 2.5),
    ({"span": "train.step", "phase": "window", "stat": "count"}, 2),
    ({"span": "compile.*", "phase": "setup", "field": "dur",
      "stat": "sum"}, 19.0),
    ({"span": ["train.build", "compile.*"], "phase": "setup",
      "field": "dur", "stat": "sum"}, 25.0),
    ({"span": "serving.sample", "phase": "window", "field": "self",
      "stat": "sum_per", "per": "train.step", "scale": 1000.0}, 250.0),
    ({"span": "train.step", "phase": "window", "field": "tokens",
      "over": "cap", "stat": "mean", "scale": 100.0}, 37.5),
    ({"span": "no.such", "phase": "window", "stat": "count"}, None),
    ({"span": "train.step", "phase": "setup", "field": "absent",
      "stat": "mean"}, None),
])
def test_program_span_reader(monkeypatch, fake_ring, args, want):
    _, t = fake_ring
    reader, obs = _obs_and_marks(
        monkeypatch, [["imports", 5.0], ["warm_steps", 40.0]], 10.0)
    _spans_at(
        t,
        ("compile.early", 103.0, 1.0, {}),          # before `imports`
        ("train.build", 106.0, 6.0, {}),
        ("compile.train_step", 120.0, 19.0, {}),
        ("train.step", 139.5, 0.3, {"tokens": 1, "cap": 4}),  # warm-up
        ("train.step", 141.0, 0.002, {"tokens": 1, "cap": 4}),
        ("serving.sample", 142.0, 0.5, {}),
        ("train.step", 149.0, 0.003, {"tokens": 2, "cap": 4}),
        ("train.step", 151.0, 0.004, {"tokens": 4, "cap": 4}),  # tail
    )
    got = reader.read(args, obs)
    assert got == want if want is None else got == pytest.approx(want)


def test_program_span_reader_returns_nothing_on_an_older_program(
        monkeypatch, fake_ring):
    reader, obs = _obs_and_marks(monkeypatch, [["imports", 1.0]], 5.0)
    args = {"span": "train.step", "phase": "window", "stat": "count"}
    monkeypatch.delattr(tracing, "query")      # the parent has none
    assert reader.read(args, obs) is None
    monkeypatch.undo()
    assert reader.read(args, {"host": {}}) is None


def test_new_layer_metrics_report_on_the_tiny_training_cell():
    """BENCHMARK.json's three program_span entries of the training cell
    (at least those: a serving configuration may bring its own) through
    ``chipbench.run.run_cell`` at the selftest's tiny sizes (the
    selftest itself runs ``unproved/manifest.json``, which no PR but a
    benchmark one may extend)."""
    from chipbench import manifest, run, selftest
    bench = manifest.load()
    assert not manifest.check(bench)
    new = {"host_dispatch_ms.train", "program_ready_s",
           "state_init_s.train"}
    assert new <= {m["name"] for m in bench["per_layer"]
                   if m["source"] == "program_span"}
    cell = manifest.cell(bench, "gpt3-1.3b.pretrain-s1024")
    cell["config"] = selftest.TINY_CONFIG["gpt_trainer"]
    cell["traffic"] = dict(cell["traffic"],
                           **selftest.TINY_TRAFFIC["train_stream"])
    with open(os.devnull, "w") as quiet:
        r = run.run_cell(cell, 2**31 + 7, 1.0, True, selftest.PEAKS,
                         plane_filter="CPU", line_filter="CpuClient",
                         log=quiet)
    m = r["metrics"]
    assert r["correct"] and new <= set(m)
    assert m["compiles_in_window"]["value"] == 0
    assert 0 < m["host_dispatch_ms.train"]["value"] \
        < m["step_ms.train"]["value"] * 1.5
    assert m["program_ready_s"]["value"] > 0
    assert m["state_init_s.train"]["value"] > 0
    specs = {os.path.basename(f)[:-5] for f in os.listdir(os.path.join(
        manifest.HERE, "layer_metrics")) if f.endswith(".json")}
    assert {"device_wait_ms.serve", "host_sample_ms.serve",
            "host_admit_ms.serve", "host_build_ms.serve",
            "host_deliver_ms.serve", "pages_in_use_pct.serve"} <= specs
