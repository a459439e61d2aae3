"""Profiler (reference: python/paddle/profiler/profiler.py:358 Profiler with
state-machine scheduler, make_scheduler:129, export_chrome_tracing:227,
summary tables; C++ host/CUPTI tracers under
/root/reference/paddle/fluid/platform/profiler/).

TPU-native: the device timeline comes from the JAX/XLA profiler (XPlane →
TensorBoard/perfetto); this module keeps the reference's python surface —
RecordEvent host annotations, the CLOSED/READY/RECORD scheduler states,
chrome-trace export of host events, and a summary table — and starts/stops
jax.profiler traces for device capture (SURVEY.md §5 tracing mapping).
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from enum import Enum
from typing import Callable, Iterable, List, Optional

__all__ = ["Profiler", "ProfilerState", "ProfilerTarget", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "export_metrics",
           "load_profiler_result", "SortedKeys", "benchmark"]


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class SortedKeys(Enum):
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    GPUTotal = 3


_events_lock = threading.Lock()
_events: List[dict] = []
# PROCESS-WIDE recording flag (was threading.local(): Profiler.start()
# only flipped the flag in the calling thread, so RecordEvents from
# dataloader/watchdog worker threads were silently dropped — the whole
# point of host tracing is seeing those threads). One-element list so
# _transition mutates in place; _events_lock still guards the list.
_recording = [False]


def _is_recording() -> bool:
    return _recording[0]


def _host_event(name: str, t0_ns: int, t1_ns: int, args=None) -> None:
    """Append one completed host event to the chrome-trace list (the
    caller has checked ``_is_recording()``). ``RecordEvent`` and
    ``observability.Span`` both end here."""
    ev = {
        "name": name, "ph": "X", "pid": os.getpid(),
        "tid": threading.get_ident(),
        "ts": t0_ns / 1000.0,
        "dur": (t1_ns - t0_ns) / 1000.0,
        "cat": "host",
    }
    if args:
        ev["args"] = {k: (v if isinstance(
            v, (int, float, str, bool, type(None))) else repr(v))
            for k, v in args.items()}
    with _events_lock:
        _events.append(ev)


class RecordEvent:
    """Host-side annotation (reference: platform/profiler/event_tracing.h:43
    RecordEvent — emitted inside every generated ad_func). Also forwards to
    jax.profiler.TraceAnnotation so events appear in XPlane traces.

    ``args`` (a dict) lands in the chrome trace event's ``args`` field —
    observability spans use it to carry request ids; it is read at
    ``end()`` time, so attributes added mid-span are captured."""

    def __init__(self, name: str, event_type=None, args=None):
        self.name = name
        self.args = args
        self._t0 = None
        self._jax_ann = None

    def begin(self):
        self._t0 = time.perf_counter_ns()
        if _is_recording():
            try:
                import jax.profiler
                self._jax_ann = jax.profiler.TraceAnnotation(self.name)
                self._jax_ann.__enter__()
            except Exception:
                self._jax_ann = None

    def end(self):
        if self._t0 is None:
            return
        t1 = time.perf_counter_ns()
        if self._jax_ann is not None:
            self._jax_ann.__exit__(None, None, None)
            self._jax_ann = None
        if _is_recording():
            _host_event(self.name, self._t0, t1, self.args)
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Reference profiler.py:129 — step-indexed state machine."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        fname = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{fname}.json")
        prof._export_chrome(path)
        print(f"[profiler] chrome trace written to {path}")

    return handler


def export_metrics(dir_name: str, worker_name: Optional[str] = None,
                   fmt: str = "prometheus"):
    """on_trace_ready-style handler writing the observability metrics
    registry snapshot next to the trace, so one run yields BOTH a
    chrome trace and a metrics snapshot::

        prof = Profiler(on_trace_ready=lambda p: (
            export_chrome_tracing("./out")(p),
            export_metrics("./out")(p)))
    """
    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        fname = worker_name or f"worker_{os.getpid()}"
        ext = "prom" if fmt == "prometheus" else "json"
        path = os.path.join(dir_name, f"{fname}.{ext}")
        prof.export_metrics(path, fmt=fmt)
        print(f"[profiler] metrics snapshot written to {path}")

    return handler


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)


class Profiler:
    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready=None, timer_only=False,
                 record_shapes=False, profile_memory=False,
                 with_flops=False):
        if scheduler is None:
            self._scheduler = lambda step: ProfilerState.RECORD
        elif isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            self._scheduler = make_scheduler(closed=start, ready=0,
                                             record=end - start, repeat=1)
        else:
            self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self.step_num = 0
        self._state = ProfilerState.CLOSED
        self._jax_dir = None
        self._step_times: List[float] = []
        self._last_step_t = None

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self._transition(self._scheduler(self.step_num))

    def stop(self):
        self._transition(ProfilerState.CLOSED, final=True)

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append((now - self._last_step_t,
                                     num_samples))
        self._last_step_t = now
        self.step_num += 1
        self._transition(self._scheduler(self.step_num))

    def _transition(self, new_state: ProfilerState, final=False):
        recording = self._state in (ProfilerState.RECORD,
                                    ProfilerState.RECORD_AND_RETURN)
        will_record = new_state in (ProfilerState.RECORD,
                                    ProfilerState.RECORD_AND_RETURN)
        if will_record and not recording:
            _recording[0] = True
            if not self._timer_only:
                try:
                    import jax.profiler
                    self._jax_dir = "/tmp/paddle_tpu_xplane"
                    jax.profiler.start_trace(self._jax_dir)
                except Exception:
                    self._jax_dir = None
        if (recording and not will_record) or \
                (final and recording):
            _recording[0] = False
            if self._jax_dir is not None:
                try:
                    import jax.profiler
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                self._jax_dir = None
            if self._on_trace_ready is not None:
                self._on_trace_ready(self)
        self._state = new_state

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- output ------------------------------------------------------------
    def _export_chrome(self, path: str):
        with _events_lock:
            events = list(_events)
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)

    def export_chrome_tracing(self, path: str):
        self._export_chrome(path)

    def export_metrics(self, path: str, fmt: str = "prometheus") -> str:
        """Write the observability default-registry snapshot to
        ``path`` (``fmt``: "prometheus" text exposition or "json") and
        return the serialized text — the metrics half of a run whose
        chrome/XPlane traces come from this same profiler."""
        from ..observability import default_registry
        reg = default_registry()
        text = reg.to_prometheus() if fmt == "prometheus" \
            else reg.to_json_str(indent=1)
        with open(path, "w") as f:
            f.write(text)
        return text

    def summary(self, sorted_by=SortedKeys.CPUTotal, op_detail=True,
                thread_sep=False, time_unit="ms"):
        with _events_lock:
            events = list(_events)
        agg = {}
        for e in events:
            st = agg.setdefault(e["name"], [0.0, 0, 0.0])
            st[0] += e["dur"] / 1000.0
            st[1] += 1
            st[2] = max(st[2], e["dur"] / 1000.0)
        rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Max(ms)':>12}"]
        for name, (total, calls, mx) in rows[:50]:
            lines.append(f"{name[:40]:<40}{calls:>8}{total:>12.3f}"
                         f"{mx:>12.3f}")
        table = "\n".join(lines)
        print(table)
        return table


class benchmark:
    """Throughput timer (reference: profiler/timer.py:351 Benchmark —
    step_info ips)."""

    def __init__(self):
        self._times = []
        self._t = None

    def begin(self):
        self._t = time.perf_counter()

    def step(self, num_samples=1):
        now = time.perf_counter()
        if self._t is not None:
            self._times.append((now - self._t, num_samples))
        self._t = now

    def step_info(self, unit="samples"):
        if not self._times:
            return "no steps recorded"
        dts = [t for t, _ in self._times]
        ns = [n for _, n in self._times]
        ips = sum(ns) / sum(dts)
        return (f"avg step {1000 * sum(dts) / len(dts):.2f} ms, "
                f"ips {ips:.1f} {unit}/s")

    def end(self):
        pass


class SummaryView:
    """profiler.SummaryView enum (profiler/profiler.py)."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(path: str):
    """Serialized-dump export hook (the reference dumps protobuf event
    trees; here the chrome-trace JSON is the canonical dump and this
    writes it at ``path``)."""
    def handler(prof):
        prof.export_chrome_tracing(path)
    return handler


__all__ += ["SummaryView", "export_protobuf"]
