"""Request-correlated spans + distributed trace propagation.

A ``span`` is the host-side annotation every instrumented layer opens
around its hot sections. One span goes three ways:

- into the JAX profiler's trace, as a ``jax.profiler.TraceAnnotation``
  carrying the span's scalar attributes, whenever ANY profiler session
  is live (``jax.profiler.start_trace``, TensorBoard's capture,
  ``profiler.Profiler``) — so the program's phases lie in the same
  ``.xplane.pb``, on the same clock, as the device's operations;
- into the chrome-trace host timeline while a ``profiler.Profiler`` is
  recording, attributes in the event's ``args`` (filter by
  ``args.request_id`` and one request's prefill/decode steps line up
  across engine iterations);
- into the process ring: a bounded :class:`TraceBuffer` of COMPLETED
  spans, installed by default on ``time.perf_counter``. Every record
  names its enclosing span (``parent``), so :func:`query` gives each
  span's self time; that is what the benchmark's ``program_span``
  reader and an operator in a debugger read.

Since the serving path spans PROCESSES (frontdoor → router → RPC →
worker engine), spans also participate in distributed tracing:

- :class:`TraceContext` — (trace_id, parent span id), minted per
  request at the router, pickled onto the request AND every cluster
  RPC frame (``serving/cluster.py`` puts the active context in each
  message, alongside the virtual clock), so worker-side engine spans
  parent correctly.
- :class:`TraceBuffer` — every ``Span.__exit__`` records ``{name, id,
  parent, t0, t1, pid, trace, attrs}`` into the installed buffer on
  the buffer's clock (cluster workers install their own with the
  engine's virtual-clock ``time_fn``). ``drain()`` hands the ring to
  the telemetry scrape; the cumulative ``drained_total`` /
  ``dropped_total`` counters let the merger detect a LOST scrape (or
  ring overflow) instead of silently truncating the timeline.
- request bindings (``bind_request``) — workers bind rid →
  TraceContext when a request arrives over RPC, so engine spans that
  only know a ``request_id`` resolve their trace without any engine
  code changes.

Cost with nothing listening: two clock reads, one ``is_enabled`` probe
of the profiler and one dict into the ring (PERF.md section 6 has the
measured microseconds).
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

__all__ = ["Span", "span", "TraceContext", "TraceBuffer",
           "install_trace_buffer", "current_trace_buffer",
           "bind_request", "unbind_request", "clear_bindings",
           "context_for", "active_context", "query"]

# the default ring holds a 50 s window of a serving cell twice over at
# the shortest step the engine can run on the chip, so that set-up's
# ``compile.*`` spans are still there when the window has closed: a
# decode program bound by its 9.7 ms of HBM time a step (PERF.md
# section 5) is ~5,200 steps x ~13 spans, ~67,000; about 80 MB when
# full; ``dropped_total`` says when it did not
DEFAULT_CAPACITY = 131072


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """Distributed trace identity carried across the RPC boundary.

    Plain picklable value: ``trace_id`` names the whole request
    lifecycle (one per router submit), ``parent_span_id`` the span
    that minted/forwarded it. Deterministic ids (``req-<rid>``) keep
    chaos episodes replayable."""

    trace_id: str
    parent_span_id: int = 0

    @classmethod
    def for_request(cls, rid: int,
                    parent_span_id: int = 0) -> "TraceContext":
        return cls(trace_id=f"req-{int(rid)}",
                   parent_span_id=int(parent_span_id))


class TraceBuffer:
    """Bounded thread-safe ring of completed-span records.

    ``time_fn`` is the clock spans are stamped on — a worker passes
    its engine clock so virtual-clock episodes produce clock-aligned
    records across processes. The cumulative counters make scrape
    loss detectable: ``recorded_total == drained_total +
    dropped_total + len(ring)`` always holds, and a consumer that
    tracks the ``drained_total`` it has ingested can tell when a
    drain it never saw happened in between."""

    def __init__(self, capacity: int = 2048,
                 time_fn: Callable[[], float] = time.monotonic):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.now = time_fn
        self._ring: deque = deque()
        self._lock = threading.Lock()
        self.recorded_total = 0
        self.drained_total = 0
        self.dropped_total = 0

    def record(self, rec: dict) -> None:
        with self._lock:
            self.recorded_total += 1
            if len(self._ring) >= self.capacity:
                self._ring.popleft()
                self.dropped_total += 1
            self._ring.append(rec)

    def drain(self) -> List[dict]:
        """Take everything recorded since the last drain (oldest
        first); bumps ``drained_total`` by the number returned."""
        with self._lock:
            out = list(self._ring)
            self._ring.clear()
            self.drained_total += len(out)
            return out

    def snapshot(self) -> List[dict]:
        """The ring as it stands (oldest first), nothing taken."""
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


# -- process-global wiring (buffer + rid bindings + active stack) -----

_buffer: Optional[TraceBuffer] = TraceBuffer(DEFAULT_CAPACITY,
                                             time.perf_counter)
_bindings: Dict[int, TraceContext] = {}
_bind_lock = threading.Lock()
_tls = threading.local()
_ids = itertools.count(1)
_pid = os.getpid()
_trace_me = None       # jax.profiler.TraceAnnotation, at first use
_profiler = None       # paddle_tpu.profiler, at first use


def _refresh_pid() -> None:
    global _pid
    _pid = os.getpid()


os.register_at_fork(after_in_child=_refresh_pid)


def _peers():
    """``(TraceAnnotation, profiler)``: imported at the first span, not
    with this module — profiler is a peer package and observability
    stays importable on its own."""
    global _trace_me, _profiler
    from jax.profiler import TraceAnnotation
    from .. import profiler
    _trace_me, _profiler = TraceAnnotation, profiler
    return _trace_me, _profiler


def install_trace_buffer(
        buf: Optional[TraceBuffer]) -> Optional[TraceBuffer]:
    """Install the process trace buffer (None uninstalls). Returns
    the previously installed buffer so callers can restore it."""
    global _buffer
    prev = _buffer
    _buffer = buf
    return prev


def current_trace_buffer() -> Optional[TraceBuffer]:
    return _buffer


def _now() -> float:
    """The installed ring's clock (``time.perf_counter`` by default)."""
    buf = _buffer
    return float(buf.now()) if buf is not None else time.perf_counter()


def bind_request(rid: int, ctx: Optional[TraceContext]) -> None:
    """rid → TraceContext: workers call this when a request arrives
    over RPC so engine spans (which only carry ``request_id``)
    resolve their trace id."""
    if ctx is None:
        return
    with _bind_lock:
        _bindings[int(rid)] = ctx


def unbind_request(rid: int) -> None:
    with _bind_lock:
        _bindings.pop(int(rid), None)


def clear_bindings() -> None:
    with _bind_lock:
        _bindings.clear()


def context_for(rid) -> Optional[TraceContext]:
    # no binding anywhere (every process but a cluster worker): no lock
    if rid is None or not _bindings:
        return None
    with _bind_lock:
        return _bindings.get(int(rid))


def has_bindings() -> bool:
    """True on a process whose requests carry trace contexts (a
    cluster worker): its batch spans list their request ids for the
    merged timeline's per-request lanes."""
    return bool(_bindings)


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def active_context() -> Optional[TraceContext]:
    """The context of the innermost open span that has one — what
    the cluster RPC client stamps on every outgoing frame."""
    st = _stack()
    return st[-1]._eff if st else None


_SCALARS = (int, float, str, bool)


class Span:
    """One host span: context manager, attributes, parent.

    ``set_attr`` may be called inside the span (attributes are read at
    exit, when the ring record and the chrome event are made; the
    profiler annotation carries the scalar ones known at entry).
    ``ctx`` attaches an explicit :class:`TraceContext`; without one,
    the request binding for ``attrs['request_id']`` and then the
    enclosing span's context are consulted. The completed span is
    recorded into the installed :class:`TraceBuffer` at exit (even when
    the body raised — a failed stage is still part of the timeline).
    """

    __slots__ = ("name", "ctx", "attrs", "id", "_parent", "_ann",
                 "_buf", "_t0", "_t0_ns", "_eff")

    def __init__(self, name: str, request_id: Optional[int] = None,
                 ctx: Optional[TraceContext] = None, **attrs: Any):
        self.name = name
        self.ctx = ctx
        if request_id is not None:
            attrs["request_id"] = request_id
        self.attrs: Dict[str, Any] = attrs
        self.id = 0
        self._parent = 0
        self._ann = None
        self._buf: Optional[TraceBuffer] = None
        self._t0 = 0.0
        self._t0_ns: Optional[int] = None
        self._eff: Optional[TraceContext] = None

    def set_attr(self, key: str, value: Any) -> "Span":
        self.attrs[key] = value
        return self

    def __enter__(self) -> "Span":
        trace_me, prof = (_trace_me, _profiler) if _profiler is not None \
            else _peers()
        st = _stack()
        parent = st[-1] if st else None
        self.id = next(_ids)
        self._parent = parent.id if parent is not None else 0
        self._eff = (self.ctx
                     or context_for(self.attrs.get("request_id"))
                     or (parent._eff if parent is not None else None))
        st.append(self)
        if trace_me.is_enabled():
            # live under ANY jax profiler session, not only ours
            self._ann = trace_me(self.name, **{
                k: v for k, v in self.attrs.items()
                if isinstance(v, _SCALARS)})
            self._ann.__enter__()
        if prof._is_recording():
            self._t0_ns = time.perf_counter_ns()
        buf = self._buf = _buffer
        if buf is not None:
            self._t0 = float(buf.now())
        return self

    def __exit__(self, *exc):
        buf = self._buf
        t1 = float(buf.now()) if buf is not None else 0.0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._t0_ns is not None:
            if _profiler._is_recording():
                _profiler._host_event(self.name, self._t0_ns,
                                      time.perf_counter_ns(), self.attrs)
            self._t0_ns = None
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        elif self in st:
            st.remove(self)
        if buf is not None:
            self._buf = None
            buf.record(_record(
                self.name, self.id, self._parent, self._t0, t1,
                self._eff, self.attrs,
                exc[0] if exc and exc[0] is not None else None))
        return False


def _record(name, sid, parent, t0, t1, eff, attrs, error=None) -> dict:
    rec = {"name": name, "id": sid, "parent": parent, "t0": t0,
           "t1": t1, "pid": _pid}
    if eff is not None:
        rec["trace"] = eff.trace_id
    if error is not None:
        rec["error"] = getattr(error, "__name__", str(error))
    if attrs:
        rec["attrs"] = dict(attrs)
    return rec


def span(name: str, request_id: Optional[int] = None,
         ctx: Optional[TraceContext] = None, **attrs: Any) -> Span:
    """Open a host span; ``request_id``/attrs flow into the ring, the
    profiler annotation and the chrome trace event's ``args``::

        with span("serving.prefill", request_id=req.rid, bucket=32):
            ...
    """
    return Span(name, request_id=request_id, ctx=ctx, **attrs)


def _record_span(name: str, t0: float, **attrs: Any) -> None:
    """``utils/compile_cache.Watched``'s way in, and nobody else's: a
    call is known to have compiled only once it is over, so its span,
    begun at ``t0`` (a reading of :func:`_now`) and ending now, is
    recorded after the fact as a child of the span that is open. It
    reaches the ring, not the profiler's trace (XLA's own compile
    events are there). Every phase known beforehand is a :class:`Span`.
    """
    buf = _buffer
    if buf is None:
        return
    st = _stack()
    parent = st[-1] if st else None
    buf.record(_record(
        name, next(_ids), parent.id if parent is not None else 0,
        float(t0), float(buf.now()),
        parent._eff if parent is not None else None, attrs))


def query(name: Optional[str] = None, t0: float = float("-inf"),
          t1: float = float("inf"),
          buffer: Optional[TraceBuffer] = None) -> dict:
    """Completed spans whose START lies in ``[t0, t1)`` on the ring's
    clock, by exact ``name`` or, with a trailing ``*``, by prefix
    (``"compile.*"``); ``None`` takes all. Each record is a copy with
    ``dur`` and ``self`` added: self time is the duration minus what
    the span's children cover. ``dropped_total`` above 0 says the ring
    overflowed since it was installed: the oldest spans (children end
    before their parents) are gone, and counts and self times from
    before that point are too low or too high.

    A read for a debugger, a notebook or the benchmark's reader —
    nothing is drained and nothing exported."""
    buf = buffer if buffer is not None else _buffer
    if buf is None:
        return {"spans": [], "dropped_total": 0, "recorded_total": 0}
    recs = buf.snapshot()
    covered: Dict[int, float] = {}
    for r in recs:
        p = r.get("parent")
        if p:
            covered[p] = covered.get(p, 0.0) + (r["t1"] - r["t0"])
    prefix = name[:-1] if name is not None and name.endswith("*") \
        else None
    out = []
    for r in recs:
        if not t0 <= r["t0"] < t1:
            continue
        if prefix is not None:
            if not r["name"].startswith(prefix):
                continue
        elif name is not None and r["name"] != name:
            continue
        dur = r["t1"] - r["t0"]
        out.append(dict(r, dur=dur, self=max(
            0.0, dur - covered.get(r.get("id"), 0.0))))
    return {"spans": out, "dropped_total": buf.dropped_total,
            "recorded_total": buf.recorded_total}
