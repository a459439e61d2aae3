"""The serving front door: streaming client API over an engine or a
replica router, chaos-certified at the boundary where clients sit.

Everything below this module is a library; this is the piece that
speaks to a client. Two layers, separable on purpose:

- :class:`FrontDoor` — transport-independent core: per-tenant
  admission (token-bucket rate limits + per-tenant in-flight caps →
  typed :class:`RateLimited` / :class:`TenantQueueFull`), deadline
  forwarding into the engine's ``deadline_s`` path, token streaming
  onto :class:`ClientStream` objects, client-disconnect propagation
  (a failed stream write, or the ``frontdoor.client_disconnect``
  probe, flags ``Request.cancel_requested`` — the engine cancels at
  the next safe point, unwinding claimed KV pages via the paged abort
  path), and the **conservation auditor mount**: ``on_attempt`` /
  ``on_submitted`` / ``on_rejected`` / ``on_delivered`` fire at THIS
  external boundary, so the chaos ledger audits exactly-once delivery
  end-to-end through the router, not just per engine.
- :class:`FrontDoorHTTPServer` — a stdlib-only (``http.server``)
  HTTP/SSE binding: ``POST /v1/generate`` (``"stream": true`` →
  ``text/event-stream`` token events; else one JSON response),
  ``GET /healthz`` (router replica states), ``GET /metrics``
  (Prometheus exposition), ``DELETE /v1/requests/<rid>``. A broken
  client socket mid-stream cancels the request in the engine.

The core is driven by ``pump()`` — one backend step + event routing —
so chaos episodes and benchmarks run it single-threaded on a virtual
clock (deterministic, sleep-free), while the HTTP server runs the
same loop on a background thread.

Fault points: ``frontdoor.stream_write`` (a token/final write to the
client fails — treated as the client going away) and
``frontdoor.client_disconnect`` (the liveness probe finds the client
gone — including MID-prefill, after KV pages are claimed).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from ..observability import default_recorder, default_registry, span
from ..resilience.faults import maybe_fail
from .errors import (EngineClosed, QueueFull, RateLimited,
                     ServingError, Shed, TenantQueueFull)
from .sampling import SamplingParams
from .scheduler import Request

__all__ = ["TenantPolicy", "TokenBucket", "ClientStream",
           "FrontDoorHandle", "FrontDoor", "FrontDoorHTTPServer"]


@dataclasses.dataclass
class TenantPolicy:
    """Admission envelope for one tenant: sustained ``rate_qps`` with
    ``burst`` headroom (None = unlimited), and at most
    ``max_inflight`` accepted-but-unfinished requests (None =
    unbounded). Tenant isolation is the point: one tenant's backlog
    or arrival spike cannot starve the others' admission."""
    rate_qps: Optional[float] = None
    burst: int = 8
    max_inflight: Optional[int] = None
    # priority tier (0 = highest): under brownout the control plane
    # sheds the highest-numbered tiers first; tier 0 is never shed
    priority: int = 0


class TokenBucket:
    """Seeded-clock token bucket (``time_fn`` injectable so chaos and
    benchmarks run it on a virtual timeline)."""

    def __init__(self, rate: float, burst: int,
                 time_fn: Callable[[], float]):
        self.rate = float(rate)
        self.burst = float(burst)
        self._now = time_fn
        self._tokens = float(burst)
        self._t_last = time_fn()

    def _refill(self) -> None:
        t = self._now()
        self._tokens = min(self.burst,
                           self._tokens + (t - self._t_last) * self.rate)
        self._t_last = t

    def try_take(self, n: float = 1.0) -> bool:
        self._refill()
        if self._tokens >= n:
            self._tokens -= n
            return True
        return False

    def retry_after_s(self, n: float = 1.0) -> float:
        self._refill()
        need = n - self._tokens
        return max(0.0, need / self.rate) if self.rate > 0 else 0.0


class ClientStream:
    """Server-side half of one client connection: ``write(event)`` is
    called by the pump (engine loop); readers (the HTTP handler
    thread, or a test) block on ``next_event``. A transport that can
    fail writes subclasses ``write`` to raise — the front door treats
    any write failure as the client being gone."""

    def __init__(self):
        self._events: deque = deque()        # guarded-by: _cond
        self._cond = threading.Condition()
        self.closed = False                  # guarded-by: _cond

    def write(self, event: dict) -> None:
        with self._cond:
            self._events.append(event)
            self._cond.notify_all()

    def close(self) -> None:
        with self._cond:
            self.closed = True
            self._cond.notify_all()

    def next_event(self, timeout: Optional[float] = None) \
            -> Optional[dict]:
        """Pop the next event, blocking up to ``timeout``; None when
        closed-and-empty or on timeout."""
        with self._cond:
            while not self._events and not self.closed:
                if not self._cond.wait(timeout=timeout):
                    return None
            return self._events.popleft() if self._events else None

    def events(self) -> List[dict]:
        with self._cond:
            return list(self._events)

    def drained(self) -> bool:
        """True when closed AND nothing is left to deliver — the SSE
        loop's locked exit probe (one lock round for what would
        otherwise be two racy reads)."""
        with self._cond:
            return self.closed and not self._events


class FrontDoorHandle:
    """One accepted request as the front door tracks it."""

    def __init__(self, req: Request, stream: Optional[ClientStream],
                 tenant: str):
        self.req = req
        self.stream = stream
        self.tenant = tenant
        self.sent = 0                  # tokens already written out
        self.disconnected = False
        self.finished = False

    @property
    def rid(self) -> int:
        return self.req.rid


class FrontDoor:
    """Transport-independent serving front door (module docstring)."""

    def __init__(self, backend, *,
                 default_policy: Optional[TenantPolicy] = None,
                 tenants: Optional[Dict[str, TenantPolicy]] = None,
                 auditor=None, registry=None, flight_recorder=None,
                 telemetry=None, watchtower=None, control=None,
                 time_fn: Callable[[], float] = time.monotonic):
        self.backend = backend
        self.default_policy = default_policy or TenantPolicy()
        self.tenant_policies = dict(tenants or {})
        self.auditor = auditor
        # serving.control.ControlPlane (optional): pump() feeds it the
        # backend depth + TTFT burn each iteration; submit() asks it
        # whether to shed (an audited typed rejection, never a LOST
        # request); a router backend gets autoscaled through it
        self.control = control
        self.now = time_fn
        self.registry = registry if registry is not None \
            else default_registry()
        # observability.ClusterTelemetry (optional): when the backend
        # is a cluster, /metrics serves the CLUSTER-merged exposition
        # (workers + router + this registry) instead of host-only
        self.telemetry = telemetry
        if telemetry is not None:
            telemetry.add_host_registry(self.registry,
                                        name="frontdoor")
        # observability.Watchtower (optional): pump() polls it (cheap
        # clock-compare between window boundaries) and the HTTP
        # binding serves its /healthz verdict + /incidents payload
        self.watchtower = watchtower
        self.recorder = flight_recorder if flight_recorder is not None \
            else default_recorder()
        self._handles: Dict[int, FrontDoorHandle] = {}  # guarded-by: _lock
        self._tenant_depth: Dict[str, int] = {}         # guarded-by: _lock
        self._buckets: Dict[str, TokenBucket] = {}      # guarded-by: _lock
        self._closed = False                            # guarded-by: _lock
        self._consecutive_pump_failures = 0             # guarded-by: _lock
        # serialize core entry points: the engine below is not thread-
        # safe, and the HTTP binding calls in from handler threads
        # while the pump loop runs on another
        self._lock = threading.RLock()
        reg = self.registry
        self._m_depth = reg.gauge(
            "ptpu_frontdoor_tenant_depth",
            "accepted-but-unfinished requests per tenant",
            labels=("tenant",))
        self._m_reject = reg.counter(
            "ptpu_frontdoor_rejected_total",
            "submissions refused at the front door",
            labels=("reason", "tier"))
        self._m_accept = reg.counter(
            "ptpu_frontdoor_accepted_total",
            "submissions accepted", labels=("tenant",))
        self._m_stream_ev = reg.counter(
            "ptpu_frontdoor_stream_events_total",
            "events written to client streams")
        self._m_disconnect = reg.counter(
            "ptpu_frontdoor_disconnects_total",
            "client connections observed gone")
        # client-disconnect propagation: the engine evaluates this
        # probe at its safe cancellation points (step-boundary sweep
        # and MID-prefill, after KV pages are claimed)
        if hasattr(backend, "cancel_probe"):
            backend.cancel_probe = self._client_gone

    # -- metrics --------------------------------------------------------
    def metrics_exposition(self) -> str:
        """The text served from ``/metrics``: the cluster-merged
        exposition when a :class:`ClusterTelemetry` is attached
        (counters summed across workers, gauges worker-labeled,
        histograms bucket-merged), else this process's registry."""
        if self.telemetry is not None:
            return self.telemetry.merged_prometheus()
        return self.registry.to_prometheus()

    # -- admission -----------------------------------------------------
    def _policy(self, tenant: str) -> TenantPolicy:
        return self.tenant_policies.get(tenant, self.default_policy)

    # requires-lock: _lock
    def _bucket(self, tenant: str) -> Optional[TokenBucket]:
        pol = self._policy(tenant)
        if pol.rate_qps is None:
            return None
        b = self._buckets.get(tenant)
        if b is None:
            b = TokenBucket(pol.rate_qps, pol.burst, self.now)
            self._buckets[tenant] = b
        return b

    def _reject(self, tenant: str, reason: str, tier: int = 0) -> None:
        self._m_reject.labels(reason=reason, tier=str(tier)).inc()
        if self.auditor is not None \
                and hasattr(self.auditor, "on_rejected"):
            self.auditor.on_rejected(tenant=tenant, reason=reason)

    def submit(self, prompt_ids, max_new_tokens: int = 16, *,
               tenant: str = "default",
               deadline_s: Optional[float] = None,
               sampling: Optional[SamplingParams] = None,
               stream: Optional[ClientStream] = None) \
            -> FrontDoorHandle:
        """Admit one client request. Every call gets exactly one
        outcome — an accepted handle (whose request the ledger then
        tracks to exactly-once delivery) or a typed refusal (audited
        via ``on_rejected``); the attempt itself is audited first, so
        the ledger can prove no request vanished at the boundary."""
        with self._lock:
            if self.auditor is not None \
                    and hasattr(self.auditor, "on_attempt"):
                self.auditor.on_attempt()
            pol = self._policy(tenant)
            tier = int(getattr(pol, "priority", 0))
            if self._closed:
                self._reject(tenant, "closed", tier)
                raise EngineClosed()
            if self.control is not None \
                    and self.control.maybe_shed(tier, tenant=tenant):
                # brownout: an AUDITED rejection at the boundary — the
                # attempt above plus this on_rejected keep the ledger's
                # admission law balanced (shed is never a LOST request)
                self._reject(tenant, "shed", tier)
                raise Shed(tenant, tier, self.control.retry_after_s())
            depth = self._tenant_depth.get(tenant, 0)
            if pol.max_inflight is not None \
                    and depth >= pol.max_inflight:
                self._reject(tenant, "tenant_queue_full", tier)
                raise TenantQueueFull(tenant, depth, pol.max_inflight)
            bucket = self._bucket(tenant)
            if bucket is not None and not bucket.try_take():
                self._reject(tenant, "rate_limited", tier)
                raise RateLimited(tenant, bucket.retry_after_s())
            try:
                req = self.backend.submit(
                    prompt_ids, max_new_tokens, sampling=sampling,
                    deadline_s=deadline_s, tenant=tenant)
            except QueueFull:
                self._reject(tenant, "queue_full", tier)
                raise
            except ServingError:
                self._reject(tenant, "unavailable", tier)
                raise
            except ValueError:
                self._reject(tenant, "invalid", tier)
                raise
            except Exception:
                # dispatch-path crash (router.dispatch fault): nothing
                # was half-submitted — a typed refusal to the caller
                self._reject(tenant, "dispatch_error", tier)
                raise
            req.priority = tier
            handle = FrontDoorHandle(req, stream, tenant)
            self._handles[req.rid] = handle
            self._tenant_depth[tenant] = depth + 1
            self._m_depth.labels(tenant=tenant).set(depth + 1)
            self._m_accept.labels(tenant=tenant).inc()
            if self.auditor is not None:
                self.auditor.on_submitted(req)
            return handle

    # -- disconnect propagation ---------------------------------------
    # the engine evaluates this probe inside backend.step(), which
    # only ever runs under pump()'s lock:
    # requires-lock: _lock
    def _client_gone(self, req: Request) -> bool:
        """Engine-side liveness probe (installed as ``cancel_probe``):
        True = nobody is listening to this request anymore."""
        h = self._handles.get(req.rid)
        if h is None:
            return False
        if h.disconnected:
            return True
        try:
            maybe_fail("frontdoor.client_disconnect", rid=req.rid,
                       tenant=h.tenant)
        except Exception:
            self._on_disconnect(h)
            return True
        return False

    # requires-lock: _lock
    def _on_disconnect(self, h: FrontDoorHandle) -> None:
        if h.disconnected:
            return
        h.disconnected = True
        h.req.cancel_requested = True
        self._m_disconnect.inc()
        if h.stream is not None:
            try:
                h.stream.close()
            except Exception:
                pass

    def disconnect(self, handle: FrontDoorHandle) -> None:
        """The transport observed the client gone (broken socket).
        The engine cancels at its next safe point; the request still
        surfaces through ``pump()`` exactly once (via='disconnect')."""
        with self._lock:
            self._on_disconnect(handle)

    def get_handle(self, rid: int) -> Optional[FrontDoorHandle]:
        """Locked handle lookup for transport threads (the DELETE
        handler resolves rid -> handle through this, never by reading
        ``_handles`` directly from its own thread)."""
        with self._lock:
            return self._handles.get(rid)

    def cancel(self, handle: FrontDoorHandle,
               reason: str = "cancelled") -> bool:
        """Explicit client cancellation (DELETE); returns False if the
        request already finished."""
        with self._lock:
            if handle.finished:
                return False
            if self.backend.cancel(handle.req, reason):
                self._finish(handle.req, [], via="cancel")
                return True
            return False

    # -- the serving loop ---------------------------------------------
    def pump(self) -> List[Request]:
        """One front-door iteration: one backend step, then route
        tokens/results to client streams and audit deliveries. Returns
        the requests that reached the client this call."""
        with span("frontdoor.pump"):
            out = self._pump_locked()
        # watchtower evaluation runs OUTSIDE the lock: between window
        # boundaries this is one clock read; at a boundary it reads
        # registry snapshots, which are internally synchronized
        wt = self.watchtower
        if wt is not None:
            wt.poll()
        return out

    # requires-lock: _lock
    def _backend_depth(self) -> float:
        """Queued + in-flight work the control plane regulates on: the
        sum of dispatchable replica loads for a router backend, else
        the engine's queue depth + active slots."""
        b = self.backend
        reps = getattr(b, "replicas", None)
        if reps is not None:
            return float(sum(r.load() for r in reps if r.dispatchable))
        sched = getattr(b, "scheduler", None)
        if sched is None:
            return 0.0
        cache = getattr(b, "cache", None)
        active = len(cache.active_slots()) if cache is not None else 0
        return float(sched.depth + active)

    # requires-lock: _lock
    def _ttft_burn(self) -> float:
        """Fast-window TTFT burn rate from the attached watchtower
        (0.0 without one — the brownout then runs on depth alone)."""
        wt = self.watchtower
        if wt is None:
            return 0.0
        try:
            rates = wt.burn_rates()
        except Exception:
            return 0.0
        burn = 0.0
        for name, w in rates.items():
            if "ttft" in name:
                burn = max(burn, float(w.get("fast", 0.0)))
        return burn

    def _pump_locked(self) -> List[Request]:
        with self._lock:
            cp = self.control
            if cp is not None:
                # controllers step BEFORE the idle early-return so the
                # brownout decays (and the autoscaler can scale down)
                # while the backend is empty
                cp.on_step(self._backend_depth(), self._ttft_burn())
                if hasattr(self.backend, "replicas"):
                    cp.maybe_scale(self.backend)
            if not self.backend.has_work():
                return []
            try:
                done = self.backend.step()
                self._consecutive_pump_failures = 0
            except Exception:
                # a router backend absorbs replica failures itself; a
                # bare-engine backend can break — recover() it, else
                # count the transient (the engine re-queued the
                # faulted request) and let the next pump retry
                self._consecutive_pump_failures += 1
                if getattr(self.backend, "_broken", None):
                    try:
                        done = self.backend.recover()["finished"]
                        self._consecutive_pump_failures = 0
                    except Exception:
                        return []
                else:
                    return []
            with span("frontdoor.deliver") as dsp:
                written = self._m_stream_ev.value
                self._route_tokens()
                out: List[Request] = []
                for req in done:
                    self._finish(req, out)
                dsp.set_attr(
                    "events", int(self._m_stream_ev.value - written))
            return out

    # requires-lock: _lock
    def _push(self, h: FrontDoorHandle, event: dict) -> bool:
        try:
            maybe_fail("frontdoor.stream_write", rid=h.req.rid)
            h.stream.write(event)
        except Exception:
            # broken pipe: the client is gone — cancellation
            # propagates through the engine's next safe point
            self._on_disconnect(h)
            return False
        self._m_stream_ev.inc()
        return True

    # requires-lock: _lock
    def _route_tokens(self) -> None:
        for h in list(self._handles.values()):
            if h.stream is None or h.disconnected:
                continue
            toks = h.req.out_tokens
            while h.sent < len(toks):
                if not self._push(h, {"event": "token",
                                      "rid": h.req.rid,
                                      "index": h.sent,
                                      "token": int(toks[h.sent])}):
                    break
                h.sent += 1

    # requires-lock: _lock
    def _finish(self, req: Request, out: List[Request],
                via: Optional[str] = None) -> None:
        h = self._handles.pop(req.rid, None)
        if h is None:
            # not front-door traffic (or already finished): backends
            # deliver exactly once, so nothing to do
            return
        h.finished = True
        depth = self._tenant_depth.get(h.tenant, 1) - 1
        self._tenant_depth[h.tenant] = depth
        self._m_depth.labels(tenant=h.tenant).set(depth)
        if h.stream is not None and not h.disconnected:
            self._push(h, {
                "event": "done", "rid": req.rid,
                "finish_reason": req.finish_reason,
                "output_ids": req.output_ids,
                "error": (f"{type(req.error).__name__}: {req.error}"
                          if req.error is not None else None)})
        if h.stream is not None:
            h.stream.close()
        if via is None:
            via = "disconnect" if h.disconnected else \
                ("stream" if h.stream is not None else "response")
        if self.auditor is not None:
            self.auditor.on_delivered(req, via=via)
        out.append(req)

    def has_work(self) -> bool:
        return self.backend.has_work()

    def run_until_idle(self, max_steps: int = 10000) -> List[Request]:
        out: List[Request] = []
        steps = 0
        while self.has_work() and steps < max_steps:
            out.extend(self.pump())
            steps += 1
            with self._lock:
                failures = self._consecutive_pump_failures
            if failures >= 10:
                break
        return out

    def drain(self, max_steps: Optional[int] = None) -> List[Request]:
        """Graceful shutdown: refuse new submissions, keep streaming
        until the backend empties (or ``max_steps`` / repeated pump
        failures cut it off), then let the backend's own ``drain()``
        cancel the remainder — every accepted request still reaches
        its client-facing terminal event exactly once."""
        with self._lock:
            self._closed = True
            out: List[Request] = []
            steps = 0
            failures0 = self._consecutive_pump_failures
            while self.backend.has_work():
                if max_steps is not None and steps >= max_steps:
                    break
                if self._consecutive_pump_failures - failures0 >= 3:
                    break
                out.extend(self.pump())
                steps += 1
            for req in self.backend.drain(max_steps=0):
                self._finish(req, out, via="drain")
            return out


# ---------------------------------------------------------------------------
# stdlib HTTP/SSE binding
# ---------------------------------------------------------------------------

class FrontDoorHTTPServer:
    """``http.server``-based binding (no dependencies by design):

    - ``POST /v1/generate`` — body ``{"prompt_ids": [...],
      "max_new_tokens": N, "stream": bool, "tenant": str,
      "deadline_s": float}``. Streaming responses are Server-Sent
      Events (``data: {json}\\n\\n`` per token, then a ``done``
      event); unary responses are one JSON object. Typed refusals map
      to HTTP: 429 (rate limit / queues full, Retry-After header),
      503 (shed at brownout — Retry-After from the controller — /
      broken / no replicas / closed), 400 (validation).
    - ``GET /healthz`` — backend health (router replica states).
    - ``GET /metrics`` — Prometheus text exposition; cluster-merged
      across workers when a ``ClusterTelemetry`` is attached.
    - ``DELETE /v1/requests/<rid>`` — cancel.

    One background thread runs the pump loop; handler threads only
    touch the front door through its lock. A client socket that dies
    mid-stream surfaces as a failed SSE write in the handler thread →
    ``front.disconnect()`` → engine cancellation (KV pages unwound)."""

    def __init__(self, front: FrontDoor, host: str = "127.0.0.1",
                 port: int = 0, pump_interval_s: float = 0.002):
        import http.server
        import json as _json

        self.front = front
        self._stop = threading.Event()
        self._pump_interval_s = pump_interval_s
        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):   # quiet by default
                pass

            def _json_response(self, code: int, obj: dict,
                               retry_after=None) -> None:
                body = _json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                if retry_after is not None:
                    # RFC 9110 delta-seconds (integer, >= 1 so an
                    # immediate-retry hint still reads as a real delay)
                    self.send_header(
                        "Retry-After",
                        str(max(1, int(float(retry_after) + 0.999))))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    backend = outer.front.backend
                    health = backend.health() \
                        if hasattr(backend, "health") else {}
                    ok = (not health) or any(
                        h["state"] == "healthy"
                        for h in health.values())
                    payload = {"ok": ok, "replicas": health}
                    wt = outer.front.watchtower
                    if wt is not None:
                        w = wt.healthz()
                        payload["watchtower"] = w
                        payload["ok"] = ok = bool(ok and w["ok"])
                    self._json_response(
                        200 if ok else 503, payload)
                elif self.path == "/incidents":
                    wt = outer.front.watchtower
                    if wt is None:
                        self._json_response(
                            404, {"error": "no watchtower attached"})
                    else:
                        self._json_response(200, wt.to_json())
                elif self.path == "/metrics":
                    body = outer.front.metrics_exposition() \
                        .encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self._json_response(404, {"error": "not found"})

            def do_DELETE(self):
                parts = self.path.rstrip("/").split("/")
                if len(parts) == 4 and parts[1] == "v1" \
                        and parts[2] == "requests":
                    try:
                        rid = int(parts[3])
                    except ValueError:
                        self._json_response(400,
                                            {"error": "bad rid"})
                        return
                    h = outer.front.get_handle(rid)
                    ok = h is not None and outer.front.cancel(h)
                    self._json_response(200 if ok else 404,
                                        {"cancelled": ok, "rid": rid})
                else:
                    self._json_response(404, {"error": "not found"})

            def do_POST(self):
                if self.path != "/v1/generate":
                    self._json_response(404, {"error": "not found"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    body = _json.loads(self.rfile.read(n) or b"{}")
                    prompt = body["prompt_ids"]
                except Exception as e:
                    self._json_response(
                        400, {"error": f"bad request: {e}"})
                    return
                stream = ClientStream() if body.get("stream") \
                    else None
                from . import errors as E
                try:
                    handle = outer.front.submit(
                        prompt,
                        int(body.get("max_new_tokens", 16)),
                        tenant=str(body.get("tenant", "default")),
                        deadline_s=body.get("deadline_s"),
                        stream=stream)
                except E.Shed as e:
                    # brownout rejection: overload semantics (503),
                    # with the controller's deterministic retry hint
                    self._json_response(
                        503, {"error": "Shed", "detail": str(e),
                              "tier": e.tier},
                        retry_after=e.retry_after_s)
                    return
                except (E.RateLimited, E.TenantQueueFull,
                        E.QueueFull) as e:
                    self._json_response(
                        429, {"error": type(e).__name__,
                              "detail": str(e)},
                        retry_after=getattr(e, "retry_after_s", 1.0))
                    return
                except ValueError as e:
                    self._json_response(
                        400, {"error": "ValueError", "detail": str(e)})
                    return
                except Exception as e:  # noqa: BLE001 — typed 503 tail
                    self._json_response(
                        503, {"error": type(e).__name__,
                              "detail": str(e)})
                    return
                outer._kick()
                if stream is None:
                    self._unary(handle)
                else:
                    self._sse(handle, stream)

            def _unary(self, handle):
                while not handle.finished \
                        and not outer._stop.is_set():
                    outer._done_cond_wait()
                req = handle.req
                self._json_response(200, {
                    "rid": req.rid,
                    "output_ids": req.output_ids,
                    "finish_reason": req.finish_reason,
                    "error": (f"{type(req.error).__name__}: "
                              f"{req.error}"
                              if req.error is not None else None)})

            def _sse(self, handle, stream):
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self.end_headers()
                while True:
                    ev = stream.next_event(timeout=0.05)
                    if ev is None:
                        if stream.drained():
                            break
                        if outer._stop.is_set():
                            break
                        continue
                    try:
                        self.wfile.write(
                            b"data: " + _json.dumps(ev).encode()
                            + b"\n\n")
                        self.wfile.flush()
                    except Exception:
                        # client socket is gone: propagate into the
                        # engine (cancel at the next safe point)
                        outer.front.disconnect(handle)
                        break
                    if ev.get("event") == "done":
                        break
                try:
                    self.wfile.flush()
                except Exception:
                    pass
                self.close_connection = True

        class Server(http.server.ThreadingHTTPServer):
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.host, self.port = self._server.server_address[:2]
        self._done_cond = threading.Condition()
        self._serve_thread = threading.Thread(
            target=self._server.serve_forever, name="frontdoor-http",
            daemon=True)
        self._pump_thread = threading.Thread(
            target=self._pump_loop, name="frontdoor-pump",
            daemon=True)

    def _kick(self) -> None:
        with self._done_cond:
            self._done_cond.notify_all()

    def _done_cond_wait(self, timeout: float = 0.05) -> None:
        with self._done_cond:
            self._done_cond.wait(timeout=timeout)

    def _pump_loop(self) -> None:
        while not self._stop.is_set():
            if self.front.has_work():
                done = self.front.pump()
                if done:
                    self._kick()
            else:
                self._done_cond_wait(self._pump_interval_s)

    def start(self) -> "FrontDoorHTTPServer":
        self._serve_thread.start()
        self._pump_thread.start()
        return self

    def shutdown(self, drain: bool = True) -> None:
        if drain:
            try:
                self.front.drain()
            except Exception:
                pass
        self._stop.set()
        self._kick()
        self._server.shutdown()
        self._server.server_close()
        self._serve_thread.join(timeout=5)
        self._pump_thread.join(timeout=5)

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"
