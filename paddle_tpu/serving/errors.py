"""Typed serving-engine errors (the engine's failure contract).

Callers branch on these instead of parsing RuntimeError strings:

- :class:`QueueFull` — ``submit()`` with the bounded admission queue at
  ``max_queue``; shed load or apply backpressure upstream.
- :class:`DeadlineExceeded` — a request missed its deadline: set as
  ``Request.error`` (with ``finish_reason == "deadline"``) when the
  engine cancels a queued or in-flight request at a step boundary.
  Never raised by ``submit()`` — whether a deadline is meetable
  depends on the queue ahead of it (a non-positive ``deadline_s`` is a
  ``ValueError``).
- :class:`EngineBroken` — ``step()``/``submit()`` after a step failed
  with donated cache pools; call ``recover()`` to rebuild and resume.
- :class:`EngineIdle` — ``step()`` with no queued or in-flight work
  (guard loops with ``has_work()``).
- :class:`EngineClosed` — ``submit()`` after ``drain()``.
- :class:`StateCacheUnsupported` — ``ServingEngine(...)`` was given an
  option that reads, shares or moves K and V by position (paged layout,
  prefix sharing, int8 KV, speculation, the KV tier or wire, a mesh,
  chunked prefill) for a model that keeps a fixed-size recurrent state
  a slot instead; refused at construction, never silently ignored.
- :class:`RequestCancelled` — set as ``Request.error`` by
  ``cancel()``/``drain(max_steps=...)`` cutoffs, and (with reason
  ``"disconnect"``) when the front door observes the client gone.

Front-door / router additions (serving/frontdoor.py, serving/router.py):

- :class:`RateLimited` — a tenant exceeded its token-bucket rate; the
  carried ``retry_after_s`` is the earliest the bucket refills.
- :class:`TenantQueueFull` — a tenant hit its per-tenant in-flight cap
  (tenant isolation: one tenant's backlog cannot starve the others).
- :class:`Shed` — the brownout controller rejected a low-priority
  request under overload (serving/control.py); an *audited* rejection
  at the client boundary (HTTP 503 + Retry-After), never a LOST
  request.
- :class:`ReplicaDead` — a replica is gone (health probe, or raised
  out of a dying replica's step); the router fails its in-flight
  requests over to peers.
- :class:`NoHealthyReplicas` — the router has no live replica to
  dispatch to; shed load upstream.
"""
from __future__ import annotations

__all__ = ["ServingError", "QueueFull", "DeadlineExceeded",
           "EngineBroken", "EngineIdle", "EngineClosed",
           "RequestCancelled", "StateCacheUnsupported", "RateLimited", "TenantQueueFull",
           "Shed", "ReplicaDead", "NoHealthyReplicas", "RemoteError"]


def _rebuild_error(cls, args, attrs):
    # bypass the subclass __init__ (whose signature is structured, not
    # (message,)): restore message via RuntimeError and attributes
    # (rid, tenant, retry_after_s, ...) from __dict__
    e = cls.__new__(cls)
    RuntimeError.__init__(e, *args)
    e.__dict__.update(attrs)
    return e


class ServingError(RuntimeError):
    """Base class for the serving engine's typed failures.

    Pickle-safe by construction: these cross the serving-cluster RPC
    boundary (serving/cluster.py ships a worker's typed refusal back
    to the router), and default exception pickling would call the
    subclass ``__init__`` with the formatted message — a TypeError for
    every subclass with a structured signature.
    """

    def __reduce__(self):
        return _rebuild_error, (type(self), self.args, dict(self.__dict__))


class QueueFull(ServingError):
    def __init__(self, depth: int, max_queue: int):
        super().__init__(
            f"admission queue full ({depth} waiting >= max_queue="
            f"{max_queue}); retry later or raise max_queue")
        self.depth = depth
        self.max_queue = max_queue


class DeadlineExceeded(ServingError):
    def __init__(self, rid, detail: str = ""):
        super().__init__(
            f"request {rid} missed its deadline"
            + (f": {detail}" if detail else ""))
        self.rid = rid


class EngineBroken(ServingError):
    def __init__(self, reason: str):
        super().__init__(
            f"ServingEngine is broken (a step failed after its cache "
            f"pools were donated — device buffers invalidated): "
            f"{reason}. Call recover() to rebuild the KV pools from "
            f"host-side request state and resume; the flight-recorder "
            f"dump has the post-mortem.")
        self.reason = reason


class EngineIdle(ServingError):
    def __init__(self):
        super().__init__(
            "step() called with no queued or in-flight work; guard the "
            "loop with has_work()")


class EngineClosed(ServingError):
    def __init__(self):
        super().__init__(
            "ServingEngine is draining/closed; submit() refused")


class StateCacheUnsupported(ServingError):
    def __init__(self, option: str, value=None):
        super().__init__(
            f"{option}={value!r} needs K and V by position; this model "
            f"keeps a fixed-size recurrent state a slot, on which it "
            f"is not supported yet")
        self.option = option


class RequestCancelled(ServingError):
    def __init__(self, rid, reason: str = "cancelled"):
        super().__init__(f"request {rid} cancelled: {reason}")
        self.rid = rid


class RateLimited(ServingError):
    def __init__(self, tenant: str, retry_after_s: float = 0.0):
        super().__init__(
            f"tenant {tenant!r} rate-limited; retry in "
            f"{retry_after_s:.3f}s")
        self.tenant = tenant
        self.retry_after_s = retry_after_s


class TenantQueueFull(ServingError):
    def __init__(self, tenant: str, depth: int, max_inflight: int):
        super().__init__(
            f"tenant {tenant!r} has {depth} requests in flight "
            f">= max_inflight={max_inflight}")
        self.tenant = tenant
        self.depth = depth
        self.max_inflight = max_inflight


class Shed(ServingError):
    def __init__(self, tenant: str, tier: int,
                 retry_after_s: float = 0.0):
        super().__init__(
            f"tenant {tenant!r} shed at brownout (tier {tier}); "
            f"retry in {retry_after_s:.3f}s")
        self.tenant = tenant
        self.tier = tier
        self.retry_after_s = retry_after_s


class ReplicaDead(ServingError):
    def __init__(self, detail: str = ""):
        super().__init__(
            "replica is dead" + (f": {detail}" if detail else ""))
        self.detail = detail


class NoHealthyReplicas(ServingError):
    def __init__(self, total: int):
        super().__init__(
            f"no healthy replica to dispatch to ({total} registered, "
            f"all draining or dead)")
        self.total = total


class RemoteError(ServingError):
    """A cluster worker raised an exception that cannot itself cross
    the pickle boundary (unknown type, unpicklable payload); carries
    the type name and rendered message instead."""

    def __init__(self, type_name: str, detail: str):
        super().__init__(f"worker raised {type_name}: {detail}")
        self.type_name = type_name
        self.detail = detail
