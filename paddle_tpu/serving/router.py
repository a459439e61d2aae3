"""Multi-replica request router: health-driven dispatch, draining,
and failover with exactly-once delivery through replica death.

A single :class:`~paddle_tpu.serving.engine.ServingEngine` process is
a single point of failure — the resilience machinery below it
(``recover()``, typed errors, conservation auditing) survives a failed
*step*, but not a dead *replica*. The router closes that gap: it
spreads requests across N engine replicas (least-loaded dispatch,
FCFS within a replica) and keeps serving through whole-replica death:

- **Health-driven draining.** Every ``step()`` round probes each
  replica first. One failed probe marks the replica SUSPECT — it
  keeps serving its in-flight work but receives no new dispatches
  (draining); ``probe_fail_threshold`` consecutive failures, or a
  :class:`ReplicaDead` raised from a probe or a step, declare it DEAD.
- **Failover = adoption.** A dead replica's requests are re-homed from
  the router's own bookkeeping (the host-side ``Request`` objects it
  dispatched): terminal requests the replica finished but never
  returned are delivered now; everything else is ``adopt()``-ed by a
  live peer, whose admission path re-prefills prompt + already-
  delivered tokens via the ``recover()`` replay contract — greedy
  outputs stay token-identical through the death, and no delivered
  token is ever retracted. With no live peer left, requests are
  cancelled (typed error attached) rather than stranded.
- **Exactly-once.** The router delivers a request to its caller
  exactly once: every path out (step return, recover report, failover,
  drain) funnels through one ``_deliver`` gate keyed on the router's
  in-flight table. The chaos harness audits this end-to-end with the
  :class:`~paddle_tpu.resilience.invariants.ConservationLedger`
  mounted at the front door (``serving/frontdoor.py``) — replica-kill
  episodes in ``resilience/chaos.py`` certify the failover path
  instead of trusting it.
- **Step-failure policy.** A replica whose step raises with a broken
  engine (donated pools) gets ``recover()`` — the single-engine
  machinery, reused per replica; repeated recover failures or repeated
  transient step failures escalate to death + failover.

Fault points (``resilience.faults``): ``router.dispatch`` fires in
``submit()`` before a request is bound to a replica (a dispatch-path
crash is a typed refusal to the caller — the request is never half-
submitted); ``router.health_probe`` fires inside the probe (probe
infrastructure failures must degrade to draining, not lose requests).

The router is drive-compatible with the engine (``submit / step /
has_work / cancel / drain``), so the front door serves one engine or
N replicas through the same loop.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

from ..observability import (TraceContext, default_recorder,
                             default_registry, span)
from ..resilience.faults import maybe_fail
from .errors import (EngineClosed, NoHealthyReplicas, ReplicaDead,
                     RequestCancelled)
from .scheduler import Request
from .sampling import SamplingParams

__all__ = ["Replica", "ReplicaRouter", "death_kind",
           "HEALTHY", "SUSPECT", "DEAD", "RETIRED"]

# free-text death reasons (which embed exception strings) normalized
# to a bounded label set before they reach a metric label or span
# attr — the registry's cardinality guard would otherwise trip on the
# embedded message text. Order matters two ways: "unreachable" is
# checked FIRST because retry exhaustion is a root cause, not a
# symptom — a partition surfaces through whatever RPC happens to run
# next ("died mid-step: ... unreachable after retries ..."), and the
# network fault must win over the router-level wrapper so watchtower
# can tell a partition from a worker death; among the rest, the
# router-level classification wins over the wrapped ReplicaDead
# message it embeds.
_DEATH_KINDS = (
    ("unreachable", "unreachable"),
    ("probe failures", "probe_failures"),
    ("step failures", "step_failures"),
    ("recover() failed", "recover_failed"),
    ("died mid-step", "died_mid_step"),
    ("died during drain", "died_during_drain"),
    ("process gone", "process_gone"),
    ("process exited", "process_exited"),
)


def death_kind(reason: str) -> str:
    """Normalize a free-text replica-death reason to a bounded set."""
    r = str(reason)
    for sub, kind in _DEATH_KINDS:
        if sub in r:
            return kind
    return "other"

HEALTHY = "healthy"    # probed clean: dispatchable
SUSPECT = "suspect"    # failed probe(s): draining, no new dispatches
DEAD = "dead"          # failed over; its engine is never touched again
RETIRED = "retired"    # drained empty on request and removed cleanly


class Replica:
    """One engine replica under the router: the engine plus the
    router's health view of it."""

    def __init__(self, replica_id: str, engine):
        self.id = str(replica_id)
        self.engine = engine
        self.state = HEALTHY
        self.alive = True          # chaos kill switch (process death)
        self.probe_failures = 0
        self.step_failures = 0
        self.recover_failures = 0

    def kill(self) -> None:
        """Simulate whole-replica death (chaos: the process is gone).
        The next probe or step raises :class:`ReplicaDead` and the
        router fails its requests over to peers."""
        self.alive = False

    @property
    def dispatchable(self) -> bool:
        return self.state == HEALTHY

    @property
    def live(self) -> bool:
        return self.state in (HEALTHY, SUSPECT)

    def load(self) -> int:
        """Queued + in-flight request count (dispatch weight)."""
        eng = self.engine
        return eng.scheduler.depth + len(eng.cache.active_slots())


class ReplicaRouter:
    """Spread requests over N engine replicas; survive replica death
    (see module docstring). Engine-shaped driving surface."""

    RID_BASE = 1 << 30



    def __init__(self, engines, *, registry=None, flight_recorder=None,
                 auditor=None,
                 probe_fail_threshold: int = 2,
                 step_fail_threshold: int = 3,
                 recover_fail_threshold: int = 3,
                 probe_timeout_s: Optional[float] = 1.0,
                 affinity=None):
        if not engines:
            raise ValueError("ReplicaRouter needs at least one engine")
        # pre-built Replica objects pass through (the cluster
        # supervisor registers RemoteReplica subclasses); bare engines
        # are wrapped with positional ids
        self.replicas = [e if isinstance(e, Replica) else
                         Replica(str(i), e)
                         for i, e in enumerate(engines)]
        ids = [r.id for r in self.replicas]
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate replica ids: {sorted(ids)}")
        self.registry = registry if registry is not None \
            else default_registry()
        self.recorder = flight_recorder if flight_recorder is not None \
            else default_recorder()
        # auditor for STANDALONE router use; under a FrontDoor the
        # ledger mounts there instead and this stays None
        self.auditor = auditor
        # serving.control.PrefixAffinityPolicy (optional): dispatch
        # prefers the replica whose radix index already holds the
        # request's prefix; least-loaded remains the fallback and the
        # only policy for dead/draining candidates
        self.affinity = affinity
        self.probe_fail_threshold = int(probe_fail_threshold)
        self.step_fail_threshold = int(step_fail_threshold)
        self.recover_fail_threshold = int(recover_fail_threshold)
        # per-probe time budget, DISTINCT from the DEAD threshold: a
        # probe that exceeds it raises TimeoutError and takes the
        # transient path (SUSPECT → drain), so ONE hung RPC never
        # triggers an instant failover. None = unbounded probes.
        self.probe_timeout_s = probe_timeout_s
        # router rids live in their own namespace, above anything an
        # engine's private counter (0, 1, ...) can reach, so a direct
        # engine.submit() on a routed engine can never mint a rid that
        # collides with a routed request in the exactly-once gate
        # (kept below the RandomState seed cap: 0x5EED + rid < 2**32)
        self._next_rid = self.RID_BASE
        self._closed = False
        # delivery sink for requests surfacing outside a step()/drain()
        # round (e.g. cancel(), failover during probes); step() swaps
        # its own list in and detaches it on exit
        self._pending_out: List[Request] = []
        # rid -> Request for everything accepted and not yet delivered:
        # THE exactly-once gate — _deliver() pops it, and a request
        # that is not in it cannot surface to the caller again
        self._inflight: Dict[int, Request] = {}
        self._owner: Dict[int, str] = {}            # rid -> replica id
        reg = self.registry
        self._m_healthy = reg.gauge(
            "ptpu_router_replica_healthy",
            "1 = replica dispatchable, 0 = draining/dead",
            labels=("replica",))
        self._m_inflight = reg.gauge(
            "ptpu_router_replica_inflight",
            "queued + in-slot requests on this replica",
            labels=("replica",))
        self._m_dispatch = reg.counter(
            "ptpu_router_dispatches_total",
            "requests dispatched to this replica",
            labels=("replica",))
        self._m_failover = reg.counter(
            "ptpu_router_failovers_total",
            "replica deaths the router failed over")
        self._m_failover_req = reg.counter(
            "ptpu_router_failover_requests_total",
            "requests re-homed to a peer after a replica death")
        self._m_deaths = reg.counter(
            "ptpu_router_replica_deaths_total",
            "replica deaths by normalized reason (death_kind)",
            labels=("reason",))
        for rep in self.replicas:
            self._m_healthy.labels(replica=rep.id).set(1)
            self._m_inflight.labels(replica=rep.id).set(0)

    # -- cancel-probe pass-through (front door installs one) ----------
    @property
    def cancel_probe(self):
        return self.replicas[0].engine.cancel_probe

    @cancel_probe.setter
    def cancel_probe(self, probe) -> None:
        for rep in self.replicas:
            rep.engine.cancel_probe = probe

    # -- dispatch ------------------------------------------------------
    def _pick_replica(self, prompt_ids=None) -> Replica:
        cands = [r for r in self.replicas if r.dispatchable]
        if not cands:
            raise NoHealthyReplicas(len(self.replicas))
        fallback = min(cands, key=lambda r: (r.load(), r.id))
        if self.affinity is not None and prompt_ids is not None:
            return self.affinity.pick(cands, prompt_ids, fallback)
        return fallback

    def submit(self, prompt_ids, max_new_tokens: int = 16,
               sampling: Optional[SamplingParams] = None,
               deadline_s: Optional[float] = None,
               tenant: Optional[str] = None) -> Request:
        """Dispatch one request to the least-loaded healthy replica.
        Typed refusals: :class:`NoHealthyReplicas`,
        :class:`EngineClosed` after ``drain()``, plus whatever the
        target engine's admission raises (``QueueFull`` etc.)."""
        if self._closed:
            raise EngineClosed()
        target = self._pick_replica(prompt_ids)
        maybe_fail("router.dispatch", replica=target.id)
        req = target.engine._build_request(
            prompt_ids, max_new_tokens, sampling, deadline_s,
            rid=self._next_rid, tenant=tenant)
        # mint the distributed trace BEFORE the dispatch RPC: the
        # context rides the pickled request to the worker, and the
        # dispatch span (ctx=) stamps it on the RPC frame too
        req.trace = TraceContext.for_request(req.rid)
        with span("router.dispatch", request_id=req.rid,
                  replica=target.id, ctx=req.trace):
            target.engine.submit_request(req)
        self._next_rid += 1
        self._inflight[req.rid] = req
        self._owner[req.rid] = target.id
        self._m_dispatch.labels(replica=target.id).inc()
        self._m_inflight.labels(replica=target.id).set(target.load())
        if self.auditor is not None:
            self.auditor.on_submitted(req)
        return req

    def has_work(self) -> bool:
        return any(r.live and r.engine.has_work()
                   for r in self.replicas)

    def add_replica(self, engine, replica_id: Optional[str] = None):
        """Register a fresh replica on a RUNNING router (the cluster
        supervisor's respawn path; also hot capacity adds). Accepts a
        bare engine or a pre-built :class:`Replica`; the new replica
        inherits the installed ``cancel_probe`` and is dispatchable
        immediately. Typed :class:`EngineClosed` after ``drain()``."""
        if self._closed:
            raise EngineClosed()
        if isinstance(engine, Replica):
            rep = engine
        else:
            rep = Replica(replica_id if replica_id is not None
                          else str(len(self.replicas)), engine)
        if any(r.id == rep.id for r in self.replicas):
            raise ValueError(
                f"replica id {rep.id!r} already registered")
        probe = None
        try:
            probe = self.replicas[0].engine.cancel_probe
        except Exception:
            pass
        if probe is not None:
            rep.engine.cancel_probe = probe
        self.replicas.append(rep)
        self._m_healthy.labels(replica=rep.id).set(1)
        self._m_inflight.labels(replica=rep.id).set(0)
        self.recorder.record("router.replica_added", replica=rep.id)
        return rep

    # -- health --------------------------------------------------------
    def probe(self, rep: Replica) -> bool:
        """One health probe: True = clean. Raises nothing; state
        transitions (SUSPECT / DEAD + failover) happen inside."""
        if not rep.live:
            return False
        try:
            maybe_fail("router.health_probe", replica=rep.id)
            if not rep.alive:
                raise ReplicaDead(f"replica {rep.id} health probe: "
                                  f"process gone")
            # engines with a real liveness check (remote replicas: one
            # RPC) answer within the probe budget. SLOW is not DEAD:
            # a TimeoutError lands in the generic arm below — SUSPECT
            # first, DEAD only after probe_fail_threshold repeats.
            # Only a torn connection (ReplicaDead) kills instantly.
            probe_fn = getattr(rep.engine, "probe", None)
            if probe_fn is not None:
                probe_fn(timeout=self.probe_timeout_s)
        except ReplicaDead as e:
            self._mark_dead(rep, str(e))
            return False
        except Exception as e:  # probe infrastructure failure
            rep.probe_failures += 1
            if rep.probe_failures >= self.probe_fail_threshold:
                self._mark_dead(
                    rep, f"{rep.probe_failures} consecutive probe "
                         f"failures ({type(e).__name__}: {e})")
            else:
                # draining: keep serving in-flight work, stop feeding
                rep.state = SUSPECT
                self._m_healthy.labels(replica=rep.id).set(0)
            return False
        rep.probe_failures = 0
        if rep.state == SUSPECT:
            rep.state = HEALTHY
            self._m_healthy.labels(replica=rep.id).set(1)
        return True

    def _mark_dead(self, rep: Replica, reason: str) -> None:
        if rep.state == DEAD:
            return
        rep.state = DEAD
        rep.alive = False
        self._m_healthy.labels(replica=rep.id).set(0)
        self._m_inflight.labels(replica=rep.id).set(0)
        kind = death_kind(reason)
        self._m_failover.inc()
        self._m_deaths.labels(reason=kind).inc()
        self.recorder.record("router.replica_dead", replica=rep.id,
                             reason=reason)
        with span("router.failover", replica=rep.id, reason=kind):
            self._failover(rep)

    def _failover(self, rep: Replica) -> None:
        """Re-home everything a dead replica held. The replica's
        engine host state is read ONE last time (and cleared, so the
        dead replica is inert afterwards); its device pools are
        considered gone with the process."""
        eng = rep.engine
        orphans: List[Request] = []
        # terminal debt a failed step stranded: finished, never
        # returned — deliver it now, exactly once
        orphans.extend(eng._undelivered)
        eng._undelivered = []
        orphans.extend(eng.scheduler.drain())
        for s in list(eng.cache.active_slots()):
            req = eng.cache.slots[s]
            try:
                eng.cache.release(s)
            except Exception:
                pass          # dying bookkeeping must not stop failover
            req.slot = None
            orphans.append(req)
        seen = set()
        for req in orphans:
            if req.rid in seen:
                continue
            seen.add(req.rid)
            if req.finished:
                self._deliver(req, self._pending_out)
                continue
            peer = self._adopt_elsewhere(req, from_replica=rep.id)
            if peer is None:
                req.finished, req.finish_reason = True, "cancelled"
                req.error = RequestCancelled(
                    req.rid, f"replica {rep.id} died with no live "
                             f"peer to adopt its requests")
                self._deliver(req, self._pending_out)
            else:
                self._owner[req.rid] = peer.id
                self._m_failover_req.inc()

    def _adopt_elsewhere(self, req: Request,
                         from_replica: Optional[str] = None
                         ) -> Optional[Replica]:
        cands = sorted((r for r in self.replicas if r.live),
                       key=lambda r: (r.state != HEALTHY, r.load(),
                                      r.id))
        # the annotated failover span: in the merged timeline it sits
        # on the router lane between the request's two worker lanes,
        # and the chrome-trace flow arrows hang off it
        with span("router.failover.rehome", request_id=req.rid,
                  ctx=getattr(req, "trace", None),
                  from_replica=from_replica) as sp:
            for rep in cands:
                try:
                    rep.engine.adopt(req)
                    sp.set_attr("to_replica", rep.id)
                    return rep
                except Exception:
                    continue
            sp.set_attr("to_replica", None)
            return None

    # -- the serving loop ---------------------------------------------
    def step(self) -> List[Request]:
        """One router round: probe every replica, then one engine
        iteration per live replica (recover / escalate to failover on
        failures). Returns every request delivered this round. Never
        raises out of a replica failure — a replica that cannot be
        saved is failed over, not surfaced as an exception."""
        with span("router.step") as sp:
            return self._step(sp)

    def _step(self, sp) -> List[Request]:
        out: List[Request] = []
        stepped = 0
        # _pending_out: delivery sink for requests surfacing OUTSIDE a
        # step (failover during submit-time probes would have no list
        # to land in) — step() always flushes it first
        self._pending_out = out
        for rep in list(self.replicas):
            self.probe(rep)
        for rep in self.replicas:
            if not rep.live or not rep.engine.has_work():
                continue
            stepped += 1
            try:
                done = rep.engine.step()
                rep.step_failures = 0
            except ReplicaDead as e:
                self._mark_dead(rep, f"died mid-step: {e}")
                continue
            except Exception as e:
                if rep.engine._broken:
                    try:
                        done = rep.engine.recover()["finished"]
                        rep.recover_failures = 0
                    except Exception as re:
                        rep.recover_failures += 1
                        if rep.recover_failures \
                                >= self.recover_fail_threshold:
                            self._mark_dead(
                                rep, f"recover() failed "
                                     f"{rep.recover_failures}x "
                                     f"({type(re).__name__}: {re})")
                        continue
                else:
                    # transient: the faulted request was re-queued by
                    # the engine; retry next round, escalate if it
                    # keeps happening
                    rep.step_failures += 1
                    if rep.step_failures >= self.step_fail_threshold:
                        self._mark_dead(
                            rep, f"{rep.step_failures} consecutive "
                                 f"step failures "
                                 f"({type(e).__name__}: {e})")
                    continue
            for req in done:
                self._deliver(req, out)
            self._m_inflight.labels(replica=rep.id).set(rep.load())
        self._pending_out = []       # detach the sink
        sp.set_attr("replicas_stepped", stepped)
        return out

    def _deliver(self, req: Request, out: List[Request]) -> None:
        """THE exactly-once gate: a request leaves the router at most
        once, whatever combination of step returns, recover reports,
        failovers and drains it rode through. Popped by OBJECT
        identity (adoption moves the same Request between engines), so
        a foreign request — e.g. someone drove engine.submit() behind
        the router's back — can never evict a routed request's
        entry."""
        if self._inflight.get(req.rid) is not req:
            return
        del self._inflight[req.rid]
        self._owner.pop(req.rid, None)
        out.append(req)
        if self.auditor is not None:
            self.auditor.on_delivered(req, via="router")

    def cancel(self, req: Request, reason: str = "cancelled") -> bool:
        """Cancel one request wherever it lives; False if it already
        finished (or was never ours)."""
        if req.rid not in self._inflight:
            return False
        owner = self._owner.get(req.rid)
        rep = next((r for r in self.replicas if r.id == owner), None)
        if rep is not None and rep.live \
                and rep.engine.cancel(req, reason):
            self._deliver(req, self._pending_out)
            return True
        return False

    def drain_replica(self, replica_id: str) -> None:
        """Gracefully take one replica out of rotation: its QUEUED
        requests move to peers now, its in-flight slots finish under
        the normal step loop, and once empty it is RETIRED (never
        dispatched again). The service keeps serving throughout —
        this is the rolling-restart primitive."""
        rep = next(r for r in self.replicas if r.id == replica_id)
        if not rep.live:
            return
        rep.state = SUSPECT
        self._m_healthy.labels(replica=rep.id).set(0)
        for req in rep.engine.scheduler.drain():
            peer = self._adopt_elsewhere(req, from_replica=rep.id)
            if peer is not None:
                self._owner[req.rid] = peer.id
            else:                      # nowhere to go: put it back
                rep.engine.scheduler.requeue(req)
        rep.state = RETIRED if not rep.engine.has_work() else SUSPECT

    def step_until_retired(self, replica_id: str,
                           max_steps: int = 1000) -> List[Request]:
        """Drive step() until a draining replica empties, then retire
        it. Returns everything delivered along the way."""
        rep = next(r for r in self.replicas if r.id == replica_id)
        out: List[Request] = []
        steps = 0
        while rep.live and rep.engine.has_work() \
                and steps < max_steps:
            out.extend(self.step())
            steps += 1
        if rep.live and not rep.engine.has_work():
            rep.state = RETIRED
            self._m_healthy.labels(replica=rep.id).set(0)
        return out

    def drain(self, max_steps: Optional[int] = None) -> List[Request]:
        """Graceful shutdown composed across replicas: refuse new
        submissions, drain every live replica (each engine's own
        ``drain()`` semantics: serve what it can, cancel the rest at
        the cutoff), then cancel anything still tracked (dead-replica
        stragglers that had no peer). Returns every request delivered
        or cancelled — and like the engine, never raises mid-loop."""
        self._closed = True
        out: List[Request] = []
        self._pending_out = out
        for rep in list(self.replicas):
            if not rep.live:
                continue
            try:
                done = rep.engine.drain(max_steps)
            except Exception as e:
                # a replica dying DURING shutdown must not abort the
                # drain of its peers: fail it over (adoption lands on
                # peers not yet drained, or the straggler sweep below
                # cancels typed) and keep going
                self._mark_dead(rep, f"died during drain: "
                                     f"{type(e).__name__}: {e}")
                continue
            for req in done:
                self._deliver(req, out)
            self._m_inflight.labels(replica=rep.id).set(0)
        for req in list(self._inflight.values()):
            if not req.finished:
                req.finished, req.finish_reason = True, "cancelled"
                req.error = RequestCancelled(
                    req.rid, "router drain: no replica could serve "
                             "this request")
            self._deliver(req, out)
        self._pending_out = []
        return out

    # -- introspection -------------------------------------------------
    def health(self) -> Dict[str, Dict[str, object]]:
        """Per-replica snapshot for /healthz and dashboards."""
        return {rep.id: {"state": rep.state,
                         "load": rep.load() if rep.live else 0,
                         "probe_failures": rep.probe_failures}
                for rep in self.replicas}
