"""End-to-end conservation invariants for chaos soaking.

PR 3 built the fault-injection and recovery machinery, and its review
still found failure-path bugs *by hand* — every one of them an
instance of a checkable global law (a finished request vanished across
a step fault; ``drain()`` dropped results it had already collected; a
timeout multiplied by the handle count). This module states those laws
once, as code, so the chaos scheduler (``resilience/chaos.py``) can
assert them after every randomized episode instead of waiting for the
next reviewer to spot the next instance:

- **Request conservation** (:class:`ConservationLedger`): every
  submitted request is delivered to a caller exactly once — via a
  ``step()`` return, a ``recover()`` report, a ``drain()`` return, or
  a successful ``cancel()`` — across any number of step faults and
  recoveries. Never lost, never duplicated, always in a terminal
  state. The serving engine feeds the ledger through its ``auditor``
  hooks at exactly the external delivery boundaries.
- **Greedy token identity** (:func:`token_prefix_violations`): a
  request's delivered tokens are a prefix of the uninjected greedy
  replay of the same prompt — faults and recoveries may shorten output
  (deadline/cancel) but never corrupt it. SPECULATIVE engines are
  audited against the same non-speculative references, so draft
  acceptance and rejected-tail rollback sit under this law too: a
  broken acceptance rule reads as divergence, not as a new invariant.
- **Loss-trajectory continuity** (:func:`loss_trajectory_violations`):
  every (step, loss) a resilient training run reports matches the
  uninjected baseline bit-for-bit, whatever crashes and restores
  happened in between.
- **Checkpoint-generation monotonicity**
  (:func:`checkpoint_monotonic_violations`): the LATEST pointer never
  moves backwards and always names a loadable checkpoint, with torn
  shard files from interrupted saves tolerated.
- **No leaks** (:func:`engine_leak_violations`,
  :func:`page_leak_violations`, :func:`thread_leak_violations`,
  :func:`pending_save_violations`): a quiesced engine holds no slots,
  queue entries, or undelivered requests; every paged-KV refcount is
  back to zero (pages free or cached, reservations returned, no
  stale page-table rows); an episode spawns no surviving non-daemon
  threads and settles every async save handle.

Checkers return a list of human-readable violation strings (empty =
invariant holds) so one episode can report every broken law at once;
``ConservationLedger.check()`` wraps that in a raised
:class:`InvariantViolation` for direct test use. Everything here is
stdlib+engine-state only — no clocks, no randomness — so a violation
is a deterministic function of the episode it audits.
"""
from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["InvariantViolation", "ConservationLedger",
           "token_prefix_violations", "engine_leak_violations",
           "page_leak_violations", "router_leak_violations",
           "frontdoor_leak_violations",
           "thread_leak_violations", "pending_save_violations",
           "loss_trajectory_violations",
           "checkpoint_monotonic_violations",
           "timeline_violations"]


class InvariantViolation(AssertionError):
    """A conservation law broke; the message lists every violation."""

    def __init__(self, violations: Sequence[str]):
        self.violations = list(violations)
        super().__init__(
            f"{len(self.violations)} invariant violation(s):\n  - "
            + "\n  - ".join(self.violations))


class ConservationLedger:
    """Double-entry accounting for serving requests.

    Plug into the engine (``ServingEngine(..., auditor=ledger)``): the
    engine calls :meth:`on_submitted` once per accepted ``submit()``
    and :meth:`on_delivered` each time a request surfaces at an
    external boundary (``step`` / ``recover`` / ``drain`` / ``cancel``
    — internal step() calls inside drain() are NOT boundaries).
    :meth:`violations` then audits the books: every submission must
    have exactly one delivery, every delivery a submission, and every
    delivered request a terminal state.

    Mounted at the FRONT DOOR (``serving/frontdoor.py``) the ledger
    additionally audits the admission boundary itself: the front door
    calls :meth:`on_attempt` once per client call and then either
    :meth:`on_submitted` (accepted) or :meth:`on_rejected` (typed
    refusal) — exactly one outcome per attempt, so a request cannot
    vanish between the client and the router.
    """

    def __init__(self):
        self.submitted: Dict[int, object] = {}        # rid -> Request
        self.delivered: Dict[int, List[str]] = {}     # rid -> [via...]
        self.attempts = 0
        self.rejected: List[Tuple[str, str]] = []   # (tenant, reason)

    # -- hooks (the engine calls these) --------------------------------
    def on_attempt(self) -> None:
        self.attempts += 1

    def on_rejected(self, tenant: str = "", reason: str = "") -> None:
        self.rejected.append((tenant, reason))

    def on_submitted(self, req) -> None:
        if req.rid in self.submitted:
            # recorded as a delivery-side anomaly at audit time
            self.delivered.setdefault(req.rid, []).append("resubmit!")
        self.submitted[req.rid] = req

    def on_delivered(self, req, via: str = "step") -> None:
        self.delivered.setdefault(req.rid, []).append(via)

    # -- audit ---------------------------------------------------------
    def violations(self) -> List[str]:
        out = []
        for rid, req in sorted(self.submitted.items()):
            vias = self.delivered.get(rid, [])
            if not vias:
                out.append(
                    f"request {rid} LOST: submitted, reached "
                    f"finished={req.finished} "
                    f"reason={req.finish_reason!r}, never delivered")
            elif len(vias) > 1:
                out.append(
                    f"request {rid} DELIVERED {len(vias)} times "
                    f"(via {vias})")
            if vias and not req.finished:
                out.append(
                    f"request {rid} delivered via {vias} but not in a "
                    f"terminal state (finished=False)")
            if vias and req.finished and req.finish_reason is None:
                out.append(
                    f"request {rid} finished without a finish_reason")
        for rid, vias in sorted(self.delivered.items()):
            if rid not in self.submitted:
                out.append(
                    f"request {rid} delivered via {vias} but never "
                    f"submitted (phantom)")
        # front-door admission law: every attempt gets exactly one
        # outcome (accept | typed reject) — only audited when the
        # boundary reports attempts at all
        if self.attempts:
            outcomes = len(self.submitted) + len(self.rejected)
            if outcomes != self.attempts:
                out.append(
                    f"front door saw {self.attempts} attempts but "
                    f"recorded {len(self.submitted)} accepts + "
                    f"{len(self.rejected)} rejects = {outcomes} "
                    f"outcomes (a request LOST — vanished at the "
                    f"boundary without an audited accept or reject)")
        return out

    def check(self) -> None:
        v = self.violations()
        if v:
            raise InvariantViolation(v)


def timeline_violations(telemetry, requests) -> List[str]:
    """Chaos trace-conservation law: every request the ledger marks
    DELIVERED has a complete merged timeline — a ``router.dispatch``
    span; a ``serving.prefill`` span if it produced tokens; a
    ``serving.decode``/``serving.verify`` span if it produced more
    than one; and, when its spans come from two different worker
    processes, a ``router.failover.rehome`` span linking the lanes.

    The law is loss-aware, not loss-blind: when the telemetry plane
    DETECTED a dropped scrape (``scrape_losses`` carries a degrading
    kind), worker-side span checks are skipped for the episode —
    detection is the requirement; a detected loss must not read as a
    phantom violation — while host-side spans (dispatch, rehome),
    which never cross the scrape, stay mandatory.
    """
    from ..observability.timeline import _HOST_PROCS, _span_rids
    out: List[str] = []
    # ANY recorded loss degrades: a SIGKILLed worker takes its
    # un-scraped buffer with it, and a drain can deliver several
    # steps between scrapes — so even "worker_died" may have eaten
    # spans of a delivered request.
    degraded = bool(telemetry.scrape_losses())
    per: Dict[int, List[dict]] = {}
    for rec in telemetry.aligned_spans():
        for rid in _span_rids(rec):
            per.setdefault(rid, []).append(rec)
    for req in requests:
        recs = per.get(req.rid, [])
        names = {r["name"] for r in recs}
        if "router.dispatch" not in names:
            out.append(
                f"request {req.rid} delivered but the merged timeline "
                f"has no router.dispatch span")
        if degraded:
            continue
        if req.out_tokens and "serving.prefill" not in names:
            out.append(
                f"request {req.rid} delivered {len(req.out_tokens)} "
                f"tokens but the merged timeline has no "
                f"serving.prefill span")
        if len(req.out_tokens) > 1 and not names & {
                "serving.decode", "serving.verify"}:
            out.append(
                f"request {req.rid} delivered {len(req.out_tokens)} "
                f"tokens but the merged timeline has no decode/verify "
                f"span")
        worker_pids = {int(r.get("pid", 0)) for r in recs
                       if str(r.get("proc")) not in _HOST_PROCS}
        if len(worker_pids) >= 2 \
                and "router.failover.rehome" not in names:
            out.append(
                f"request {req.rid} has spans from worker pids "
                f"{sorted(worker_pids)} but no router.failover.rehome "
                f"span links its lanes")
    return out


def token_prefix_violations(
        pairs: Iterable[Tuple[object, Sequence[int]]]) -> List[str]:
    """Greedy token identity vs the uninjected replay.

    ``pairs`` yields ``(request, reference_tokens)`` where
    ``reference_tokens`` is the clean greedy generation for the same
    prompt, at least as long as the request could have produced. A
    normally-finished request (``length``/``eos``) must match the
    reference exactly over its full output; a deadline-cancelled or
    caller-cancelled request may stop early but every token it DID
    deliver must still match (prefix property of greedy decoding:
    token *t* depends only on the prefix, so recovery re-prefills must
    reproduce it bit-for-bit).
    """
    out = []
    for req, ref in pairs:
        got = list(req.out_tokens)
        if len(got) > len(ref):
            out.append(
                f"request {req.rid} emitted {len(got)} tokens, "
                f"reference replay has only {len(ref)}")
            continue
        if got != list(ref[:len(got)]):
            out.append(
                f"request {req.rid} tokens diverged from the "
                f"uninjected replay: got {got}, want "
                f"{list(ref[:len(got)])} "
                f"(reason={req.finish_reason!r})")
        if req.finish_reason == "length" \
                and len(got) != req.max_new_tokens:
            out.append(
                f"request {req.rid} finished 'length' with "
                f"{len(got)}/{req.max_new_tokens} tokens")
    return out


def engine_leak_violations(engine) -> List[str]:
    """A quiesced engine must hold nothing: no leased slots, no queued
    requests, no undelivered terminal requests — and, on a SPECULATIVE
    engine, no draft-proposer state for requests that are no longer in
    a slot (eviction/deadline/cancel/recover must release it, or a
    long-lived engine's proposer index grows without bound). On a
    DISAGGREGATED mesh engine this is also the cross-group law's
    engine half: no request may still hold a KV span staged on the
    prefill group (computed but never installed on the decode pool —
    every handoff must complete or unwind); the decode-group half is
    :func:`page_leak_violations`, which audits the pool the handoff
    targets."""
    out = []
    staged = getattr(engine, "_staged_handoffs", None)
    if staged:
        out.append(
            f"staged KV handoffs for rids {sorted(staged)} never "
            f"installed on the decode group or unwound")
    active = engine.cache.active_slots()
    if active:
        out.append(
            f"leaked slots {active}: "
            f"{[engine.cache.slots[s].rid for s in active]}")
    queued = engine.scheduler.pending()
    if queued:
        out.append(
            f"leaked queue entries {[r.rid for r in queued]}")
    if engine._undelivered:
        out.append(
            f"undelivered terminal requests "
            f"{[r.rid for r in engine._undelivered]}")
    if getattr(engine, "speculative", False):
        live = {engine.cache.slots[s].rid
                for s in engine.cache.active_slots()}
        # EVERY configured proposer is audited, not just the active
        # one: the tuner may have routed requests through either, and
        # the draft proposer additionally leases KV-pool slots whose
        # leak this catches (free_slots exhaustion = silent k=1
        # degrade, invisible to token identity)
        props = getattr(engine, "_proposers", None) \
            or {"ngram": engine.proposer}
        for kind in sorted(props):
            stale = [rid for rid in props[kind].tracked()
                     if rid not in live]
            if stale:
                out.append(
                    f"leaked {kind} draft-proposer state for rids "
                    f"{stale} (request gone, proposer state still "
                    f"held)")
    # chunked-prefill half of the law: a quiesced engine may hold no
    # PREFILLING work — the chunk FIFO must be empty (every chunked
    # admission either finished its final chunk or was unwound) and no
    # per-request local KV buffers may survive (disaggregated chunk
    # prefills stage them until the final-chunk handoff)
    fifo = getattr(engine, "_chunk_fifo", None)
    if fifo:
        out.append(
            f"leaked PREFILLING slots {list(fifo)} in the chunk FIFO "
            f"(mid-prefill request neither finished nor unwound)")
    local = getattr(engine, "_chunk_local", None)
    if local:
        out.append(
            f"leaked chunk-local KV buffers for rids {sorted(local)}")
    # tiered-KV half: a quiesced engine may hold no request staged
    # mid-promotion (dst pages claimed, host payload not installed) —
    # every promotion must commit or unwind through abort_sequence
    promos = getattr(engine, "_staged_promotions", None)
    if promos:
        out.append(
            f"staged KV promotions for rids {sorted(promos)} never "
            f"committed or unwound")
    return out


def page_leak_violations(engine) -> List[str]:
    """No-leaked-pages law for the PAGED KV cache: once an engine
    quiesces (drain/recover complete, no active slots), every page
    refcount must be back to zero — each page is either on the free
    list or parked refcount-0 in the prefix index (cached), the
    reservation budget is fully returned, and no freed slot's page
    table row still points at a page. A violation means some
    failure path (aborted prefill, eviction, deadline cancel,
    recover) dropped a refcount on the floor — exactly the class of
    bug paging adds to the engine's failure surface.

    No-op (empty) for an engine that holds no pages (a state
    engine)."""
    cache = engine.cache
    if not getattr(engine, "paged", False):
        return []
    out = []
    import numpy as np
    referenced = np.nonzero(cache.refcnt[1:] > 0)[0] + 1
    if len(referenced):
        out.append(
            f"leaked page refcounts: pages {referenced.tolist()} "
            f"held {cache.refcnt[referenced].tolist()} refs after "
            f"quiesce")
    if cache.committed_pages != 0:
        out.append(
            f"leaked page reservations: committed budget "
            f"{cache.committed_pages} != 0 after quiesce")
    if cache._plans:
        out.append(
            f"leaked admission plans for rids "
            f"{sorted(cache._plans)}")
    exact_cached = sum(1 for n in cache._node_of_page.values()
                       if cache.refcnt[n.page] == 0)
    if exact_cached != cache.cached_page_count():
        out.append(
            f"cached-page counter drifted: maintained "
            f"{cache.cached_page_count()} != scanned {exact_cached}")
    accounted = cache.free_page_count() + exact_cached
    if accounted != cache.num_pages - 1:
        out.append(
            f"page accounting hole: free ({cache.free_page_count()})"
            f" + cached ({exact_cached}) != "
            f"{cache.num_pages - 1} usable pages")
    rows = np.nonzero(cache.page_table.any(axis=1))[0]
    stale = [int(s) for s in rows if cache.slots[s] is None]
    if stale:
        out.append(
            f"freed slots {stale} still hold page-table entries "
            f"{[cache.page_table[s].tolist() for s in stale]}")
    # host/disk tier half of the law, when the cache is tiered: every
    # promotion pin must be returned, every RAM-resident key must be
    # anchored by a live HOST node in the radix tree (an unanchored
    # buffer is host memory nothing can ever promote or evict —
    # the cross-tier leak), and every HOST node must resolve to tier
    # data (a dataless node would promote garbage)
    tier = getattr(cache, "tier", None)
    if tier is not None:
        pins = {k: c for k, c in tier.pin_counts().items() if c}
        if pins:
            out.append(
                f"leaked tier pins after quiesce: "
                f"{[(len(k), c) for k, c in sorted(pins.items())]} "
                f"(key_len, count)")
        host_keys = set()
        stack = [cache._root]
        while stack:
            nd = stack.pop()
            stack.extend(nd.children.values())
            if nd.page < 0:
                host_keys.add(cache._node_key(nd))
        orphans = [k for k in tier.ram_keys() if k not in host_keys]
        if orphans:
            out.append(
                f"orphaned host-tier buffers: {len(orphans)} RAM "
                f"entries (lens {sorted(len(k) for k in orphans)}) "
                f"with no HOST radix node anchoring them")
        dead = [k for k in host_keys if not tier.has(k)]
        if dead:
            out.append(
                f"dataless HOST radix nodes: {len(dead)} nodes "
                f"(lens {sorted(len(k) for k in dead)}) whose tier "
                f"entry is gone — a match would promote garbage")
    return out


def router_leak_violations(router) -> List[str]:
    """Cross-replica no-leak law: a quiesced router tracks nothing
    (its exactly-once in-flight table is empty) and every LIVE replica
    passes the single-engine leak audits — slots, queue entries,
    undelivered terminal requests, and paged-KV refcounts. DEAD
    replicas are exempt from engine/page audits (their pools died with
    the process; what must not leak is REQUESTS, which the in-flight
    table and the conservation ledger audit), but failover must have
    left their host containers empty — a request still sitting in a
    dead replica is a request nobody will ever serve."""
    out = []
    if router._inflight:
        out.append(
            f"router still tracks rids "
            f"{sorted(router._inflight)} after quiesce")
    for rep in router.replicas:
        if rep.state == "dead":
            eng = rep.engine
            stranded = [r.rid for r in eng.scheduler.pending()]
            stranded += [eng.cache.slots[s].rid
                         for s in eng.cache.active_slots()]
            stranded += [r.rid for r in eng._undelivered]
            if stranded:
                out.append(
                    f"dead replica {rep.id} still holds rids "
                    f"{sorted(stranded)} (failover left them behind)")
            continue
        for v in engine_leak_violations(rep.engine):
            out.append(f"replica {rep.id}: {v}")
        for v in page_leak_violations(rep.engine):
            out.append(f"replica {rep.id}: {v}")
    return out


def frontdoor_leak_violations(front) -> List[str]:
    """Boundary no-leak law: once the front door drains, every handle
    was closed out (no client left waiting forever) and every
    tenant's in-flight depth is back to zero."""
    out = []
    if front._handles:
        out.append(
            f"front door still holds handles for rids "
            f"{sorted(front._handles)} after quiesce")
    bad = {t: d for t, d in front._tenant_depth.items() if d != 0}
    if bad:
        out.append(f"tenant depth counters not back to zero: {bad}")
    return out


def thread_leak_violations(before: Iterable[threading.Thread]) \
        -> List[str]:
    """No NEW non-daemon thread may survive an episode (async
    checkpoint writers are daemons and must already be joined via
    ``wait_for_pending_saves``)."""
    known = set(before)
    out = []
    for t in threading.enumerate():
        if t not in known and t.is_alive() and not t.daemon:
            out.append(f"leaked non-daemon thread {t.name!r}")
    return out


def pending_save_violations() -> List[str]:
    """Every async checkpoint save is settled (the episode must call
    ``wait_for_pending_saves`` first; this audits that none raced
    past it)."""
    from ..distributed import checkpoint
    out = []
    for h in checkpoint._pending:
        if not h.done():
            out.append("async save handle still writing after the "
                       "episode settled")
    return out


def loss_trajectory_violations(
        reports: Sequence[dict],
        baseline_losses: Sequence[Tuple[int, float]]) -> List[str]:
    """Every (step, loss) recorded across the episode's run attempts
    (in-process restores AND process relaunches) must match the
    uninjected baseline, and each report must be one clean trajectory
    (strictly increasing steps — restores re-record, they don't
    duplicate)."""
    base = dict(baseline_losses)
    out = []
    for i, rep in enumerate(reports):
        steps = [s for s, _ in rep["losses"]]
        if steps != sorted(set(steps)):
            out.append(
                f"run {i}: loss trajectory not strictly increasing "
                f"({steps})")
        for s, l in rep["losses"]:
            if s not in base:
                out.append(f"run {i}: loss recorded for unknown "
                           f"step {s}")
            elif l != base[s]:
                out.append(
                    f"run {i}: loss at step {s} diverged from the "
                    f"uninjected baseline: {l!r} != {base[s]!r}")
    return out


def checkpoint_monotonic_violations(
        ckpt_dir: str, template_factory,
        latest_history: Sequence[Optional[int]] = (),
        expect_final: Optional[int] = None) -> List[str]:
    """The LATEST pointer never moves backwards and always names a
    loadable checkpoint, whatever torn shard files interrupted saves
    left behind.

    ``template_factory`` builds a fresh state template for
    ``load_state_dict``; ``latest_history`` is the sequence of LATEST
    values the episode observed (None = not yet published) and must be
    non-decreasing; ``expect_final`` pins the final pointer value.
    """
    import os

    from ..distributed.checkpoint import load_state_dict
    out = []
    seen = [s for s in latest_history if s is not None]
    if any(b < a for a, b in zip(seen, seen[1:])):
        out.append(f"LATEST moved backwards: {seen}")
    p = os.path.join(ckpt_dir, "LATEST")
    if not os.path.exists(p):
        out.append(f"no LATEST pointer under {ckpt_dir}")
        return out
    with open(p) as f:
        latest = int(f.read().strip())
    if expect_final is not None and latest != expect_final:
        out.append(f"LATEST == {latest}, expected {expect_final}")
    if seen and latest < seen[-1]:
        out.append(
            f"final LATEST {latest} older than observed {seen[-1]}")
    try:
        tmpl = template_factory()
        load_state_dict(tmpl, os.path.join(ckpt_dir,
                                           f"step_{latest}"))
        if int(tmpl["step"]) != latest:
            out.append(
                f"LATEST checkpoint carries step {tmpl['step']}, "
                f"pointer says {latest}")
    except Exception as e:      # noqa: BLE001 — any load failure is
        out.append(             # exactly what this invariant forbids
            f"LATEST checkpoint step_{latest} failed to load: "
            f"{type(e).__name__}: {e}")
    return out
