"""The serving load loop on the real clock: one thread sends what is
due, pumps the front door once, and stamps every token at the client
stream's ``write``.

Shared by the ``closed_loop`` and ``open_loop`` drivers, which differ
only in when the next request falls due (a ``source``). A run is: warm
every prefill bucket the mix can hit and the decode program; check two
requests against the plain reference; ramp (the server is joined
mid-stream: the first requests are as if partly done); the measured
window; with ``--trace 1`` a traced tail; then a drain, without new
requests, until every request that was due in the window has its first
token or has ended.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from . import reference, seeding
from .setup_marks import mark
from .traffic_gen import RequestMix

# no token for this long while requests are in flight: the engine is
# stuck (the router swallows a failing step and retries it), so the run
# fails instead of hanging until the driver kills it
STALL_LIMIT_S = 60.0


class Rec:
    """One request as its client sees it, and the client's end of its
    stream (what the front door needs of a ``ClientStream``: ``write``
    and ``close``): events are stamped at ``write`` and none is kept."""
    __slots__ = ("due", "sent", "want", "times", "done", "ok", "client",
                 "clock")

    def __init__(self, due: float, want: int, client, clock):
        self.due, self.want, self.client = due, want, client
        self.clock = clock
        self.sent: Optional[float] = None
        self.times: List[float] = []     # token deliveries
        self.done: Optional[float] = None
        self.ok: Optional[bool] = None

    def write(self, event: dict) -> None:
        if event["event"] == "token":
            self.times.append(self.clock())
        elif event["event"] == "done":
            self.done = self.clock()
            self.ok = (event["finish_reason"] == "length"
                       and len(event["output_ids"]) == self.want)

    def close(self) -> None:
        pass


class ClosedSource:
    """``clients`` callers, each sending its next request when its last
    one has ended."""

    def __init__(self, clients: int, start: float):
        self._due = [(start, c) for c in range(clients)]

    def pop_due(self, now: float):
        out, self._due = self._due, []
        return out

    def on_done(self, rec: Rec) -> None:
        self._due.append((rec.done, rec.client))

    def next_due(self) -> Optional[float]:
        return self._due[0][0] if self._due else None

    def shift(self, dt: float) -> None:
        pass


class OpenSource:
    """Independent users: requests fall due on a schedule fixed before
    the run, whatever the server does."""

    def __init__(self, arrivals):
        self._arrivals = arrivals
        self._next = next(arrivals)
        self._shift = 0.0
        self.open = True

    def pop_due(self, now: float):
        out = []
        while self.open and self._next + self._shift <= now:
            out.append((self._next + self._shift, None))
            self._next = next(self._arrivals)
        return out

    def on_done(self, rec: Rec) -> None:
        pass

    def next_due(self) -> Optional[float]:
        return self._next + self._shift if self.open else None

    def shift(self, dt: float) -> None:
        """The schedule pauses while the profiler starts."""
        self._shift += dt


class Loop:
    def __init__(self, system, mix: RequestMix, tracer,
                 clock=time.perf_counter, sleep=time.sleep):
        self.system, self.tracer = system, tracer
        self.clock, self.sleep = clock, sleep
        self._requests = mix.requests()
        self.recs: List[Rec] = []
        self._live: List[Rec] = []
        self.steps = 0
        self._seen = 0          # tokens of the live requests, last look
        self._progress = clock()

    def send(self, due: float, client, truncate: float = 1.0) -> Rec:
        from paddle_tpu.serving import ServingError
        prompt, want = next(self._requests)
        want = max(1, int(round(want * truncate)))
        rec = Rec(due, want, client, self.clock)
        with self.tracer.span("submit"):
            rec.sent = self.clock()
            try:
                self.system.front.submit(prompt, want, stream=rec)
                self._live.append(rec)
            except (ServingError, ValueError):
                rec.done, rec.ok = self.clock(), False   # refused
        self.recs.append(rec)
        return rec

    def run(self, source, until: float, sending: bool = True,
            stop_when=None) -> None:
        """Send what is due and pump, until ``until`` (or, in the drain,
        until ``stop_when()``)."""
        front = self.system.front
        while True:
            now = self.clock()
            if now >= until or (stop_when is not None and stop_when()):
                return
            if sending:
                for due, client in source.pop_due(now):
                    self.send(due, client)
            if front.has_work():
                with self.tracer.span("pump"):
                    front.pump()
                self.steps += 1
                if sum(len(r.times) for r in self._live) != self._seen:
                    self._progress = self.clock()
                still = []
                for rec in self._live:
                    if rec.done is None:
                        still.append(rec)
                    else:
                        source.on_done(rec)
                self._live = still
                self._seen = sum(len(r.times) for r in still)
                if self.clock() - self._progress > STALL_LIMIT_S:
                    raise SystemExit(
                        f"chipbench: no token for {STALL_LIMIT_S:.0f} s "
                        f"with {len(still)} requests in flight: the "
                        f"engine is stuck")
            else:
                nxt = source.next_due() if sending else None
                wake = until if nxt is None else min(nxt, until)
                with self.tracer.span("generator_wait"):
                    self.sleep(max(0.0, min(wake - self.clock(), 0.05)))
                self._progress = self.clock()


def warm_and_check(system, traffic: dict, seed: int) -> dict:
    """Compile or load every program the mix can reach (one prompt of
    each prefill bucket's own length, two tokens each: the second comes
    from the decode program), then hold two greedy requests against the
    plain reference."""
    from paddle_tpu.serving import bucket_for
    eng, front = system.engine, system.front
    rng = seeding.host_rng(seed, 6)
    lo, hi = traffic["prompt_tokens"]["log_uniform"]
    buckets = sorted({bucket_for(n, eng.min_bucket, eng.max_len)
                      for n in range(lo, hi + 1)})
    for b in buckets:
        front.submit(rng.integers(1, system.vocab, b, dtype=np.int64), 2)
        front.run_until_idle()
        mark(f"bucket_{b}")
    worst = 0.0
    for n in traffic["check_prompt_tokens"]:
        prompt = rng.integers(1, system.vocab, n, dtype=np.int64)
        h = front.submit(prompt, int(traffic["check_new_tokens"]))
        front.run_until_idle()
        worst = max([worst] + [float(d) for d in system.logit_deficits(
            prompt, h.req.output_ids)])
    mark("reference_check")
    return {"buckets": buckets, "worst_logit_deficit_std": float(worst),
            "ok": bool(worst <= reference.LLAMA_LOGIT_TOL_STD)}


def _engine_marks(system) -> dict:
    """What the engine's own counters say now (per-layer runs only:
    ``summary()`` sorts its sample windows)."""
    eng = system.engine
    s = eng.metrics.summary()
    h = eng.registry.get("ptpu_serving_step_seconds")
    return {"steps": s["steps"],
            "occ_sum": s["occupancy_mean"] * s["steps"] * eng.max_slots,
            "step_sum": h.sum, "step_count": h.count,
            "queue_waits": len(
                eng.metrics.snapshot_windows()["queue_wait"])}


def drive(system, traffic: dict, seed: int, seconds: float, tracer,
          make_source, initial_inflight: int) -> dict:
    """The whole run of a serving cell; ``make_source(mix, start)``
    gives the closed or the open source."""
    clock = time.perf_counter
    check = warm_and_check(system, traffic, seed)
    mix = RequestMix(traffic, system.vocab, seed)
    loop = Loop(system, mix, tracer)
    per_layer = bool(tracer.seconds)
    main_s = seconds - tracer.seconds
    start = clock()
    source = make_source(mix, start)
    # the requests in flight when the run is joined are partly done:
    # every closed-loop client's first, or the open loop's first few
    first = source.pop_due(start) + [(start, None)] * initial_inflight
    for (due, client), frac in zip(first, mix.truncation(len(first))):
        loop.send(due, client, truncate=frac)
    w0 = start + float(traffic["ramp_seconds"])
    loop.run(source, w0)
    mark("ramp")
    programs0 = system.programs()
    marks0 = _engine_marks(system) if per_layer else None
    w0 = clock()
    mid = w0 + main_s / 2
    loop.run(source, mid)
    depth_mid = system.engine.scheduler.depth
    loop.run(source, w0 + main_s)
    w1 = clock()
    depth_end = system.engine.scheduler.depth
    programs1 = system.programs()
    marks1 = _engine_marks(system) if per_layer else None
    if per_layer:
        t = clock()
        tracer.start()
        source.shift(clock() - t)
        steps0 = loop.steps
        loop.run(source, clock() + tracer.seconds)
        tracer.stop(loop.steps - steps0)
    # drain: nothing new is sent; every request that was due in the
    # window gets the chance to show its first token
    owed = [r for r in loop.recs
            if w0 <= r.due < w1 and not r.times and r.done is None]
    loop.run(source, clock() + float(traffic["drain_seconds"]),
             sending=False,
             stop_when=lambda: all(r.times or r.done is not None
                                   for r in owed))
    return reduce_window(loop.recs, w0, w1, system, check, marks0,
                         marks1, programs1 - programs0,
                         depth_mid, depth_end)


def reduce_window(recs, w0, w1, system, check, marks0, marks1, compiles,
                  depth_mid, depth_end) -> dict:
    """Client-side records to metrics: everything over all the work and
    all the time of the window [w0, w1)."""
    span = w1 - w0
    tokens, gaps = 0, []
    for r in recs:
        ts = r.times
        for i, t in enumerate(ts):
            if w0 <= t < w1:
                tokens += 1
                if i:
                    gaps.append(t - ts[i - 1])
    due_in = [r for r in recs if w0 <= r.due < w1]
    ttft = [(r.times[0] - r.due) if r.times and r.ok is not False
            else span for r in due_in]
    ended = [r for r in recs if r.done is not None and w0 <= r.done < w1]
    failed = sum(1 for r in ended if not r.ok)
    e2e = {"serve_tokens_per_s": tokens / span}
    if gaps:
        e2e["itl_p95_ms"] = float(np.percentile(gaps, 95)) * 1e3
    if ttft:
        e2e["ttft_p90_ms"] = float(np.percentile(ttft, 90)) * 1e3
    host = {"window_s": span,
            "completed_rps": (len(ended) - failed) / span}
    registry, samples = {}, {
        "gen_late_s": [r.sent - r.due for r in due_in]}
    if marks0 is not None:
        steps = marks1["steps"] - marks0["steps"]
        if steps:
            host["occupancy_pct"] = 100.0 * (
                marks1["occ_sum"] - marks0["occ_sum"]) / steps \
                / system.engine.max_slots
        registry["ptpu_serving_step_seconds"] = {
            "sum": marks1["step_sum"] - marks0["step_sum"],
            "count": marks1["step_count"] - marks0["step_count"]}
        samples["queue_wait_s"] = list(
            system.engine.metrics.snapshot_windows()["queue_wait"]
        )[marks0["queue_waits"]:marks1["queue_waits"]]
    return {
        "t_window": w0,
        "attempted": sum(1 for r in recs if w0 <= r.sent < w1),
        "failed": failed,
        "checks": {
            "engine_logits_match_reference": check["ok"],
            "every_ended_request_complete": failed == 0,
            "tokens_delivered": tokens > 0,
        },
        "notes": {"check": check, "tokens": tokens, "gaps": len(gaps),
                  "requests_due": len(due_in),
                  "requests_ended": len(ended),
                  "completed_rps": host["completed_rps"],
                  "queue_depth_mid": depth_mid,
                  "queue_depth_end": depth_end,
                  "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3
                  if ttft else None,
                  "itl_p50_ms": float(np.percentile(gaps, 50)) * 1e3
                  if gaps else None},
        "end_to_end": e2e,
        "obs": {"host": host, "samples": samples, "registry": registry,
                "counters": {"compiles_in_window": compiles}},
    }
