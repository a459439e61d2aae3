"""The serving load loop on the real clock: one thread sends what is
due, pumps the front door once, and stamps every token at the client
stream's ``write``.

Shared by the ``closed_loop`` and ``open_loop`` drivers, which differ
only in when the next request falls due (a ``source``). A run is: warm
every program the mix can reach; ramp (the server is joined mid-stream:
the first requests are as if partly done); the measured window; with
``--trace 1`` a traced tail; then a drain, without new requests, until
every request that was due in the window has its first token or has
ended. What the window served is held against the plain reference
afterwards (``verify``), once the engine and its pool are freed.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Optional

import numpy as np

from . import reference, seeding
from .setup_marks import compiles, mark
from .traffic_gen import RequestMix

# no token for this long while requests are in flight: the engine is
# stuck (the router swallows a failing step and retries it), so the run
# fails instead of hanging until the driver kills it
STALL_LIMIT_S = 60.0


class Rec:
    """One request as its client sees it, and the client's end of its
    stream (what the front door needs of a ``ClientStream``: ``write``
    and ``close``): events are stamped at ``write``; of their contents
    only the finished request's output ids are kept, for ``verify``."""
    __slots__ = ("due", "sent", "want", "times", "done", "ok", "client",
                 "clock", "prompt", "outputs")

    def __init__(self, due: float, prompt, want: int, client, clock):
        self.due, self.want, self.client = due, want, client
        self.prompt, self.outputs = prompt, None
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
            self.outputs = list(event["output_ids"])
            self.ok = (event["finish_reason"] == "length"
                       and len(self.outputs) == self.want)

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
        # cached positions the decode steps attended over, and how many
        # decode steps there were (pumps that gave a second or later
        # token to some request): what ``decode_hbm_roofline`` counts
        self.live_positions = 0
        self.decode_steps = 0
        self._seen = 0          # tokens of the live requests, last look
        self._progress = clock()
        # every pump as (start, seconds): a stall shows here as one
        # long pump, a slow run as a long median
        self.pumps: List[tuple] = []

    def send(self, due: float, client, truncate: float = 1.0) -> Rec:
        from paddle_tpu.serving import ServingError
        prompt, want = next(self._requests)
        want = max(1, int(round(want * truncate)))
        rec = Rec(due, prompt, want, client, self.clock)
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
                self.pumps.append((now, self.clock() - now))
                self.steps += 1
                if sum(len(r.times) for r in self._live) != self._seen:
                    self._progress = self.clock()
                still, positions = [], 0
                for rec in self._live:
                    # a request past its first token was in this
                    # step's decode batch: its prompt and the tokens
                    # before this one were the cache it read
                    if len(rec.times) > 1:
                        positions += len(rec.prompt) + len(rec.times) - 1
                    if rec.done is None:
                        still.append(rec)
                    else:
                        source.on_done(rec)
                if positions:
                    self.live_positions += positions
                    self.decode_steps += 1
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


def reachable_buckets(eng, mix: RequestMix) -> dict:
    """The programs the mix's prompts reach, by kind: a prompt whose
    first token starts a cached page is a one-token prefix hit
    (``slot_cache._match_prefix``), served by a page copy and the EXTEND
    program at the bucket of the tail, one token shorter. A mix whose
    prompts all start with the tokenizer's first id meets that on every
    request, so its window runs no prefill program; a mix of uniform ids
    meets it on about one request in a hundred, and needs both."""
    from paddle_tpu.serving import bucket_for
    sharing = eng.paged and eng.prefix_sharing
    lengths = [int(n) for n in mix.prompt_lengths]
    whole = sorted({bucket_for(n, eng.min_bucket, eng.max_len)
                    for n in lengths})
    tails = sorted({bucket_for(max(n - 1, 1), eng.min_bucket, eng.max_len)
                    for n in lengths}) if sharing else []
    if sharing and mix.bos is not None:
        # one prompt a page long, through the prefill program of its
        # bucket, puts the first id's page into the index
        whole = [bucket_for(eng.page_size, eng.min_bucket, eng.max_len)]
    return {"prefill": whole, "extend": tails}


def warm(system, mix: RequestMix, seed: int) -> dict:
    """Compile or load every program the mix can reach, and no other.
    For each prefill bucket one prompt of the bucket's own length, two
    tokens (the second comes from the decode program). Then for each
    extend bucket a prompt that repeats the first token of a cached
    prompt at least a page long and differs after it: the engine serves
    it by the page copy and the extend program of that bucket."""
    eng, front = system.engine, system.front
    rng = seeding.host_rng(seed, 6)
    reach = reachable_buckets(eng, mix)

    def serve(n, head=()):
        prompt = rng.integers(1, system.vocab, n, dtype=np.int64)
        prompt[:len(head)] = head
        front.submit(prompt, 2)
        front.run_until_idle()
        return prompt

    donor = None
    for b in reach["prefill"]:
        prompt = serve(b, () if mix.bos is None else (mix.bos,))
        if donor is None and b >= eng.page_size:
            donor = prompt
        mark(f"bucket_{b}")
    if reach["extend"]:
        if donor is None:
            raise SystemExit("chipbench: no prompt of the mix fills a "
                             "page, so no prefix can be shared")
        differs = donor[1] % (system.vocab - 1) + 1
        for b in reach["extend"]:
            serve(b, (donor[0], differs))
        mark("extend_buckets")
    return reach


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


class HostWatch:
    """What the host did to the process between two looks: its CPU
    seconds and the seconds inside Python's cyclic collector. Notes
    only: they tell a run that the machine slowed from one the program
    slowed."""

    def __init__(self):
        import gc
        self.gc_s, self._t = 0.0, 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.gc_s += time.perf_counter() - self._t

    def look(self) -> dict:
        return {"process_cpu_s": time.process_time(), "gc_s": self.gc_s}

    def close(self) -> None:
        import gc
        gc.callbacks.remove(self._on_gc)


def _work_marks(loop) -> tuple:
    return loop.live_positions, loop.decode_steps


def drive(system, traffic: dict, seed: int, seconds: float, tracer,
          make_source, initial_inflight: int) -> dict:
    """The whole run of a serving cell; ``make_source(mix, start)``
    gives the closed or the open source."""
    clock = time.perf_counter
    mix = RequestMix(traffic, system.vocab, seed)
    buckets = warm(system, mix, seed)
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
    watch = HostWatch()
    host0 = watch.look()
    w0 = clock()
    mid = w0 + main_s / 2
    loop.run(source, mid)
    depth_mid = system.engine.scheduler.depth
    loop.run(source, w0 + main_s)
    w1 = clock()
    host1 = watch.look()
    watch.close()
    depth_end = system.engine.scheduler.depth
    programs1 = system.programs()
    marks1 = _engine_marks(system) if per_layer else None
    traced_work = None
    if per_layer:
        t = clock()
        tracer.start()
        source.shift(clock() - t)
        steps0, work0 = loop.steps, _work_marks(loop)
        loop.run(source, clock() + tracer.seconds)
        tracer.stop(loop.steps - steps0)
        traced_work = [b - a for a, b in zip(work0, _work_marks(loop))]
    # drain: nothing new is sent; every request that was due in the
    # window gets the chance to show its first token
    owed = [r for r in loop.recs
            if w0 <= r.due < w1 and not r.times and r.done is None]
    loop.run(source, clock() + float(traffic["drain_seconds"]),
             sending=False,
             stop_when=lambda: all(r.times or r.done is not None
                                   for r in owed))
    out = reduce_window(loop.recs, w0, w1, system, marks0, marks1,
                        programs1 - programs0, depth_mid, depth_end,
                        traced_work)
    inside = [p for p in loop.pumps if w0 <= p[0] < w1]
    out["notes"].update(
        buckets=buckets, compiles=compiles(w0),
        host={k: round(host1[k] - host0[k], 3) for k in host0},
        # the longest as [seconds into the window, ms]
        pumps={"count": len(inside),
               "p50_ms": round(float(np.median([p[1] for p in inside]))
                               * 1e3, 2) if inside else None,
               "longest": [[round(p[0] - w0, 2), round(p[1] * 1e3, 1)]
                           for p in sorted(inside, key=lambda p: -p[1])[:3]]})
    recs = loop.recs
    out["verify"] = lambda control=False: verify(
        system, traffic, seed, recs, w0, w1, control)
    return out


def verify(system, traffic: dict, seed: int, recs, w0, w1,
           control: bool = False) -> list:
    """What the window served against the plain reference, once it has
    closed: a sample, drawn from the seed, of the requests that ended in
    it, the longest among them; for every served token of each, how far
    the reference's logit of it lies under the reference's best
    (``reference.llama_served_gaps``): the widest gap, and the mean
    over all the tokens compared. The engine and its pool are freed
    first; the reference reads the benchmark's own weights.
    ``control`` (``python3 -m chipbench.control``, never a benchmark
    run) adds the same reading of the tokens the int8 forward puts
    first, under the same limits: it has to come out as not correct."""
    ended = [r for r in recs if r.ok and w0 <= r.done < w1]
    k = int(traffic["verify_requests"])
    sample = []
    if ended:
        longest = max(ended, key=lambda r: len(r.prompt) + len(r.outputs))
        rest = [r for r in ended if r is not longest]
        order = seeding.host_rng(seed, 7).permutation(len(rest))
        sample = [longest] + [rest[i] for i in order[:k - 1]]
    system.free()
    out = []
    for tag, on in (("served", False), ("control", True))[:1 + control]:
        gaps = np.concatenate(
            [system.served_gaps(r.prompt, r.outputs, control=on)
             for r in sample]) if sample else np.full(1, np.nan)
        over = (f"{len(gaps)} tokens of {len(sample)} requests, "
                f"{int((gaps > 0).sum())} of them under the best")
        out += [
            {"name": f"{tag}_logit_gap_std", "over": over,
             "value": float(gaps.max()),
             "limit": reference.LLAMA_LOGIT_TOL_STD},
            {"name": f"{tag}_logit_gap_mean_std", "over": over,
             "value": float(gaps.mean()),
             "limit": reference.LLAMA_LOGIT_MEAN_TOL_STD}]
    return out


def reduce_window(recs, w0, w1, system, marks0, marks1, compiles,
                  depth_mid, depth_end, traced_work=None) -> dict:
    """Client-side records to metrics: everything over all the work and
    all the time of the window [w0, w1)."""
    span = w1 - w0
    tokens, firsts, prompt_tokens, gaps = 0, 0, 0, []
    for r in recs:
        ts = r.times
        for i, t in enumerate(ts):
            if w0 <= t < w1:
                tokens += 1
                if i:
                    gaps.append(t - ts[i - 1])
                else:
                    firsts += 1
                    prompt_tokens += len(r.prompt)
    due_in = [r for r in recs if w0 <= r.due < w1]
    ttft = [(r.times[0] - r.due) if r.times and r.ok is not False
            else span for r in due_in]
    ended = [r for r in recs if r.done is not None and w0 <= r.done < w1]
    failed = sum(1 for r in ended if not r.ok)
    e2e = {"serve_tokens_per_s": tokens / span}
    if gaps:
        e2e["itl_p95_ms"] = float(np.percentile(gaps, 95)) * 1e3
    host = {"window_s": span,
            "completed_rps": (len(ended) - failed) / span}
    if ttft:
        # the third quartile is the end-to-end metric and the p90 stands
        # beside it for the readers: over the ~125 requests of a window
        # the p90 spreads by 3-9% within a set of runs, too wide for any
        # bound a PR could be held to, the p75 by 1-3% (PERF.md, PR 29)
        for q in (75, 90):
            e2e[f"ttft_p{q}_ms"] = host[f"ttft_p{q}_ms"] = \
                float(np.percentile(ttft, q)) * 1e3
    if system is not None:
        # a prompt's tokens and every served token but a request's last
        # pass through the layers; the head is applied once a served
        # token (the first comes from the prefill's last position)
        flops = 2.0 * (system.layer_params * (prompt_tokens + tokens
                                              - firsts)
                       + system.head_params * tokens)
        host["model_flops_per_s_per_chip"] = flops / span / system.chips
    if traced_work and traced_work[1]:
        host["decode_positions"] = traced_work[0] / traced_work[1]
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
        "compared": [
            {"name": "requests_failed", "value": failed, "limit": 0},
            {"name": "windows_without_a_token", "value": int(not tokens),
             "limit": 0}],
        "notes": {"tokens": tokens, "gaps": len(gaps),
                  "prompt_tokens": prompt_tokens,
                  "requests_due": len(due_in),
                  "requests_ended": len(ended),
                  "completed_rps": host["completed_rps"],
                  "queue_depth_mid": depth_mid,
                  "queue_depth_end": depth_end,
                  "compiles_in_window": compiles,
                  "ttft_p90_ms": host.get("ttft_p90_ms"),
                  "ttft_ms": [round(t * 1e3, 1) for t in ttft],
                  # a stall shows here and not in a percentile
                  "itl_top3_ms": [round(g * 1e3, 1)
                                  for g in sorted(gaps)[-3:]],
                  "itl_p50_ms": float(np.percentile(gaps, 50)) * 1e3
                  if gaps else None,
                  # where the steps with an extend begin and how they
                  # are spread: ms at each percentile of the gaps
                  "itl_ms_at": {str(q): round(float(
                      np.percentile(gaps, q)) * 1e3, 1) for q in (
                      75, 80, 85, 87.5, 90, 92.5, 94, 95, 96, 97.5, 99)}
                  if gaps else None},
        "end_to_end": e2e,
        "obs": {"host": host, "samples": samples, "registry": registry,
                "counters": {"compiles_in_window": compiles}},
    }


@contextlib.contextmanager
def altered_tokens(every: int):
    """The fault a serving cell can have, planted for ``chipbench.planted``
    and the selftest: every ``every``-th token the engine samples is
    replaced by its neighbour in the vocabulary, where it is produced, so
    that the request goes on from the altered token."""
    import paddle_tpu.serving.engine as engine_module
    real, calls = engine_module.sample_token, [0]

    def altered(logits, params, rng):
        calls[0] += 1
        tok = real(logits, params, rng)
        return (tok + 1) % len(logits) if calls[0] % every == 0 else tok

    engine_module.sample_token = altered
    try:
        yield
    finally:
        engine_module.sample_token = real
