"""Capture a profiler trace of a short window and reduce it.

``Tracer`` wraps ``jax.profiler`` around the traced tail of a run and
marks the benchmark's own host spans (``pump``, ``submit``,
``generator_wait``, ``next_batch``, ``device_get``) with
``TraceAnnotation``, so they land in the same file and on the same
clock as the device's operations. ``reduce`` turns the ``.xplane.pb``
into what the readers and the result line need: per-operation self
times, the busy union, the idle gaps and which host span covered each.
"""
from __future__ import annotations

import contextlib
import os
import shutil
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from . import step_budget, xplane

# the benchmark's own host spans carry this prefix; idle gaps are
# labelled by them and nothing else (engine-internal spans are a later
# issue's)
PREFIX = "chipbench."


class Tracer:
    """The traced tail of a ``--trace 1`` run. ``start``/``stop`` stall
    the caller (the profiler starts threads and, at stop, writes the
    file); the drivers keep both outside what they time."""

    def __init__(self, logdir: str, seconds: float):
        self.logdir = logdir
        self.seconds = float(seconds)
        self.active = False
        self.window_s = 0.0
        self.steps = 0
        self._t0 = 0.0

    def start(self) -> None:
        import jax
        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir, exist_ok=True)
        jax.profiler.start_trace(self.logdir)
        self.active = True
        self._t0 = time.perf_counter()

    def stop(self, steps: int) -> None:
        """``steps``: train or engine steps that ran inside the window;
        the caller has drained the device before calling."""
        import jax
        self.window_s = time.perf_counter() - self._t0
        self.steps = int(steps)
        jax.profiler.stop_trace()
        self.active = False

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span, recorded only while the trace is on."""
        if not self.active:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield


class NoTracer:
    """``--trace 0``: spans cost one attribute read."""
    active = False
    seconds = 0.0

    @contextlib.contextmanager
    def span(self, name: str):
        yield


def _union(intervals: List[Tuple[int, int]]) -> List[List[int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


MODULE_LINE = "XLA Modules"


def module_symbol(event_name: str) -> str:
    """``jit_ptpu_decode(1234)`` -> ``jit_ptpu_decode``: a program's
    name without its fingerprint."""
    return event_name.split("(", 1)[0]


def _device_lines(path: str, plane_filter: str,
                  line_filter: Optional[str]):
    """({plane_name: (line_name, [(op, start_ps, end_ps)], module events)}
    of each device plane's per-operation line — 'XLA Ops' where present,
    else its busiest — with its line of whole programs (``MODULE_LINE``,
    empty where the backend writes none), and the benchmark's own host
    spans of every plane)."""
    out = {}
    host = []
    for pname, lines in xplane.planes_abs(path):
        for _, events in lines:
            host.extend(e for e in events if e[0].startswith(PREFIX))
        if plane_filter not in pname:
            continue
        by_name = {ln: [e for e in ev if not e[0].startswith(PREFIX)]
                   for ln, ev in lines
                   if line_filter is None or line_filter in ln}
        if not by_name:
            continue
        line = "XLA Ops" if "XLA Ops" in by_name else \
            max(by_name, key=lambda k: len(by_name[k]))
        if by_name[line]:
            out[pname] = (line, by_name[line],
                          dict(lines).get(MODULE_LINE, []))
    return out, host


def reduce(path: str, window_s: float, steps: int,
           plane_filter: str = "TPU", chips: int = 1,
           line_filter: Optional[str] = None) -> Optional[dict]:
    """The trace as numbers; None if no device plane matched.

    ``busy_s`` is the union of the operation intervals of a device's
    line, averaged over the ``chips`` device planes found; ``ops`` are
    self times (nested envelopes keep only their remainder) of the
    first device, per ``xplane.op_symbol``, with ``ops_n`` the events
    behind each; ``modules`` are that device's whole programs, seconds
    and calls per ``module_symbol``; ``gaps`` are that device's
    longest idle intervals, each with the host span of ours that
    covered most of it."""
    dev, host = _device_lines(path, plane_filter, line_filter)
    if not dev:
        return None
    names = sorted(dev)[:max(chips, 1)]
    busy = []
    for n in names:
        merged = _union([(s, e) for _, s, e in dev[n][1]])
        busy.append(sum(e - s for s, e in merged) / 1e12)
    first = names[0]
    line, events, module_events = dev[first]
    self_ms = xplane.self_times(events)
    by_symbol: Dict[str, float] = defaultdict(float)
    for name, ms in self_ms.items():
        by_symbol[xplane.op_symbol(name)] += ms / 1e3
    calls: Dict[str, int] = defaultdict(int)
    for name, _, _ in events:
        calls[xplane.op_symbol(name)] += 1
    modules: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    for name, s, e in module_events:
        m = modules[module_symbol(name)]
        m[0] += (e - s) / 1e12
        m[1] += 1
    budget = step_budget.budget_from_times(
        self_ms, steps=max(steps, 1), line=line, plane=first,
        collectives=step_budget.collective_detail(
            events, steps=max(steps, 1)))
    merged = _union([(s, e) for _, s, e in events])
    gaps = [(merged[i + 1][0] - merged[i][1], merged[i][1],
             merged[i + 1][0]) for i in range(len(merged) - 1)]
    gaps.sort(reverse=True)
    labelled = []
    for dur, s, e in gaps[:10]:
        cover: Dict[str, int] = defaultdict(int)
        for hname, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                cover[hname[len(PREFIX):]] += ov
        label = max(cover, key=cover.get) if cover else "unattributed"
        labelled.append([label, dur / 1e12])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": float(window_s),
        "steps": int(steps),
        "devices": len(names),
        "ops_s": dict(by_symbol),
        "ops_n": dict(calls),
        "modules": {k: list(v) for k, v in modules.items()},
        "buckets_ms_per_step": budget["buckets"],
        "collectives": budget["collectives"],
        "gaps": labelled,
        "idle_gap_total_s": sum(g[0] for g in gaps) / 1e12,
    }


def breakdown(tr: dict) -> dict:
    """The result line's ``breakdown``: at most 10 entries a list."""
    ops = sorted(tr["ops_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": tr["gaps"][:5]}
