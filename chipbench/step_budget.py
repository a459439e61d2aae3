"""Step-budget buckets: device self-times of one profiled step, by family.

The benchmark's own copy of the sound part of
``benchmarks/step_budget.py`` (PR 25): ``classify``,
``budget_from_times`` and ``collective_detail``, with the recorded
fixture's expectation. The capture loop, the CLI and the mesh smoke stay
in ``benchmarks/``; ``chipbench/trace.py`` captures. One bucket measures
the wrong thing under its name on the committed int8 recipe: ``matmul``
is 0.0 there (the dots run inside ``fusion`` events), so no metric reads
it.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Optional

from . import xplane

SCHEMA = "ptpu_step_budget_v2"

# The stable bucket-key set. Adding a key is a schema bump; the
# selftest and tests/test_step_budget.py pin this exact set.
# v2 keeps the buckets of v1 and ADDS the top-level `collectives`
# record (per-kind totals + exposed-vs-overlapped split) — the
# multichip-overlap artifact ROADMAP item #3 asks for.
BUCKET_KEYS = ("matmul", "flash", "quantize", "optimizer", "copy_slice",
               "collective", "fusion", "rng", "loop", "other")

# Buckets whose device time counts as COMPUTE COVER for the collective
# overlap split: a collective interval inside their union is hidden
# behind useful work, the remainder is EXPOSED wall time. copy/loop/
# rng/other are deliberately excluded — a while-envelope spans the
# whole step and would declare every collective "overlapped".
COMPUTE_COVER_BUCKETS = ("matmul", "flash", "fusion", "quantize",
                         "optimizer")

# Classification by the HLO lhs SYMBOL only (xplane.op_symbol) — the
# event name embeds the whole instruction text including operand lists,
# which is full of red herrings. First match wins, so the specific
# custom-call families (flash/quantize/optimizer) come before the
# generic ones. The substring tables live in xplane.py (shared with
# its human-readable bucketize) so the two classifiers cannot drift.
_CLASSES = (
    ("flash", xplane.FLASH_KEYS),
    ("quantize", xplane.QUANTIZE_KEYS),
    ("optimizer", xplane.OPTIMIZER_KEYS),
    ("matmul", xplane.MATMUL_KEYS),
    ("copy_slice", xplane.COPY_KEYS),
    ("collective", xplane.COLLECTIVE_KEYS),
    ("rng", xplane.RNG_KEYS),
    ("loop", xplane.LOOP_KEYS),
    ("fusion", ("fusion",)),
)


def classify(op_name: str) -> str:
    """Bucket key for one op event name."""
    sym = xplane.op_symbol(op_name).lower()
    for bucket, keys in _CLASSES:
        if any(k in sym for k in keys):
            return bucket
    return "other"


def empty_collectives() -> dict:
    """The zero collectives record (CPU smoke, single-chip steps)."""
    return {"by_kind": {}, "total_ms": 0.0, "exposed_ms": 0.0,
            "overlapped_ms": 0.0, "overlap_frac": 0.0}


def collective_detail(events, steps: int = 1) -> dict:
    """The multichip-overlap artifact: decompose one line's RAW event
    intervals ``[(op_name, start_ps, end_ps)]`` into per-collective-
    kind totals and the EXPOSED vs OVERLAPPED split — the part of
    every collective's span covered by the union of compute intervals
    (COMPUTE_COVER_BUCKETS) is hidden behind useful work; the rest is
    serial communication wall time. An overlap REGRESSION (async
    collectives silently turning synchronous) shows up as exposed_ms
    growing at constant total_ms — schema-guarded instead of being a
    profiler anecdote."""
    coll = []
    cover = []
    by_kind = defaultdict(float)
    n = max(steps, 1)
    for name, s, e in events:
        b = classify(name)
        if b == "collective":
            sym = xplane.op_symbol(name).lower()
            kind = next((k for k in xplane.COLLECTIVE_KEYS
                         if k in sym), "collective")
            coll.append((s, e, kind))
        elif b in COMPUTE_COVER_BUCKETS:
            cover.append((s, e))
    merged = []
    for s, e in sorted(cover):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    total_ps = overlapped_ps = 0
    for s, e, kind in coll:
        total_ps += e - s
        by_kind[kind] += (e - s) / 1e9 / n
        for cs, ce in merged:
            if ce <= s:
                continue
            if cs >= e:
                break
            overlapped_ps += min(e, ce) - max(s, cs)
    ms = lambda ps: ps / 1e9 / n
    return {
        "by_kind": dict(sorted(by_kind.items())),
        "total_ms": ms(total_ps),
        "exposed_ms": ms(total_ps - overlapped_ps),
        "overlapped_ms": ms(overlapped_ps),
        "overlap_frac": (overlapped_ps / total_ps
                         if total_ps else 0.0),
    }


def budget_from_times(per_op: Dict[str, float], steps: int = 1,
                      line: str = "", plane: str = "",
                      collectives: Optional[dict] = None) -> dict:
    """Collapse {op_name: total_ms} into the schema-stable record.
    ``collectives`` carries the interval-level overlap record when the
    caller has one (budget_from_xplane does); else the zero record —
    the key is always present, schema-stable."""
    buckets = defaultdict(float)
    for name, ms in per_op.items():
        buckets[classify(name)] += ms / max(steps, 1)
    out = {k: buckets.get(k, 0.0) for k in BUCKET_KEYS}
    return {
        "schema": SCHEMA,
        "steps": int(steps),
        "plane": plane,
        "line": line,
        "total_ms": sum(out.values()),
        "buckets": out,
        "collectives": (collectives if collectives is not None
                        else empty_collectives()),
    }


# expected per-step buckets of fixtures/mini_step.xplane.pb (one op a
# bucket plus a nested while region; written by benchmarks/step_budget.py
# --write-fixture) at steps=2, in ms:
#   while envelope self = 10 - (4 + 3 + 2) = 1 ms
FIXTURE_EXPECT = {
    "matmul": 1.5, "flash": 2.5, "quantize": 1.25, "optimizer": 0.75,
    "copy_slice": 1.75, "collective": 0.125, "fusion": 2.0,
    "rng": 0.125, "loop": 0.5, "other": 0.5,
}
