"""Minimal XPlane (jax.profiler) parser: device events and self times.

The benchmark's own copy of the sound part of ``benchmarks/xplane.py``
(PR 25): the reduction from trace to metrics is part of the yardstick,
so it lives under ``chipbench/`` where no later PR can move it by
editing ``benchmarks/``. jax.profiler.start_trace writes
``plugins/profile/<ts>/*.xplane.pb`` (tensorflow XSpace proto); this
decodes just enough of the schema (tsl/profiler/protobuf/xplane.proto):
planes -> lines -> events with per-plane event-metadata tables.

Kept: the proto walk, ``self_times`` (the nested-event subtraction of
``op_self_times``), ``op_symbol`` and the op-family tables. Added:
``planes_abs`` puts every event on one absolute clock, so idle gaps on a
device line can be attributed to the host annotations that covered
them. Not copied: the fixture writer, ``op_times`` and ``bucketize``.

Key subtlety: a line's events NEST (a while-loop region event contains
its body's op events) — summing raw durations double-counts.
``self_times`` computes per-op SELF time (duration minus contained
children), which is what a step waterfall needs.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple


def _read_varint(b: bytes, i: int) -> Tuple[int, int]:
    out = 0
    shift = 0
    while True:
        byte = b[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return out, i
        shift += 7


def fields(b: bytes):
    """Yield (field_no, wire_type, value) — value is int for varint,
    bytes for length-delimited; fixed32/64 returned as raw ints."""
    i = 0
    n = len(b)
    while i < n:
        tag, i = _read_varint(b, i)
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _read_varint(b, i)
        elif wt == 2:
            ln, i = _read_varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wt == 5:
            v = int.from_bytes(b[i:i + 4], "little")
            i += 4
        elif wt == 1:
            v = int.from_bytes(b[i:i + 8], "little")
            i += 8
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fno, wt, v


def _decode_plane(pb: bytes):
    name = ""
    lines = []
    meta: Dict[int, str] = {}
    for fno, _, v in fields(pb):
        if fno == 2:
            name = v.decode(errors="replace")
        elif fno == 3:
            lines.append(v)
        elif fno == 4:  # map<int64, XEventMetadata>
            k = m_name = None
            for f2, _, v2 in fields(v):
                if f2 == 1:
                    k = v2
                elif f2 == 2:
                    for f3, _, v3 in fields(v2):
                        if f3 == 2:
                            m_name = v3.decode(errors="replace")
                        elif f3 == 3 and not m_name:
                            m_name = v3.decode(errors="replace")
            if k is not None and m_name:
                meta[k] = m_name
    return name, lines, meta


def _decode_line(line_pb: bytes):
    """(line_name, [(metadata_id, offset_ps, duration_ps), ...])."""
    name = ""
    events = []
    for fno, _, v in fields(line_pb):
        if fno == 2:
            name = v.decode(errors="replace")
        elif fno == 4:  # XEvent
            mid = off = dur = 0
            for f2, _, v2 in fields(v):
                if f2 == 1:
                    mid = v2
                elif f2 == 2:
                    off = v2
                elif f2 == 3:
                    dur = v2
            events.append((mid, off, dur))
    return name, events


def planes_abs(xplane_path: str):
    """Yield (plane_name, [(line_name, [(op_name, start_ps, end_ps)])])
    with every event on ONE absolute clock: an XLine carries
    ``timestamp_ns`` (field 3) and its events an offset from it, so
    events of different lines and planes are comparable only after the
    line's base is added."""
    raw = open(xplane_path, "rb").read()
    if xplane_path.endswith(".gz"):
        raw = gzip.decompress(raw)
    for fno, _, v in fields(raw):
        if fno != 1:
            continue
        name, line_pbs, meta = _decode_plane(v)
        lines = []
        for lp in line_pbs:
            base_ps = 0
            for f2, wt, v2 in fields(lp):
                if f2 == 3 and wt == 0:
                    base_ps = v2 * 1000
                    break
            lname, events = _decode_line(lp)
            lines.append((lname, [
                (meta.get(mid, f"#{mid}"), base_ps + off,
                 base_ps + off + dur) for mid, off, dur in events]))
        yield name, lines


def self_times(events) -> Dict[str, float]:
    """{op_name: self_ms} of one line's ``[(op_name, start_ps,
    end_ps)]``: event duration minus the time covered by nested
    (contained) events — leaf ops keep their full duration, loop/region
    envelopes only their non-child remainder."""
    acc: Dict[str, float] = defaultdict(float)
    # sort by start asc, end desc => parents before children
    evs = sorted(((s, e, name) for name, s, e in events),
                 key=lambda ev: (ev[0], -ev[1]))
    stack: List[list] = []   # [start, end, name, child_cover]

    def pop_into_parent(ev):
        start, end, name, cover = ev
        acc[name] += max(end - start - cover, 0) / 1e9
        if stack:
            stack[-1][3] += end - start

    for start, end, name in evs:
        while stack and start >= stack[-1][1]:
            pop_into_parent(stack.pop())
        stack.append([start, end, name, 0])
    while stack:
        pop_into_parent(stack.pop())
    return dict(acc)


def latest_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {logdir}")
    return paths[-1]


_SYM_RE = re.compile(r"^%?([\w.\-]+)")


def op_symbol(event_name: str) -> str:
    """The HLO lhs symbol (``%fusion.339 = ...`` -> ``fusion.339``) —
    event names embed the whole instruction text including operand
    lists, so classification must NEVER substring-match the full
    name."""
    m = _SYM_RE.match(event_name)
    return m.group(1) if m else event_name


# Op-family substring tables, read by ``step_budget.classify``.
FLASH_KEYS = ("fa_fwd", "fa_bwd", "flash_attention")
QUANTIZE_KEYS = ("_rowq", "_colq", "_sr_colq", "rowq_ln",
                 "sr_cast_ln", "quantize")
OPTIMIZER_KEYS = ("fused_adamw", "adamw")
MATMUL_KEYS = ("dot", "gemm", "convolution")
COPY_KEYS = ("copy", "transpose", "bitcast", "slice",
             "dynamic-update-slice", "dynamic-slice", "pad",
             "concatenate", "reshape", "convert", "reduce-precision")
COLLECTIVE_KEYS = ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")
RNG_KEYS = ("rng",)
LOOP_KEYS = ("while", "condition", "body", "conditional")
