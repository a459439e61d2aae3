"""Minimal XPlane (jax.profiler) parser: per-op device-time totals.

jax.profiler.start_trace writes ``plugins/profile/<ts>/*.xplane.pb``
(tensorflow XSpace proto). This decodes just enough of the schema —
planes → lines → events with per-plane event-metadata tables — to
produce the step-decomposition ledgers in the rounds-1-5 notes (git history
before PR 23) without any
tensorflow/tensorboard dependency. Wire format details follow
tsl/profiler/protobuf/xplane.proto; decoding is the same
varint/length-delimited walk as paddle_tpu/onnx/proto.py:read_fields.

Key subtlety: a line's events NEST (a while-loop region event contains
its body's op events), and DMA lines record ASYNC copies that overlap
compute — summing raw durations double-counts. ``op_self_times``
computes per-op SELF time (duration minus contained children) per
line, which is what a step waterfall needs.
"""
from __future__ import annotations

import glob
import gzip
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple


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


def planes(xplane_path: str):
    """Yield (plane_name, [(line_name, events)], metadata) per plane."""
    raw = open(xplane_path, "rb").read()
    if xplane_path.endswith(".gz"):
        raw = gzip.decompress(raw)
    for fno, _, v in fields(raw):
        if fno != 1:       # XSpace.planes
            continue
        name, line_pbs, meta = _decode_plane(v)
        yield name, [_decode_line(lp) for lp in line_pbs], meta


def op_self_times(xplane_path: str, plane_filter: str = "TPU",
                  line_filter: Optional[str] = None,
                  planes_data=None) -> Dict[str, Dict[str, float]]:
    """{line_name: {op_name: self_ms}} for matching planes.

    Self time = event duration minus time covered by nested (contained)
    events on the same line — leaf ops keep their full duration, loop/
    region envelopes only their non-child remainder. ``planes_data``
    (a materialized ``planes()`` result) skips re-parsing the proto
    when the caller needs several views of one trace.
    """
    out: Dict[str, Dict[str, float]] = {}
    for pname, lines, meta in (planes(xplane_path)
                               if planes_data is None else planes_data):
        if plane_filter not in pname:
            continue
        for lname, events in lines:
            if line_filter is not None and line_filter not in lname:
                continue
            acc = out.setdefault(lname, defaultdict(float))
            # sort by start asc, end desc => parents before children
            evs = sorted(((off, off + dur, mid)
                          for mid, off, dur in events),
                         key=lambda e: (e[0], -e[1]))
            stack: List[list] = []   # [start, end, mid, child_cover]
            def pop_into_parent(ev):
                start, end, mid, cover = ev
                self_ps = max(end - start - cover, 0)
                acc[meta.get(mid, f"#{mid}")] += self_ps / 1e9
                if stack:
                    stack[-1][3] += end - start
            for start, end, mid in evs:
                while stack and start >= stack[-1][1]:
                    pop_into_parent(stack.pop())
                stack.append([start, end, mid, 0])
            while stack:
                pop_into_parent(stack.pop())
    return {k: dict(v) for k, v in out.items()}


def op_intervals(xplane_path: str, plane_filter: str = "TPU",
                 line_filter: Optional[str] = None,
                 planes_data=None
                 ) -> Dict[str, List[Tuple[str, int, int]]]:
    """{line_name: [(op_name, start_ps, end_ps)]} — RAW event
    intervals for matching planes, no self-time subtraction. Overlap
    analysis (step_budget's collective exposed-vs-hidden split) needs
    the original spans, envelopes included. ``planes_data`` as in
    :func:`op_self_times`."""
    out: Dict[str, List[Tuple[str, int, int]]] = {}
    for pname, lines, meta in (planes(xplane_path)
                               if planes_data is None else planes_data):
        if plane_filter not in pname:
            continue
        for lname, events in lines:
            if line_filter is not None and line_filter not in lname:
                continue
            acc = out.setdefault(lname, [])
            for mid, off, dur in events:
                acc.append((meta.get(mid, f"#{mid}"), off, off + dur))
    return out


def op_times(xplane_path: str,
             plane_filter: str = "TPU") -> Dict[str, float]:
    """op name -> total RAW duration ms (all lines; overlap-naive —
    prefer op_self_times for waterfalls)."""
    totals: Dict[str, float] = defaultdict(float)
    for pname, lines, meta in planes(xplane_path):
        if plane_filter not in pname:
            continue
        for _, events in lines:
            for mid, _, dur in events:
                totals[meta.get(mid, f"#{mid}")] += dur / 1e9
    return dict(totals)


# ---------------------------------------------------------------------------
# minimal writer — the inverse of ``planes()`` for exactly the subset
# this parser reads. Exists so selftests can ship a CHECKED-IN miniature
# fixture (benchmarks/step_budget.py --selftest) and unit tests can
# round-trip synthetic traces without TPU hardware.
# ---------------------------------------------------------------------------

def _enc_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _enc_tag(fno: int, wt: int) -> bytes:
    return _enc_varint((fno << 3) | wt)


def _enc_len(fno: int, payload: bytes) -> bytes:
    return _enc_tag(fno, 2) + _enc_varint(len(payload)) + payload


def _enc_int(fno: int, v: int) -> bytes:
    return _enc_tag(fno, 0) + _enc_varint(v)


def encode_xspace(planes_data) -> bytes:
    """Encode [(plane_name, [(line_name, [(op_name, offset_ps,
    duration_ps), ...]), ...]), ...] as an XSpace proto byte string.
    Event-metadata ids are assigned per plane in first-seen order."""
    space = bytearray()
    for pname, lines in planes_data:
        plane = bytearray()
        plane += _enc_len(2, pname.encode())
        meta_ids: Dict[str, int] = {}
        line_blobs = []
        for lname, events in lines:
            line = bytearray()
            line += _enc_len(2, lname.encode())
            for op_name, off, dur in events:
                mid = meta_ids.setdefault(op_name, len(meta_ids) + 1)
                ev = (_enc_int(1, mid) + _enc_int(2, int(off))
                      + _enc_int(3, int(dur)))
                line += _enc_len(4, bytes(ev))
            line_blobs.append(bytes(line))
        for lb in line_blobs:
            plane += _enc_len(3, lb)
        for op_name, mid in meta_ids.items():
            md = _enc_int(1, mid) + _enc_len(2, op_name.encode())
            entry = _enc_int(1, mid) + _enc_len(2, md)
            plane += _enc_len(4, entry)
        space += _enc_len(1, bytes(plane))
    return bytes(space)


def write_xspace(path: str, planes_data) -> str:
    """Write an ``encode_xspace`` fixture to ``path`` (.gz honored)."""
    raw = encode_xspace(planes_data)
    if path.endswith(".gz"):
        raw = gzip.compress(raw)
    with open(path, "wb") as f:
        f.write(raw)
    return path


def latest_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {logdir}")
    return paths[-1]


import re as _re

_SYM_RE = _re.compile(r"^%?([\w.\-]+)")


def op_symbol(event_name: str) -> str:
    """The HLO lhs symbol (``%fusion.339 = ...`` -> ``fusion.339``) —
    event names embed the whole instruction text including operand
    lists, so classification must NEVER substring-match the full
    name."""
    m = _SYM_RE.match(event_name)
    return m.group(1) if m else event_name


# Shared op-family substring tables — consumed by ``bucketize`` below
# AND by benchmarks/step_budget.py's schema classifier. Edit HERE only:
# the two bucketizers drifting apart on the same trace is exactly the
# hand-transcription failure mode the tooling exists to eliminate.
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

_BUCKETS = [
    ("custom-call", ("custom-call", "checkpoint", "rematted",
                     "closed_call") + OPTIMIZER_KEYS + QUANTIZE_KEYS
                    + FLASH_KEYS),
    ("matmul/conv", MATMUL_KEYS),
    ("copy/slice", COPY_KEYS),
    ("collective", COLLECTIVE_KEYS),
    ("rng", RNG_KEYS),
    ("loop/control", LOOP_KEYS),
    ("fusion", ("fusion",)),
]


def bucketize(totals: Dict[str, float]) -> List[Tuple[str, float]]:
    """Collapse per-op totals into readable buckets (ms), classifying
    by the lhs SYMBOL only (operand text is full of red herrings)."""
    out: Dict[str, float] = defaultdict(float)
    for name, ms in totals.items():
        sym = op_symbol(name).lower()
        for bucket, keys in _BUCKETS:
            if any(k in sym for k in keys):
                out[bucket] += ms
                break
        else:
            out["other"] += ms
    return sorted(out.items(), key=lambda kv: -kv[1])
