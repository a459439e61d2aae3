"""Graph-break-and-resume for the SOT bytecode tier.

Reference behavior: the SOT translator compiles the captured PREFIX
when it cannot continue, executes the breaking construct eagerly, and
RESUMES capture after it
(jit/sot/opcode_translator/executor/opcode_executor.py:1603,
_break_graph_when_if:1801, _break_graph_when_for_loop:2015) — a
mid-body break no longer abandons the whole function to eager.

TPU-native version: the bytecode interpreter (opcode_executor.py) runs
the function as a chain of SEGMENTS. Each segment is the maximal
instruction range that traces cleanly; it is replayed under ``jax.jit``
as a pure function of the frame's tensor leaves (everything else is
pinned by the cache key). The breaking instruction between segments
executes EAGERLY on real values — where a tensor ``bool`` is an
ordinary Python bool and side effects are plain Python — and capture
resumes at the next pc. A bytecode-level tensor ``while`` therefore
runs as one compiled segment per iteration with only the loop
condition eager, instead of abandoning the function.

Scope (falls back to whole-function eager outside it): functions
without closure cells, with hashable non-tensor frame state at segment
boundaries, and non-generator code objects. Like every to_static
capture in this repo, outputs are DETACHED — differentiate inside the
captured program (TrainStep pattern), not through it. Mutable
containers that are ALIASED in frame state refuse segmentation (the
pytree round-trip would split the aliases); live iterators likewise.
"""
from __future__ import annotations

import inspect
import types
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from .opcode_executor import (GraphBreak, OpcodeExecutor, _Frame,
                              _State, _STOPPED, _GEN_FLAGS)

__all__ = ["SegmentedFunction", "segmentable"]


class _AliasedState(Exception):
    """Segment END state aliases a mutable container: crossing the
    jit boundary would split the aliases — run the range eagerly."""

_MAX_SEGMENTS_PER_CALL = 512   # past this, finish eagerly (no abort)
_MAX_CACHED_SEGMENTS = 128     # per function; beyond: eager-step only
_MISSING_GLOBAL = object()     # guard token for an unbound global name


def _has_aliased_mutables(state) -> bool:
    """True when any mutable container is reachable TWICE."""
    seen = set()

    def walk(v):
        if isinstance(v, (list, dict, set, bytearray)):
            if id(v) in seen:
                return True
            seen.add(id(v))
        if isinstance(v, dict):
            return any(walk(x) for x in v.values())
        if isinstance(v, (list, tuple)):
            return any(walk(x) for x in v)
        return False

    return walk(list(state))


def _mutable_ids(obj, acc=None) -> frozenset:
    """ids of every mutable container reachable from ``obj``."""
    if acc is None:
        acc = set()
    if isinstance(obj, (list, dict, set, bytearray)):
        if id(obj) in acc:
            return acc
        acc.add(id(obj))
    if isinstance(obj, dict):
        for v in obj.values():
            _mutable_ids(v, acc)
    elif isinstance(obj, (list, tuple)):
        for v in obj:
            _mutable_ids(v, acc)
    return acc


def _contains_ids(state, ids) -> bool:
    if not ids:
        return False

    def walk(v):
        if id(v) in ids:
            return True
        if isinstance(v, dict):
            return any(walk(x) for x in v.values())
        if isinstance(v, (list, tuple)):
            return any(walk(x) for x in v)
        return False

    return walk(list(state))


def segmentable(fn) -> bool:
    target = fn.__func__ if isinstance(fn, types.MethodType) else fn
    if not isinstance(target, types.FunctionType):
        return False
    code = target.__code__
    return not (code.co_flags & _GEN_FLAGS) \
        and not code.co_cellvars and not code.co_freevars


def _is_tensorish(v) -> bool:
    from ..framework.tensor import Tensor
    return isinstance(v, (Tensor, jax.Array, jax.core.Tracer))


def _flatten_vals(vals):
    """(leaves, treedef, wrapped-flags): tensor leaves come out as raw
    jax arrays; every other leaf is 'static'."""
    from ..framework.tensor import Tensor
    leaves, treedef = jax.tree.flatten(
        vals, is_leaf=lambda x: isinstance(x, Tensor))
    dyn, static, spec = [], [], []
    for l in leaves:
        if _is_tensorish(l):
            spec.append("T" if isinstance(l, Tensor) else "A")
            dyn.append(l._data if isinstance(l, Tensor) else l)
        else:
            spec.append(None)
            static.append(l)
    return dyn, static, tuple(spec), treedef


def _unflatten_vals(dyn, static, spec, treedef):
    from ..framework.tensor import Tensor
    dyn_it = iter(dyn)
    st_it = iter(static)
    leaves = []
    for s in spec:
        if s is None:
            leaves.append(next(st_it))
        elif s == "T":
            leaves.append(Tensor(next(dyn_it)))
        else:
            leaves.append(next(dyn_it))
    return jax.tree.unflatten(treedef, leaves)


def _hashable(x) -> bool:
    try:
        hash(x)
        return True
    except TypeError:
        return False


class SegmentedFunction:
    """Callable that runs ``fn``'s bytecode as compiled segments with
    eager breaking ops between them (see module docstring)."""

    def __init__(self, fn: Callable):
        if isinstance(fn, types.MethodType):
            self._self = fn.__self__
            fn = fn.__func__
        else:
            self._self = None
        if not isinstance(fn, types.FunctionType):
            raise GraphBreak(f"not a Python function: {fn!r}")
        if not segmentable(fn):
            raise GraphBreak("not segmentable (cells/generator)")
        # static pre-check: EVERY opcode must have a handler, so the
        # driver can never die mid-call on an unknown op after side
        # effects already ran (it could not safely re-run eagerly)
        from .opcode_executor import instructions_sans_caches
        for ins in instructions_sans_caches(fn.__code__):
            if not hasattr(OpcodeExecutor, "_op_" + ins.opname):
                raise GraphBreak(
                    f"unsupported opcode {ins.opname} (pre-check)")
        self.fn = fn
        # (start_pc, static_key, avals) -> segment record
        self._segments: Dict[Tuple, Tuple] = {}
        # Global reads are trace-time constants inside a compiled
        # segment, but this tier exists for SIDE-EFFECTING functions —
        # where a baked read feeds a replayed write (``G = G + 1``
        # would re-store the trace-time G+1 forever). Guard segment
        # keys on the current values of every name the bytecode
        # LOAD_GLOBALs: a changed global re-specializes the segment
        # (bounded by _MAX_CACHED_SEGMENTS, past which the driver
        # eager-steps — correct, and self-limiting for globals that
        # change every call).
        import dis
        self._global_names = tuple(sorted({
            ins.argval for ins in dis.get_instructions(fn.__code__)
            if ins.opname == "LOAD_GLOBAL"}))

    def _globals_guard(self):
        toks = []
        g = self.fn.__globals__
        for name in self._global_names:
            v = g.get(name, _MISSING_GLOBAL)
            if isinstance(v, (int, float, bool, str, bytes,
                              type(None))):
                toks.append((name, type(v).__name__, v))
            else:
                # objects (modules, functions, classes): identity-
                # stable in practice; id() keys re-binding, not
                # interior mutation (interior mutation of a read-only
                # global is out of scope, as in the reference SOT)
                toks.append((name, "id", id(v)))
        return tuple(toks)

    # -- frame state <-> pytree -------------------------------------------
    def _snapshot(self, f: _Frame):
        # kwnames rides along: a boundary between KW_NAMES and CALL
        # must not drop it (it is a static tuple of strings)
        return (list(f.stack), list(f.locals), f.kwnames)

    def _segment_key(self, pc: int, state, arg_mut_ids=frozenset()):
        if _contains_ids(state, arg_mut_ids):
            # a mutable container the CALLER holds a reference to: the
            # pytree round-trip at a boundary would rebuild it as a new
            # object, so post-boundary mutations would miss the
            # caller's copy — eager-step instead
            return None, None
        if _has_aliased_mutables(state):
            # the pytree round-trip would materialize aliases as
            # SEPARATE objects; post-boundary mutations would miss the
            # other name — eager-step instead (correctness first)
            return None, None
        dyn, static, spec, treedef = _flatten_vals(state)
        for s in static:
            if not _hashable(s):
                return None, None
            if hasattr(s, "__next__"):
                # a live iterator in frame state is STATEFUL: baking it
                # into a compiled segment would consume it at trace
                # time and replay exhausted — eager-step instead
                return None, None
        avals = tuple((tuple(a.shape), str(a.dtype)) for a in dyn)
        return (pc, tuple(static), spec, treedef, avals,
                self._globals_guard()), dyn

    # -- one segment ------------------------------------------------------
    def _discover(self, pc: int, state, dyn):
        """Trace from ``pc`` to find where (or whether) capture breaks,
        then build the jitted replay for the clean range."""
        _, static, spec, treedef = _flatten_vals(state)
        probe_ex = [None]

        def replay(dyn_in, stop_pc):
            ex = OpcodeExecutor(self.fn.__code__, self.fn.__globals__,
                                None, _State(strict=True))
            probe_ex[0] = ex
            stack, locals_, kwn = _unflatten_vals(dyn_in, static,
                                                  spec, treedef)
            f = _Frame.__new__(_Frame)
            f.stack = list(stack)
            f.locals = list(locals_)
            f.cells = []
            f.pc = pc
            f.kwnames = tuple(kwn)
            r = ex._execute(f, stop_pc=stop_pc)
            if r is _STOPPED:
                snap = self._snapshot(f)
                if _has_aliased_mutables(snap):
                    raise _AliasedState()
                return ("stopped", snap, f.pc)
            return ("returned", r)

        # discovery trace: does the rest of the function capture whole?
        stop_pc = None
        static_out = {}

        def traced(dyn_in, _stop=None):
            r = replay(dyn_in, _stop)
            if r[0] == "returned":
                dyn_o, st_o, sp_o, td_o = _flatten_vals(r[1])
                static_out["v"] = ("returned", st_o, sp_o, td_o)
                return dyn_o
            dyn_o, st_o, sp_o, td_o = _flatten_vals(r[1])
            static_out["v"] = ("stopped", st_o, sp_o, td_o, r[2])
            return dyn_o

        try:
            jitted = jax.jit(lambda d: traced(d, None))
            out = jitted(dyn)   # traces now; may GraphBreak
            return ("run", jitted, dict(static_out)), out
        except GraphBreak:
            ex = probe_ex[0]
            stop_pc = ex.last_break_pc if ex is not None else None
            if stop_pc is None:
                raise
        if stop_pc == pc:
            # the very first op breaks: nothing to compile here
            return ("eager-op", None, None), None
        static_out.clear()
        try:
            jitted = jax.jit(lambda d: traced(d, stop_pc))
            out = jitted(dyn)
        except _AliasedState:
            return ("eager-op", None, None), None
        return ("run", jitted, dict(static_out)), out

    # -- driver -----------------------------------------------------------
    def __call__(self, *args, **kwargs):
        from .static_function import capture_telemetry
        fn = self.fn
        if self._self is not None:
            args = (self._self,) + args
        try:
            ba = inspect.signature(fn).bind(*args, **kwargs)
        except TypeError as e:
            raise GraphBreak(f"bad call signature: {e}")
        ba.apply_defaults()
        eager_state = _State()
        eager_ex = OpcodeExecutor(fn.__code__, fn.__globals__, None,
                                  eager_state)
        f = eager_ex.make_frame(dict(ba.arguments))
        # mutable containers the CALLER can still see (argument-
        # reachable): crossing a jit boundary must never clone them
        # (walked as a TUPLE: a temporary list would put its own id in
        # the set, and the next list born at that address would match)
        arg_mut_ids = frozenset(_mutable_ids(tuple(ba.arguments.values())))
        segments_run = 0
        while True:
            segments_run += 1
            # Past the cap (a pathological number of boundaries), stop
            # compiling and FINISH the call with eager interpretation:
            # side effects already happened, so aborting to a whole-
            # function eager re-run would repeat them.
            overloaded = segments_run > _MAX_SEGMENTS_PER_CALL
            key = dyn = None
            if not overloaded:
                key, dyn = self._segment_key(
                    f.pc, (f.stack, f.locals, f.kwnames), arg_mut_ids)
            rec = None
            if key is not None:
                rec = self._segments.get(key)
                if rec is None and \
                        len(self._segments) < _MAX_CACHED_SEGMENTS:
                    try:
                        rec, out = self._discover(
                            f.pc, (f.stack, f.locals, f.kwnames), dyn)
                        self._segments[key] = rec
                    except GraphBreak:
                        rec = ("eager-op", None, None)
                        self._segments[key] = rec
                elif rec is not None:
                    out = rec[1](dyn) if rec[0] == "run" else None
            if rec is None or rec[0] == "eager-op":
                # unsegmentable state or an op that refuses to trace:
                # run ONE instruction eagerly and resume capture
                capture_telemetry.bump("partial_eager_ops")
                try:
                    r = eager_ex._step(f)
                except GraphBreak as e:
                    # cannot continue AND cannot re-run (side effects
                    # already happened): surface loudly, never twice
                    raise RuntimeError(
                        f"partial capture aborted mid-call at pc "
                        f"{f.pc}: {e}") from e
                if r is None:
                    f.pc += 1
                elif isinstance(r, tuple):
                    return r[0]
                continue
            kind = rec[2]["v"][0]
            capture_telemetry.bump("partial_segments_run")
            if kind == "returned":
                _, st_o, sp_o, td_o = rec[2]["v"]
                return _unflatten_vals(list(out), st_o, sp_o, td_o)
            _, st_o, sp_o, td_o, next_pc = rec[2]["v"]
            stack, locals_, kwn = _unflatten_vals(list(out), st_o,
                                                  sp_o, td_o)
            f.stack = list(stack)
            f.locals = list(locals_)
            f.kwnames = tuple(kwn)
            f.pc = next_pc
