"""Where the persistent XLA compile cache lives.

Entry points (``chip_smoke.py``, ``bench.py``, each ``benchmarks/*.py``
``main``, ``serving/worker.py``) call :func:`enable_compile_cache`
before their first compile. ``import paddle_tpu`` and the test suite
never do: six test workers must not share a cache directory.

Every compile of a program of ours is also an EVENT (:class:`Watched`):
a ``compile.<kind>`` span in the observability ring, one INFO line on
``logging.getLogger("paddle_tpu.compile")`` and one increment of
``ptpu_compiles_total{program=<kind>}``, with what the installed JAX
reports through ``jax.monitoring`` (trace and backend seconds, cache
hit or miss) attached.
"""
from __future__ import annotations

import logging
import os
import threading

from ..observability import default_registry, tracing
from .native_build import REPO_ROOT

# the path is part of every cache key's lookup: it never moves
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when the caller's environment sets it
    (JAX reads that itself; nothing is overridden), otherwise the one
    fixed, git-ignored ``<checkout>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


# -- compile events ----------------------------------------------------

log = logging.getLogger("paddle_tpu.compile")
_tls = threading.local()
_listening = False

_TRACE_S = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_S = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_S = "/jax/compilation_cache/cache_retrieval_time_sec"
_USES_CACHE = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _on_duration(event: str, secs: float, **_kw) -> None:
    w = getattr(_tls, "watch", None)
    if w is None:
        return
    if event == _TRACE_S:      # nested traces end first: keep the outer
        w["trace_s"] = max(w.get("trace_s", 0.0), secs)
    elif event == _BACKEND_S:  # holds the cache's retrieval on a hit
        w["backend_s"] = w.get("backend_s", 0.0) + secs
    elif event == _RETRIEVAL_S:
        w["retrieval_s"] = w.get("retrieval_s", 0.0) + secs


def _on_event(event: str, **_kw) -> None:
    w = getattr(_tls, "watch", None)
    if w is None:
        return
    if event == _CACHE_HIT:
        w["cache"] = "hit"
    elif event == _USES_CACHE:
        w.setdefault("cache", "miss")


def note_trace(kind: str, key=None) -> None:
    """Called from INSIDE a function being jitted, so it runs only while
    JAX traces it: the :class:`Watched` call around it is compiling
    program ``kind`` for shape ``key``."""
    w = getattr(_tls, "watch", None)
    if w is not None:
        w["noted"].append((kind, key))


def note_fact(name: str, value) -> None:
    """Called like :func:`note_trace`, while a watched program traces:
    one more attribute (``name``) of its ``compile.<kind>`` span."""
    w = getattr(_tls, "watch", None)
    if w is not None:
        w[name] = value


def noted_fact(name: str, default=None):
    """What :func:`note_fact` last said of ``name`` in the watched call
    that is tracing now."""
    return (getattr(_tls, "watch", None) or {}).get(name, default)


class Watched:
    """A jitted program whose every compile is an event.

    Calls pass through. A call during which the program traced is
    recorded whole (trace, lowering, backend compile or cache load) as
    a ``compile.<kind>`` span with ``key``, ``cache`` (``hit`` /
    ``miss`` / ``off``: what JAX said of the persistent cache; ``off``
    also where it says nothing), ``trace_s`` and ``backend_s``; logged;
    counted in ``registry`` (the process default when None); and
    appended to ``sink``, a list (the engine's step record). The
    program says that it traced by calling :func:`note_trace` in its
    body, beside its ``trace_counts`` bump, so events and counts agree
    by construction. The trainer's step is the one body that may not
    change (its source lines are in its persistent-cache key), so it
    is given ``kind`` here instead: its call is an event when JAX
    reported a backend compile on this thread, and its ``key`` is the
    shape of the call's last argument (the batch). Every other
    attribute is the jitted function's own (``lower``,
    ``_cache_size``)."""

    __slots__ = ("fn", "registry", "kind", "sink")

    def __init__(self, fn, registry=None, kind=None, sink=None):
        global _listening
        if not _listening:
            _listening = True
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
        self.fn, self.registry, self.kind = fn, registry, kind
        self.sink = sink

    def __getattr__(self, name):
        return getattr(self.fn, name)

    def __call__(self, *args, **kwargs):
        prev = getattr(_tls, "watch", None)
        w = _tls.watch = {"noted": []}
        first = self.kind is not None and self.fn._cache_size() == 0
        t0 = tracing._now()
        try:
            return self.fn(*args, **kwargs)
        finally:
            _tls.watch = prev
            # a body that cannot note its trace: JAX reported a backend
            # compile (a new argument signature alone re-traces without
            # one), or (a JAX that reports nothing) it was the first call
            if self.kind is not None and (first or "backend_s" in w):
                w["noted"].append((self.kind, "x".join(
                    str(d) for d in getattr(args[-1], "shape", ()))))
            if w["noted"]:
                self._emit(w, t0)

    def _emit(self, w: dict, t0: float) -> None:
        noted = w.pop("noted")
        facts = dict({"cache": "off", "trace_s": 0.0, "backend_s": 0.0},
                     **w)
        reg = self.registry if self.registry is not None \
            else default_registry()
        total = reg.counter("ptpu_compiles_total",
                            "programs traced and compiled (or loaded "
                            "from the persistent cache)",
                            labels=("program",))
        total_s = tracing._now() - t0
        for kind, key in noted:
            tracing._record_span(f"compile.{kind}", t0, key=key, **facts)
            total.labels(program=kind).inc()
            if self.sink is not None:
                self.sink.append(dict(facts, kind=kind, key=key,
                                      total_s=total_s))
            log.info("kind=%s key=%s total_s=%.3f %s", kind, key,
                     total_s, " ".join(
                         f"{k}={v:.3f}" if isinstance(v, float)
                         else f"{k}={v}" for k, v in facts.items()))
