"""Continuous-batching serving engine over the static KV-cache decode
path.

ONE compiled decode-step program (fixed ``[max_slots, 1]`` token block,
per-slot positions, active-slot mask and, for a model that caches K
and V, the static page table) serves any mix of in-flight requests;
prefill compiles once per power-of-2 length bucket (full-prompt and
shared-prefix-extend flavors). Compare
``benchmarks/bench_llama_decode.py``'s synchronized path, where every
sequence in a batch starts and stops together and slots idle while the
longest request finishes — here freed slots are refilled from the
queue at every iteration (Orca-style iteration-level scheduling), so
ragged traffic keeps the batch dense, and the paged pool admits by
FREE PAGES rather than worst-case rows, so the same KV bytes carry
several times more concurrent requests (docs/SERVING.md).

Synchronous API by design (the repo's serving story is one compiled
program per step, driven by a host loop):

    engine = ServingEngine(model, max_slots=8, max_len=256, eos_id=2)
    r1 = engine.submit(prompt, max_new_tokens=32)
    while engine.has_work():
        finished = engine.step()
    print(r1.output_ids, engine.metrics.summary())

``speculative=True`` swaps the decode step for ONE widened k-token
VERIFY program fed by self-drafted n-gram proposals
(spec_decode.NgramProposer) — greedy outputs stay provably
token-identical to this path and to ``generate()``; see
docs/SERVING.md "Speculative decoding". Steps where no row has a
draft are GATED back onto the k=1 decode program (identical tokens at
1/k the compute; ``spec_gate=False`` pins the always-widened flavor).

``mesh=`` (a ProcessMesh with a single ``model`` axis) makes the
engine TENSOR-PARALLEL: KV pools shard on kv_heads, params by the
family's output-dim-only ``tp_param_spec`` rules, and every program
jits under the mesh with explicit shardings — still ONE decode
program per mesh shape, and still bitwise token-identical to the
single-chip engine. ``prefill_devices=k`` partitions the mesh into a
prefill group and a decode group with an explicit device_put KV
handoff between them (docs/SERVING.md "Multi-chip serving").

Resilience contract (docs/RESILIENCE.md): a step that fails with
donated cache pools marks the engine broken — ``recover()`` rebuilds
the cache pool from host-side request state (re-prefilling
in-flight requests; greedy replay is verified token-identical) instead
of the old permanently-poisoned dead-end. Admission is bounded
(``max_queue`` → typed ``QueueFull``), requests carry optional
deadlines (cancelled at step boundaries with ``finish_reason ==
"deadline"``), and ``drain()`` shuts down gracefully. Fault points
``serving.step.decode`` / ``serving.step.prefill``
(resilience.faults) make every one of these paths testable on CPU.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import Tensor, no_grad
from ..observability import default_recorder, default_registry, span
from ..observability.tracing import has_bindings
from ..resilience.faults import InjectedFault, maybe_fail
from ..utils.compile_cache import Watched, note_trace, noted_fact
from .errors import (DeadlineExceeded, EngineBroken, EngineClosed,
                     EngineIdle, QueueFull, RequestCancelled,
                     StateCacheUnsupported)
from .kv_tier import HostPageTier, PersistentPrefixStore
from .mesh import MeshContext
from .metrics import EngineMetrics
from .sampling import (ArgmaxRow, SamplingParams, sample_token,
                       sampling_dist)
from .scheduler import FIFOScheduler, Request, bucket_for
from .slot_cache import SlotCache
from .spec_decode import DraftModelProposer, NgramProposer
from .spec_tune import SpecTuner

__all__ = ["ServingEngine"]


class _ModelAdapter:
    """Uniform view over the causal LMs the engine can serve. The model
    states what each of its layers needs held between steps
    (``cache_spec()``: a ``models/_decode_cache.CacheSpec``, K and V by
    position or a fixed-size recurrent state, layer by layer), runs its
    backbone over the matching cache tuples (``cached_forward(ids,
    caches)``) and has a logits head (``_head``); this class turns the
    engine's pools into those tuples and knows nothing else of a model
    family."""

    def __init__(self, model):
        self.model = model
        if not hasattr(model, "cache_spec"):
            raise TypeError(
                f"{type(model).__name__} exposes no static-cache decode "
                "path the serving engine can drive (expected "
                "cache_spec(), cached_forward(ids, caches) and _head)")
        self.spec = spec = model.cache_spec()
        # tensor-parallel shard rules for raw_state() param names
        # (serving/mesh.py builds NamedShardings from these); None =
        # every param replicated, which is always correct
        self.tp_param_spec = getattr(model, "tp_param_spec", None)
        self.call = model.cached_forward
        self.head = model._head
        # what a step counted beside its logits: small integer arrays
        # by name, published under those names (none for most models)
        self.counters = getattr(model, "step_counters", dict)
        self.num_layers = spec.count("kv")      # layers with K/V pools
        self.kv_heads = spec.kv_heads
        self.head_dim = spec.head_dim
        self.max_positions = spec.max_positions
        self.dtype = spec.dtype

    def prefill_caches(self, bucket: int, true_len):
        """Per-layer cache tuples for one prompt run from scratch."""
        shape = (1, bucket, self.kv_heads, self.head_dim)
        return [(jnp.zeros(shape, self.dtype),
                 jnp.zeros(shape, self.dtype), 0) if kind == "kv"
                else (None, None, true_len) for kind in self.spec.layers]

    def by_layer(self, kv, state) -> list:
        """One entry a layer, in the model's order, from one list over
        the K/V layers and one over the state layers."""
        kv, state = iter(kv), iter(state)
        return [next(kv if kind == "kv" else state)
                for kind in self.spec.layers]

    def by_kind(self, per_layer):
        """The inverse of ``by_layer``: ``(kv entries, state entries)``."""
        pick = lambda kind: [c for c, k in zip(per_layer, self.spec.layers)
                             if k == kind]
        return pick("kv"), pick("state")


class _Step:
    """One enqueued k=1 decode step: the (slot, request) rows it runs,
    its outputs (on the device until the step is read), and what its
    ``serving.decode`` span says of it."""
    __slots__ = ("rows", "logits", "best", "counted", "on_device",
                 "live_pages", "ahead", "discarded")

    def __init__(self, rows, logits, best, counted, on_device: bool,
                 live_pages: int, ahead: bool):
        self.rows, self.logits, self.best = rows, logits, best
        self.counted, self.on_device = counted, on_device
        self.live_pages, self.ahead = live_pages, ahead
        self.discarded = 0


class ServingEngine:
    """Slot-based continuous-batching engine (see module docstring)."""

    def __init__(self, model, max_slots: int = 8,
                 max_len: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 min_bucket: int = 16,
                 max_queue: Optional[int] = None,
                 time_fn: Callable[[], float] = time.perf_counter,
                 registry=None, flight_recorder=None,
                 auditor=None,
                 cancel_probe: Optional[Callable] = None,
                 kv_layout: Optional[str] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 prefix_sharing: Optional[bool] = None,
                 speculative: bool = False,
                 spec_k: int = 4,
                 spec_ngram: int = 2,
                 spec_gate: bool = True,
                 spec_proposer: str = "ngram",
                 draft_model=None,
                 spec_sampled: bool = False,
                 spec_tune: bool = False,
                 mesh=None,
                 prefill_devices: int = 0,
                 prefill_chunk: Optional[int] = None,
                 chunk_control=None,
                 admission_lookahead: int = 0,
                 kv_host_tier: bool = False,
                 host_tier_pages: Optional[int] = None,
                 prefix_store_dir: Optional[str] = None,
                 kv_transport=None):
        self.adapter = _ModelAdapter(model)
        model.eval()
        self.max_slots = int(max_slots)
        self.max_len = int(max_len or self.adapter.max_positions)
        if self.max_len > self.adapter.max_positions:
            raise ValueError(
                f"max_len {self.max_len} exceeds the model's position "
                f"range {self.adapter.max_positions}")
        self.eos_id = eos_id
        if max_queue is not None and max_queue < 1:
            raise ValueError(
                f"max_queue must be >= 1 or None, got {max_queue}")
        self.max_queue = max_queue
        self.min_bucket = min(int(min_bucket), self.max_len)
        # chunked prefill (docs/SERVING.md "Chunked prefill"): split
        # every admitted prompt into `prefill_chunk`-token chunks and
        # run at most ONE chunk per step alongside the decode program,
        # so a long prompt can never stall in-flight decodes for its
        # whole prefill. Power-of-2 and >= the bucket floor so every
        # non-final chunk IS its own bucket (zero padding) and the
        # chunk-program compile count stays O(log max_len).
        self.prefill_chunk = None
        if prefill_chunk is not None:
            c = int(prefill_chunk)
            if c < 1 or (c & (c - 1)):
                raise ValueError(
                    f"prefill_chunk must be a power of 2, got "
                    f"{prefill_chunk}")
            if bucket_for(c, self.min_bucket, self.max_len) != c:
                raise ValueError(
                    f"prefill_chunk {c} must be a prefill bucket "
                    f"(>= the min_bucket floor and <= max_len "
                    f"{self.max_len})")
            self.prefill_chunk = c
        # serving.control.ChunkBudgetController (optional, requires
        # prefill_chunk): scales the per-step prefill token budget as
        # a multiple of the FIXED compiled chunk — the chunk program
        # is one cached jit, so the budget changes how many times it
        # runs per step, never its shape. None keeps the legacy
        # at-most-one-chunk-per-step behaviour bit-identical.
        if chunk_control is not None and self.prefill_chunk is None:
            raise ValueError(
                "chunk_control requires prefill_chunk (the controller "
                "scales the chunked-prefill budget)")
        self.chunk_control = chunk_control
        if admission_lookahead < 0:
            raise ValueError(
                f"admission_lookahead must be >= 0, got "
                f"{admission_lookahead}")
        self.admission_lookahead = int(admission_lookahead)
        # what the model's layers keep decides everything below: the
        # engine asks its cache manager, never the model's family
        layers = self.adapter.spec.layers
        self.paged = "kv" in layers           # has K/V layers: pages
        self.stateful = "state" in layers     # has state layers: rows
        if self.stateful:
            # a layer keeps a fixed-size state a slot: what shares,
            # rewinds, quantizes or moves a cache by position cannot
            # restore or carry a state yet and is refused by name,
            # never silently ignored; with no K/V layer at all there
            # are no pages to size either
            for option, value, asked in (
                    ("kv_layout", kv_layout, kv_layout not in (
                        None, "paged" if self.paged else "state")),
                    ("page_size", page_size,
                     page_size is not None and not self.paged),
                    ("num_pages", num_pages,
                     num_pages is not None and not self.paged),
                    ("kv_dtype", kv_dtype, kv_dtype is not None),
                    ("prefix_sharing", prefix_sharing,
                     bool(prefix_sharing)),
                    ("speculative", speculative, bool(speculative)),
                    ("draft_model", draft_model, draft_model is not None),
                    ("mesh", mesh, mesh is not None),
                    ("prefill_devices", prefill_devices,
                     bool(prefill_devices)),
                    ("prefill_chunk", prefill_chunk,
                     prefill_chunk is not None),
                    ("kv_host_tier", kv_host_tier, bool(kv_host_tier)),
                    ("host_tier_pages", host_tier_pages,
                     host_tier_pages is not None),
                    ("prefix_store_dir", prefix_store_dir,
                     prefix_store_dir is not None),
                    ("kv_transport", kv_transport,
                     kv_transport is not None)):
                if asked:
                    raise StateCacheUnsupported(option, value)
        elif kv_layout == "state":
            raise ValueError(
                f"kv_layout='state' is for a model whose cache_spec() "
                f"is a recurrent state; {type(model).__name__} caches "
                f"K and V")
        elif kv_layout == "contiguous":
            raise ValueError(
                "kv_layout='contiguous': the contiguous slot pool is "
                "gone, pages serve K and V (leave kv_layout unset)")
        elif kv_layout not in (None, "paged"):
            raise ValueError(
                f"kv_layout follows from the model's cache_spec() "
                f"('paged' for {type(model).__name__}), got "
                f"{kv_layout!r}")
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (model dtype) or 'int8', got "
                f"{kv_dtype!r}")
        if self.paged:
            if page_size is None:
                # largest power-of-2 divisor of max_len, capped at 128
                # (the TPU-friendly default page)
                page_size = 128
                while self.max_len % page_size:
                    page_size //= 2
            self.page_size = int(page_size)
            self.num_pages = num_pages        # None = capacity parity
            self.kv_quant = kv_dtype == "int8"
            # a page alone does not restore a state: no sharing beside
            # state layers
            self.prefix_sharing = not self.stateful \
                if prefix_sharing is None else bool(prefix_sharing)
        else:
            # a slot's state is its one page: nothing is shared and
            # nothing quantized
            self.page_size, self.num_pages = self.max_len, None
            self.kv_quant = self.prefix_sharing = False
        # KV tiering (docs/SERVING.md "KV tiering"): demote cold
        # refcount-0 prefix pages to pinned host RAM instead of
        # destroying them, promote back on radix hit; an optional
        # disk store under the RAM tier keeps shared prompts warm
        # across recover() and process restarts
        self.kv_host_tier = bool(kv_host_tier) \
            or prefix_store_dir is not None
        self.prefix_store_dir = prefix_store_dir
        if host_tier_pages is not None and not self.kv_host_tier:
            raise ValueError(
                "host_tier_pages requires kv_host_tier=True (or "
                "prefix_store_dir=)")
        if self.kv_host_tier:
            if not self.prefix_sharing:
                raise ValueError(
                    "kv_host_tier requires prefix_sharing enabled "
                    "(the tier is keyed by radix chunks)")
            if mesh is not None:
                raise ValueError(
                    "kv_host_tier is not supported on mesh engines "
                    "yet: demotion would have to gather sharded "
                    "pools per page (see ROADMAP)")
        # cross-host KV wire (serving/kv_wire.py): when set, every
        # disaggregated prefill->decode handoff round-trips its KV
        # blocks through the transport's digest-verified socket path
        # before the decode-side install — the seam a cross-host
        # prefill/decode split plugs into. Same staged/abort contract;
        # a KVWireError past the transport's retry budget aborts the
        # handoff exactly like a device-fabric failure.
        self.kv_transport = kv_transport
        if kv_transport is not None and prefill_devices <= 0:
            raise ValueError(
                "kv_transport requires a disaggregated mesh "
                "(prefill_devices > 0): only the prefill->decode "
                "handoff crosses the wire")
        # speculative decoding: drafts (n-gram lookup or a small draft
        # MODEL) verified k tokens per weight pass through ONE widened
        # verify program; greedy rows keep the bitwise identity law,
        # sampled rows opt into rejection-sampling acceptance via
        # spec_sampled=True, and spec_tune=True closes the loop from
        # the accepted-length EWMA back to per-step (k, proposer)
        # choices. See docs/SERVING.md "Speculative decoding".
        self.speculative = bool(speculative)
        if self.speculative:
            if spec_k < 2:
                raise ValueError(
                    f"spec_k must be >= 2 (k includes the k=1 base "
                    f"token), got {spec_k}")
            if spec_proposer not in ("ngram", "draft"):
                raise ValueError(
                    f"spec_proposer must be 'ngram' or 'draft', got "
                    f"{spec_proposer!r}")
            if spec_proposer == "draft" and draft_model is None:
                raise ValueError(
                    "spec_proposer='draft' requires draft_model=")
            self.spec_k = int(spec_k)
            # every configured proposer lives for the engine's
            # lifetime (the tuner switches between them per step) and
            # is admitted/evicted/recovered in lockstep via
            # _proposer_release/_proposer_retain
            self._proposers = {
                "ngram": NgramProposer(ngram=spec_ngram,
                                       max_draft=self.spec_k - 1)}
            if draft_model is not None:
                self._proposers["draft"] = DraftModelProposer(
                    draft_model, max_slots=self.max_slots,
                    max_len=self.max_len,
                    max_draft=self.spec_k - 1)
            self.spec_proposer = spec_proposer
            self.proposer = self._proposers[spec_proposer]
            self.spec_sampled = bool(spec_sampled)
            # skip the k-wide verify program on steps where NO row has
            # a draft (all wlen == 1): the k=1 decode program emits the
            # provably identical token at 1/k the verify compute.
            # Trace counts stay bounded: <= 1 decode + <= 1 verify.
            self.spec_gate = bool(spec_gate)
            # tuner starts optimistic on the CONFIGURED proposer and
            # probes the others round-robin once traffic stops paying
            self._tuner = SpecTuner(
                k_max=self.spec_k,
                proposers=tuple(
                    [self.spec_proposer]
                    + [k for k in self._proposers
                       if k != self.spec_proposer])) \
                if spec_tune else None
        elif spec_k != 4 or spec_ngram != 2 or spec_gate is not True \
                or spec_proposer != "ngram" or draft_model is not None \
                or spec_sampled or spec_tune:
            raise ValueError(
                "spec_k/spec_ngram/spec_gate/spec_proposer/"
                "draft_model/spec_sampled/spec_tune only apply with "
                "speculative=True")
        # tensor-parallel serving mesh (docs/SERVING.md "Multi-chip
        # serving"): KV pools + shardable params split over the
        # mesh's `model` axis; with prefill_devices > 0 the mesh is
        # PARTITIONED into a prefill group and a decode group and
        # finished prefill KV spans are handed off via device_put
        self.meshctx = None
        if mesh is not None:
            self.meshctx = MeshContext(mesh,
                                       kv_heads=self.adapter.kv_heads,
                                       prefill_devices=prefill_devices)
        elif prefill_devices:
            raise ValueError(
                "prefill_devices (disaggregated prefill/decode) "
                "requires mesh=")
        # rid -> slot for requests whose prefilled KV is computed on
        # the prefill group but not yet installed on the decode pool —
        # the cross-group no-leak law audits this is empty at quiesce
        self._staged_handoffs = {}
        # chunked-prefill state: PREFILLING slots in admission order
        # (the head advances one chunk per step) and, on disaggregated
        # engines, rid -> per-layer local KV buffers accumulating the
        # chunks on the PREFILL group until the final-span handoff.
        # Both are audited empty at quiesce (no-leak law).
        self._chunk_fifo: List[int] = []
        self._chunk_local = {}
        # name -> (source array, mesh-placed copy), per group:
        # re-placing every step would re-transfer params the model
        # still holds. Keyed by NAME with the source kept alive in the
        # entry (an id()-keyed cache would go stale when a checkpoint
        # load frees old arrays and a new one reuses the address)
        self._placed = {"decode": {}, "prefill": {}}
        # group -> (param-name key, shardings dict): the shardings are
        # static per (names, mesh), so don't rebuild NamedShardings on
        # every step
        self._shardings_cache = {}
        # host/disk KV tier OUTLIVES the cache object: recover()'s
        # _new_cache() rebinds a fresh radix tree onto the same tier
        # (rehydration), which is what keeps warm prefixes across
        # pool rebuilds
        self._kv_tier = None
        if self.kv_host_tier:
            ad = self.adapter
            store = None
            if prefix_store_dir is not None:
                store = PersistentPrefixStore(
                    prefix_store_dir, num_layers=ad.num_layers,
                    page_size=self.page_size, kv_heads=ad.kv_heads,
                    head_dim=ad.head_dim, dtype=ad.dtype,
                    quant=self.kv_quant)
            self._kv_tier = HostPageTier(
                ad.num_layers, self.page_size, ad.kv_heads,
                ad.head_dim, ad.dtype, quant=self.kv_quant,
                capacity_pages=host_tier_pages, store=store)
        # rid -> slot for requests whose host-tier pages are being
        # promoted onto fresh device pages but not yet committed —
        # audited empty at quiesce exactly like _staged_handoffs
        self._staged_promotions = {}
        self.cache = self._new_cache()
        self.scheduler = FIFOScheduler()
        self.registry = registry if registry is not None \
            else default_registry()
        # `is None`, not truthiness: an EMPTY FlightRecorder is falsy
        # (it has __len__), and `or` would silently swap it for the
        # global one
        self.recorder = flight_recorder if flight_recorder is not None \
            else default_recorder()
        self.metrics = EngineMetrics(self.max_slots, time_fn,
                                     registry=self.registry)
        self._params_pf = self._buffers_pf = None
        self._refresh_state()
        self._decode_jit = None
        self._verify_jit = None
        self._prefill_jit = None
        self._extend_jit = None
        self._copy_jit = None
        self._install_jit = None
        self._promote_jit = None
        self._chunk_jit = None
        self._chunk_local_jit = None
        self._chunk_fin_jit = None
        self._tokens_jit = None
        # where a decode step's tokens lie: replicated over a mesh, as
        # the decode program takes them (_tokens_fn)
        self._tokens_sharding = self.meshctx.repl("decode") \
            if self.meshctx is not None else None
        # the decode step enqueued one step ahead and not read yet
        # (_decode_plain), or None: the engine is drained
        self._ahead: Optional[_Step] = None
        self._next_rid = 0
        self._step_idx = 0
        # set when a step fails after donating the cache pools (device
        # buffers invalidated); recover() clears it
        self._broken: Optional[str] = None
        self._closed = False
        # requests that reached a terminal state inside a FAILED step
        # (deadline sweep, decode finisher evicted before the raise) or
        # were discovered finished-in-slot by recover(): they must
        # still surface through the next successful step()/recover()/
        # drain() exactly once — never lost, never duplicated. The
        # list survives a recover() that itself faults mid-re-prefill.
        self._undelivered: List[Request] = []
        # optional conservation auditor (resilience.invariants duck
        # type: on_submitted(req) / on_delivered(req, via)) — called at
        # the EXTERNAL delivery boundaries only, so a ledger sees
        # exactly what callers see
        self.auditor = auditor
        # optional liveness callback(req) -> bool (True = the client
        # behind this request is gone). The front door installs one so
        # a disconnect observed on an HTTP thread propagates into
        # engine cancellation at the next safe point: the step-boundary
        # sweep, or mid-prefill AFTER pages are claimed (so the abort
        # path unwinds them). Requests also carry their own
        # `cancel_requested` flag, checked first.
        self.cancel_probe = cancel_probe
        # optional watchtower (observability.watchtower) installed by
        # Watchtower.attach_engine(); the step hot path bumps its
        # counter — one increment, nothing else (micro-asserted)
        self._watchtower = None
        self._in_drain = False
        # python-side-effect counters bumped at TRACE time: the compile-
        # count contract (1 decode + O(log max_len) prefill buckets) is
        # asserted against these in tests
        self.trace_counts = {"decode": 0, "verify": 0, "draft": 0,
                             "prefill": {},
                             "extend": {}, "copy": 0, "install": {},
                             "chunk": {}, "promote": 0, "tokens": 0}
        # what the decode program's attention traced as, also on its
        # compile.decode span: "paged_kernel" (the live-pages Pallas
        # kernel) or "einsum"; None until it has traced
        self.decode_attend: Optional[str] = None
        if self.speculative and "draft" in self._proposers:
            # the draft proposer's ONE compiled program bumps the
            # engine's own trace-count ledger, so the compile contract
            # (1 decode + 1 verify + 1 draft) is asserted in one place
            self._proposers["draft"].trace_counts = self.trace_counts
            self._proposers["draft"].registry = self.registry
        # compile events of the running step ({kind, key, seconds,
        # cache}; utils/compile_cache.Watched fills it), taken into the
        # step's flight-recorder record
        self._compiles: List[dict] = []
        reg = self.registry
        self._m_queue_depth = reg.gauge(
            "ptpu_serving_queue_depth", "requests waiting for a slot")
        self._m_active = reg.gauge(
            "ptpu_serving_active_slots", "slots decoding this step")
        self._m_step = reg.histogram(
            "ptpu_serving_step_seconds",
            "wall time of one engine iteration (engine clock)")
        self._m_prefill = reg.counter(
            "ptpu_serving_prefills_total", "prefill program runs",
            labels=("bucket",))
        self._m_evict = reg.counter(
            "ptpu_serving_evictions_total", "slots freed",
            labels=("reason",))
        self._m_reject = reg.counter(
            "ptpu_serving_rejected_total",
            "submissions refused at admission", labels=("reason",))
        self._m_replay_mismatch = reg.counter(
            "ptpu_serving_recover_replay_mismatch_total",
            "recovery re-prefills whose greedy replay token diverged "
            "from the already-delivered token")
        self._m_sampled = reg.counter(
            "ptpu_serving_sampled_tokens_total",
            "tokens of plain decode steps, by where each was chosen: "
            "the decode program's argmax (device) or the fetched "
            "logits (host)", labels=("where",))
        self._m_ahead = reg.counter(
            "ptpu_serving_decode_ahead_total",
            "plain decode steps enqueued one step ahead, fed the step "
            "before's argmax on the device before it was read")
        self._m_discarded = reg.counter(
            "ptpu_serving_decode_discarded_rows_total",
            "rows of a step enqueued ahead thrown away unread: the "
            "request finished at the step before (or left its slot), or "
            "sampled another token than the one fed ahead",
            labels=("reason",))
        if self.prefill_chunk is not None:
            self._m_chunk_steps = reg.counter(
                "ptpu_serving_chunk_steps_total",
                "chunked-prefill chunk program runs")
        if self.stateful:
            self._m_state_bytes = reg.gauge(
                "ptpu_serving_state_bytes",
                "total device bytes of the recurrent-state pool")
            self._m_state_bytes.set(self.cache.state_bytes())
            self._m_state_slots = reg.gauge(
                "ptpu_serving_state_slots_in_use",
                "slots whose recurrent state belongs to a request")
        # ptpu_serving_<name>_total for each name of the model's
        # step_counters(), made when the decode program first reports it
        self._m_counted: dict = {}
        if self.paged:
            self._m_pages_free = reg.gauge(
                "ptpu_serving_pages_free", "KV pages on the free list")
            self._m_pages_active = reg.gauge(
                "ptpu_serving_pages_active",
                "KV pages referenced by at least one request")
            self._m_pages_cached = reg.gauge(
                "ptpu_serving_pages_cached",
                "refcount-0 prefix-index pages (reclaimable)")
            self._m_kv_bytes = reg.gauge(
                "ptpu_serving_kv_bytes",
                "total device bytes of the paged KV pool (+scales)")
            self._m_kv_bytes.set(self.cache.kv_bytes())
            # what one page holds over every K/V layer (K, V, scales):
            # with live positions, the bytes a decode step's attention
            # has to read, whatever dtype the pool is in
            self._page_bytes = (self.cache.kv_bytes()
                                // self.cache.num_pages)
            self._m_prefix_hit = reg.counter(
                "ptpu_serving_prefix_hit_tokens_total",
                "prompt tokens served from shared prefix pages")
            self._m_prefix_lookup = reg.counter(
                "ptpu_serving_prefix_lookup_tokens_total",
                "prompt tokens eligible for prefix matching")
            self._m_cow = reg.counter(
                "ptpu_serving_cow_copies_total",
                "copy-on-write page copies")
            self._last_page_stats = {"prefix_hit_tokens": 0,
                                     "prefix_lookup_tokens": 0,
                                     "cow_copies": 0}
            self.peak_active_slots = 0
        if self.speculative:
            self._m_spec_acc = reg.histogram(
                "ptpu_serving_spec_accepted_length",
                "tokens emitted per row per verify step (1 = k=1 "
                "fallback or fully rejected draft), by the proposer "
                "that drafted the row ('none' = undrafted)",
                buckets=tuple(float(i) for i in
                              range(1, self.spec_k + 1)),
                labels=("proposer",))
            self._m_spec_proposer = reg.counter(
                "ptpu_spec_proposer_total",
                "rows drafted per verify step, by proposer kind",
                labels=("kind",))
            if self._tuner is not None:
                self._m_spec_tuner_k = reg.gauge(
                    "ptpu_spec_tuner_k",
                    "spec window k the autotuner is running per "
                    "request class (1 = speculation off)",
                    labels=("klass",))
            # host-side aggregate: the SPEC_DECODE bench line and
            # spec_stats() read this (registry histograms only keep
            # bucketized counts)
            self._spec = {"steps": 0, "gated_steps": 0, "rows": 0,
                          "emitted": 0,
                          "draft_tokens": 0, "accepted_draft_tokens": 0,
                          "draft_faults": 0, "resamples": 0,
                          "draft_s": 0.0,
                          "acc_len_hist": [0] * (self.spec_k + 1)}

    def _new_cache(self):
        """Fresh cache manager (init + recover): K/V pages for the
        model's K/V layers, a state row a slot for its state layers. On
        a mesh engine the pages are committed SHARDED (kv_heads over
        the `model` axis) to the DECODE group, which owns all pool
        state — disaggregated prefills hand their KV over."""
        ad = self.adapter
        kv_sh = sc_sh = None
        if self.meshctx is not None:
            kv_sh = self.meshctx.kv_sharding()
            sc_sh = self.meshctx.scale_sharding()
        return SlotCache(
            ad.spec.layers, ad.spec.state, self.max_slots, self.max_len,
            ad.kv_heads, ad.head_dim, ad.dtype,
            page_size=self.page_size, num_pages=self.num_pages,
            quant=self.kv_quant,
            prefix_sharing=self.prefix_sharing,
            kv_sharding=kv_sh, scale_sharding=sc_sh,
            tier=self._kv_tier)

    def _refresh_state(self) -> None:
        """Re-snapshot the model weights (checkpoint loads /
        quantization on the live model take effect next step). Mesh
        engines additionally commit the snapshot to the mesh via the
        family's tp_param_spec rules — cached by source-array identity
        so an unchanged model costs no transfer — and, when
        disaggregated, keep a second placed copy on the prefill group
        (each chip group holds its own weights, the standard
        disaggregated-serving memory layout)."""
        params, buffers = self.adapter.model.raw_state()
        if self.meshctx is None:
            self._params, self._buffers = params, buffers
            return
        m = self.meshctx
        self._params, self._buffers = self._place_state(
            params, buffers, self._param_shardings(params, "decode"),
            m.repl("decode"), self._placed["decode"])
        if m.disaggregated:
            self._params_pf, self._buffers_pf = self._place_state(
                params, buffers,
                self._param_shardings(params, "prefill"),
                m.repl("prefill"), self._placed["prefill"])

    def _param_shardings(self, params, group):
        """Per-param NamedSharding dict, cached per group: static for
        a given (param-name set, mesh), so the per-step refresh only
        pays a tuple compare. A same-NAME shape change (no known
        path) would surface as a loud device_put error, never a
        silently wrong sharding."""
        key = tuple(params)
        got = self._shardings_cache.get(group)
        if got is None or got[0] != key:
            got = (key, self.meshctx.param_shardings(
                params, self.adapter, group))
            self._shardings_cache[group] = got
        return got[1]

    @staticmethod
    def _place_state(params, buffers, param_sh, repl, cache):
        fresh = {}

        def put(name, src, sh):
            got = cache.get(name)
            # identity check against the LIVE source kept in the
            # entry: a swapped array (checkpoint load) re-places even
            # if the new object reuses the old one's address
            if got is not None and got[0] is src:
                placed = got[1]
            else:
                placed = jax.device_put(src, sh)
            fresh[name] = (src, placed)
            return placed

        p = {n: put(("p", n), a, param_sh[n])
             for n, a in params.items()}
        b = {n: put(("b", n), a, repl) for n, a in buffers.items()}
        cache.clear()
        cache.update(fresh)
        return p, b

    def _publish_page_stats(self, sp=None) -> None:
        """Pool and prefix counters into the registry and, as this
        step's counts, onto the ``serving.step`` span ``sp``."""
        c = self.cache
        in_use = c.active_page_count()
        self._m_pages_free.set(c.free_page_count())
        self._m_pages_active.set(in_use)
        self._m_pages_cached.set(c.cached_page_count())
        last = self._last_page_stats
        if sp is not None:
            sp.set_attr("pages_in_use", in_use)
            sp.set_attr("pages_reserved", c.committed_pages)
            sp.set_attr("pages_total", c.num_pages - 1)
            sp.set_attr("page_bytes", self._page_bytes)
            sp.set_attr("prefix_hit_tokens", c.prefix_hit_tokens
                        - last["prefix_hit_tokens"])
            sp.set_attr("prefix_lookup_tokens", c.prefix_lookup_tokens
                        - last["prefix_lookup_tokens"])
        for counter, key in ((self._m_prefix_hit, "prefix_hit_tokens"),
                             (self._m_prefix_lookup,
                              "prefix_lookup_tokens"),
                             (self._m_cow, "cow_copies")):
            cur = getattr(c, key)
            if cur > last[key]:
                counter.inc(cur - last[key])
            last[key] = cur

    def spec_stats(self) -> dict:
        """Speculative-decoding snapshot (raises on a non-speculative
        engine): verify steps, per-row emission totals, draft
        proposal/acceptance counts, accepted-length histogram."""
        if not self.speculative:
            raise RuntimeError("spec_stats() on a non-speculative "
                               "engine")
        s = dict(self._spec)
        s["acc_len_hist"] = list(s["acc_len_hist"])
        s["k"] = self.spec_k
        s["proposer"] = self.spec_proposer
        s["sampled"] = self.spec_sampled
        s["draft_hit_rate"] = (
            s["accepted_draft_tokens"] / s["draft_tokens"]
            if s["draft_tokens"] else 0.0)
        s["accepted_per_step"] = (
            s["emitted"] / s["rows"] if s["rows"] else 0.0)
        if self._tuner is not None:
            s["tuner"] = self._tuner.snapshot()
        return s

    def _proposer_release(self, rid: int) -> None:
        """Release one rid's draft state from EVERY configured
        proposer (the tuner may have moved a request between kinds
        mid-flight; all of them hold lockstep-evicted state)."""
        if self.speculative:
            for p in self._proposers.values():
                p.release(rid)

    def _proposer_retain(self, rids) -> None:
        if self.speculative:
            keep = list(rids)
            for p in self._proposers.values():
                p.retain(keep)

    def paged_stats(self) -> dict:
        """Paged-pool snapshot for benchmarks/dashboards (raises on a
        state engine): cache page/prefix/COW counters plus the peak
        concurrent in-flight requests this engine reached."""
        if not self.paged:
            raise RuntimeError("paged_stats() on a state engine")
        s = self.cache.stats()
        s["peak_active_slots"] = self.peak_active_slots
        s["prefix_hit_rate"] = (
            s["prefix_hit_tokens"] / s["prefix_lookup_tokens"]
            if s["prefix_lookup_tokens"] else 0.0)
        return s

    # -- public API ----------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int = 16,
               sampling: Optional[SamplingParams] = None,
               deadline_s: Optional[float] = None,
               tenant: Optional[str] = None) -> Request:
        """Queue one request; returns its handle (tokens appear on it
        as steps run).

        ``deadline_s`` (seconds from now, engine clock): the request is
        cancelled at the first step boundary past the deadline —
        ``finish_reason`` becomes ``"deadline"`` and ``Request.error``
        carries a typed :class:`DeadlineExceeded`.

        Typed refusals: :class:`EngineClosed` after ``drain()``,
        :class:`EngineBroken` until ``recover()``, :class:`QueueFull`
        when ``max_queue`` requests are already waiting.
        """
        # refuse BEFORE building: a typed refusal must not consume a
        # rid or pay input validation (submit_request re-checks for
        # callers that build first, e.g. the router)
        self._check_admission()
        return self.submit_request(self._build_request(
            prompt_ids, max_new_tokens, sampling, deadline_s,
            tenant=tenant))

    def _check_admission(self) -> None:
        if self._closed:
            raise EngineClosed()
        if self._broken:
            raise EngineBroken(self._broken)
        if self.max_queue is not None \
                and self.scheduler.depth >= self.max_queue:
            self._m_reject.labels(reason="queue_full").inc()
            raise QueueFull(self.scheduler.depth, self.max_queue)

    def _build_request(self, prompt_ids, max_new_tokens: int = 16,
                       sampling: Optional[SamplingParams] = None,
                       deadline_s: Optional[float] = None,
                       rid: Optional[int] = None,
                       tenant: Optional[str] = None) -> Request:
        """Validate inputs and build a Request WITHOUT enqueuing it.
        ``rid=None`` draws from this engine's counter; the replica
        router passes its own (globally unique across replicas, so a
        request keeps one identity through failover adoption)."""
        ids = np.asarray(getattr(prompt_ids, "numpy", lambda: prompt_ids)()
                         ).astype(np.int64)
        if ids.ndim == 2 and ids.shape[0] == 1:   # [1, T] batch-of-one
            ids = ids[0]
        if ids.ndim != 1:
            # a [B, T] batch must not silently flatten into ONE merged
            # request — submit() takes one sequence per call
            raise ValueError(
                f"submit() takes a single prompt sequence; got shape "
                f"{ids.shape}. Call submit() once per request.")
        if ids.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if ids.size + max_new_tokens - 1 > self.max_len:
            raise ValueError(
                f"prompt ({ids.size}) + max_new_tokens "
                f"({max_new_tokens}) - 1 exceeds max_len {self.max_len}")
        sampling = sampling or SamplingParams()
        sampling.validate()
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be > 0, got {deadline_s}")
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        req = Request(rid=rid, prompt=ids,
                      max_new_tokens=int(max_new_tokens),
                      sampling=sampling,
                      deadline=(self.metrics.now() + deadline_s
                                if deadline_s is not None else None),
                      tenant=tenant)
        req._rng = np.random.RandomState(
            sampling.seed if sampling.seed is not None
            else 0x5EED + req.rid)
        return req

    def submit_request(self, req: Request) -> Request:
        """Enqueue a pre-built Request (typed admission checks apply;
        ``submit()`` is ``submit_request(_build_request(...))``)."""
        self._check_admission()
        # sampled BEFORE the request enters the queue: a request that
        # arrives while other work is in flight may see its first
        # token blocked behind prefills — the decode-stall histogram's
        # population (docs/SERVING.md "Chunked prefill")
        stalled = self.has_work()
        self.scheduler.add(req)
        self.metrics.on_submit(req.rid, stalled=stalled)
        self._m_queue_depth.set(self.scheduler.depth)
        if self.auditor is not None:
            self.auditor.on_submitted(req)
        return req

    def adopt(self, req: Request) -> Request:
        """Take over an existing request mid-flight (router failover:
        its previous replica died). The request may already carry
        delivered tokens — admission then re-prefills prompt + those
        tokens via the ``recover()`` replay contract, so greedy output
        stays token-identical and nothing is retracted. Bypasses
        ``max_queue`` (a failover must never drop a request the
        service already accepted) and does NOT re-audit submission
        (the request was audited where it first entered)."""
        if self._closed:
            raise EngineClosed()
        if self._broken:
            raise EngineBroken(self._broken)
        req.slot = None
        req.prefill_pos = None
        stalled = self.has_work()
        self.scheduler.add(req)
        self.metrics.on_submit(req.rid, stalled=stalled)
        self._m_queue_depth.set(self.scheduler.depth)
        return req

    def has_work(self) -> bool:
        return self.scheduler.has_pending() or \
            bool(self.cache.active_slots())

    def probe(self, timeout: Optional[float] = None) -> dict:
        """Health probe: a cheap, non-mutating liveness summary. The
        router calls this on every replica each round; the cluster's
        RemoteReplica turns it into one RPC with ``timeout`` as the
        per-call deadline (a slow worker surfaces as TimeoutError →
        SUSPECT, never an instant ReplicaDead). In-process, a broken
        engine is still *alive* — it answers probes and recovers — so
        this never raises."""
        del timeout  # in-process: answering at all is the liveness
        return {"broken": self._broken,
                "queued": self.scheduler.depth,
                "active": len(self.cache.active_slots())}

    def step(self) -> List[Request]:
        """One engine iteration: admit into free slots (bucketed
        prefill), then one decode step over every occupied slot, then
        evict finished sequences. Returns requests finished this step.

        Every step appends a flight-recorder record (latency, slot
        occupancy, queue depth, admissions/evictions, compile events);
        if the step raises, the recorder ring dumps to disk before the
        exception propagates — the post-mortem for a dead serving
        loop.

        Typed refusals: :class:`EngineBroken` until ``recover()`` after
        a donated-pool step failure; :class:`EngineIdle` when there is
        no queued or in-flight work (guard loops with ``has_work()``).
        """
        if self._broken:
            raise EngineBroken(self._broken)
        if not self.has_work():
            raise EngineIdle()
        t0 = self.metrics.now()
        step_idx = self._step_idx
        self._step_idx += 1
        del self._compiles[:]
        # the finished list is allocated HERE, outside the try: a
        # request that reaches a terminal state early in the step
        # (deadline sweep, decode finisher) is already evicted from its
        # slot/queue, so if the step then faults it exists nowhere else
        # — it must survive the raise or it is lost forever
        finished: List[Request] = []
        with span("serving.step", step=step_idx) as sp:
            try:
                admitted, n_active = self._step_inner(finished, sp)
            except Exception as e:
                self._on_step_error(step_idx, e, finished)
                raise
            with span("serving.publish"):
                return self._publish_step(sp, step_idx, t0, admitted,
                                          n_active, finished)

    def _on_step_error(self, step_idx: int, e: Exception,
                       finished: List[Request]) -> None:
        # a step enqueued ahead is abandoned with the failed one: the
        # next step (or recover()) starts again from the host's tokens
        self._ahead = None
        if finished:
            self._undelivered.extend(finished)
        if self._donate():
            # the jit call may have CONSUMED the donated pools
            # before failing: ks/vs can reference deleted device
            # buffers, and any later step would die confusingly —
            # refuse further use until recover() rebuilds them
            self._broken = f"step #{step_idx}: " \
                           f"{type(e).__name__}: {e}"
        try:
            self.recorder.record(
                "serving.step_error", step=step_idx,
                error=f"{type(e).__name__}: {e}")
            path = self.recorder.dump(
                reason=f"ServingEngine.step #{step_idx} raised "
                       f"{type(e).__name__}: {e}",
                registry=self.registry)
            import sys
            print(f"[serving] flight recorder dumped to {path}",
                  file=sys.stderr)
        except Exception:
            pass               # never mask the original failure

    def _publish_step(self, sp, step_idx: int, t0: float, admitted,
                      n_active: int,
                      finished: List[Request]) -> List[Request]:
        """What a step tells the outside once its work is done: the
        step span's counts, registry, flight recorder, auditor."""
        dt = self.metrics.now() - t0
        depth = self.scheduler.depth
        self._m_step.observe(dt)
        self._m_queue_depth.set(depth)
        self._m_active.set(n_active)
        sp.set_attr("active_slots", n_active)
        sp.set_attr("queue_depth", depth)
        wt = self._watchtower
        if wt is not None:
            wt.observe_step()
        if self._undelivered:
            # requests stranded by an earlier FAILED step ride the
            # first successful step out (they finished first: prepend)
            finished = self._undelivered + finished
        # the whole batch stays OWED until the return below actually
        # happens: if the recorder or a caller-supplied auditor raises
        # first, the next step()/recover()/drain() still delivers
        # (at worst re-auditing a prefix — detectable — never losing)
        self._undelivered = finished
        self.recorder.record(
            "serving.step", step=step_idx, step_latency_s=dt,
            active_slots=n_active, queue_depth=depth,
            admitted=admitted,
            evicted=[(r.rid, r.finish_reason) for r in finished],
            compiles=list(self._compiles))
        if self.auditor is not None and not self._in_drain:
            # drain() audits its aggregate return instead, so each
            # request is audited at exactly ONE external boundary
            for r in finished:
                self.auditor.on_delivered(r, via="step")
        self._undelivered = []
        return finished

    def _admit(self, finished: List[Request]):
        """The step's sweeps and admission (span ``serving.admit``);
        returns the (slot, request) pairs to prefill."""
        # 0) deadline + disconnect sweeps — cancel expired requests and
        # requests whose client went away BEFORE spending a prefill or
        # decode slot-step on them
        self._expire_deadlines(finished)
        self._sweep_disconnects(finished)
        # re-snapshot the weights so checkpoint loads / quantization on
        # the live model object take effect next step (same pytree
        # structure -> no retrace; the arrays are just jit arguments)
        self._refresh_state()
        # 1) admission — freed slots refill BEFORE the decode so a new
        # request's first decode token rides this very step. Paged:
        # admission is gated by FREE PAGES, not just free slots — the
        # claim reserves the request's worst-case page span so decode
        # can never run out of pages mid-flight
        claim = None
        refused: List[int] = []      # rids whose page claim failed
        if self.paged:
            claim = lambda req: self.cache.try_reserve(
                req, req.prompt,
                req.prompt_len + req.max_new_tokens) \
                or refused.append(req.rid)
        pairs = self.scheduler.admissions(
            self.cache.free_slots(), claim=claim,
            lookahead=self.admission_lookahead,
            unclaim=self.cache.cancel_reservation if self.paged
            else None)
        return pairs, len(refused)

    def _step_inner(self, finished: List[Request], sp=None):
        admitted: List[int] = []
        with span("serving.admit") as asp:
            pairs, refused = self._admit(finished)
            asp.set_attr("admitted", len(pairs))
            asp.set_attr("refused_for_pages", refused)
        # per-step prefill token budget (chunked engines): one chunk's
        # worth. Prompts that fit run the MONOLITHIC prefill program
        # inside the budget (the degenerate case IS the unchunked
        # path); longer prompts claim their slot/pages now and enter
        # the PREFILLING fifo, advancing one chunk per step below —
        # so no step ever runs more than `prefill_chunk` prefill
        # tokens plus the one-token-per-slot decode.
        chunk = self.prefill_chunk
        budget = chunk
        if chunk is not None and self.chunk_control is not None:
            # adaptive budget: queued + chunk-pending work pushes it
            # up, the active-decode population (the requests every
            # extra chunk would stall) pulls it back down
            budget = self.chunk_control.step_budget(
                chunk,
                self.scheduler.depth + len(self._chunk_fifo),
                stall=float(len(self.cache.active_slots())))
        for i, (slot, req) in enumerate(pairs):
            try:
                if chunk is None:
                    self._prefill(slot, req)
                else:
                    n_ids = req.prompt_len + max(
                        0, len(req.out_tokens) - 1)
                    if not self._chunk_fifo and n_ids <= budget:
                        self._prefill(slot, req)
                        budget -= n_ids
                    else:
                        self._begin_chunked(slot, req)
            except RequestCancelled as e:
                # the client vanished while THIS request was being
                # prefilled: the abort path already unwound its pages
                # (paged) and no slot was assigned — cancel just this
                # request and keep admitting the rest of the batch
                self._finish_disconnect(req, exc=e, finished=finished)
                continue
            except Exception:
                # admissions() popped the WHOLE batch: everything not
                # yet prefilled goes back to the queue head in FCFS
                # order, or a recovered engine silently loses them
                # (their page reservations return with them)
                for _, later in reversed(pairs[i + 1:]):
                    if self.paged:
                        self.cache.cancel_reservation(later)
                    self.scheduler.requeue(later)
                if req.slot is None and not req.out_tokens:
                    if self.paged:
                        self.cache.cancel_reservation(req)
                    self.scheduler.requeue(req)
                raise
            admitted.append(req.rid)
            if req.finished:
                self._evict(slot, req, finished)
        # 1b) PREFILLING work within what is left of the step's
        # prefill budget, interleaved with the decode below. Without a
        # chunk controller this is AT MOST ONE chunk program run per
        # step (the legacy contract, bit-identical); with one, the
        # same compiled program runs back-to-back until the adaptive
        # budget is spent.
        ran = 0
        while chunk is not None and self._chunk_fifo:
            head = self.cache.slots[self._chunk_fifo[0]]
            n_ids = head.prompt_len + max(0, len(head.out_tokens) - 1)
            take = min(chunk, n_ids - head.prefill_pos)
            if take > budget:
                break
            self._chunk_step(finished)
            budget -= take
            ran += 1
            if self.chunk_control is None and ran >= 1:
                break
        # 2) one decode step over all occupied slots — the speculative
        # engine runs its widened k-token VERIFY program instead (same
        # contract: ONE compiled program for any request mix).
        # PREFILLING slots (mid-chunked-prefill) hold no decodable
        # token yet and are skipped until their final chunk.
        active = [s for s in self.cache.active_slots()
                  if self.cache.slots[s].prefill_pos is None]
        if active:
            if self.speculative:
                self._decode_verify(active, finished)
            else:
                self._decode_plain(active, finished)
        with span("serving.publish"):
            self.metrics.on_step(len(active))
            if self.paged:
                self.peak_active_slots = max(self.peak_active_slots,
                                             len(active))
                self._publish_page_stats(sp)
            if self.stateful:
                in_use = len(self.cache.active_slots())
                self._m_state_slots.set(in_use)
                if sp is not None:
                    sp.set_attr("state_slots_in_use", in_use)
                    sp.set_attr("state_slots_total", self.max_slots)
                    sp.set_attr("state_bytes",
                                in_use * self.cache.slot_bytes)
        return admitted, len(active)

    def _decode_plain(self, active, finished: List[Request]) -> None:
        """The k=1 decode step (non-speculative engines), run one step
        ahead where the engine can (docs/SERVING.md "One step ahead"):
        the next step, fed this step's argmax where it lies on the
        device, is enqueued before this step's tokens are read, so the
        host samples, delivers and admits while the device runs it.
        ``self._ahead`` is the step an earlier call enqueued so; a call
        without one builds its step from the host's tokens, and a call
        that enqueues none after it is a synchronous step."""
        step, self._ahead = self._ahead, None
        if step is not None:
            # a request that left its slot since (a cancel, a deadline,
            # a disconnect) finished: its row is thrown away unread
            ran = step.rows
            step.rows = [(s, r) for s, r in ran if self.cache.slots[s] is r]
            self._discard(step, "finished", len(ran) - len(step.rows))
            if not step.rows:
                step = None
        if step is None:
            ran = [(s, self.cache.slots[s]) for s in active]
        with self._decode_span("serving.decode",
                               [r for _, r in ran]) as dsp:
            if step is None:
                step = self._enqueue_decode(ran)
            rows = self._ahead_rows(step, active)
            nxt = self._enqueue_decode(rows, step.best) if rows else None
            dsp.set_attr("ahead", int(step.ahead))
            dsp.set_attr("discarded", step.discarded)
            if self.paged:
                # the pages a length-aware attention has to read
                dsp.set_attr("live_pages", step.live_pages)
            # the batch decides what the step fetches: all rows greedy,
            # the program's argmax ([slots] int32); one row that
            # samples, the logits, which its seeded host stream draws
            # from (a mixed batch takes the host path whole). What the
            # model counted crosses with the tokens, in one fetch: a
            # fetch of its own costs its fixed ~0.4 ms a step
            on_device = step.on_device
            fetched, counted = self._fetch(
                "serving.decode.fetch",
                step.best if on_device else step.logits,
                beside=step.counted)
            if counted:
                self._publish_counted(dsp, counted)
        n = len(step.rows)
        with span("serving.sample", rows=n,
                  device_rows=n if on_device else 0,
                  host_rows=0 if on_device else n) as sp:
            self._m_sampled.labels(
                where="device" if on_device else "host").inc(n)
            n0 = len(finished)
            for s, req in step.rows:
                row = ArgmaxRow(step.logits, s, int(fetched[s])) \
                    if on_device else fetched[s]
                tok = sample_token(row, req.sampling, req._rng)
                req.out_tokens.append(tok)
                self.metrics.on_token(req.rid)
                if self._is_finished(req, tok):
                    self._evict(s, req, finished)
            sp.set_attr("finished", len(finished) - n0)
        if nxt is not None:
            # the step ahead was fed the argmax: a row whose request
            # ended at this step, or whose sampled token is another,
            # is thrown away; the latter runs its position again from
            # the sampled token, which overwrites the K and V written
            keep = []
            for s, req in nxt.rows:
                if req.finished:
                    self._discard(nxt, "finished")
                elif req.out_tokens[-1] != int(fetched[s]):
                    self._discard(nxt, "resampled")
                else:
                    keep.append((s, req))
            nxt.rows = keep
            self._ahead = nxt if keep else None

    def _ahead_rows(self, step: _Step, active) -> list:
        """The rows of a step to enqueue behind ``step`` before it is
        read, or none: the engine then drains. A step runs ahead only
        where its guess cannot outlive it: K/V pages alone (a page
        written from a token that is then not the sampled one is
        written again from the sampled one; a recurrent state folds it
        in for good), no drafts, every row greedy and in the step, and
        nothing waiting for the boundary between the two steps: an
        admission into a slot free now or freed by length at this
        step, a chunk, a cancel, a deadline. A row that ends by length
        at this step is left out."""
        if self.stateful or self.speculative or not step.on_device \
                or len(step.rows) != len(active) or self._chunk_fifo:
            return []
        now = self.metrics.now()
        rows, frees = [], False
        for s, req in step.rows:
            if req.cancel_requested or (req.deadline is not None
                                        and now > req.deadline):
                return []
            if len(req.out_tokens) + 1 >= req.max_new_tokens:
                frees = True
            else:
                rows.append((s, req))
        if self.scheduler.has_pending() \
                and (frees or self.cache.free_slots()):
            return []
        return rows

    def _enqueue_decode(self, rows, fed=None) -> _Step:
        """Build and enqueue one decode step over ``rows``, (slot,
        request) pairs: each request's last token at its next position
        or, with ``fed`` (the argmax of the step enqueued just before,
        still on the device), that token one position further on."""
        ahead = fed is not None
        with span("serving.decode.build") as sp:
            toks = np.zeros((self.max_slots,), np.int32)
            pos = np.zeros((self.max_slots,), np.int32)
            mask = np.zeros((self.max_slots,), bool)
            copies = []
            for s, req in rows:
                toks[s] = req.out_tokens[-1]
                pos[s] = req.next_pos + ahead
                mask[s] = True
                if self.paged:
                    # the write may cross into a new page (allocate)
                    # or a shared one (COW) — resolve BEFORE the step.
                    # One page a row, the one holding pos: a retried
                    # step claims it again idempotently and writes it
                    # anyway, pos stays inside the row's reservation
                    # (a row that ends by length is never run ahead),
                    # and release() frees it on evict
                    # ptpu-lint: disable=PTL301 -- no claim to strand
                    c = self.cache.ensure_decode_page(s, int(pos[s]))
                    if c is not None:
                        copies.append(c)
            # COW copies run BEFORE the fault point:
            # ensure_decode_page already flipped the table rows, and a
            # retried (non-broken) step would not re-issue a lost copy
            # — device state must be consistent with the table when
            # the fault can fire
            live = 0
            if self.paged:
                self._run_copies(copies)
                live = int((pos[mask] // self.cache.page_size + 1).sum())
            sp.set_attr("cow_copies", len(copies))
        maybe_fail("serving.step.decode", step=self._step_idx - 1)
        if self.meshctx is not None:
            # mesh engines: the SHARDED decode program is about to run
            # (chaos kill point for the tensor-parallel flavor)
            maybe_fail("serving.decode.sharded",
                       step=self._step_idx - 1, tp=self.meshctx.tp)
        with span("serving.decode.enqueue", batch=len(rows)):
            c = self.cache
            if not ahead:
                # the host's tokens go where the argmax lies
                fed = jax.device_put(toks, self._tokens_sharding)
            logits, best, counted, ks, vs, kss, vss, *pools = \
                self._decode_fn()(
                    self._params, self._buffers,
                    self._tokens_fn()(fed), pos, mask,
                    c.page_table.copy(), c.ks, c.vs, c.kss, c.vss,
                    *c.pools)
            c.ks, c.vs = list(ks), list(vs)
            c.kss, c.vss = list(kss), list(vss)
            c.pools = pools
        if ahead:
            self._m_ahead.inc()
        return _Step(rows, logits, best, counted,
                     all(r.sampling.temperature <= 0 for _, r in rows),
                     live, ahead)

    def _discard(self, step: _Step, reason: str, n: int = 1) -> None:
        """``n`` rows of ``step``, enqueued ahead, thrown away unread."""
        if n:
            step.discarded += n
            self._m_discarded.labels(reason=reason).inc(n)

    def _publish_counted(self, dsp, counted: dict) -> None:
        """What the model's decode program counted beside its logits
        (``step_counters()``: small integer arrays by name), each summed
        onto the ``serving.decode`` span as the attribute ``name`` and
        into the registry counter ``ptpu_serving_<name>_total``."""
        for name, value in counted.items():
            n = int(np.sum(value))
            dsp.set_attr(name, n)
            if name not in self._m_counted:
                self._m_counted[name] = self.registry.counter(
                    f"ptpu_serving_{name}_total",
                    f"{name}: what the model's decode program counted "
                    "(step_counters()), summed over the decode steps")
            self._m_counted[name].inc(n)

    def _decode_span(self, name: str, reqs, **attrs):
        """The batch span of one decode or verify step over the requests
        ``reqs``. It carries the batch size; the request ids ride along
        only on a process whose requests have trace contexts (a cluster
        worker: the merged timeline fans batch spans out to per-request
        lanes)."""
        if has_bindings():
            attrs["request_ids"] = [r.rid for r in reqs]
        return span(name, batch=len(reqs), **attrs)

    def _decode_verify(self, active, finished: List[Request]) -> None:
        """One speculative verify step: draft up to k-1 tokens per
        eligible row (n-gram prompt lookup or the small draft model,
        per the configured/tuned proposer), score all k candidate
        positions in ONE widened forward over the static cache, and
        emit the accepted prefix — for greedy rows provably the tokens
        sequential greedy decode would have produced, since each
        position's logits are computed under the identical causal mask
        and cache state; for sampled rows (spec_sampled=True) the
        rejection-sampling rule in ``_emit_verified``, which preserves
        the k=1 sampling distribution exactly (see docs/SERVING.md).

        Rows without a usable draft (no n-gram hit, sampled decoding
        without spec_sampled, tuner says off, or 1 token of budget
        left) run at per-row length 1 INSIDE the same program — the
        k=1 fallback costs no extra compile. wlen write-masks the
        PADDED lanes beyond each row's draft window; drafted-but-
        rejected tokens DO write k/v, which is safe because those
        positions sit beyond the new write position (causal-masked
        until overwritten, exactly like any stale tail) and are never
        shared/indexed — so the only rollback needed is returning
        over-allocated pages.

        A draft proposal that FAILS (fault point ``serving.spec.draft``
        or a real draft-model error) is contained to that row's step:
        the row falls back to k=1, the proposer's state for the rid is
        unwound (``_on_draft_fault``), and the step proceeds — a draft
        model must never be able to take down target decoding."""
        K = self.spec_k
        toks = np.zeros((self.max_slots, K), np.int64)
        pos = np.zeros((self.max_slots,), np.int32)
        wlen = np.zeros((self.max_slots,), np.int32)
        mask = np.zeros((self.max_slots,), bool)
        row_kind = {}          # slot -> proposer kind that DRAFTED
        row_draft = {}         # slot -> draft tokens (sampled rows)
        row_qs = {}            # slot -> per-draft q dists ([] = point mass)
        attempted = {}         # slot -> (klass, kind) fed to the tuner
        for s in active:
            req = self.cache.slots[s]
            toks[s, 0] = req.out_tokens[-1]
            pos[s] = req.next_pos
            mask[s] = True
            n = 1
            sampled = req.sampling.temperature > 0
            klass = "sampled" if sampled else "greedy"
            kind = self.spec_proposer
            k_cap = K
            if self._tuner is not None:
                k_cap, kind = self._tuner.decide(klass)
            # a draft longer than the remaining token budget is wasted
            # verify compute AND would write past the admission
            # reservation — clamp so every write stays inside the
            # request's reserved span
            budget = req.max_new_tokens - len(req.out_tokens)
            want = min(K - 1, budget - 1, k_cap - 1)
            if want > 0 and kind is not None \
                    and (not sampled or self.spec_sampled):
                prop = self._proposers[kind]
                attempted[s] = (klass, kind)
                draft, qs = (), []
                t0 = self.metrics.now()
                try:
                    maybe_fail("serving.spec.draft",
                               step=self._step_idx - 1, slot=s)
                    if sampled \
                            and isinstance(prop, DraftModelProposer):
                        draft, qs = prop.propose_sampled(
                            req.rid, req.full_ids, want,
                            req.sampling, req._rng)
                    else:
                        # point-mass proposal: q is a delta on the
                        # drafted token (qs=[] signals this to the
                        # acceptance rule)
                        draft = prop.propose(
                            req.rid, req.full_ids, want)
                except Exception as exc:
                    draft, qs = (), []
                    self._on_draft_fault(s, req, prop, exc)
                finally:
                    dt = self.metrics.now() - t0
                    self._spec["draft_s"] += dt
                    self.metrics.on_draft(dt)
                if len(draft):
                    toks[s, 1:1 + len(draft)] = draft
                    n = 1 + len(draft)
                    row_kind[s] = kind
                    if sampled:
                        row_draft[s], row_qs[s] = draft, qs
                    self._spec["draft_tokens"] += len(draft)
                    self._m_spec_proposer.labels(kind=kind).inc()
            wlen[s] = n
        if self.spec_gate and all(int(wlen[s]) == 1 for s in active):
            # no row drafted this step: every lane would run the
            # k-wide program at wlen 1 — the k=1 decode program emits
            # the PROVABLY identical token (same logits row, same
            # per-row RNG stream for sampled rows, same page/EOS
            # bookkeeping) at 1/k the verify compute. No page state
            # was touched yet, so delegating is clean; trace counts
            # stay bounded at <= 1 decode + <= 1 verify program.
            # the mid-verify kill point still guards EVERY speculative
            # decode step (drafts considered, nothing emitted yet) —
            # gating must not thin the chaos sweep's kill cadence
            maybe_fail("serving.decode.verify",
                       step=self._step_idx - 1, gated=True)
            n_rows = len(active)
            self._decode_plain(active, finished)
            # accounting AFTER the delegated step succeeds: a fault
            # inside it replays through this gate on recover, and a
            # pre-bump would double-count rows that delivered once
            self._spec["gated_steps"] += 1
            self._spec["rows"] += n_rows
            self._spec["emitted"] += n_rows
            self._spec["acc_len_hist"][1] += n_rows
            for _ in range(n_rows):
                self._m_spec_acc.labels(proposer="none").observe(1.0)
            # rows that TRIED to draft and came back empty are signal
            # the tuner must see (accepted length 1), else an always-
            # missing proposer never reads as "not paying"
            self._tuner_step(attempted, {s: 1 for s in attempted})
            return
        copies = []
        try:
            with span("serving.decode.build") as sp:
                for s in active:
                    copies += self.cache.ensure_decode_range(
                        s, self.cache.slots[s].next_pos,
                        int(wlen[s]))
                # COW copies BEFORE the kill point (same reason as
                # the plain decode: flipped table rows must never
                # outrun their copies)
                self._run_copies(copies)
                sp.set_attr("cow_copies", len(copies))
            # mid-verify-step kill point: drafts built, pages
            # claimed/COW'd, nothing emitted yet — recovery must
            # replay token-identically and leak no pages
            # (chaos-audited)
            maybe_fail("serving.decode.verify",
                       step=self._step_idx - 1)
            if self.meshctx is not None:
                maybe_fail("serving.decode.sharded",
                           step=self._step_idx - 1,
                           tp=self.meshctx.tp)
            with self._decode_span("serving.verify",
                                   [self.cache.slots[s] for s in active],
                                   k=K):
                with span("serving.decode.enqueue", batch=len(active)):
                    logits, greedy, acc, ks, vs, kss, vss = \
                        self._verify_fn()(
                            self._params, self._buffers, toks,
                            pos, mask, wlen,
                            self.cache.page_table.copy(),
                            self.cache.ks, self.cache.vs,
                            self.cache.kss, self.cache.vss)
                    self.cache.ks, self.cache.vs = list(ks), list(vs)
                    self.cache.kss, self.cache.vss = \
                        list(kss), list(vss)
                logits, greedy, acc = (
                    self._fetch("serving.decode.fetch", x)
                    for x in (logits, greedy, acc))
        except Exception:
            # a verify step that dies here (fault point, program
            # failure) never emitted a token, but ensure_decode_range
            # already claimed every page the k-wide write window
            # touches. Those extra pages sit past each row's next
            # write position and nothing frees them until the request
            # finishes — on a non-broken engine they silently shrink
            # the admission pool on every faulted step. Return them
            # NOW; the retried step re-claims idempotently (the page
            # holding next_pos itself is kept — the retry writes it).
            for s in active:
                req = self.cache.slots[s]
                if req is not None:
                    self.cache.rollback_speculation(s, req.next_pos)
            raise
        emitted_by_slot = {}
        try:
            with span("serving.sample", rows=len(active)):
                for s in active:
                    req = self.cache.slots[s]
                    emitted = self._emit_verified(
                        s, req, greedy[s], int(acc[s]), logits[s],
                        draft=row_draft.get(s), qs=row_qs.get(s))
                    emitted_by_slot[s] = emitted
                    self._spec["rows"] += 1
                    self._spec["emitted"] += emitted
                    self._spec["accepted_draft_tokens"] += emitted - 1
                    self._spec["acc_len_hist"][min(emitted, K)] += 1
                    self._m_spec_acc.labels(
                        proposer=row_kind.get(s, "none")).observe(
                            float(emitted))
                    if not req.finished:
                        # return pages past the next write position that
                        # only rejected draft tokens touched (finished
                        # rows release everything below)
                        self.cache.rollback_speculation(s, req.next_pos)
                    if req.finished:
                        self._evict(s, req, finished)
        except Exception:
            # a fault mid-emission (serving.spec.resample) leaves rows
            # not yet emitted this pass with over-claimed pages — the
            # same debt the pre-verify except arm pays. Tokens already
            # appended stay appended (out_tokens only ever grows; the
            # retried step continues from the advanced next_pos).
            for s in active:
                req = self.cache.slots[s]
                if req is not None and not req.finished:
                    self.cache.rollback_speculation(s, req.next_pos)
            raise
        self._spec["steps"] += 1
        # feed the tuner every ATTEMPTED row's accepted length (an
        # empty draft reads as 1: speculation didn't pay on that row)
        self._tuner_step(attempted,
                         {s: emitted_by_slot.get(s, 1)
                          for s in attempted})

    def _emit_verified(self, slot: int, req: Request,
                       greedy_row: np.ndarray, acc: int,
                       logits_row: np.ndarray, draft=None,
                       qs=None) -> int:
        """Apply one row's verify result: append the accepted tokens.
        Greedy rows: the first ``acc`` in-program argmax tokens,
        stopping AT an EOS exactly like sequential decode (the bitwise
        token-identity law). Undrafted sampled rows: one host-sampled
        token from position 0 — bit-identical to the k=1 path, same
        per-request RNG stream. Drafted sampled rows
        (``spec_sampled=True``): speculative REJECTION SAMPLING —
        draft j is accepted with probability min(1, p_j(t)/q_j(t))
        where p_j = sampling_dist(logits[j]) is the target
        distribution at that position and q_j the draft's (a point
        mass for n-gram drafts, ``qs[j]`` for the draft model, which
        DREW the token from exactly that q); on the first rejection
        ONE token is resampled from the normalized residual
        max(p - q, 0) and the rest of the draft is discarded; if every
        draft survives, a bonus token is sampled from the position
        AFTER the draft. By the standard speculative-sampling
        argument (Leviathan et al.) each emitted token is distributed
        EXACTLY as sequential sampling from p — the distribution-
        parity law the seed-band harness checks. Returns how many
        tokens were emitted. Factored out so the chaos pinned-red
        test can swap in a deliberately broken acceptance."""
        if req.sampling.temperature > 0:
            sp, rng = req.sampling, req._rng
            if draft is None or len(draft) == 0:
                tok = sample_token(logits_row[0], sp, rng)
                req.out_tokens.append(tok)
                self.metrics.on_token(req.rid)
                self._is_finished(req, tok)
                return 1
            emitted = 0
            for j in range(len(draft)):
                t = int(draft[j])
                p = sampling_dist(logits_row[j], sp)
                pt = float(p[t])
                qt = float(qs[j][t]) if qs else 1.0
                if qt > 0.0 and pt > 0.0 \
                        and float(rng.uniform()) < min(1.0, pt / qt):
                    req.out_tokens.append(t)
                    self.metrics.on_token(req.rid)
                    emitted += 1
                    if self._is_finished(req, t):
                        return emitted
                    continue
                # first rejection: emit ONE corrective token from the
                # residual — conditioned on rejecting q's token, the
                # residual is exactly what sequential sampling from p
                # has left (fault-point-guarded: a crash here must
                # neither lose nor duplicate tokens)
                maybe_fail("serving.spec.resample",
                           step=self._step_idx - 1, slot=slot)
                if qs:
                    res = np.maximum(p - qs[j], 0.0)
                else:
                    res = p.copy()
                    res[t] = 0.0
                tot = res.sum()
                # q >= p everywhere means rejection was measure-zero
                # (float dust): fall back to p itself
                res = p if tot <= 0.0 else res / tot
                tok = int(rng.choice(res.size, p=res))
                req.out_tokens.append(tok)
                self.metrics.on_token(req.rid)
                emitted += 1
                self._spec["resamples"] += 1
                self._is_finished(req, tok)
                return emitted
            # every draft accepted: the verify pass already computed
            # the next position's logits — the classic free bonus
            tok = sample_token(logits_row[len(draft)], sp, rng)
            req.out_tokens.append(tok)
            self.metrics.on_token(req.rid)
            emitted += 1
            self._is_finished(req, tok)
            return emitted
        emitted = 0
        for j in range(acc):
            tok = int(greedy_row[j])
            req.out_tokens.append(tok)
            self.metrics.on_token(req.rid)
            emitted += 1
            if self._is_finished(req, tok):
                # sequential decode stops AT the EOS — accepted
                # tokens beyond it must not surface
                break
        return emitted

    def _on_draft_fault(self, slot: int, req: Request, proposer,
                        exc: Exception) -> None:
        """Contain a failed draft proposal to one row of one step: the
        row falls back to k=1 and the proposer's state for this rid is
        unwound (next step re-derives it from confirmed history). A
        REAL draft-model failure may have died with donated pools in
        flight, so the draft proposer's whole pool is reset — the same
        poisoned-donation reasoning as ``recover()``, scoped to the
        draft side. Factored out (like ``_emit_verified``) so the
        chaos pinned-red test can re-introduce the pre-fix shape
        (request-fatal draft faults) and prove the conservation ledger
        catches it."""
        if isinstance(exc, InjectedFault) \
                or not isinstance(proposer, DraftModelProposer):
            proposer.unwind(req.rid)
        else:
            proposer.reset()
        self._spec["draft_faults"] += 1

    def _tuner_step(self, attempted: dict, accepted: dict) -> None:
        """Feed one verify step's accepted lengths to the autotuner
        and advance its clock + gauges (no-op without spec_tune)."""
        if self._tuner is None:
            return
        for s, (klass, kind) in attempted.items():
            self._tuner.observe(klass, kind, accepted.get(s, 1))
        self._tuner.on_step()
        snap = self._tuner.snapshot()
        for klass, st in snap["classes"].items():
            self._m_spec_tuner_k.labels(klass=klass).set(st["k"])

    def _evict(self, slot: int, req: Request,
               finished: List[Request]) -> None:
        # a PREFILLING request can reach a terminal state mid-chunked-
        # prefill (deadline, disconnect, drain cutoff): drop its chunk
        # bookkeeping so release() below is the whole cleanup
        self._clear_chunk_state(slot, req)
        self.cache.release(slot)
        req.slot = None
        finished.append(req)
        self._m_evict.labels(reason=req.finish_reason or "unknown").inc()
        self.metrics.on_finished(req.rid)
        self._proposer_release(req.rid)

    def _expire_deadlines(self, finished: List[Request]) -> None:
        """Cancel queued and in-flight requests past their deadline
        (step-boundary sweep; XLA steps are not interruptible
        mid-kernel, so the boundary is the cancellation grain)."""
        now = self.metrics.now()
        for req in self.scheduler.expire(now):
            req.finished, req.finish_reason = True, "deadline"
            req.error = DeadlineExceeded(
                req.rid, "expired while queued")
            self.metrics.on_finished(req.rid)
            finished.append(req)
        for s in self.cache.active_slots():
            req = self.cache.slots[s]
            if req.deadline is not None and now > req.deadline:
                req.finished, req.finish_reason = True, "deadline"
                req.error = DeadlineExceeded(
                    req.rid, f"expired in slot {s} after "
                             f"{len(req.out_tokens)} token(s)")
                self._evict(s, req, finished)

    def _cancel_requested(self, req: Request) -> bool:
        """True if the client behind ``req`` is known gone: either the
        request's own flag (set by the front door, possibly from an
        HTTP thread) or the installed ``cancel_probe``. A probe that
        itself dies must never take the engine down — it just reads
        as 'still connected'."""
        if req.cancel_requested:
            return True
        probe = self.cancel_probe
        if probe is None:
            return False
        try:
            if probe(req):
                req.cancel_requested = True
                return True
        except Exception:
            return False
        return False

    def _finish_disconnect(self, req: Request,
                           detail: Optional[str] = None,
                           exc: Optional[BaseException] = None,
                           finished: Optional[List[Request]] = None) \
            -> None:
        """Terminal bookkeeping shared by every path that observes the
        client gone (prefill abort, queued/slot sweeps, recover): one
        place to keep the disconnect state/metric story consistent.
        Callers that evict a slot pass ``finished=None`` and let
        ``_evict`` do the delivery accounting."""
        req.finished, req.finish_reason = True, "disconnect"
        req.error = exc if exc is not None \
            else RequestCancelled(req.rid, detail or "disconnect")
        if finished is not None:
            self.metrics.on_finished(req.rid)
            finished.append(req)

    def _sweep_disconnects(self, finished: List[Request]) -> None:
        """Cancel queued and in-flight requests whose client went away
        (same step-boundary grain as the deadline sweep); freed slots
        return their KV pages via the normal release path."""
        if self.cancel_probe is None and \
                not any(r.cancel_requested
                        for r in self.scheduler.pending()) and \
                not any(self.cache.slots[s].cancel_requested
                        for s in self.cache.active_slots()):
            return
        for req in list(self.scheduler.pending()):
            if self._cancel_requested(req):
                self.scheduler.remove(req)
                self._finish_disconnect(
                    req, "client disconnected while queued",
                    finished=finished)
        for s in self.cache.active_slots():
            req = self.cache.slots[s]
            if self._cancel_requested(req):
                self._finish_disconnect(
                    req, f"client disconnected in slot {s} after "
                         f"{len(req.out_tokens)} token(s)")
                self._evict(s, req, finished)

    def cancel(self, req: Request, reason: str = "cancelled") -> bool:
        """Cancel one request (queued or in-flight); returns False if
        it already finished. Delivered tokens stay on the handle."""
        if req.finished:
            return False
        if self.scheduler.remove(req):
            pass
        elif req.slot is not None \
                and self.cache.slots[req.slot] is req:
            self._clear_chunk_state(req.slot, req)
            self.cache.release(req.slot)
            req.slot = None
            self._m_evict.labels(reason=reason).inc()
        else:
            return False
        req.finished, req.finish_reason = True, reason
        req.error = RequestCancelled(req.rid, reason)
        self.metrics.on_finished(req.rid)
        self._proposer_release(req.rid)
        if self.auditor is not None:
            self.auditor.on_delivered(req, via="cancel")
        return True

    def recover(self) -> dict:
        """Rebuild device state from host-side request state after a
        failed step, instead of abandoning the engine.

        Fresh KV pools are allocated (the old ones may reference
        deleted device buffers after donation), every in-flight request
        is re-prefilled over its prompt + already-delivered tokens
        (positions ``0..next_pos-1``), and decoding resumes exactly
        where it stopped. For greedy requests the re-prefill logits
        re-predict the last delivered token — verified and counted in
        ``ptpu_serving_recover_replay_mismatch_total`` (delivered
        tokens are never retracted). Safe to call repeatedly: a fault
        during recovery leaves the engine broken and the next
        ``recover()`` starts over from the same host state.

        Returns a report: recovered slot count, replay mismatches,
        latency, finished requests that were evicted (they completed
        in the failed step but were never returned).
        """
        t0 = self.metrics.now()
        reason = self._broken
        in_flight = [(s, r) for s, r in enumerate(self.cache.slots)
                     if r is not None]
        # chunked-prefill state dies with the old pools: recovery
        # re-prefills every in-flight request MONOLITHICALLY (the
        # re-prefill program writes the whole span in one pass, which
        # is the chunked path's degenerate case — token-identical);
        # fresh admissions after recovery re-chunk normally
        self._chunk_fifo.clear()
        self._chunk_local.clear()
        for _, r in in_flight:
            r.prefill_pos = None
        if self.paged:
            # flush the dying pool's counter deltas, then re-baseline:
            # the fresh pool restarts its raw counters at zero and a
            # stale baseline would swallow all increments after this
            self._publish_page_stats()
            self._last_page_stats = {k: 0
                                     for k in self._last_page_stats}
        # staged promotions die with the old pools; the tier itself
        # SURVIVES — _new_cache() rehydrates its radix index from the
        # tier, so demoted prefixes stay warm across the rebuild
        self._staged_promotions.clear()
        self._ahead = None
        self.cache = self._new_cache()
        self._refresh_state()
        # accumulate on the ENGINE, not a local: if a re-prefill below
        # faults, these requests are gone from the slot table, and the
        # retrying recover() must still deliver them in its report.
        # _undelivered also carries requests a FAILED step finished but
        # never returned (same conservation debt, same payoff point).
        finished = self._undelivered
        todo = []
        for s, req in in_flight:
            if req.finished:
                # completed inside the failed step, never delivered:
                # evict now and hand it back via the report
                req.slot = None
                self._m_evict.labels(
                    reason=req.finish_reason or "unknown").inc()
                self.metrics.on_finished(req.rid)
                finished.append(req)
            else:
                # re-assign bookkeeping FIRST so a fault mid-re-prefill
                # leaves the slot table complete and recover() can
                # simply run again
                self.cache.assign(s, req)
                todo.append((s, req))
        mismatches = 0
        for s, req in todo:
            if self._cancel_requested(req):
                # the client vanished while the engine was down: don't
                # pay a re-prefill nobody is listening to
                self.cache.release(s)
                req.slot = None
                self._finish_disconnect(
                    req, "client disconnected during recover()",
                    finished=finished)
                continue
            if not req.out_tokens:
                # the failed step died between slot assignment and the
                # first sampled token: finish the prefill now
                logits = self._prefill_raw(s, req.prompt,
                                           request_id=req.rid,
                                           req=req)
                tok = sample_token(logits, req.sampling, req._rng)
                req.out_tokens.append(tok)
                self.metrics.on_token(req.rid)
                if self._is_finished(req, tok):
                    self._evict(s, req, finished)
                continue
            ids = req.prompt if len(req.out_tokens) <= 1 else \
                np.concatenate([req.prompt,
                                np.asarray(req.out_tokens[:-1],
                                           np.int64)])
            logits = self._prefill_raw(s, ids, request_id=req.rid,
                                       req=req)
            if req.sampling.temperature <= 0 \
                    and int(np.argmax(logits)) != req.out_tokens[-1]:
                mismatches += 1
                self._m_replay_mismatch.inc()
        if self.speculative:
            # prune draft-proposer state to the requests that survived
            # into the rebuilt slot table (a finished/disconnected
            # request's index must not outlive it — the no-leak law);
            # EVERY configured proposer prunes, not just the active one
            self._proposer_retain(
                r.rid for r in self.cache.slots if r is not None)
        self._broken = None
        dt = self.metrics.now() - t0
        report = {"reason": reason,
                  "recovered_slots": len(todo),
                  "replay_mismatches": mismatches,
                  "finished": list(finished),
                  "latency_s": dt}
        self.recorder.record(
            "serving.recover", reason=reason, latency_s=dt,
            recovered_slots=len(todo), replay_mismatches=mismatches,
            evicted=[(r.rid, r.finish_reason) for r in finished])
        if self.auditor is not None:
            for r in report["finished"]:
                self.auditor.on_delivered(r, via="recover")
        # consumed only once the report is actually on its way to the
        # caller: a recorder/auditor raise above leaves the debt in
        # place for the next step()/recover() instead of losing it
        self._undelivered = []
        return report

    def inflight_rids(self) -> set:
        """Every request id the engine itself still owns: queued,
        decoding in a slot, staged mid-handoff/promotion, or finished
        but not yet delivered. The complement of this set against
        ``metrics.inflight_phases()`` is watchtower's orphan detector:
        a rid the metrics ledger tracks that appears in none of these
        places has been dropped by a fault that unwound the engine's
        bookkeeping but never requeued or finished the request."""
        rids = {r.rid for r in self.scheduler.pending()}
        for s in self.cache.active_slots():
            rids.add(self.cache.slots[s].rid)
        rids.update(r.rid for r in self._undelivered)
        rids.update(self._staged_handoffs)
        rids.update(self._staged_promotions)
        return rids

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drive step() until the queue and every slot drain."""
        done: List[Request] = []
        steps = 0
        while self.has_work():
            done.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return done

    def drain(self, max_steps: Optional[int] = None) -> List[Request]:
        """Graceful shutdown: refuse new submissions (submit() raises
        :class:`EngineClosed` from now on) and serve the queue plus
        every in-flight slot to completion. If ``max_steps`` runs out
        first — or the engine is (or becomes) broken and the caller
        chose shutdown over ``recover()``, or steps keep failing —
        whatever remains is cancelled (``finish_reason ==
        "cancelled"``) instead of being stranded un-finished. Returns
        every request finished or cancelled during the drain.

        drain() never raises out of the step loop: a mid-drain step
        exception must not discard the already-finished ``done`` list.
        A transient step failure (engine not broken: the faulted
        request was re-queued) is retried; after ``_DRAIN_MAX_FAILURES``
        consecutive failures the remainder is cancelled with the last
        error attached, and ``done`` is returned intact."""
        self._closed = True
        done: List[Request] = []
        steps = 0
        failures = 0
        last_err: Optional[BaseException] = None
        self._in_drain = True
        try:
            while self.has_work():
                if max_steps is not None and steps >= max_steps:
                    cutoff = "drain cutoff"
                elif self._broken:
                    cutoff = f"drain on broken engine ({self._broken})"
                elif failures >= self._DRAIN_MAX_FAILURES:
                    cutoff = (f"drain aborted after {failures} "
                              f"consecutive step failures "
                              f"({type(last_err).__name__}: {last_err})")
                else:
                    cutoff = None
                if cutoff is not None:
                    for req in self.scheduler.drain():
                        req.finished, req.finish_reason = \
                            True, "cancelled"
                        req.error = RequestCancelled(req.rid, cutoff)
                        self.metrics.on_finished(req.rid)
                        done.append(req)
                    for s in self.cache.active_slots():
                        req = self.cache.slots[s]
                        req.finished, req.finish_reason = \
                            True, "cancelled"
                        req.error = RequestCancelled(req.rid, cutoff)
                        self._evict(s, req, done)
                    break
                try:
                    done.extend(self.step())
                    steps += 1
                    failures = 0
                except Exception as e:
                    # the failed step's own finishers sit in
                    # _undelivered (see step()); the next loop pass
                    # either retries, or the cutoff collects them below
                    failures += 1
                    last_err = e
        finally:
            self._in_drain = False
        if self._undelivered:
            # terminal requests stranded by a failed step with no
            # successful step left to carry them out
            done.extend(self._undelivered)
        self._proposer_retain(())          # drained engine holds none
        # owe the whole return until it happens: if the auditor raises
        # here, a re-issued drain() flushes the debt to the caller
        self._undelivered = done
        if self.auditor is not None:
            for r in done:
                self.auditor.on_delivered(r, via="drain")
        self._undelivered = []
        return done

    # consecutive failed steps a drain() absorbs before giving up on
    # serving the backlog and cancelling the remainder
    _DRAIN_MAX_FAILURES = 3

    # -- internals -----------------------------------------------------
    def _is_finished(self, req: Request, tok: int) -> bool:
        if self.eos_id is not None and tok == self.eos_id:
            req.finished, req.finish_reason = True, "eos"
        elif len(req.out_tokens) >= req.max_new_tokens:
            req.finished, req.finish_reason = True, "length"
        return req.finished

    def _prefill(self, slot: int, req: Request) -> None:
        """Run the bucketed prefill program for one request, write its
        k/v into the slot's pages (or its state into the slot's row),
        and sample its first token (TTFT).

        A request adopted mid-flight (router failover: it already
        carries delivered tokens) re-prefills prompt + those tokens
        instead — the ``recover()`` replay contract: greedy replay
        re-predicts the last delivered token (mismatches counted,
        tokens never retracted) and decode resumes where it stopped."""
        self.metrics.on_first_prefill(req.rid)   # queue wait ends here
        if req.out_tokens:
            ids = req.prompt if len(req.out_tokens) <= 1 else \
                np.concatenate([req.prompt,
                                np.asarray(req.out_tokens[:-1],
                                           np.int64)])
            logits = self._prefill_raw(slot, ids, request_id=req.rid,
                                       req=req, cancel_check=True)
            self.cache.assign(slot, req)
            req.slot = slot
            if req.sampling.temperature <= 0 \
                    and int(np.argmax(logits)) != req.out_tokens[-1]:
                self._m_replay_mismatch.inc()
            return
        logits = self._prefill_raw(slot, req.prompt,
                                   request_id=req.rid, req=req,
                                   cancel_check=True)
        self.cache.assign(slot, req)
        req.slot = slot
        with span("serving.sample", rows=1):
            tok = sample_token(logits, req.sampling, req._rng)
            req.out_tokens.append(tok)
            self.metrics.on_token(req.rid)
            self._is_finished(req, tok)

    def _prefill_raw(self, slot: int, ids: np.ndarray,
                     request_id=None, req=None,
                     cancel_check: bool = False) -> np.ndarray:
        """Write ``ids``'s k/v into positions ``0..len-1`` of the slot
        (a stateful model: build the slot's state from them) via the
        bucketed prefill program and return the host logits at the
        last real token. Shared by admission prefill and
        ``recover()``'s re-prefill (which replays prompt + delivered
        tokens through the same program).

        K/V: the prompt is first matched against the prefix index —
        matched pages are referenced instead of recomputed and only
        the tail runs through a prefill program (the full-prompt
        program when nothing matched, the paged EXTEND program — which
        attends over the shared pages — otherwise). A failure after
        pages were claimed unwinds them (abort_sequence)."""
        maybe_fail("serving.step.prefill", slot=slot)
        n = int(ids.shape[0])
        if not self.paged:
            if cancel_check and req is not None \
                    and self._cancel_requested(req):
                # disconnect observed before the prefill program runs
                # (the paged path checks AFTER pages are claimed, so
                # the abort path is what gets exercised there)
                raise RequestCancelled(
                    req.rid, "client disconnected before prefill")
            bucket = bucket_for(n, self.min_bucket, self.max_len)
            self._m_prefill.labels(bucket=bucket).inc()
            with span("serving.prefill", request_id=request_id,
                      bucket=bucket, prompt_tokens=n,
                      program="prefill",
                      replay=bool(req is not None
                                  and req.out_tokens)) as sp:
                padded = np.zeros((1, bucket), np.int64)
                padded[0, :n] = ids
                logits = self._run_prefill(padded, n, slot, sp,
                                           np.zeros((0,), np.int32))
                return self._fetch("serving.prefill.fetch", logits)
        cache = self.cache
        disagg = self.meshctx is not None \
            and self.meshctx.disaggregated
        try:
            if req.rid not in cache._plans:
                # admission reserves at claim time; recover()'s
                # re-prefill reserves here (a fresh pool always fits
                # what it held). Inside the unwind scope: a failure
                # here routes through abort_sequence, which no-ops on
                # a missing plan
                if not cache.try_reserve(req, ids,
                                         req.prompt_len
                                         + req.max_new_tokens):
                    raise RuntimeError(
                        f"request {req.rid}: page reservation failed "
                        f"on re-prefill (pool too small for "
                        f"in-flight set)")
            # same-wave sharing: earlier admissions in THIS batch have
            # registered their pages since the claim — re-match now
            cache.refresh_reservation(req, ids)
            start, copies = cache.begin_sequence(slot, req, ids)
            # mid-prefill fault point: pages are claimed, the table
            # row is live, nothing has run on device yet — the abort
            # path below must return every page (chaos-audited)
            maybe_fail("serving.prefill.paged", slot=slot,
                       shared=start > 0)
            if cancel_check and self._cancel_requested(req):
                # disconnect landed MID-prefill: pages are claimed and
                # the table row is live — raising here routes through
                # abort_sequence below, which must return every page
                # (pinned by the page-leak chaos law)
                raise RequestCancelled(
                    req.rid, "client disconnected mid-prefill")
            self._run_copies(copies)
            # promoted host/disk pages install BEFORE the extend
            # program attends over them (staged; unwinds on fault)
            self._stage_promotions(req, slot)
            tail = n - start
            bucket = bucket_for(tail, self.min_bucket, self.max_len)
            self._m_prefill.labels(bucket=bucket).inc()
            with span("serving.prefill", request_id=request_id,
                      bucket=bucket, prompt_tokens=n,
                      program="extend" if start else "prefill",
                      shared_prefix=start,
                      replay=bool(req.out_tokens)) as sp:
                padded = np.zeros((1, bucket), np.int64)
                padded[0, :tail] = ids[start:]
                row = cache.page_table[slot]
                if start == 0 and disagg:
                    # full prefill on the PREFILL group; the page
                    # blocks (int8-quantized there when configured)
                    # hand off to the decode pool at the claimed ids
                    npages = (bucket + cache.page_size - 1) \
                        // cache.page_size
                    logits, kb, vb, ksb, vsb = self._prefill_fn()(
                        self._params_pf, self._buffers_pf, padded,
                        np.int32(n))
                    self._kv_handoff(req, slot, (kb, vb, ksb, vsb),
                                     page_ids=row[:npages].copy(),
                                     cancel_check=cancel_check)
                elif start == 0:
                    npages = (bucket + cache.page_size - 1) \
                        // cache.page_size
                    logits = self._run_prefill(padded, n, slot, sp,
                                               row[:npages].copy())
                else:
                    # prefix-hit EXTEND: stays on the decode group —
                    # it attends over shared pages already resident
                    # in the decode-owned pool
                    logits, ks, vs, kss, vss = self._extend_fn()(
                        self._params, self._buffers, padded,
                        np.int32(start), np.int32(tail), row.copy(),
                        cache.ks, cache.vs, cache.kss, cache.vss)
                    cache.ks, cache.vs = list(ks), list(vs)
                    cache.kss, cache.vss = list(kss), list(vss)
                cache.register_prefix(slot, ids)
                return self._fetch("serving.prefill.fetch", logits)
        except Exception:
            # the cross-group unwind: drop the staged prefill-side
            # span (if a handoff was in flight) AND any staged
            # promotion WITH the decode-side page claims — the leak
            # audit checks every half
            self._staged_handoffs.pop(req.rid, None)
            self._staged_promotions.pop(req.rid, None)
            cache.abort_sequence(slot, req)
            raise

    def _run_prefill(self, padded, n: int, slot: int, prefill_span,
                     page_ids):
        """Dispatch the from-scratch prefill program of ``padded``'s
        bucket: the bucket's K and V into the pages ``page_ids``, the
        new state over the slot's row (its reset on reuse, under
        ``serving.state.reset``). Returns the device logits."""
        c = self.cache
        with self._state_reset(slot, prefill_span) if self.stateful \
                else contextlib.nullcontext():
            logits, ks, vs, kss, vss, *pools = self._prefill_fn()(
                self._params, self._buffers, padded, np.int32(n),
                page_ids, np.int32(slot), c.ks, c.vs, c.kss, c.vss,
                *c.pools)
        c.ks, c.vs = list(ks), list(vs)
        c.kss, c.vss = list(kss), list(vss)
        c.pools = pools
        return logits

    def _state_reset(self, slot: int, prefill_span):
        """Around the prefill dispatch of a model with state layers: the
        slot's reset
        on reuse and its new state's installation (the program builds
        the state from nothing and overwrites the slot's whole row):
        span ``serving.state.reset``. K and V in pages need none: the
        causal mask hides a stale tail."""
        prefill_span.set_attr("state_reset", True)
        self.cache.reset(slot)
        return span("serving.state.reset", slot=slot,
                    state_bytes=self.cache.slot_bytes)

    # -- chunked prefill ----------------------------------------------
    @staticmethod
    def _replay_ids(req: Request) -> np.ndarray:
        """The token span a (re-)prefill writes: the prompt, plus all
        but the last delivered token for adopted/replayed requests
        (the last token is re-predicted by the final logits — the
        recover() replay contract)."""
        return req.prompt if len(req.out_tokens) <= 1 else \
            np.concatenate([req.prompt,
                            np.asarray(req.out_tokens[:-1], np.int64)])

    def _begin_chunked(self, slot: int, req: Request) -> None:
        """Claim a slot for a CHUNKED prefill without running any
        compute: the request enters the PREFILLING state (slot leased,
        pages placed, ``prefill_pos`` at the shared-prefix boundary)
        and advances one chunk per step from the fifo head
        (``_chunk_step``). Admission already committed the worst-case
        page reservation at claim time, so chunking can never run out
        of pages mid-prompt."""
        self.metrics.on_first_prefill(req.rid)   # queue wait ends here
        ids = self._replay_ids(req)
        cache = self.cache
        try:
            if req.rid not in cache._plans:
                # inside the unwind scope (abort_sequence no-ops on a
                # missing plan), so a reservation that fails halfway
                # can never strand its pinned pages
                if not cache.try_reserve(req, ids,
                                         req.prompt_len
                                         + req.max_new_tokens):
                    raise RuntimeError(
                        f"request {req.rid}: page reservation "
                        f"failed at chunked admission")
            cache.refresh_reservation(req, ids)
            start, copies = cache.begin_sequence(slot, req, ids)
            self._run_copies(copies)
            self._stage_promotions(req, slot)
        except Exception:
            # pages claimed but the slot never assigned: the standard
            # abort path returns every claim, and the caller
            # (_step_inner) requeues the request
            self._staged_promotions.pop(req.rid, None)
            cache.abort_sequence(slot, req)
            raise
        cache.assign(slot, req)
        req.slot = slot
        req.prefill_pos = int(start)
        self._chunk_fifo.append(slot)
        if self._params_pf is not None and start == 0:
            # disaggregated: chunks accumulate in local buffers on the
            # PREFILL group; the final span hands off to the decode
            # pool. Prefix-hit admissions (start > 0) instead chunk
            # through the decode-group program, like extends — they
            # attend over shared pages resident in that pool.
            self._chunk_local[req.rid] = self._new_chunk_local()

    def _new_chunk_local(self):
        """Fresh per-layer [1, max_len] KV buffers on the prefill
        group (zeros: never-written tails stay finite, and the causal
        mask zeroes their softmax weight exactly)."""
        ad = self.adapter
        shape = (1, self.max_len, ad.kv_heads, ad.head_dim)
        sh = self.meshctx.kv_sharding("prefill")
        mk = lambda: [jax.device_put(jnp.zeros(shape, ad.dtype), sh)
                      for _ in range(ad.num_layers)]
        return mk(), mk()

    def _chunk_step(self, finished: List[Request]) -> None:
        """Advance the PREFILLING fifo head by one chunk: write chunk
        tokens ``prefill_pos .. prefill_pos + t - 1`` into the slot's
        KV (attending over everything already written — bitwise what
        the monolithic prefill computed for the same positions), and
        on the FINAL chunk sample the first token and enter decode."""
        slot = self._chunk_fifo[0]
        req = self.cache.slots[slot]
        ids = self._replay_ids(req)
        n = int(ids.shape[0])
        pos = req.prefill_pos
        t = min(self.prefill_chunk, n - pos)
        final = pos + t >= n
        try:
            # mid-chunk fault point: slot leased, pages claimed, part
            # of the prompt already written — the unwind below must
            # free pages AND the lease and requeue (chaos-audited)
            maybe_fail("serving.prefill.chunk", slot=slot, pos=pos,
                       final=final)
            if self._cancel_requested(req):
                raise RequestCancelled(
                    req.rid, "client disconnected mid-chunked-prefill")
            bucket = bucket_for(t, self.min_bucket, self.max_len)
            self._m_prefill.labels(bucket=bucket).inc()
            with span("serving.chunk_prefill", request_id=req.rid,
                      bucket=bucket, prompt_tokens=n,
                      program="chunk", pos=pos, chunk=t, final=final,
                      replay=bool(req.out_tokens)):
                padded = np.zeros((1, bucket), np.int64)
                padded[0, :t] = ids[pos:pos + t]
                logits = self._run_chunk(slot, req, padded, pos, t,
                                         final, ids)
        except RequestCancelled as e:
            self._unwind_chunk(slot, req, requeue=False)
            self._finish_disconnect(req, exc=e, finished=finished)
            return
        except Exception:
            self._unwind_chunk(slot, req, requeue=True)
            raise
        req.prefill_pos = pos + t
        self._m_chunk_steps.inc()
        if final:
            self._finish_chunked(slot, req, ids, logits, finished)

    def _run_chunk(self, slot: int, req: Request, padded, pos: int,
                   t: int, final: bool, ids) -> np.ndarray:
        """Run one chunk program (on the prefill group's local buffers
        or through the decode group's pages) and return the host
        logits at the chunk's last real token (only the FINAL chunk's
        logits are consumed)."""
        if req.rid in self._chunk_local:
            # disaggregated local-buffer mode (a full prefill):
            # compute on the prefill group; the final span ships
            # through the _kv_handoff staging contract
            logits = self._chunk_local_run(req, padded, pos, t)
            if final:
                self._chunk_finalize_handoff(slot, req,
                                             int(ids.shape[0]))
            return logits
        cache = self.cache
        row = cache.page_table[slot]
        logits, ks, vs, kss, vss = self._chunk_fn()(
            self._params, self._buffers, padded,
            np.int32(pos), np.int32(t), row.copy(),
            cache.ks, cache.vs, cache.kss, cache.vss)
        cache.ks, cache.vs = list(ks), list(vs)
        cache.kss, cache.vss = list(kss), list(vss)
        return self._fetch("serving.prefill.fetch", logits)

    def _chunk_local_run(self, req: Request, padded, pos: int,
                         t: int) -> np.ndarray:
        kb, vb = self._chunk_local[req.rid]
        logits, kb2, vb2 = self._chunk_local_fn()(
            self._params_pf, self._buffers_pf, padded,
            np.int32(pos), np.int32(t), kb, vb)
        self._chunk_local[req.rid] = (list(kb2), list(vb2))
        return self._fetch("serving.prefill.fetch", logits)

    def _chunk_finalize_handoff(self, slot: int, req: Request,
                                n: int) -> None:
        """Disaggregated final chunk: paginate (and int8-
        quantize, when configured) the accumulated local buffers and
        install them at the claimed page ids via the standard KV
        handoff."""
        cache = self.cache
        bucket = bucket_for(n, self.min_bucket, self.max_len)
        npg = (bucket + cache.page_size - 1) // cache.page_size
        kb, vb = self._chunk_local[req.rid]
        blocks = self._chunk_fin_fn(npg)(kb, vb)
        row = cache.page_table[slot]
        self._kv_handoff(req, slot, blocks,
                         page_ids=row[:npg].copy())

    def _finish_chunked(self, slot: int, req: Request, ids,
                        logits: np.ndarray,
                        finished: List[Request]) -> None:
        """Final chunk done: leave the PREFILLING state and enter
        decode (or, on a replay, verify the re-predicted token) —
        exactly what the tail of the monolithic ``_prefill`` does."""
        self._chunk_fifo.pop(0)
        req.prefill_pos = None
        self._chunk_local.pop(req.rid, None)
        self.cache.register_prefix(slot, ids)
        if req.out_tokens:
            if req.sampling.temperature <= 0 \
                    and int(np.argmax(logits)) != req.out_tokens[-1]:
                self._m_replay_mismatch.inc()
            return
        with span("serving.sample", rows=1):
            tok = sample_token(logits, req.sampling, req._rng)
            req.out_tokens.append(tok)
            self.metrics.on_token(req.rid)
            if self._is_finished(req, tok):
                self._evict(slot, req, finished)

    def _clear_chunk_state(self, slot: int, req: Request) -> None:
        """Drop a PREFILLING request's chunk bookkeeping (fifo entry,
        local buffers, staged handoff) WITHOUT touching the cache —
        the terminal paths (_evict, cancel) release the slot
        themselves."""
        if req.prefill_pos is None:
            return
        req.prefill_pos = None
        if slot in self._chunk_fifo:
            self._chunk_fifo.remove(slot)
        self._chunk_local.pop(req.rid, None)
        self._staged_handoffs.pop(req.rid, None)

    def _unwind_chunk(self, slot: int, req: Request,
                      requeue: bool) -> None:
        """Unwind a PREFILLING slot after a mid-chunk fault or
        cancel: chunk bookkeeping dies, the page claims return via
        the standard abort path, and the lease frees (abort_sequence
        zeroed the table row and popped the plan, so release() has
        nothing left to double-unref). ``requeue`` puts the request
        back at the queue head — its replay re-chunks
        token-identically."""
        self._clear_chunk_state(slot, req)
        self.cache.abort_sequence(slot, req)
        self.cache.release(slot)
        req.slot = None
        if requeue:
            self.scheduler.requeue(req)

    def _run_copies(self, copies) -> None:
        """Run COW page copies on device (host-picked src/dst, one
        tiny compiled program reused for every copy)."""
        for src, dst in copies:
            c = self.cache
            out = self._copy_fn()(np.int32(src), np.int32(dst),
                                  c.ks, c.vs, c.kss, c.vss)
            c.ks, c.vs = list(out[0]), list(out[1])
            c.kss, c.vss = list(out[2]), list(out[3])

    def _prog_shardings(self, group: str = "decode"):
        """Sharding trees for jitting one engine program under the
        mesh: (params dict, buffers dict, replicated, per-layer KV
        pool list, per-layer scale list — empty when not int8)."""
        m, ad = self.meshctx, self.adapter
        L = ad.num_layers
        params = self._params if group == "decode" else self._params_pf
        bufs = self._buffers if group == "decode" else self._buffers_pf
        return (self._param_shardings(params, group),
                m.replicated_tree(bufs, group),
                m.repl(group),
                [m.kv_sharding(group)] * L,
                [m.scale_sharding(group)] * L if self.kv_quant else [])

    def _paged_caches(self, ks, vs, kss, vss, table, pos, wlen=None):
        """Per-layer paged cache tuples for the model forward
        (scales None on the model-dtype path; ``wlen`` appends the
        per-row write-length element — the speculative verify
        7-tuple flavor)."""
        tail = (wlen,) if wlen is not None else ()
        return [(k, v, kss[i] if kss else None,
                 vss[i] if vss else None, table, pos) + tail
                for i, (k, v) in enumerate(zip(ks, vs))]

    @staticmethod
    def _unpack_paged(new_caches):
        d = lambda x: getattr(x, "_data", x)
        quant = bool(new_caches) and new_caches[0][2] is not None
        return ([d(c[0]) for c in new_caches],
                [d(c[1]) for c in new_caches],
                [d(c[2]) for c in new_caches] if quant else [],
                [d(c[3]) for c in new_caches] if quant else [])

    def _count_trace(self, kind: str, key=None) -> None:
        """Called in the body of every engine program, so it runs only
        while JAX traces it: one more compile of ``kind`` (for bucket
        or shape ``key``) in ``trace_counts``, and a ``compile.<kind>``
        event from the watched call around the trace."""
        if key is None:
            self.trace_counts[kind] += 1
        else:
            per_key = self.trace_counts[kind]
            per_key[key] = per_key.get(key, 0) + 1
        note_trace(kind, key)

    def _jit(self, fn, **jit_kw):
        """``jax.jit`` of one engine program under its stable name
        (the device trace's module line reads ``jit_<name>``), its
        compiles watched. It traces under ``no_grad``: serving never
        differentiates, and the op dispatch would otherwise trace every
        op of a trainable model under ``jax.vjp``, where the JVP of a
        cache write whose indices may repeat (the trash page) computes
        even its primal by selects over the whole pool."""
        return Watched(jax.jit(no_grad()(fn), **jit_kw), self.registry,
                       sink=self._compiles)

    def _fetch(self, name: str, x, beside=None):
        """``device_get`` under its own span: the host WAITS for the
        device here, so this time is not idle. ``beside``: a pytree of
        small arrays fetched in the same call (returned after ``x``);
        the span's ``bytes`` are ``x``'s."""
        with span(name) as sp:
            if beside is None:
                out = np.asarray(jax.device_get(x))
            else:
                out, beside = jax.device_get((x, beside))
                out = np.asarray(out)
            sp.set_attr("bytes", out.nbytes)
        return out if beside is None else (out, beside)

    def _prefill_fn(self):
        """Full-prompt prefill program, one compile per bucket length:
        run the prompt through a local [1, bucket] static cache, take
        the logits at the LAST REAL token (the bucket tail is
        padding), and splice the local k/v into the slot's allocated
        pages (quantized on the int8 path); a stateful model's new
        state overwrites the slot's row. Pad-tail garbage is
        harmless: the per-slot causal mask hides positions > the
        current length, and each decode step overwrites position
        ``len`` right before attending it; padded PAGE slots point at
        the reserved trash page.

        DISAGGREGATED engines compile a COMPUTE-ONLY flavor on the
        PREFILL group instead: it returns the finished KV span
        (paginated + int8-quantized page blocks) rather than writing
        the pool — the decode group owns the pool, and
        ``_kv_handoff`` ships + installs the span explicitly."""
        if self._prefill_jit is not None:
            return self._prefill_jit
        ad = self.adapter

        def local_run(params, buffers, ids, true_len):
            Lb = ids.shape[1]
            self._count_trace("prefill", Lb)
            local = ad.prefill_caches(Lb, true_len)
            with ad.model.bind_state(params, buffers):
                h, new_caches = ad.call(Tensor(ids), local)
                h_last = jax.lax.dynamic_slice_in_dim(
                    h._data, true_len - 1, 1, axis=1)
                logits = ad.head(Tensor(h_last))._data[0, -1]
            return logits, new_caches

        from ..models._decode_cache import quantize_kv_page
        by_kind = ad.by_kind
        P = self.cache.page_size
        quant = self.kv_quant
        disagg = self.meshctx is not None \
            and self.meshctx.disaggregated

        def paginate_fn(npg, pad):
            def paginate(c):
                a = getattr(c, "_data", c)
                if pad:
                    a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                return a.reshape(npg, P, *a.shape[2:])
            return paginate

        if disagg:
            def ptpu_prefill(params, buffers, ids, true_len):
                logits, new_caches = local_run(params, buffers, ids,
                                               true_len)
                new_caches, _ = by_kind(new_caches)
                npg = (ids.shape[1] + P - 1) // P
                paginate = paginate_fn(npg, npg * P - ids.shape[1])
                kb, vb, ksb, vsb = [], [], [], []
                for c in new_caches:
                    kpg, vpg = paginate(c[0]), paginate(c[1])
                    if quant:
                        # quantize on the PREFILL group: the handoff
                        # then ships int8 + scales, not model-dtype
                        kq, ksc = quantize_kv_page(kpg)
                        vq, vsc = quantize_kv_page(vpg)
                        kb.append(kq)
                        vb.append(vq)
                        ksb.append(ksc)
                        vsb.append(vsc)
                    else:
                        kb.append(kpg)
                        vb.append(vpg)
                return logits, kb, vb, ksb, vsb

            psh, bsh, R, kv, sc = self._prog_shardings("prefill")
            self._prefill_jit = self._jit(
                ptpu_prefill, in_shardings=(psh, bsh, R, R),
                out_shardings=(R, kv, kv, sc, sc))
            return self._prefill_jit

        def ptpu_prefill(params, buffers, ids, true_len, page_ids, slot,
                         ks, vs, kss, vss, *pools):
            # pools: the state rows, a list a state array: the slot's
            # whole row is overwritten, which is a state's reset on
            # reuse
            logits, new_caches = local_run(params, buffers, ids,
                                           true_len)
            new_caches, new_state = by_kind(new_caches)
            splice = lambda pool, c: jax.lax.dynamic_update_slice(
                pool, getattr(c, "_data", c).astype(pool.dtype),
                (slot,) + (0,) * (pool.ndim - 1))
            pools = tuple([splice(p, c[a]) for p, c in zip(pool, new_state)]
                          for a, pool in enumerate(pools))
            npg = page_ids.shape[0]
            paginate = paginate_fn(npg, npg * P - ids.shape[1])

            for i, c in enumerate(new_caches):
                kpg, vpg = paginate(c[0]), paginate(c[1])
                if quant:
                    kq, ksc = quantize_kv_page(kpg)
                    vq, vsc = quantize_kv_page(vpg)
                    ks[i] = ks[i].at[page_ids].set(kq)
                    vs[i] = vs[i].at[page_ids].set(vq)
                    kss[i] = kss[i].at[page_ids].set(ksc)
                    vss[i] = vss[i].at[page_ids].set(vsc)
                else:
                    ks[i] = ks[i].at[page_ids].set(
                        kpg.astype(ks[i].dtype))
                    vs[i] = vs[i].at[page_ids].set(
                        vpg.astype(vs[i].dtype))
            return (logits, ks, vs, kss, vss) + pools

        jit_kw = {}
        if self.meshctx is not None:
            psh, bsh, R, kv, sc = self._prog_shardings()
            jit_kw = dict(
                in_shardings=(psh, bsh, R, R, R, R, kv, kv, sc, sc),
                out_shardings=(R, kv, kv, sc, sc))
        self._prefill_jit = self._jit(
            ptpu_prefill, donate_argnums=self._donate_idx(
                *range(6, 10 + len(self.cache.pools))), **jit_kw)
        return self._prefill_jit

    def _extend_fn(self):
        """Shared-prefix tail prefill ("extend"), one compile per tail
        bucket: the tail tokens run through the PAGED cache path at
        start position ``start``, attending over the already-shared
        prefix pages through the slot's page table and writing their
        own k/v through it (bucket-padding writes past the table fall
        into the trash page). Logits at the last REAL tail token.

        Disaggregation note: extends run on the DECODE group even when
        full prefills are offloaded — they attend over shared pages
        that already live in the decode-owned pool, and a prefix-hit
        tail is short by construction (docs/SERVING.md)."""
        if self._extend_jit is not None:
            return self._extend_jit
        ad = self.adapter
        jit_kw = {}
        if self.meshctx is not None:
            psh, bsh, R, kv, sc = self._prog_shardings()
            jit_kw = dict(
                in_shardings=(psh, bsh, R, R, R, R, kv, kv, sc, sc),
                out_shardings=(R, kv, kv, sc, sc))

        def ptpu_extend(params, buffers, ids, start, true_tail, row, ks, vs,
                 kss, vss):
            Lb = ids.shape[1]
            self._count_trace("extend", Lb)
            caches = self._paged_caches(ks, vs, kss, vss,
                                        row[None, :], start)
            with ad.model.bind_state(params, buffers):
                h, new_caches = ad.call(Tensor(ids), caches)
                h_last = jax.lax.dynamic_slice_in_dim(
                    h._data, true_tail - 1, 1, axis=1)
                logits = ad.head(Tensor(h_last))._data[0, -1]
            return (logits,) + self._unpack_paged(new_caches)

        self._extend_jit = self._jit(
            ptpu_extend, donate_argnums=self._donate_idx(6, 7, 8, 9),
            **jit_kw)
        return self._extend_jit

    def _chunk_fn(self):
        """Chunked-prefill chunk program, one compile per chunk
        bucket: write chunk tokens ``start .. start + true_len - 1``
        into the slot's KV and attend over everything already written
        — positions beyond each query are masked to EXACT zero
        probability, so the outputs are bitwise what the monolithic
        prefill computed for the same positions (the greedy-identity
        argument, docs/SERVING.md "Chunked prefill"). Non-final
        chunks are exactly ``prefill_chunk`` tokens — their own
        bucket, zero padding; the final chunk's bucket padding is
        trash-redirected, the standard stale-tail story.

        It is the EXTEND machinery verbatim (page-table writes at a
        mid-prompt start), counted under "chunk" so the
        compile-budget pins see chunk programs separately."""
        if self._chunk_jit is not None:
            return self._chunk_jit
        ad = self.adapter
        jit_kw = {}
        if self.meshctx is not None:
            psh, bsh, R, kv, sc = self._prog_shardings()
            jit_kw = dict(
                in_shardings=(psh, bsh, R, R, R, R, kv, kv, sc, sc),
                out_shardings=(R, kv, kv, sc, sc))

        def ptpu_chunk(params, buffers, ids, start, true_len, row, ks,
                 vs, kss, vss):
            Lb = ids.shape[1]
            self._count_trace("chunk", Lb)
            caches = self._paged_caches(ks, vs, kss, vss,
                                        row[None, :], start)
            with ad.model.bind_state(params, buffers):
                h, new_caches = ad.call(Tensor(ids), caches)
                h_last = jax.lax.dynamic_slice_in_dim(
                    h._data, true_len - 1, 1, axis=1)
                logits = ad.head(Tensor(h_last))._data[0, -1]
            return (logits,) + self._unpack_paged(new_caches)

        self._chunk_jit = self._jit(
            ptpu_chunk, donate_argnums=self._donate_idx(6, 7, 8, 9),
            **jit_kw)
        return self._chunk_jit

    def _chunk_local_fn(self):
        """Disaggregated chunk program on the PREFILL group: advance
        one chunk through the request's local [1, max_len] buffers
        (write-masked past ``true_len``); the final span ships via
        the finalize program and ``_kv_handoff``. One compile per
        chunk bucket — the buffers are
        always full-length, so the key space is the ids bucket
        alone."""
        if self._chunk_local_jit is not None:
            return self._chunk_local_jit
        ad = self.adapter

        def ptpu_chunk(params, buffers, ids, start, true_len, kb, vb):
            Lb = ids.shape[1]
            key = ("local", Lb)
            self._count_trace("chunk", key)
            wl = jnp.reshape(jnp.asarray(true_len, jnp.int32), (1,))
            caches = [(k, v, start, wl) for k, v in zip(kb, vb)]
            with ad.model.bind_state(params, buffers):
                h, new_caches = ad.call(Tensor(ids), caches)
                h_last = jax.lax.dynamic_slice_in_dim(
                    h._data, true_len - 1, 1, axis=1)
                logits = ad.head(Tensor(h_last))._data[0, -1]
            kb2 = [getattr(c[0], "_data", c[0]) for c in new_caches]
            vb2 = [getattr(c[1], "_data", c[1]) for c in new_caches]
            return logits, kb2, vb2

        psh, bsh, R, kv, _ = self._prog_shardings("prefill")
        self._chunk_local_jit = self._jit(
            ptpu_chunk, in_shardings=(psh, bsh, R, R, R, kv, kv),
            out_shardings=(R, kv, kv),
            donate_argnums=self._donate_idx(5, 6))
        return self._chunk_local_jit

    def _chunk_fin_fn(self, npg: int):
        """Disaggregated finalize program, one compile per page
        count: paginate the accumulated local buffers into the
        request's ``npg`` page blocks (int8-quantized here on the
        quantized path — every page is complete by now, so per-page
        scales are exact) for the standard handoff install."""
        if self._chunk_fin_jit is None:
            self._chunk_fin_jit = {}
        fn = self._chunk_fin_jit.get(npg)
        if fn is not None:
            return fn
        from ..models._decode_cache import quantize_kv_page
        P = self.cache.page_size
        quant = self.kv_quant
        m = self.meshctx
        L = self.adapter.num_layers
        kv = [m.kv_sharding("prefill")] * L
        sc = [m.scale_sharding("prefill")] * L if quant else []

        def ptpu_chunk_fin(kb, vb):
            key = ("fin", npg)
            self._count_trace("chunk", key)
            kpg, vpg, kspg, vspg = [], [], [], []
            for k, v in zip(kb, vb):
                kp = k[:, :npg * P].reshape(npg, P, *k.shape[2:])
                vp = v[:, :npg * P].reshape(npg, P, *v.shape[2:])
                if quant:
                    kq, ksc = quantize_kv_page(kp)
                    vq, vsc = quantize_kv_page(vp)
                    kpg.append(kq)
                    vpg.append(vq)
                    kspg.append(ksc)
                    vspg.append(vsc)
                else:
                    kpg.append(kp)
                    vpg.append(vp)
            return kpg, vpg, kspg, vspg

        fn = self._jit(ptpu_chunk_fin, in_shardings=(kv, kv),
                     out_shardings=(kv, kv, sc, sc))
        self._chunk_fin_jit[npg] = fn
        return fn

    def _install_fn(self, npg: int):
        """Decode-group INSTALL program for one handed-off KV span
        (disaggregated engines only), compiled once per page count:
        scatter the shipped page blocks (int8 + scales on the
        quantized path) into the pool at the claimed page ids. The
        shape key space is the prefill bucket set, so installs stay
        inside the same O(log max_len) compile budget as prefills."""
        if self._install_jit is None:
            self._install_jit = {}
        fn = self._install_jit.get(npg)
        if fn is not None:
            return fn
        m = self.meshctx
        L = self.adapter.num_layers
        R = m.repl()
        kv = [m.kv_sharding()] * L
        sc = [m.scale_sharding()] * L if self.kv_quant else []

        def ptpu_install(page_ids, kb, vb, ksb, vsb, ks, vs, kss, vss):
            self._count_trace("install", npg)
            ks = [p.at[page_ids].set(b.astype(p.dtype))
                  for p, b in zip(ks, kb)]
            vs = [p.at[page_ids].set(b.astype(p.dtype))
                  for p, b in zip(vs, vb)]
            kss = [p.at[page_ids].set(b)
                   for p, b in zip(kss, ksb)]
            vss = [p.at[page_ids].set(b)
                   for p, b in zip(vss, vsb)]
            return ks, vs, kss, vss

        fn = self._jit(
            ptpu_install,
            in_shardings=(R, kv, kv, sc, sc, kv, kv, sc, sc),
            out_shardings=(kv, kv, sc, sc),
            donate_argnums=self._donate_idx(5, 6, 7, 8))
        self._install_jit[npg] = fn
        return fn

    def _kv_handoff(self, req, slot, blocks, page_ids,
                    cancel_check: bool = False) -> None:
        """Disaggregated prefill -> decode KV handoff: ship a finished
        prefill's KV span from the prefill group to the decode group
        (explicit cross-group ``jax.device_put``) and install it into
        the decode-owned pool. The ``serving.kv.handoff`` fault point
        fires BETWEEN compute and install — a raise here (injected
        fault, client disconnect observed mid-handoff) routes through
        the caller's abort path, so a half-handed-off request unwinds
        on BOTH groups: the staged span is dropped with this frame and
        the decode pool's page claims return via abort_sequence. The
        staging ledger `_staged_handoffs` is audited empty at quiesce
        (cross-group no-leak law, resilience/invariants.py)."""
        m = self.meshctx
        rid = req.rid
        # staged BEFORE the kill point; popped on successful install,
        # or by the caller's ABORT path on any raise below — the same
        # path that returns the decode-side page claims, so a
        # regression that forgets either unwind half trips the
        # cross-group leak audit (a finally here would clear it
        # unconditionally and make that audit vacuous)
        self._staged_handoffs[rid] = slot
        maybe_fail("serving.kv.handoff", slot=slot, rid=rid)
        if cancel_check and self._cancel_requested(req):
            # the client vanished while its KV sat staged on the
            # prefill group: don't ship or install a span nobody
            # will decode — the abort path frees the page claims
            raise RequestCancelled(
                rid, "client disconnected mid-KV-handoff")
        if self.kv_transport is not None:
            # cross-host hop: the blocks leave as bytes on a real
            # socket and come back digest-verified (kv_wire.py) —
            # what lands on the decode group below is what the wire
            # delivered, not the local arrays. A KVWireError past the
            # transport's retry budget raises HERE, inside the staged
            # window, so the caller's abort path unwinds both halves.
            parts = [list(p) for p in blocks]
            flat = [np.asarray(a) for part in parts for a in part]
            with span("serving.kv_wire", slot=slot, request_id=rid,
                      arrays=len(flat)):
                flat = self.kv_transport.ship(rid, flat)
            it = iter(flat)
            blocks = tuple([next(it) for _ in part]
                           for part in parts)
        L = self.adapter.num_layers
        dec_kv = [m.kv_sharding()] * L
        c = self.cache
        with span("serving.kv_handoff", slot=slot, request_id=rid):
            kb, vb, ksb, vsb = blocks
            kb = jax.device_put(list(kb), dec_kv)
            vb = jax.device_put(list(vb), dec_kv)
            if self.kv_quant:
                dec_sc = [m.scale_sharding()] * L
                ksb = jax.device_put(list(ksb), dec_sc)
                vsb = jax.device_put(list(vsb), dec_sc)
            out = self._install_fn(int(page_ids.shape[0]))(
                page_ids, kb, vb, list(ksb), list(vsb),
                c.ks, c.vs, c.kss, c.vss)
            c.ks, c.vs = list(out[0]), list(out[1])
            c.kss, c.vss = list(out[2]), list(out[3])
        self._staged_handoffs.pop(rid, None)

    def _stage_promotions(self, req, slot: int) -> None:
        """Install this request's planned tier promotions onto their
        fresh device pages BEFORE the extend program reads them —
        the host-tier mirror of :meth:`_kv_handoff`'s staged
        install/abort contract. Staged in ``_staged_promotions``
        before the ``serving.kv.promote`` kill point; popped on
        successful commit, or unwound HERE via ``abort_sequence`` on
        any raise (the caller's handler re-aborting is a safe no-op:
        the plan is already popped). A fault therefore returns the
        promotion dst pages AND the tier pins in the same unwind, so
        neither tier leaks."""
        plan = self.cache._plans.get(req.rid)
        if plan is None or not plan["promote"]:
            return
        rid = req.rid
        c = self.cache
        self._staged_promotions[rid] = slot
        self.metrics.on_promotion_start(rid)
        t0 = self.metrics.now()
        try:
            maybe_fail("serving.kv.promote", slot=slot, rid=rid,
                       pages=len(plan["promote"]))
            with span("serving.kv_promote", slot=slot,
                      request_id=rid, pages=len(plan["promote"])):
                work = c.begin_promotions(req)
                # async H2D first: every payload is on its way to the
                # device before the first install dispatch
                shipped = []
                for node, dst, payload, label in work:
                    kb = jax.device_put(list(payload["k"]))
                    vb = jax.device_put(list(payload["v"]))
                    ksb = jax.device_put(list(payload["ks"])) \
                        if self.kv_quant else []
                    vsb = jax.device_put(list(payload["vs"])) \
                        if self.kv_quant else []
                    shipped.append((dst, kb, vb, ksb, vsb))
                fn = self._promote_fn()
                for dst, kb, vb, ksb, vsb in shipped:
                    out = fn(np.int32(dst), kb, vb, ksb, vsb,
                             c.ks, c.vs, c.kss, c.vss)
                    c.ks, c.vs = list(out[0]), list(out[1])
                    c.kss, c.vss = list(out[2]), list(out[3])
                c.commit_promotions(req, work)
        except BaseException:
            self._staged_promotions.pop(rid, None)
            c.abort_sequence(slot, req)
            raise
        self._staged_promotions.pop(rid, None)
        self.metrics.on_promotion(rid, self.metrics.now() - t0)

    def _copy_fn(self):
        """COW page copy (compiled once): pool[dst] <- pool[src] for
        every layer's k/v (+scale) pool."""
        if self._copy_jit is not None:
            return self._copy_jit
        jit_kw = {}
        if self.meshctx is not None:
            _, _, R, kv, sc = self._prog_shardings()
            jit_kw = dict(in_shardings=(R, R, kv, kv, sc, sc),
                          out_shardings=(kv, kv, sc, sc))

        def ptpu_copy(src, dst, ks, vs, kss, vss):
            self._count_trace("copy")
            cp = lambda pool: pool.at[dst].set(pool[src])
            return ([cp(p) for p in ks], [cp(p) for p in vs],
                    [cp(p) for p in kss], [cp(p) for p in vss])

        self._copy_jit = self._jit(
            ptpu_copy, donate_argnums=self._donate_idx(2, 3, 4, 5),
            **jit_kw)
        return self._copy_jit

    def _promote_fn(self):
        """Tier promotion install (compiled once): scatter ONE host-
        tier page's k/v blocks (+int8 scales) into a fresh device page
        across every layer pool. One page per call keeps the program
        shape static — promotion cost is page-count many dispatches of
        the same compiled program, never a recompile."""
        if self._promote_jit is not None:
            return self._promote_jit

        def ptpu_promote(dst, kb, vb, ksb, vsb, ks, vs, kss, vss):
            self._count_trace("promote")
            put = lambda pool, b: pool.at[dst].set(
                b.astype(pool.dtype))
            return ([put(p, b) for p, b in zip(ks, kb)],
                    [put(p, b) for p, b in zip(vs, vb)],
                    [put(p, b) for p, b in zip(kss, ksb)],
                    [put(p, b) for p, b in zip(vss, vsb)])

        self._promote_jit = self._jit(
            ptpu_promote, donate_argnums=self._donate_idx(5, 6, 7, 8))
        return self._promote_jit

    def _decode_fn(self):
        """THE decode-step program (compiled once): every occupied slot
        advances one token at its own position; the active-slot mask
        pins inactive lanes to position 0 and zeroes their logits so
        they stay numerically inert whatever garbage their row holds.
        Beside the logits it returns their argmax a slot, ``[slots]``
        int32: a greedy step fetches that and leaves the logits on the
        device (``_decode_plain``).
        K and V flow through the page tables (inactive rows pinned to
        the trash page); state layers read and rewrite their slot rows,
        active slots only; what the model counted in the step
        (``step_counters()``: a dict, empty for most) rides along.

        Mesh flavor: the SAME program jitted under the decode group's
        mesh with explicit in/out shardings — params by the family's
        tp_param_spec rules, pools split on kv_heads, token/position/
        mask blocks replicated. Still exactly ONE compile per mesh
        shape, and bitwise token-identical to the single-chip program
        (output-dim-only sharding: no float sum is re-associated)."""
        if self._decode_jit is not None:
            return self._decode_jit
        ad = self.adapter
        greedy = lambda logits: jnp.argmax(logits, axis=-1).astype(jnp.int32)
        by_layer, by_kind = ad.by_layer, ad.by_kind
        jit_kw = {}
        if self.meshctx is not None:
            psh, bsh, R, kv, sc = self._prog_shardings()
            jit_kw = dict(
                in_shardings=(psh, bsh, R, R, R, R, kv, kv, sc, sc),
                out_shardings=(R, R, {}, kv, kv, sc, sc))

        def ptpu_decode(params, buffers, toks, pos, active, tables, ks,
                        vs, kss, vss, *pools):
            self._count_trace("decode")
            pos_eff = jnp.where(active, pos, 0).astype(jnp.int32)
            tab_eff = jnp.where(active[:, None], tables, 0)
            caches = by_layer(
                self._paged_caches(ks, vs, kss, vss, tab_eff, pos_eff),
                [row + (pos_eff, active) for row in zip(*pools)])
            with ad.model.bind_state(params, buffers):
                h, new_caches = ad.call(Tensor(toks), caches)
                logits = ad.head(h[:, -1:])._data[:, -1]
                counted = ad.counters()
            self.decode_attend = noted_fact("attend")
            logits = jnp.where(active[:, None], logits, 0.0)
            new_caches, new_state = by_kind(new_caches)
            d = lambda x: getattr(x, "_data", x)
            return (logits, greedy(logits), counted) \
                + self._unpack_paged(new_caches) \
                + tuple([d(c[a]) for c in new_state]
                        for a in range(len(pools)))

        self._decode_jit = self._jit(
            ptpu_decode, donate_argnums=self._donate_idx(
                *range(6, 10 + len(self.cache.pools))), **jit_kw)
        return self._decode_jit

    def _tokens_fn(self):
        """The decode program's ``[slots, 1]`` token block from a
        ``[slots]`` int32 vector: the host's tokens, or a step's argmax
        left on the device (a step enqueued ahead). Both reach the
        decode program through this one program, so the decode program
        sees one argument signature and compiles once; mesh engines get
        the block replicated, as the decode program takes it."""
        if self._tokens_jit is not None:
            return self._tokens_jit
        R = self._tokens_sharding
        jit_kw = {} if R is None else dict(in_shardings=R, out_shardings=R)

        def ptpu_tokens(toks):
            self._count_trace("tokens")
            return toks[:, None]

        self._tokens_jit = self._jit(ptpu_tokens, **jit_kw)
        return self._tokens_jit

    def _verify_fn(self):
        """THE speculative verify program (compiled once per engine):
        every occupied slot advances up to k tokens at its own
        position. The input block per row is [last emitted token,
        draft_1 .. draft_{k-1}] (padded past the row's per-row length
        ``wlen``); the cache write of token j is masked to j < wlen
        (models/_decode_cache wlen contract), the causal mask already
        scopes position j to everything <= pos + j, and the program
        returns, for every row: the k position logits, the k greedy
        (argmax) tokens, and the ACCEPTED LENGTH — 1 (the k=1 base
        token, always emitted) plus the leading run of draft tokens
        that equal the greedy token predicted one position earlier.
        That acceptance rule is exactly greedy sequential decode run k
        steps ahead, which is the token-identity proof: an accepted
        token had the same logits inputs (same cache state, same
        causal scope) as its sequential counterpart. k=1 fallback rows
        are just wlen=1 rows of the SAME program — zero extra
        compiles, trace-count asserted."""
        if self._verify_jit is not None:
            return self._verify_jit
        ad = self.adapter
        jit_kw = {}
        if self.meshctx is not None:
            psh, bsh, R, kv, sc = self._prog_shardings()
            jit_kw = dict(
                in_shardings=(psh, bsh, R, R, R, R, R, kv, kv, sc, sc),
                out_shardings=(R, R, R, kv, kv, sc, sc))

        def accept(toks, logits, wl_eff, active):
            K = toks.shape[1]
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K]
            if K > 1:
                # draft j (input position j, 1-based) is accepted iff
                # it equals the greedy prediction at position j-1 AND
                # is a real draft token (j < wlen); the leading-run
                # length is a cumprod sum
                m = (toks[:, 1:].astype(jnp.int32) == g[:, :-1]) \
                    & (jnp.arange(1, K, dtype=jnp.int32)[None, :]
                       < wl_eff[:, None])
                acc = 1 + jnp.sum(jnp.cumprod(m.astype(jnp.int32),
                                              axis=1), axis=1)
            else:
                acc = jnp.ones(toks.shape[0], jnp.int32)
            acc = jnp.where(active, acc, 0).astype(jnp.int32)
            return g, acc

        def ptpu_verify(params, buffers, toks, pos, active, wlen, tables,
                 ks, vs, kss, vss):
            self._count_trace("verify")
            pos_eff = jnp.where(active, pos, 0).astype(jnp.int32)
            wl_eff = jnp.where(active, wlen, 0).astype(jnp.int32)
            tab_eff = jnp.where(active[:, None], tables, 0)
            caches = self._paged_caches(ks, vs, kss, vss,
                                        tab_eff, pos_eff, wlen=wl_eff)
            with ad.model.bind_state(params, buffers):
                h, new_caches = ad.call(Tensor(toks), caches)
                logits = ad.head(h)._data            # [B, K, vocab]
            logits = jnp.where(active[:, None, None], logits, 0.0)
            g, acc = accept(toks, logits, wl_eff, active)
            return (logits, g, acc) + self._unpack_paged(new_caches)

        self._verify_jit = self._jit(
            ptpu_verify, donate_argnums=self._donate_idx(7, 8, 9, 10),
            **jit_kw)
        return self._verify_jit

    @staticmethod
    def _donate():
        """Donation enable flag: non-empty means the jit update of the
        pools is in-place on device. CPU ignores donation and warns, so
        skip it there. The programs derive their own argument indices
        from this flag via ``_donate_idx`` (tests monkeypatch
        ``_donate`` to simulate the TPU donated-pool failure mode)."""
        return () if jax.default_backend() == "cpu" else (5, 6)

    def _donate_idx(self, *idx):
        return idx if self._donate() else ()
