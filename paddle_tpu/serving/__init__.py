"""paddle_tpu.serving — continuous-batching LLM serving engine.

Iteration-level scheduling (Orca) over a BLOCK-PAGED KV cache
(PagedAttention-style fixed-size pages + static per-slot page tables,
copy-on-write prefix sharing keyed by prompt content, optional int8
KV with per-page scales — all inside the repo's compile-once decode
design): one compiled decode-step program serves ANY mix of in-flight
requests, admission is gated by free PAGES (worst-case span reserved,
so decode never preempts), and finished sequences (EOS / length cap)
are evicted immediately, their shared prompt pages staying cached for
later requests. A layer that keeps a recurrent state and no K/V
(``"state"`` in ``cache_spec().layers``) is served from a state row a
slot instead, in the same cache manager (``SlotCache``), beside the
pages of the layers that keep K and V; nothing chooses between the two,
what each layer of the model keeps does.

    engine = ServingEngine(model, max_slots=8, max_len=512, eos_id=2)
    req = engine.submit(prompt_ids, max_new_tokens=64)
    done = engine.run()            # or step() per iteration
    print(req.output_ids, engine.metrics.summary())

Compile count is 1 decode program + O(log max_len) prefill/extend
buckets (+1 COW copy program), asserted in
tests/test_serving_engine.py + tests/test_paged_kv.py via trace
counting — paging adds ZERO decode compiles.

``speculative=True`` turns on SELF-SPECULATIVE decoding: an n-gram /
prompt-lookup proposer (``spec_decode.NgramProposer``, no second
model) drafts up to ``spec_k - 1`` tokens per greedy row per step and
ONE widened verify program scores all k candidate positions in a
single weight pass, emitting the longest accepted prefix — provably
token-identical to non-speculative greedy decode (the acceptance rule
IS sequential greedy run k steps ahead; tests/test_spec_decode.py).
Rows with no usable draft run at k=1 inside the same program.

``mesh=`` (a ProcessMesh with a ``model`` axis) makes the engine
TENSOR-PARALLEL — KV pools and shardable params split across chips,
one decode program per mesh shape, greedy outputs bitwise identical
to single-chip — and ``prefill_devices=k`` DISAGGREGATES prefill from
decode with an explicit KV handoff between the two chip groups
(serving/mesh.py, docs/SERVING.md "Multi-chip serving").

Failure contract (docs/RESILIENCE.md): typed errors in ``errors``
(``QueueFull`` / ``DeadlineExceeded`` / ``EngineBroken`` /
``EngineIdle`` / ``EngineClosed``), ``ServingEngine.recover()`` after
a donated-pool step failure, per-request ``deadline_s``, bounded
``max_queue`` admission, and ``drain()`` for graceful shutdown.
"""
from .cluster import (ClusterSupervisor, RemoteEngine,  # noqa: F401
                      RemoteReplica, WorkerHandle)
from .engine import ServingEngine  # noqa: F401
from .control import (Actuator, BrownoutController,  # noqa: F401
                      ChunkBudgetController, ControlPlane,
                      PrefixAffinityPolicy, ReplicaAutoscaler)
from .errors import (DeadlineExceeded, EngineBroken,  # noqa: F401
                     EngineClosed, EngineIdle, NoHealthyReplicas,
                     QueueFull, RateLimited, RemoteError, ReplicaDead,
                     RequestCancelled, ServingError, Shed,
                     StateCacheUnsupported, TenantQueueFull)
from .frontdoor import (ClientStream, FrontDoor,  # noqa: F401
                        FrontDoorHandle, FrontDoorHTTPServer,
                        TenantPolicy, TokenBucket)
from .mesh import MeshContext  # noqa: F401
from .metrics import EngineMetrics  # noqa: F401
from .router import Replica, ReplicaRouter  # noqa: F401
from .sampling import SamplingParams, sample_token  # noqa: F401
from .scheduler import (FIFOScheduler, Request, bucket_for,  # noqa: F401
                        prefill_buckets)
from .slot_cache import PagedKVCache, SlotCache  # noqa: F401
from .spec_decode import (DraftModelProposer,  # noqa: F401
                          NgramProposer)
from .spec_tune import SpecTuner  # noqa: F401

__all__ = ["ServingEngine", "EngineMetrics", "MeshContext",
           "SamplingParams",
           "sample_token", "FIFOScheduler", "Request", "bucket_for",
           "prefill_buckets", "PagedKVCache",
           "SlotCache", "StateCacheUnsupported",
           "NgramProposer", "DraftModelProposer", "SpecTuner",
           "ServingError",
           "QueueFull", "DeadlineExceeded", "EngineBroken",
           "EngineIdle", "EngineClosed", "RequestCancelled",
           "RateLimited", "TenantQueueFull", "ReplicaDead",
           "NoHealthyReplicas", "RemoteError", "Shed",
           "ReplicaRouter", "Replica",
           "Actuator", "BrownoutController", "ChunkBudgetController",
           "ControlPlane", "PrefixAffinityPolicy",
           "ReplicaAutoscaler",
           "ClusterSupervisor", "RemoteEngine", "RemoteReplica",
           "WorkerHandle",
           "FrontDoor", "FrontDoorHTTPServer", "FrontDoorHandle",
           "ClientStream", "TenantPolicy", "TokenBucket"]
