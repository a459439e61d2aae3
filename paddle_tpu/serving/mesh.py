"""Tensor-parallel mesh context for the serving engine.

``MeshContext`` resolves the ``ServingEngine(mesh=...)`` argument — a
:class:`~paddle_tpu.distributed.ProcessMesh` with a ``model`` axis (or
a raw ``jax.sharding.Mesh``) — into the concrete shardings every
engine program is jitted under:

- **KV pools** shard over the ``model`` axis on their ``kv_heads``
  dimension (the ``[pages, page, KV, D]`` pools, and the prefill
  group's ``[1, Tmax, KV, D]`` chunk buffers; int8 per-page scales
  ``[pages, page, KV]`` follow on their last axis), so each chip holds
  ``1/tp`` of the KV bytes — the serving memory bottleneck.
- **Model params** shard over the same axis via the model family's
  ``tp_param_spec`` rules (models/llama.py, models/gpt.py). The rules
  are OUTPUT-DIM-ONLY by design: a weight is only ever split along a
  non-contracted dimension, so every floating-point reduction (matmul
  contraction, softmax, RMSNorm) runs over exactly the operands the
  single-chip program reduces, in the same shapes — which is what
  makes sharded greedy decode provably BITWISE token-identical to the
  single-chip engine and ``generate()`` (the law the whole serving
  stack is chaos-certified against). Row-parallel slices whose psum
  would re-associate float adds (down_proj / fc1 contractions) stay
  replicated; see docs/SERVING.md "Multi-chip serving".

**Disaggregated prefill/decode** (``prefill_devices=k``): the mesh's
device list is partitioned into a PREFILL group (first ``k`` devices)
and a DECODE group (the rest), each re-meshed over its own ``model``
axis. The decode group owns the KV pool and the one compiled decode /
verify / COW-copy / install programs; full prefills run on the prefill
group and hand their finished KV spans to the decode group through an
explicit ``jax.device_put`` KV handoff (engine ``_prefill_raw``),
audited by the ``serving.kv.handoff`` fault point and the cross-group
no-leak laws (resilience/invariants.py). Prefix-hit EXTEND prefills
stay on the decode group, where the shared pages already live.

Everything here is plain GSPMD under ``jax.jit`` with explicit
in/out shardings — no shard_map — so it runs on this repo's oldest
supported jax line and on the CPU-emulated 8-device mesh
(``--xla_force_host_platform_device_count=8``) that the MULTICHIP
artifacts and tier-1 tests use.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["MeshContext"]


def _flat_devices(mesh) -> list:
    """Device list of a ProcessMesh (via its process ids) or raw Mesh."""
    if isinstance(mesh, Mesh):
        return list(mesh.devices.flat)
    if hasattr(mesh, "process_ids"):            # ProcessMesh duck type
        devices = jax.devices()
        ids = mesh.process_ids
        if max(ids) >= len(devices):
            raise ValueError(
                f"mesh names device {max(ids)} but only "
                f"{len(devices)} are visible")
        return [devices[i] for i in ids]
    raise TypeError(
        f"mesh must be a paddle_tpu.distributed.ProcessMesh or a "
        f"jax.sharding.Mesh, got {type(mesh).__name__}")


class MeshContext:
    """Resolved sharding context (see module docstring).

    ``axis`` is the model-parallel axis name; the incoming mesh must
    be one-dimensional over it (serving TP composes with replica-level
    scale-out via the router, not with extra mesh axes)."""

    AXIS = "model"

    def __init__(self, mesh, kv_heads: int, prefill_devices: int = 0):
        if hasattr(mesh, "dim_names") and not isinstance(mesh, Mesh):
            if list(mesh.dim_names) != [self.AXIS]:
                raise ValueError(
                    f"serving mesh must be 1-D with the single axis "
                    f"{self.AXIS!r}, got dims {list(mesh.dim_names)}")
        elif isinstance(mesh, Mesh) and tuple(mesh.axis_names) != (
                self.AXIS,):
            raise ValueError(
                f"serving mesh must be 1-D with the single axis "
                f"{self.AXIS!r}, got axes {mesh.axis_names}")
        devices = _flat_devices(mesh)
        if len(set(d.id for d in devices)) != len(devices):
            raise ValueError("serving mesh repeats a device")
        self.prefill_devices = int(prefill_devices)
        if self.prefill_devices < 0:
            raise ValueError(
                f"prefill_devices must be >= 0, got {prefill_devices}")
        if self.prefill_devices:
            if self.prefill_devices >= len(devices):
                raise ValueError(
                    f"prefill_devices ({prefill_devices}) must leave "
                    f"at least one device for the decode group "
                    f"(mesh has {len(devices)})")
            pf = devices[:self.prefill_devices]
            dec = devices[self.prefill_devices:]
            self.prefill_mesh: Optional[Mesh] = Mesh(
                np.array(pf), (self.AXIS,))
            self.decode_mesh = Mesh(np.array(dec), (self.AXIS,))
        else:
            self.prefill_mesh = None
            self.decode_mesh = Mesh(np.array(devices), (self.AXIS,))
        for name, m in (("decode", self.decode_mesh),
                        ("prefill", self.prefill_mesh)):
            if m is not None and kv_heads % m.size != 0:
                raise ValueError(
                    f"kv_heads ({kv_heads}) must divide over the "
                    f"{name} group's model axis (size {m.size}) — "
                    f"the KV pools shard on the kv_heads dimension")

    # -- introspection ---------------------------------------------------
    @property
    def disaggregated(self) -> bool:
        return self.prefill_mesh is not None

    @property
    def tp(self) -> int:
        """Decode-group tensor-parallel degree (the pool's shard
        count). The compile-once contract is one decode program per
        MESH SHAPE — enforced by the engine's per-instance jit
        memoization (an engine has exactly one mesh) and pinned by
        the trace-count assertions in tests/test_tp_serving.py."""
        return int(self.decode_mesh.size)

    def _mesh(self, group: str) -> Mesh:
        if group == "decode" or self.prefill_mesh is None:
            return self.decode_mesh
        return self.prefill_mesh

    # -- sharding builders ----------------------------------------------
    def repl(self, group: str = "decode") -> NamedSharding:
        return NamedSharding(self._mesh(group), PartitionSpec())

    def kv_sharding(self, group: str = "decode") -> NamedSharding:
        """Pool and chunk-buffer sharding: [.., .., KV, D] over kv_heads."""
        return NamedSharding(self._mesh(group),
                             PartitionSpec(None, None, self.AXIS, None))

    def scale_sharding(self, group: str = "decode") -> NamedSharding:
        """int8 per-page scale sharding: [pages, page, KV] over KV."""
        return NamedSharding(self._mesh(group),
                             PartitionSpec(None, None, self.AXIS))

    def replicated_tree(self, tree, group: str = "decode"):
        r = self.repl(group)
        return jax.tree.map(lambda _: r, tree)

    def param_shardings(self, params: dict, adapter,
                        group: str = "decode") -> dict:
        """Per-param NamedSharding dict for one ``raw_state()`` params
        snapshot, from the model family's ``tp_param_spec`` rules
        (replicated where the rule returns None — including every
        param of an unknown family, which is always correct, just
        unsharded)."""
        mesh = self._mesh(group)
        rule = getattr(adapter, "tp_param_spec", None)
        out = {}
        for name, arr in params.items():
            spec = rule(name, arr.shape, int(mesh.size)) \
                if rule is not None else None
            out[name] = NamedSharding(mesh, spec if spec is not None
                                      else PartitionSpec())
        return out
