"""Mixture-of-Experts with expert parallelism.

Reference: python/paddle/incubate/distributed/models/moe/moe_layer.py:263
MoELayer with gshard/switch gates and count-based all-to-all dispatch via
global_scatter/global_gather (distributed/utils/moe_utils.py:20/:153 +
CUDA kernels).

TPU-native (GShard-style dense dispatch): routing builds one-hot
dispatch/combine tensors [tokens, experts, capacity] and the token
movement is two einsums — when the expert dim is sharded over the mesh's
expert axis, XLA lowers those einsums to exactly the all-to-all pair the
reference implements by hand, and they overlap with expert compute.
Static shapes (capacity) keep everything jit-compatible.

Two routing cores, each for what it serves. ``_topk_gating`` is the
trainer's (``GPTSpmdTrainer._block_moe``) and ``MoELayer``'s: top-1 or
top-2 with a capacity, a dense ``[tokens, experts, capacity]`` dispatch
whose einsums XLA partitions over an expert mesh axis, tokens over the
capacity dropped, an auxiliary loss. ``route_topk`` + ``expert_share``
is a served model's (``models/solar.py``): top-k of any k over ALL the
published experts, no capacity and no dropped token, the assignments
whose expert this chip holds sorted by expert and run through a grouped
matrix product (``ops/grouped_matmul.py``), so that work follows the
assignments held and not experts x capacity. It is told which experts
it holds (``first_expert``, and as many as its weights have) and gives
this chip's part of the layer's result: on one chip without the
exchange; the all-to-all over an ``expert`` mesh axis is what is left
before ``_block_moe`` can move onto it.
"""
from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor, apply_op
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer

from ..ops import grouped_matmul as gm

__all__ = ["MoELayer", "GShardGate", "SwitchGate", "NaiveGate",
           "moe_dispatch_combine", "route_topk", "expert_share"]

_F32 = jnp.float32


def route_topk(x, router, k: int):
    """Softmax scores of ``x [T, D]`` over all the experts of ``router
    [D, E]`` and each token's ``k`` largest, their weights normalised
    over the ``k``: ``(weights [T, k] float32, experts [T, k] int32)``.
    Float32 at ``Precision.HIGHEST`` throughout: a router decides by
    rank, and rounding can swap a token's k-th and (k+1)-th expert."""
    scores = jax.nn.softmax(jnp.matmul(
        x.astype(_F32), router.astype(_F32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(scores, k)
    return w / jnp.sum(w, axis=-1, keepdims=True), idx.astype(jnp.int32)


def expert_share(x, weights, experts, gate, up, down, first_expert: int,
                 *, name: str = "expert_gmm", kernel=None):
    """This chip's part of a routed SwiGLU layer: ``sum over a token's
    chosen experts that are held here of weight * down_e(silu(gate_e x)
    * up_e x)``.

    ``x [T, D]`` float32; ``weights, experts [T, k]`` from
    ``route_topk`` (normalised over all ``k``, held or not); ``gate, up
    [H, D, F]`` and ``down [H, F, D]`` the ``H`` experts held, which are
    experts ``first_expert .. first_expert + H - 1``. No capacity: every
    assignment whose expert is held is computed. Returns ``(y [T, D]
    float32, counts [H] int32)``, the assignments each held expert got.

    The ``T * k`` assignments are sorted by expert, those of experts not
    held last; the grouped product visits only the rows of a held
    expert, so the matrix work follows the assignments held."""
    T, D = x.shape
    k = experts.shape[1]
    H = gate.shape[0]
    local = experts.reshape(-1) - first_expert
    held = (local >= 0) & (local < H)
    key = jnp.where(held, local, H)
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros(H + 1, jnp.int32).at[key].add(1)[:H]
    x = x.astype(_F32)
    if gm.use_kernel(kernel) and gate.dtype == jnp.bfloat16:
        # the rows are cut into their two bfloat16 pieces before they
        # are gathered: the pieces of T rows, not of T * k
        rows = gm.pieces(x)[:, order // k]
    else:
        rows = x[order // k]
    gmm = lambda a, w: gm.grouped_matmul(a, w, counts, name=name,
                                         kernel=kernel)
    y = gmm(jax.nn.silu(gmm(rows, gate)) * gmm(rows, up), down)
    # back to the tokens' order by a gather, then the k parts of a token
    # are weighted and summed; the rows of no held expert are whatever
    # the kernel's buffers held, and are dropped here by a select, never
    # multiplied by a zero
    y = y[jnp.argsort(order)].reshape(T, k, D)
    w = weights.reshape(T, k).astype(_F32)
    part = jnp.where(held.reshape(T, k, 1), y * w[..., None], 0.0)
    return jnp.sum(part, axis=1), counts


def _topk_gating(logits, capacity, topk=2):
    """GShard top-k (k=1 Switch, k=2 GShard) gating with capacity,
    returning dispatch+combine tensors and the load-balancing aux
    loss. This is THE routing core: the GPTSpmdTrainer's MoE blocks
    (models/gpt.py:_block_moe) and the nn-API MoELayer below both run
    through it."""
    if topk not in (1, 2):
        raise ValueError(f"topk must be 1 or 2, got {topk}")
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # expert SELECTION happens on the raw masks; capacity masking is
    # applied only afterwards — a token whose top-1 overflowed must
    # still pick its true second-best expert, not re-pick the full one
    g1_idx = jnp.argmax(probs, axis=-1)
    m1 = jax.nn.one_hot(g1_idx, E, dtype=jnp.float32)
    # positions within each expert (prefix-sum over tokens)
    pos1 = jnp.cumsum(m1, axis=0) * m1 - m1  # 0-based slot of each token

    if topk == 2:
        probs_wo1 = probs * (1 - m1)
        g2_idx = jnp.argmax(probs_wo1, axis=-1)
        m2 = jax.nn.one_hot(g2_idx, E, dtype=jnp.float32)
        pos2 = (jnp.cumsum(m2, axis=0) - m2 +
                jnp.sum(m1, axis=0, keepdims=True)) * m2
        keep2 = jnp.sum(pos2 * m2, axis=-1) < capacity

    keep1 = jnp.sum(pos1 * m1, axis=-1) < capacity
    m1 = m1 * keep1[:, None]
    w1 = jnp.sum(probs * m1, axis=-1)
    slot1 = jnp.sum(pos1 * m1, axis=-1).astype(jnp.int32)
    c1 = jax.nn.one_hot(slot1, capacity, dtype=jnp.float32)

    if topk == 2:
        m2 = m2 * keep2[:, None]
        w2 = jnp.sum(probs * m2, axis=-1)
        denom = jnp.maximum(w1 + w2, 1e-9)
        w1n, w2n = w1 / denom, w2 / denom
        slot2 = jnp.sum(pos2 * m2, axis=-1).astype(jnp.int32)
        c2 = jax.nn.one_hot(slot2, capacity, dtype=jnp.float32)
        combine = (w1n[:, None, None] * m1[:, :, None] * c1[:, None, :]
                   + w2n[:, None, None] * m2[:, :, None]
                   * c2[:, None, :])
    else:  # Switch: route everything to the single winner
        combine = w1[:, None, None] * m1[:, :, None] * c1[:, None, :]
    dispatch = combine > 0.0

    # load-balance aux loss (GShard eq.4 / Switch eq.): fraction of
    # tokens whose top-1 is e, times the mean router prob of e
    density = jnp.mean(m1, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E
    return dispatch, combine, aux


_top2_gating = _topk_gating  # back-compat alias


def moe_dispatch_combine(x, gate_logits, capacity, topk=2):
    """Return (expert_inputs [E, C, D], combine [T, E, C], aux_loss)."""
    dispatch, combine, aux = _topk_gating(gate_logits, capacity, topk)
    expert_inputs = jnp.einsum("tec,td->ecd",
                               dispatch.astype(x.dtype), x)
    return expert_inputs, combine, aux


class NaiveGate(Layer):
    def __init__(self, d_model, num_experts, topk=2):
        super().__init__()
        self.wg = self.create_parameter(
            [d_model, num_experts],
            default_initializer=I.XavierUniform())
        self.num_experts = num_experts
        self.topk = topk

    def forward(self, x):
        return F.linear(x, self.wg)


GShardGate = NaiveGate


class SwitchGate(NaiveGate):
    def __init__(self, d_model, num_experts, topk=1):
        super().__init__(d_model, num_experts, topk=1)


class MoELayer(Layer):
    """Expert-parallel MoE FFN.

    ``experts`` weights are stacked [E, ...] and (when a mesh with an
    expert axis is set) sharded over it; the dispatch/combine einsums then
    compile to the all-to-all pair over ICI.
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 gate: Optional[Layer] = None, capacity_factor: float = 1.25,
                 expert_axis: str = "data", activation: Callable = None,
                 name=None):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.expert_axis = expert_axis
        self.gate = gate or NaiveGate(d_model, num_experts)
        init = I.XavierUniform()
        self.w_in = self.create_parameter([num_experts, d_model, d_hidden],
                                          default_initializer=init)
        self.b_in = self.create_parameter([num_experts, d_hidden],
                                          is_bias=True)
        self.w_out = self.create_parameter([num_experts, d_hidden, d_model],
                                           default_initializer=init)
        self.b_out = self.create_parameter([num_experts, d_model],
                                           is_bias=True)
        # set by forward(); ON the autograd tape — add
        # ``aux_weight * layer.aux_loss`` to the training objective so
        # balance gradients reach the gate (the trainer does exactly
        # this through the schedule's aux side channel; at the nn API
        # the user owns the objective, reference moe_layer.py:263)
        self.aux_loss = None
        self._shard_experts()

    def _shard_experts(self):
        from ..distributed.process_mesh import get_mesh
        from ..distributed.api import shard_tensor
        from ..distributed.placements import Replicate, Shard
        mesh = get_mesh()
        if mesh is None or self.expert_axis not in mesh.dim_names:
            return
        if self.num_experts % mesh.get_dim_size(self.expert_axis):
            return
        for name in ("w_in", "b_in", "w_out", "b_out"):
            p = self._parameters[name]
            placements = [Replicate()] * mesh.ndim
            placements[mesh.dim_names.index(self.expert_axis)] = Shard(0)
            self._parameters[name] = shard_tensor(p, mesh, placements)

    def forward(self, x):
        orig_shape = x.shape
        d = orig_shape[-1]
        xf = x.reshape([-1, d])
        logits = self.gate(xf)
        T = xf.shape[0]
        capacity = max(
            1, int(self.capacity_factor * T
                   * getattr(self.gate, "topk", 2) / self.num_experts))

        topk = getattr(self.gate, "topk", 2)

        def run(x2, lg, wi, bi, wo, bo):
            expert_in, combine, aux = moe_dispatch_combine(
                x2, lg, capacity, topk=topk)
            h = jnp.einsum("ecd,edh->ech", expert_in, wi.astype(x2.dtype))
            h = jax.nn.gelu(h + bi[:, None, :].astype(x2.dtype),
                            approximate=True)
            out_e = jnp.einsum("ech,ehd->ecd", h, wo.astype(x2.dtype))
            out_e = out_e + bo[:, None, :].astype(x2.dtype)
            y = jnp.einsum("tec,ecd->td", combine.astype(x2.dtype), out_e)
            return y, aux

        y, aux = apply_op(run, xf, logits, self.w_in, self.b_in,
                          self.w_out, self.b_out, _op_name="moe_layer")
        self.aux_loss = aux
        return y.reshape(orig_shape)


def global_scatter(x, local_count, global_count, group=None):
    """API-compat shim for the reference's count-based all-to-all
    (distributed/utils/moe_utils.py:20). On TPU, dispatch is the
    capacity-shaped einsum above; this eager shim routes by repeat."""
    return x


def global_gather(x, local_count, global_count, group=None):
    return x
