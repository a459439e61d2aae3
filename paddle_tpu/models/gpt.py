"""GPT model family — the flagship LLM stack.

Two implementations, by design:

1. ``GPTModel``/``GPTForCausalLM`` — imperative ``nn.Layer`` model built
   from the fleet TP layer library (VocabParallelEmbedding /
   Column/RowParallelLinear), the analog of the reference's fleet GPT
   (test/auto_parallel/hybrid_strategy/semi_auto_llama.py is the shape of
   this). Runs eagerly, under to_static, and under GSPMD meshes.

2. ``GPTSpmdTrainer`` — the performance path: a single jitted training
   step over a ('pipe','data','fsdp','sep','model') mesh composing
   - tp:   head/ffn dims sharded over 'model' (Megatron partitioning),
   - sp:   activation seq dim sharded over 'sep' (q local, k/v gathered),
   - dp:   batch over 'data',
   - fsdp: weight hidden-dim sharded over 'fsdp' (ZeRO-3; XLA gathers at
           use and reduce-scatters grads),
   - pp:   stage-stacked blocks pipelined via
           distributed.pipeline.pipeline_forward (scan + ppermute),
   with bf16 compute, fp32 master params/optimizer state, remat per block.
   This is what the reference needs its entire fleet/meta_parallel +
   pipeline-pass + sharding-pass machinery for (SURVEY.md §2.2 P2-P10);
   here it is ~300 lines because the mesh does the orchestration.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..nn import functional as F
from ..nn.layer_base import Layer
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.norm import LayerNorm
from ..nn.layer.container import LayerList
from ..framework.tensor import Tensor, apply_op
from ..observability import span
from ..utils.compile_cache import Watched
from ._decode_cache import (CacheSpec, cache_attend, check_cache_pos,
                            paged_cache_attend)

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM", "GPTSpmdTrainer",
           "build_mesh", "tp_param_spec"]


# Tensor-parallel SERVING shard rules for the imperative GPT family
# (GPTForCausalLM.raw_state() names). Output-dim-only, same contract
# as models/llama.tp_param_spec: shards only non-contracted dims so
# sharded decode stays bitwise token-identical to single-chip (fc1
# stays replicated — sharding it would turn fc2's contraction into a
# float-reassociating psum). The fused qkv output and its bias shard
# along 3*H*D; the tied wte shards over vocab (it is both the
# embedding table and the logits head's rhs, contracted over hidden).
_TP_OUT_DIM = ("qkv.weight", "proj.weight", "fc2.weight")
_TP_OUT_BIAS = ("qkv.bias", "proj.bias", "fc2.bias")


def tp_param_spec(name: str, shape, tp: int, axis: str = "model"):
    """PartitionSpec for one ``raw_state()`` param under the serving
    engine's tensor-parallel mesh, or None for replicated (see
    models/llama.tp_param_spec — same contract)."""
    if tp <= 1:
        return None
    if name.endswith(_TP_OUT_DIM) and len(shape) == 2 \
            and shape[-1] % tp == 0:
        return P(None, axis)
    if name.endswith(_TP_OUT_BIAS) and len(shape) == 1 \
            and shape[0] % tp == 0:
        return P(axis)
    if name.endswith("wte.weight") and len(shape) == 2 \
            and shape[0] % tp == 0:
        return P(axis, None)
    return None


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    ffn_mult: int = 4
    dropout: float = 0.0
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def ffn_size(self):
        return self.hidden_size * self.ffn_mult


# ---------------------------------------------------------------------------
# 1) imperative model (TP-aware via fleet layers when a mesh is set)
# ---------------------------------------------------------------------------

class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig, use_tp: bool = False):
        super().__init__()
        self.cfg = cfg
        self.ln1 = LayerNorm(cfg.hidden_size)
        self.ln2 = LayerNorm(cfg.hidden_size)
        if use_tp:
            from ..distributed.fleet.mp_layers import (ColumnParallelLinear,
                                                       RowParallelLinear)
            self.qkv = ColumnParallelLinear(cfg.hidden_size,
                                            3 * cfg.hidden_size,
                                            gather_output=False)
            self.proj = RowParallelLinear(cfg.hidden_size, cfg.hidden_size,
                                          input_is_parallel=True)
            self.fc1 = ColumnParallelLinear(cfg.hidden_size, cfg.ffn_size,
                                            gather_output=False)
            self.fc2 = RowParallelLinear(cfg.ffn_size, cfg.hidden_size,
                                         input_is_parallel=True)
        else:
            self.qkv = Linear(cfg.hidden_size, 3 * cfg.hidden_size)
            self.proj = Linear(cfg.hidden_size, cfg.hidden_size)
            self.fc1 = Linear(cfg.hidden_size, cfg.ffn_size)
            self.fc2 = Linear(cfg.ffn_size, cfg.hidden_size)
        self.drop = Dropout(cfg.dropout)

    def forward(self, x, cache=None):
        """cache: optional (k_cache [b, Tmax, H, D], v_cache, pos) — the
        fixed-buffer serving decode path (mirrors llama's static cache;
        pos is a scalar, or with `wlen` also a per-row [b] vector of
        write positions). Returns (out, cache') when given."""
        b, t, d = x.shape
        h = self.ln1(x)
        qkv = self.qkv(h)
        n_local = qkv.shape[-1] // (3 * self.cfg.head_dim)
        qkv = qkv.reshape([b, t, 3, n_local, self.cfg.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        new_cache = None
        if cache is not None and len(cache) in (6, 7):
            # paged pool flavor (see llama._forward_static_cache):
            # (k_pool, v_pool, k_scale, v_scale, page_table, pos);
            # the 7-tuple appends a per-row write length `wlen` — the
            # speculative VERIFY flavor (masked writes -> trash page)
            if len(cache) == 7:
                kp, vp, ksc, vsc, table, pos, wlen = cache
            else:
                kp, vp, ksc, vsc, table, pos = cache
                wlen = None
            # t=1: bucket-padded extend writes past the table are
            # legal (trash-redirected); only the start pos is checked
            check_cache_pos(pos, 1, table.shape[1] * kp.shape[1])
            out_dtype = getattr(x, "_data", x).dtype
            has_wl = wlen is not None

            def fp(q, k, v, kp, vp, table, p, *rest):
                if has_wl:
                    wl, rest = jnp.asarray(rest[0], jnp.int32), rest[1:]
                else:
                    wl = None
                ks, vs = rest if rest else (None, None)
                out, kp2, vp2, ks2, vs2 = paged_cache_attend(
                    q, k, v, kp, vp, ks, vs, table,
                    jnp.asarray(p, jnp.int32), jnp.dtype(out_dtype),
                    wlen=wl)
                return (out, kp2, vp2, ks2, vs2) if rest \
                    else (out, kp2, vp2)

            args = (q, k, v, kp, vp, table, pos) \
                + ((wlen,) if has_wl else ()) \
                + ((ksc, vsc) if ksc is not None else ())
            res = apply_op(fp, *args,
                           _op_name="gpt_paged_cache_attn")
            if ksc is not None:
                attn, kp2, vp2, ks2, vs2 = res
            else:
                (attn, kp2, vp2), ks2, vs2 = res, None, None
            new_cache = (kp2, vp2, ks2, vs2, table, pos + t)
        elif cache is not None:
            if len(cache) == 4:     # speculative VERIFY flavor
                k_cache, v_cache, pos, wlen = cache
            else:
                k_cache, v_cache, pos = cache
                wlen = None
            # verify writes past the buffer are index-dropped, so only
            # the start position is checked on that flavor
            check_cache_pos(
                pos, 1 if wlen is not None else t, k_cache.shape[1])

            def f(q, k, v, kc, vc, p, *rest):
                wl = jnp.asarray(rest[0], jnp.int32) if rest else None
                return cache_attend(q, k, v, kc, vc,
                                    jnp.asarray(p, jnp.int32), wlen=wl)

            args = (q, k, v, k_cache, v_cache, pos) \
                + ((wlen,) if wlen is not None else ())
            attn, kc2, vc2 = apply_op(f, *args,
                                      _op_name="gpt_static_cache_attn")
            new_cache = (kc2, vc2, pos + t)
        else:
            attn = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, training=self.training)
            attn = attn.reshape([b, t, n_local * self.cfg.head_dim])
        # ONE tail for both paths: the engine's token-parity guarantee
        # rides on cached and uncached decode sharing these exact ops
        x = x + self.drop(self.proj(attn))
        h = self.ln2(x)
        x = x + self.drop(self.fc2(F.gelu(self.fc1(h), approximate=True)))
        return x if new_cache is None else (x, new_cache)


class GPTModel(Layer):
    def __init__(self, cfg: GPTConfig, use_tp: bool = False):
        super().__init__()
        self.cfg = cfg
        if use_tp:
            from ..distributed.fleet.mp_layers import VocabParallelEmbedding
            self.wte = VocabParallelEmbedding(cfg.vocab_size,
                                              cfg.hidden_size)
        else:
            self.wte = Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.blocks = LayerList([GPTBlock(cfg, use_tp)
                                 for _ in range(cfg.num_layers)])
        self.ln_f = LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, caches=None):
        b, t = input_ids.shape
        from ..ops.creation import arange
        if caches is not None:
            # serving decode: learned positions come from the cache's
            # write position (scalar, or per-row for the engine's
            # slots); pos is the LAST element of the fixed-buffer
            # 3-tuple and paged 6-tuple flavors, second-to-last in the
            # VERIFY flavors (4/7-tuples, which append `wlen`)
            verify = len(caches[0]) in (4, 7)
            base = caches[0][-2] if verify else caches[0][-1]

            def mk_pos(p):
                p = jnp.asarray(p, jnp.int32)
                ar = jnp.arange(t, dtype=jnp.int32)
                out = p[:, None] + ar[None, :] if p.ndim >= 1 \
                    else (p + ar)[None, :]
                if verify:
                    # rows near their cap may run p + t past the wpe
                    # table; those positions are write-masked anyway —
                    # clip so the embedding gather stays in range
                    out = jnp.minimum(out, self.cfg.max_seq_len - 1)
                return out

            positions = apply_op(mk_pos, base, _op_name="gpt_cache_pos")
            x = self.wte(input_ids) + self.wpe(positions)
            new_caches = []
            for blk, c in zip(self.blocks, caches):
                x, nc = blk(x, c)
                new_caches.append(nc)
            return self.ln_f(x), new_caches
        pos = arange(t, dtype="int64").unsqueeze(0)
        x = self.wte(input_ids) + self.wpe(pos)
        for blk in self.blocks:
            x = blk(x)
        return self.ln_f(x)


class GPTForCausalLM(Layer):
    def __init__(self, cfg: GPTConfig, use_tp: bool = False):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg, use_tp)
        if not cfg.tie_embeddings:
            self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                  bias_attr=False)

    def forward(self, input_ids, caches=None):
        if caches is not None:
            h, new_caches = self.gpt(input_ids, caches=caches)
            return self._head(h), new_caches
        return self._head(self.gpt(input_ids))

    def _head(self, h):
        if self.cfg.tie_embeddings:
            from ..ops.linalg import matmul
            return matmul(h, self.gpt.wte.weight, transpose_y=True)
        return self.lm_head(h)

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        return F.cross_entropy(
            logits.reshape([-1, self.cfg.vocab_size]),
            labels.reshape([-1]))

    # -- what the serving engine asks of a model -------------------------
    tp_param_spec = staticmethod(tp_param_spec)

    def cached_forward(self, ids, caches):
        return self.gpt(ids, caches=caches)

    def cache_spec(self) -> CacheSpec:
        cfg = self.cfg
        qw = self.gpt.blocks[0].qkv.weight
        return CacheSpec(
            layers=("kv",) * len(self.gpt.blocks),
            kv_heads=qw.shape[-1] // (3 * cfg.head_dim),
            head_dim=cfg.head_dim, dtype=self.gpt.wte.weight._data.dtype,
            max_positions=cfg.max_seq_len)


# ---------------------------------------------------------------------------
# 2) SPMD trainer: one jitted step over the full hybrid mesh
# ---------------------------------------------------------------------------

AXES = ("pipe", "data", "fsdp", "sep", "model")

# Sharding specs of the stacked [S, L, ...] blocks leaves (mirrors
# _init_params); the per-layer pytree layout (layer_unroll="full")
# re-places each unstacked leaf with the tail of the same spec.
_BLOCK_SPECS = {
    "ln1_g": ("pipe", None, None), "ln1_b": ("pipe", None, None),
    "ln2_g": ("pipe", None, None), "ln2_b": ("pipe", None, None),
    "wqkv": ("pipe", None, "fsdp", "model"),
    "bqkv": ("pipe", None, "model"),
    "wproj": ("pipe", None, "model", "fsdp"),
    "bproj": ("pipe", None, None),
    "win": ("pipe", None, "fsdp", "model"),
    "bin": ("pipe", None, "model"),
    "wout": ("pipe", None, "model", "fsdp"),
    "bout": ("pipe", None, None),
    "wg": ("pipe", None, None, None),
    "w_in": ("pipe", None, "data", "fsdp", "model"),
    "b_in": ("pipe", None, "data", "model"),
    "w_out": ("pipe", None, "data", "model", "fsdp"),
    "b_out": ("pipe", None, "data", None),
}


def build_mesh(n_devices: Optional[int] = None,
               pipe: int = 1, data: Optional[int] = None, fsdp: int = 1,
               sep: int = 1, model: int = 1) -> Mesh:
    """Mesh over the hybrid axes; 'data' absorbs the remainder."""
    devices = jax.devices()
    n = n_devices or len(devices)
    fixed = pipe * fsdp * sep * model
    if data is None:
        if n % fixed != 0:
            raise ValueError(f"{n} devices not divisible by {fixed}")
        data = n // fixed
    shape = (pipe, data, fsdp, sep, model)
    return Mesh(np.asarray(devices[:int(np.prod(shape))]).reshape(shape),
                AXES)


def _spec(mesh: Mesh, *entries) -> NamedSharding:
    return NamedSharding(mesh, P(*entries))


class GPTSpmdTrainer:
    # class-level defaults so __new__-built instances (AOT tests) and
    # hot paths see consistent attributes without per-site guards
    lr_schedule = None
    ce_int8 = False
    int8_guard_period = 0
    int8_guard_threshold = 0.10
    _unroll_full = False
    fuse_bwd_colq = False
    _host_step = 0
    _guard_fn = None
    _guard_events = ()   # __init__ replaces with a per-instance list

    """Functional GPT pretraining step, fully sharded.

    Parameter shardings (fp32 masters; bf16 cast inside the step):
      wte [V, D]          ('model', 'fsdp')  — vocab-parallel embedding
      wpe [T, D]          (None, 'fsdp')
      blocks (stacked [S, Lps, ...], S over 'pipe'):
        wqkv [S,Lps,D,3D]  ('pipe', None, 'fsdp', 'model')
        wproj [S,Lps,D,D]  ('pipe', None, 'model', 'fsdp')
        win  [S,Lps,D,F]   ('pipe', None, 'fsdp', 'model')
        wout [S,Lps,F,D]   ('pipe', None, 'model', 'fsdp')
        ln scales/biases   ('pipe', None, None)
      with moe_experts=E, win/bin/wout/bout are replaced by:
        wg    [S,Lps,D,E]    ('pipe', None, None, None)  — gate
        w_in  [S,Lps,E,D,F]  ('pipe', None, 'data', 'fsdp', 'model')
        b_in  [S,Lps,E,F]    ('pipe', None, 'data', 'model')
        w_out [S,Lps,E,F,D]  ('pipe', None, 'data', 'model', 'fsdp')
        b_out [S,Lps,E,D]    ('pipe', None, 'data', None)
        (experts sharded over 'data' = expert parallelism)
      ln_f [D]            (None,)
    Activations: (batch='data', seq='sep') with q-local/kv-gathered
    attention (Megatron-SP over 'sep').
    """

    def __init__(self, cfg: GPTConfig, mesh: Mesh,
                 microbatches: Optional[int] = None,
                 learning_rate: float = 3e-4, weight_decay: float = 0.1,
                 beta1: float = 0.9, beta2: float = 0.95,
                 grad_clip: float = 1.0, seed: int = 0,
                 use_flash: Optional[bool] = None,
                 remat: bool = True,
                 mixed_precision: bool = True,
                 moment_dtype: Any = jnp.float32,
                 master_dtype: Any = jnp.float32,
                 quant8: bool = False,
                 pipeline_schedule: str = "gpipe",
                 vpp_chunks: int = 2,
                 moe_experts: int = 0,
                 moe_capacity_factor: float = 1.25,
                 moe_aux_weight: float = 1e-2,
                 fused_optimizer: Optional[bool] = None,
                 moment8: bool = False,
                 layer_unroll: int = 1,
                 ce_chunks: int = 16,
                 ce_int8: bool = False,
                 fuse_gelu_quant: Optional[bool] = None,
                 fuse_ln_quant: Optional[bool] = None,
                 fuse_bwd_colq: Optional[bool] = None,
                 lr_schedule=None,
                 int8_guard_period: int = 0,
                 int8_guard_threshold: float = 0.10):
        # set-up by phase: the configuration, then the state
        with span("train.build"):
            self.cfg = cfg
            self.mesh = mesh
            self.remat = remat  # per-block activation checkpointing
            # AMP-O2 contract (reference python/paddle/amp/auto_cast.py O2
            # `decorate`): compute/grads in cfg.dtype, fp32 master params in
            # the optimizer. Grads materialize at cfg.dtype (half the HBM of
            # fp32 grads), masters+update stay fp32.
            self.mixed_precision = mixed_precision
            # AdamW moment storage dtype; bf16 moments let ~1.3B params fit
            # a single 16G chip (update math still fp32)
            self.moment_dtype = moment_dtype
            # Master-weight storage dtype. fp32 = classic AMP-O2 masters.
            # bf16 = store masters AT compute precision and apply the AdamW
            # update with stochastic rounding (update math in fp32, the
            # rounding noise is unbiased so tiny updates accumulate in
            # expectation — the bf16+SR training recipe). Halves master HBM
            # and removes the per-step master->compute cast entirely, which
            # is what frees enough HBM for save_dots remat at 1.3B/16G.
            self.master_dtype = master_dtype
            self._stoch_round = (jnp.dtype(master_dtype) == jnp.bfloat16)
            # int8 MXU forward for the wide block matmuls (qkv/ffn), exact
            # bf16 backward — ~2x MXU rate on v5e (ops/quant_matmul.py).
            # quant8="dgrad" additionally runs the activation gradient on
            # the int8 MXU (wgrad stays exact bf16). quant8="wgrad" runs
            # ALL THREE matmuls int8 — the weight gradient quantizes with
            # stochastic rounding along the token axis, which keeps it
            # unbiased so Adam's moments integrate the noise to zero
            # (ops/quant_matmul.int8_linear_all8); SR streams are seeded
            # per (step, layer, site) from the optimizer step counter.
            self.quant8 = quant8
            # lr_schedule: traced fn step_f32 -> multiplier on the base lr
            # (cosine decay etc.); costs nothing — the multiplier rides the
            # fused kernel's scalar vector.
            self.lr_schedule = lr_schedule
            # int8 drift guard: every `period` steps measure the relative
            # dgrad error of the int8 path on ONE layer-0 matmul (~1% of a
            # step); if it exceeds the threshold, fall back one quant tier
            # (wgrad -> dgrad -> exact) and recompile the step. Exists
            # because the 500-step parity runs end with wqkv SNR ~1 — the
            # default is earned, but nothing should drift unwatched.
            self.int8_guard_period = int(int8_guard_period)
            self.int8_guard_threshold = float(int8_guard_threshold)
            if self.int8_guard_period and mesh.shape.get("pipe", 1) > 1:
                # the probe indexes blocks leaves as [S, L, ...][0, 0];
                # pipelined/VPP layouts need their own probe — refuse
                # loudly rather than crash inside the jitted probe
                raise ValueError(
                    "int8_guard_period requires a single-stage mesh "
                    "(pipe=1)")
            self._guard_fn = None
            self._guard_events = []
            self._host_step = 0
            if quant8 == "wgrad" and mesh.shape.get("pipe", 1) > 1:
                # the pipeline paths do not thread the per-step SR seed;
                # running them would silently reuse one stream every step —
                # exactly the data-correlated bias SR exists to remove
                raise ValueError(
                    "quant8='wgrad' supports single-stage meshes (pipe=1); "
                    "pipeline schedules keep wgrad exact (use 'dgrad')")
            # pp schedule: "gpipe" = autodiff'd scan+ppermute forward
            # (F-then-B); "1f1b" = explicit on-device 1F1B train schedule
            # (distributed/pipeline.pipeline_train_1f1b) with O(S) instead
            # of O(M) in-flight activations per stage; "vpp" = interleaved
            # virtual-pipeline (each rank holds vpp_chunks model chunks —
            # fill bubble shrinks by 1/V) and "zb" = ZeroBubble ZB-H1
            # (backward split into input-grad and weight-grad jobs, W fills
            # the cooldown bubble) — both execute their job tables on
            # device via distributed/pipeline_scheduled.py
            aliases = {"fthenb": "gpipe", "zero_bubble": "zb",
                       "interleaved": "vpp"}
            pipeline_schedule = aliases.get(pipeline_schedule,
                                            pipeline_schedule)
            if pipeline_schedule not in ("gpipe", "1f1b", "vpp", "zb"):
                raise ValueError(f"unknown pipeline_schedule "
                                 f"{pipeline_schedule!r}")
            self.pipeline_schedule = pipeline_schedule
            # chunked params only make sense with a pipe axis: with pipe=1
            # every schedule degenerates to the plain forward, which
            # consumes unchunked [S=1, L, ...] stage params
            self.V = int(vpp_chunks) if (pipeline_schedule == "vpp"
                                         and mesh.shape["pipe"] > 1) else 1
            # MoE-FFN variant: E experts per block, GShard top-2 dispatch,
            # experts sharded over the 'data' mesh axis (expert parallelism
            # — the dispatch/combine einsums lower to the all-to-all pair
            # the reference's global_scatter/global_gather implement by
            # hand, moe_layer.py:263); the load-balance aux loss is
            # accumulated through the layer scan and added to the CE loss.
            self.moe_experts = int(moe_experts)
            self.moe_capacity_factor = moe_capacity_factor
            self.moe_aux_weight = moe_aux_weight
            # single-pass Pallas AdamW (ops/fused_adamw.py): one kernel per
            # leaf reads p/g/m/v and writes p/m/v with in-kernel SR random
            # bits — 14 bytes/param of HBM traffic vs ~26 for the XLA
            # multi-pass schedule. Only meaningful on a real TPU; the
            # unsharded leaves the kernel needs exist when no mesh axis
            # shards params in ways the 2-D collapse can't see, so gate to
            # single-device meshes (GSPMD partitions pallas_call manually
            # sharded kernels poorly).
            if fused_optimizer is None:
                fused_optimizer = (jax.default_backend() == "tpu"
                                   and mesh.size == 1)
            self.fused_optimizer = fused_optimizer
            # int8 moment storage for fused-eligible leaves (round-5 lever
            # b): m int8-SR, v as sqrt(v) int8-SR, per-row f32
            # scales — 14 -> ~10 B/param of optimizer HBM traffic
            # (ops/fused_adamw.fused_adamw_update8). Parity-gated like every
            # quantization default: benchmarks/parity_int8.py --moment8.
            self.moment8 = bool(moment8)
            if self.moment8 and not (self.fused_optimizer
                                     and mesh.size == 1):
                # mesh.size must be checked here too: fused_optimizer=True
                # passed explicitly on a multi-device mesh would otherwise
                # let the opaque fused_adamw_update8 pallas_call reach the
                # partitioner, which replicates custom calls (same gate as
                # pallas_ops.single_device_tpu)
                raise ValueError(
                    "moment8 rides the fused AdamW kernel, which requires "
                    "a SINGLE-device TPU mesh (got fused_optimizer="
                    f"{self.fused_optimizer}, mesh.size={mesh.size}); it "
                    "has no XLA fallback path")
            # unroll policy for the per-stage layer loop. An int is the
            # classic lax.scan body-unroll factor: the body is replicated
            # but params/carries stay STACKED [L, ...], so every
            # remat-saved residual still round-trips HBM through a
            # dynamic-update-slice into the stacked buffer (plus a matching
            # dynamic-slice in the backward) — measured ~49 ms of pure
            # stacking traffic on the 1.3B step, and scan-unroll alone
            # measured a LOSS (round 3/5). "full" is the structural fix
            # (round 6): blocks params live as a PER-LAYER pytree (a dict
            # of "layer_NNN" subtrees, no [L, ...] leading dim anywhere —
            # dict-shaped so checkpointing flattens it like any state), the
            # stage runs as a Python loop, and remat saves/gradients/
            # optimizer state are per-layer leaves — XLA writes each
            # layer's residuals and weight-grad dequants straight from the
            # producing fusion instead of DUS-stacking them. Costs compile
            # time roughly linearly in num_layers; requires pipe=1 (the
            # pipeline shard_map consumes stacked stage params).
            self._unroll_full = (layer_unroll == "full")
            if self._unroll_full:
                if mesh.shape["pipe"] > 1 or self.V > 1:
                    raise ValueError(
                        "layer_unroll='full' requires a single-stage mesh "
                        "(pipe=1, vpp_chunks=1): pipeline schedules consume "
                        "stacked [S, L, ...] stage params")
                self.layer_unroll = cfg.num_layers
            else:
                self.layer_unroll = int(layer_unroll)
            # vocab-chunk count for the fused CE: fewer chunks = bigger
            # (faster) head matmuls but a larger live logits buffer
            self.ce_chunks = int(ce_chunks)
            # int8-MXU CE head matmuls (fwd + recompute + dx; dhead exact —
            # it feeds the tied embedding's Adam state). ~31 ms of head
            # matmuls at the flagship shape; earn/reject via parity_int8.
            self.ce_int8 = bool(ce_int8)
            # producer-fused gelu->quantize for the ffn2 site (round-5
            # lever d); auto-on for the all-int8 recipe. Note: removes the
            # standalone "ffn_act" residual, so policies that SAVE ffn_act
            # (save_attn_ffn) force it off.
            if fuse_gelu_quant and quant8 != "wgrad":
                raise ValueError(
                    "fuse_gelu_quant rides the all-int8 recipe: it needs "
                    "quant8='wgrad' (the fused op quantizes both the fwd "
                    "row and the wgrad SR column streams)")
            if fuse_gelu_quant is None:
                fuse_gelu_quant = quant8 == "wgrad"
            self.fuse_gelu_quant = bool(fuse_gelu_quant) and \
                remat != "save_attn_ffn"
            # producer-fused LayerNorm->quantize for the qkv/ffn1 sites
            # (round-5 lever a): same mechanism as fuse_gelu_quant — the
            # rowq kernel computes LN stats + normalize + quantize in one
            # read of the pre-LN residual; the wgrad colq kernel reuses the
            # emitted [M,1] stats. Default OFF: measured a structural LOSS
            # on the flagship step (337.4 -> 344-356 ms across full/qkv/
            # ffn1/fwd-only variants) — the custom-call boundary breaks
            # XLA's residual-add/bias/save fusions around each site, which
            # costs more than the saved LN-output round-trip (trace diff in
            # the rounds-1-5 notes (git history before PR 23); contrast
            # fuse_gelu_quant, whose site
            # feeds another custom call, not an XLA fusion).
            if fuse_ln_quant and quant8 != "wgrad":
                raise ValueError(
                    "fuse_ln_quant rides the all-int8 recipe: it needs "
                    "quant8='wgrad' (the fused op quantizes both the fwd "
                    "row and the wgrad SR column streams)")
            if fuse_ln_quant is None:
                fuse_ln_quant = False
            # True = both sites; "qkv"/"ffn1" = that site only (A/B probes)
            if fuse_ln_quant not in (True, False, "qkv", "ffn1"):
                raise ValueError(
                    f"fuse_ln_quant must be True/False/'qkv'/'ffn1', got "
                    f"{fuse_ln_quant!r}")
            self.fuse_ln_quant = fuse_ln_quant
            # fuse_ln_quant's wgrad sub-knob (ADVICE r5): True computes the
            # LN inside the backward column-quantize path from the saved
            # [M,1] stats (two reads of the pre-LN x, no h buffer); False
            # re-materializes LN(x) once and runs the plain one-pass colq
            # kernel. None defers to env PTPU_FUSE_BWD_COLQ (default off —
            # the A/B that earned the default is in the rounds-1-5 notes (git
            # history before PR 23)).
            # The [M,1] mean/rstd residuals are only SAVED when the branch
            # is on (ops/quant_matmul.int8_ln_linear_all8).
            if fuse_bwd_colq is None:
                from ..ops.quant_matmul import _env_fuse_bwd_colq
                fuse_bwd_colq = _env_fuse_bwd_colq()
            self.fuse_bwd_colq = bool(fuse_bwd_colq)
            if self.moe_experts and mesh.shape["pipe"] > 1 \
                    and self.pipeline_schedule == "gpipe":
                raise NotImplementedError(
                    "MoE + pipeline parallelism requires an explicit "
                    "schedule engine ('1f1b', 'vpp' or 'zb'): the "
                    "autodiff'd GPipe scan has no aux-loss side channel")
            # Pallas flash attention on real TPU; XLA einsum attention
            # elsewhere (interpret-mode pallas is orders slower on CPU, and
            # the Mosaic kernel does not lower on GPU backends)
            if use_flash is None:
                use_flash = jax.default_backend() == "tpu"
            self.use_flash = use_flash
            self.S = mesh.shape["pipe"]
            if cfg.num_layers % (self.S * self.V):
                raise ValueError("num_layers must divide pp degree "
                                 "(x vpp_chunks for 'vpp')")
            self.Lps = cfg.num_layers // (self.S * self.V)
            self.M = microbatches or max(2 * self.S, 1)
            if self.pipeline_schedule == "vpp" and self.S > 1 \
                    and self.M % self.S:
                raise ValueError("interleaved schedule needs "
                                 "microbatches % pp degree == 0")
            self._sched_cache = None
            self.lr = learning_rate
            self.wd = weight_decay
            self.betas = (beta1, beta2)
            self.grad_clip = grad_clip
        with span("train.init_state") as sp:
            self._init_state(seed)
            sp.set_attr("n_params", self.n_params())
        self._step_fn = None

    # -- init --------------------------------------------------------------
    def _init_state(self, seed: int) -> None:
        """Parameters and optimizer state, created on the mesh."""
        mesh = self.mesh
        self.params = self._init_params(jax.random.key(seed))
        zeros_moment = lambda p: jnp.zeros(  # noqa: E731
            p.shape, self.moment_dtype, device=p.sharding)
        # the counter (and the int8 moment state below) are committed
        # to the mesh like the step outputs that replace them: an
        # uncommitted array has another type than step 1's output, and
        # step 2 then traces and compiles the whole program again
        step0 = jnp.zeros((), jnp.int32, device=_spec(mesh))
        if self.moment8:
            from ..ops.fused_adamw import (moment8_eligible,
                                           moment8_init)

            on_mesh = partial(jax.device_put, device=_spec(mesh))

            def m_leaf(p):
                if moment8_eligible(p):
                    mq, msc, _, _ = moment8_init(p)
                    return (on_mesh(mq), on_mesh(msc))
                return zeros_moment(p)

            def v_leaf(p):
                if moment8_eligible(p):
                    _, _, vq, vsc = moment8_init(p)
                    return (on_mesh(vq), on_mesh(vsc))
                return zeros_moment(p)

            self.opt_state = {
                "step": step0,
                "m": jax.tree.map(m_leaf, self.params),
                "v": jax.tree.map(v_leaf, self.params),
            }
        else:
            self.opt_state = {
                "step": step0,
                "m": jax.tree.map(zeros_moment, self.params),
                "v": jax.tree.map(zeros_moment, self.params),
            }

    def _init_params(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        D, V, T, Ff = (cfg.hidden_size, cfg.vocab_size, cfg.max_seq_len,
                       cfg.ffn_size)
        S, L = self.S, self.Lps
        k = jax.random.split(key, 8)
        std = 0.02
        resid_std = std / math.sqrt(2 * cfg.num_layers)

        mdt = self.master_dtype
        n_chunks = self.V

        def vshape(shape, spec):
            # interleaved VPP: blocks leaves grow a leading chunk dim
            # [V, S, ...] — chunk c of pipe-rank r is virtual stage
            # c*S + r (pipeline_scheduled.py)
            if n_chunks > 1 and spec and spec[0] == "pipe":
                return (n_chunks,) + shape, (None,) + spec
            return shape, spec

        def init(key, shape, scale, spec):
            shape, spec = vshape(shape, spec)
            arr = (scale * jax.random.normal(key, shape,
                                             jnp.float32)).astype(mdt)
            return jax.device_put(arr, _spec(self.mesh, *spec))

        def zeros(shape, spec):
            shape, spec = vshape(shape, spec)
            return jax.device_put(jnp.zeros(shape, mdt),
                                  _spec(self.mesh, *spec))

        def ones(shape, spec):
            shape, spec = vshape(shape, spec)
            return jax.device_put(jnp.ones(shape, mdt),
                                  _spec(self.mesh, *spec))

        params = {
            "wte": init(k[0], (V, D), std, ("model", "fsdp")),
            "wpe": init(k[1], (T, D), std, (None, "fsdp")),
            "ln_f_g": ones((D,), (None,)),
            "ln_f_b": zeros((D,), (None,)),
            "blocks": {
                "ln1_g": ones((S, L, D), ("pipe", None, None)),
                "ln1_b": zeros((S, L, D), ("pipe", None, None)),
                "ln2_g": ones((S, L, D), ("pipe", None, None)),
                "ln2_b": zeros((S, L, D), ("pipe", None, None)),
                "wqkv": init(k[2], (S, L, D, 3 * D), std,
                             ("pipe", None, "fsdp", "model")),
                "bqkv": zeros((S, L, 3 * D), ("pipe", None, "model")),
                "wproj": init(k[3], (S, L, D, D), resid_std,
                              ("pipe", None, "model", "fsdp")),
                "bproj": zeros((S, L, D), ("pipe", None, None)),
            },
        }
        if not self.moe_experts:
            params["blocks"].update({
                "win": init(k[4], (S, L, D, Ff), std,
                            ("pipe", None, "fsdp", "model")),
                "bin": zeros((S, L, Ff), ("pipe", None, "model")),
                "wout": init(k[5], (S, L, Ff, D), resid_std,
                             ("pipe", None, "model", "fsdp")),
                "bout": zeros((S, L, D), ("pipe", None, None)),
            })
        else:
            E = self.moe_experts
            b = params["blocks"]
            km = jax.random.split(k[7], 3)
            # experts over 'data' (expert parallelism), fsdp/tp inside
            # each expert; the gate is tiny and replicated
            b["wg"] = init(km[0], (S, L, D, E), std,
                           ("pipe", None, None, None))
            b["w_in"] = init(km[1], (S, L, E, D, Ff), std,
                             ("pipe", None, "data", "fsdp", "model"))
            b["b_in"] = zeros((S, L, E, Ff), ("pipe", None, "data",
                                              "model"))
            b["w_out"] = init(km[2], (S, L, E, Ff, D), resid_std,
                              ("pipe", None, "data", "model", "fsdp"))
            b["b_out"] = zeros((S, L, E, D), ("pipe", None, "data",
                                              None))
        if not self.cfg.tie_embeddings:
            params["head"] = init(k[6], (D, V), std, ("fsdp", "model"))
        if self._unroll_full:
            # per-layer pytree layout (layer_unroll="full"): blocks is
            # a dict of per-layer subtrees keyed "layer_000".. — no
            # [S, L, ...] leading dims, so remat saves, gradients, and
            # optimizer state are per-layer leaves that never
            # round-trip HBM through dynamic-update-slice stacking.
            # Zero-padded string keys keep sorted() == layer order AND
            # keep the tree dict-shaped, which is what
            # distributed/checkpoint.save_state_dict flattens. Values
            # come from the SAME stacked init (identical RNG draws),
            # so rolled/unrolled trainers with equal seeds start
            # bit-identical.
            blocks = params["blocks"]
            params["blocks"] = {
                f"layer_{li:03d}": {
                    k2: jax.device_put(
                        v[0, li],
                        _spec(self.mesh, *_BLOCK_SPECS[k2][2:]))
                    for k2, v in blocks.items()}
                for li in range(L)}
        return params

    # -- model -------------------------------------------------------------
    def _mm(self, seed=None):
        # bf16 in/out einsums: the TPU MXU accumulates bf16 products in
        # fp32 internally, so a bf16 output dtype only rounds the final
        # result while halving the HBM write (measured ~7% step win vs
        # preferred_element_type=f32 + cast). ``site`` decorrelates the
        # SR streams of the three matmul sites in a block (wgrad mode).
        if self.quant8 == "wgrad":
            from ..ops.quant_matmul import int8_linear_all8, site_seed
            return lambda a, w, site=0: int8_linear_all8(
                a, w, site_seed(seed, site))
        if self.quant8 == "dgrad":
            from ..ops.quant_matmul import int8_linear_dgrad8
            return lambda a, w, site=0: int8_linear_dgrad8(a, w)
        if self.quant8:
            from ..ops.quant_matmul import int8_linear
            return lambda a, w, site=0: int8_linear(a, w)
        return lambda a, w, site=0: jnp.einsum("btd,df->btf", a, w)

    def _attn_sublayer(self, x, bp, mm, act, seed=None):
        """ln1 + qkv + attention + proj + residual on [mb, T, D]."""
        cfg = self.cfg
        mb, T, D = x.shape
        H, dh = cfg.num_heads, cfg.head_dim
        if self.quant8 == "wgrad" and self.fuse_ln_quant in (True, "qkv"):
            from ..ops.quant_matmul import int8_ln_linear_all8, site_seed
            qkv = int8_ln_linear_all8(
                x, bp["ln1_g"], bp["ln1_b"],
                bp["wqkv"].astype(x.dtype), site_seed(seed, 1),
                fuse_bwd_colq=self.fuse_bwd_colq)
        else:
            h = _layer_norm(x, bp["ln1_g"], bp["ln1_b"])
            qkv = mm(h, bp["wqkv"].astype(x.dtype), 1)
        qkv = qkv + bp["bqkv"].astype(x.dtype)
        qkv = checkpoint_name(qkv, "qkv_out")
        shape = self.mesh.shape
        # zero-relayout path: the hsplit flash kernel consumes the qkv
        # matmul's native [mb, T, H*dh] layout (column slices per head
        # inside the kernel's BlockSpecs) — no (T,H) transposes at all.
        # Gated to model==1: with TP the packed 3HD columns are sharded
        # over 'model', and a plain column slice would cross shards.
        # dh must be lane-aligned (128): the kernel's column blocks are
        # dh wide, and Mosaic requires the last block dim % 128 == 0
        # when it is not the whole array dim (interpret mode does NOT
        # check this — dh=64 passes CPU tests but fails on hardware)
        hsplit_ok = (self.use_flash and shape["sep"] == 1
                     and shape["pipe"] == 1 and shape["model"] == 1
                     and T % 128 == 0 and dh % 128 == 0
                     and mb % shape["data"] == 0)
        if hsplit_ok:
            from ..ops.pallas_ops import flash_attention_qkv_fused
            spec = P("data", None, None)
            f = jax.shard_map(
                partial(flash_attention_qkv_fused, num_heads=H,
                        causal=True),
                in_specs=(spec,), out_specs=spec,
                axis_names=set(self.mesh.axis_names),
                check_vma=False)
            attn = f(qkv)
        else:
            qkv4 = qkv.reshape(mb, T, 3, H, dh)
            q, k, v = qkv4[:, :, 0], qkv4[:, :, 1], qkv4[:, :, 2]
            attn = self._attention(q, k, v, act).reshape(mb, T, H * dh)
        attn = checkpoint_name(attn, "attn_out")
        proj = jnp.einsum("btf,fd->btd", attn, bp["wproj"].astype(x.dtype))
        x = x + proj + bp["bproj"].astype(x.dtype)
        return act(x, _spec(self.mesh, "data", "sep", None))

    def _block(self, x, bp, seed=None):
        """One transformer block on [mb, T, D] activations (GSPMD view)."""
        act = partial(jax.lax.with_sharding_constraint)
        mm = self._mm(seed)
        x = self._attn_sublayer(x, bp, mm, act, seed)

        if self.quant8 == "wgrad" and self.fuse_ln_quant in (True, "ffn1"):
            from ..ops.quant_matmul import int8_ln_linear_all8, site_seed
            a = int8_ln_linear_all8(
                x, bp["ln2_g"], bp["ln2_b"],
                bp["win"].astype(x.dtype), site_seed(seed, 2),
                fuse_bwd_colq=self.fuse_bwd_colq)
        else:
            h = _layer_norm(x, bp["ln2_g"], bp["ln2_b"])
            a = mm(h, bp["win"].astype(x.dtype), 2)
        a = a + bp["bin"].astype(x.dtype)
        a = checkpoint_name(a, "ffn1_out")  # pre-gelu: gelu vjp needs it
        if self.quant8 == "wgrad" and self.fuse_gelu_quant:
            # round-5 lever d: gelu computed INSIDE the ffn2 quantize
            # kernels (fwd rowq + wgrad SR colq) — the bf16 gelu output
            # never lands in HBM and the quantizers stop re-reading it
            from ..ops.quant_matmul import (int8_gelu_linear_all8,
                                            site_seed)
            o = int8_gelu_linear_all8(a, bp["wout"].astype(x.dtype),
                                      site_seed(seed, 3))
        else:
            a = jax.nn.gelu(a, approximate=True)
            a = checkpoint_name(a, "ffn_act")
            o = mm(a, bp["wout"].astype(x.dtype), 3)
        o = checkpoint_name(o, "ffn2_out")
        x = x + o + bp["bout"].astype(x.dtype)
        return act(x, _spec(self.mesh, "data", "sep", None))

    def _block_moe(self, x, bp, seed=None):
        """Transformer block with a GShard top-2 MoE FFN; returns
        (x, load_balance_aux). Experts live on the 'data' mesh axis —
        the dispatch/combine einsums below ARE the all-to-all pair."""
        from ..incubate.moe import moe_dispatch_combine
        act = partial(jax.lax.with_sharding_constraint)
        mm = self._mm(seed)
        x = self._attn_sublayer(x, bp, mm, act, seed)
        mb, T, D = x.shape
        E = self.moe_experts

        h = _layer_norm(x, bp["ln2_g"], bp["ln2_b"])
        hf = h.reshape(mb * T, D)
        logits = jnp.einsum("td,de->te", hf.astype(jnp.float32),
                            bp["wg"].astype(jnp.float32))
        capacity = max(1, int(self.moe_capacity_factor * mb * T * 2 / E))
        expert_in, combine, aux = moe_dispatch_combine(hf, logits,
                                                       capacity)
        expert_in = act(expert_in,
                        _spec(self.mesh, "data", None, "fsdp"))
        a = jnp.einsum("ecd,edf->ecf", expert_in,
                       bp["w_in"].astype(h.dtype))
        a = jax.nn.gelu(a + bp["b_in"][:, None, :].astype(h.dtype),
                        approximate=True)
        o = jnp.einsum("ecf,efd->ecd", a, bp["w_out"].astype(h.dtype))
        o = o + bp["b_out"][:, None, :].astype(h.dtype)
        y = jnp.einsum("tec,ecd->td", combine.astype(h.dtype), o)
        x = x + y.reshape(mb, T, D)
        return act(x, _spec(self.mesh, "data", "sep", None)), aux

    def _attention(self, q, k, v, act):
        """Causal self-attention on [mb, T, H, dh]; Pallas flash kernel on
        TPU (batch over 'data', heads over 'model' via shard_map), XLA
        einsum with Megatron-SP (q seq-sharded, k/v gathered) otherwise."""
        mb, T, H, dh = q.shape
        shape = self.mesh.shape
        # pipe must be 1: the Mosaic lowering requires manual_axes to
        # cover EVERY mesh axis, and nested shard_map manual-axes do not
        # union with the pipeline's, so flash attention cannot run inside
        # the pipe shard_map (pipe>1 configs use the XLA einsum path)
        flash_ok = (self.use_flash and shape["sep"] == 1
                    and shape["pipe"] == 1
                    and T % 128 == 0 and dh in (64, 128, 256)
                    and H % shape["model"] == 0
                    and mb % shape["data"] == 0)
        if flash_ok:
            from ..ops.pallas_ops import flash_attention_fwd
            spec = P("data", None, "model", None)
            f = jax.shard_map(
                partial(flash_attention_fwd, causal=True),
                in_specs=(spec, spec, spec),
                out_specs=spec,
                axis_names=set(self.mesh.axis_names),  # fully manual
                check_vma=False)
            return f(q, k, v)
        # long-context path: Ulysses all-to-all attention — seq-sharded
        # activations become head-sharded full-sequence blocks, so per-chip
        # kv memory is S*(H/n)*D instead of the gathered S*H*D
        ulysses_ok = (self.use_flash and shape["pipe"] == 1
                      and shape["sep"] > 1
                      and T % 128 == 0 and dh in (64, 128, 256)
                      and H % (shape["model"] * shape["sep"]) == 0
                      and mb % shape["data"] == 0)
        if ulysses_ok:
            from ..ops.pallas_ops import ulysses_attention
            return ulysses_attention(
                q, k, v, self.mesh, axis="sep", causal=True,
                manual_axes=set(self.mesh.axis_names),
                use_flash=jax.default_backend() == "tpu",
                in_spec=P("data", "sep", "model", None))
        # SP: q stays seq-sharded; k/v gathered over 'sep'
        q = act(q, _spec(self.mesh, "data", "sep", "model", None))
        k = act(k, _spec(self.mesh, "data", None, "model", None))
        v = act(v, _spec(self.mesh, "data", None, "model", None))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        logits = logits / math.sqrt(dh)
        causal = jnp.tril(jnp.ones((T, T), bool))
        logits = jnp.where(causal, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    def _stage_fn(self, stage_params, x, seed=None):
        """One pipeline stage = Lps blocks, scanned.

        remat: False = save everything; True = full per-block remat;
        "save_attn" / "save_attn_ffn" = selective policies that keep the
        expensive flash-attention output (and optionally the ffn
        activation) while recomputing the cheap elementwise tail;
        "save_dots" = save every matmul output (recompute only norms /
        elementwise) — remat's 2N extra FLOPs shrink to ~0 at the cost
        of ~9 activation buffers per layer."""
        blk = self._remat_wrap(self._block)
        if self._unroll_full:
            # per-layer pytree path: stage_params maps "layer_NNN" ->
            # per-layer dict; residual saves and weight grads are
            # per-layer leaves (no stacked carries, no DUS)
            for li, key in enumerate(sorted(stage_params)):
                bp = stage_params[key]
                if self.quant8 == "wgrad":
                    x = blk(x, bp, self._layer_seed(seed, li))
                else:
                    x = blk(x, bp)
            return x
        if self.quant8 == "wgrad":
            xs = (stage_params, self._layer_seeds(seed))
            body = lambda carry, t: (blk(carry, t[0], t[1]), None)
        else:
            xs = stage_params
            body = lambda carry, bp: (blk(carry, bp), None)
        x, _ = jax.lax.scan(body, x, xs,
                            unroll=min(self.layer_unroll, self.Lps))
        return x

    def _layer_seeds(self, seed):
        """Per-layer SR seed array for the wgrad scan: layers sit 16
        apart so _mm's ``s*8 + site`` keeps (layer, site) streams
        distinct — ONE definition for the dense and MoE stages."""
        base = jnp.int32(1) if seed is None else seed
        return base + jnp.arange(self.Lps, dtype=jnp.int32) * 16

    def _layer_seed(self, seed, li):
        """Scalar layer seed for the unrolled path — same derivation
        as _layer_seeds, so rolled and unrolled draw IDENTICAL SR
        streams (the bit-parity test relies on it)."""
        base = jnp.int32(1) if seed is None else seed
        return base + jnp.int32(li * 16)

    def _remat_wrap(self, block_fn):
        """Apply the configured remat policy to a block fn (shared by
        the dense and MoE stages)."""
        if not self.remat:
            return block_fn
        if self.remat == "save_attn":
            pol = jax.checkpoint_policies.save_only_these_names("attn_out")
        elif self.remat == "save_attn_ffn":
            pol = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "ffn_act")
        elif self.remat == "save_dots":
            # matmul outputs + the flash kernel's own residuals (out,
            # lse): backward recomputes only layernorms/elementwise —
            # remat overhead drops from ~33% of step FLOPs to ~0
            pol = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse"))
        elif self.remat == "save_main":
            # like save_dots but drops the attention-proj output buffer
            # (cheapest matmul, 2/24 of block FLOPs to recompute) —
            # ~0.6G less HBM at bs6/1.3B, which is what lets this fit
            # alongside bf16 masters on a 16G chip. ffn2_out is NOT
            # saved: the residual-add backward is identity in it, so
            # saving it only costs a stacked buffer + copy traffic
            pol = jax.checkpoint_policies.save_only_these_names(
                "qkv_out", "ffn1_out", "flash_out", "flash_lse")
        elif self.remat == "save_qkv":
            # S=2048 memory recipe: drops the stacked ffn1_out residual
            # too (~3.2 GB at bs4/seq2048) — backward re-runs the ffn1
            # matmul and gelu from the recomputed ln2 output in exchange
            # for the batch size the freed HBM buys
            pol = jax.checkpoint_policies.save_only_these_names(
                "qkv_out")
        elif self.remat == "save_qkv_ffn":
            # drops the flash out/lse residuals too: backward re-runs
            # the flash FORWARD kernel from the saved qkv projection
            # (~13 ms/step at 1.3B) in exchange for ~1.2 GB of stacked
            # residual HBM — the trade that buys layer_unroll room
            pol = jax.checkpoint_policies.save_only_these_names(
                "qkv_out", "ffn1_out")
        else:
            return jax.checkpoint(block_fn)
        return jax.checkpoint(block_fn, policy=pol)

    def _stage_fn_moe(self, stage_params, x, seed=None):
        """MoE stage: like _stage_fn but threads the summed
        load-balance aux loss through the layer scan."""
        blk = self._remat_wrap(self._block_moe)
        if self._unroll_full:
            aux = jnp.zeros((), jnp.float32)
            for li, key in enumerate(sorted(stage_params)):
                bp = stage_params[key]
                if self.quant8 == "wgrad":
                    x, a = blk(x, bp, self._layer_seed(seed, li))
                else:
                    x, a = blk(x, bp)
                aux = aux + a
            return x, aux
        if self.quant8 == "wgrad":
            xs = (stage_params, self._layer_seeds(seed))

            def body(carry, t):
                x, aux = carry
                x, a = blk(x, t[0], t[1])
                return (x, aux + a), None
        else:
            xs = stage_params

            def body(carry, bp):
                x, aux = carry
                x, a = blk(x, bp)
                return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   xs,
                                   unroll=min(self.layer_unroll, self.Lps))
        return x, aux

    def _embed(self, wte, wpe, input_ids):
        """Token + position embedding, activation-sharded (shared by the
        autodiff'd path and the explicit 1F1B path)."""
        T = input_ids.shape[1]
        dtype = self.cfg.dtype
        x = wte.astype(dtype)[input_ids] + \
            wpe.astype(dtype)[jnp.arange(T)][None]
        return jax.lax.with_sharding_constraint(
            x, _spec(self.mesh, "data", "sep", None))

    def _forward_loss(self, params, input_ids, labels, seed=None):
        cfg = self.cfg
        B, T = input_ids.shape
        dtype = cfg.dtype
        if self.quant8 == "wgrad" and seed is None:
            seed = jnp.int32(1)
        x = self._embed(params["wte"], params["wpe"], input_ids)

        moe_aux = None
        if self.S == 1:
            # no pipeline: run the (single) stage outside the pipe
            # shard_map (lets Pallas flash run); microbatches still scan
            # so per-step working shapes match the pipelined path
            stage = params["blocks"] if self._unroll_full \
                else jax.tree.map(lambda a: a[0], params["blocks"])
            stage_fn = self._stage_fn_moe if self.moe_experts \
                else self._stage_fn
            if self.M > 1:
                if B % self.M:
                    raise ValueError(
                        f"batch {B} not divisible by microbatches {self.M}")
                xm = x.reshape(self.M, B // self.M, T, cfg.hidden_size)
                if self.quant8 == "wgrad":
                    # fold the microbatch index into the SR seed so the
                    # M summed wgrads draw independent streams
                    mb_seeds = seed + (jnp.arange(self.M, dtype=jnp.int32)
                                       + 1) * jnp.int32(-1640531527)
                    out = jax.lax.map(
                        lambda t: stage_fn(stage, t[0], t[1]),
                        (xm, mb_seeds))
                else:
                    out = jax.lax.map(partial(stage_fn, stage), xm)
                if self.moe_experts:
                    x, aux_m = out
                    moe_aux = jnp.mean(aux_m)
                else:
                    x = out
                x = x.reshape(B, T, cfg.hidden_size)
            else:
                if self.quant8 == "wgrad":
                    out = stage_fn(stage, x, seed)
                    x, moe_aux = out if self.moe_experts else (out, None)
                elif self.moe_experts:
                    x, moe_aux = stage_fn(stage, x)
                else:
                    x = stage_fn(stage, x)
        else:
            M = self.M
            mb = B // M
            x_micro = x.reshape(M, mb, T, cfg.hidden_size)
            from ..distributed.pipeline import pipeline_forward
            out = pipeline_forward(self._stage_fn, params["blocks"],
                                   x_micro, self.mesh, axis="pipe",
                                   remat=False)
            x = out.reshape(B, T, cfg.hidden_size)
        x = _layer_norm(x, params["ln_f_g"], params["ln_f_b"])
        shape = self.mesh.shape
        # fused vocab-chunked CE when no axis shards the vocab/seq dims:
        # never materializes [B,T,V] logits (ops/fused_ce.py)
        if (shape["model"] == 1 and shape["sep"] == 1
                and cfg.vocab_size % self.ce_chunks == 0):
            from ..ops.fused_ce import fused_softmax_cross_entropy
            # tied head passes wte's native [V, D] layout straight
            # through (vocab_major): the .T would cost a materialized
            # 200MB transpose for dhead in the backward (~7 ms/step,
            # r5 chrome trace bitcast_convert_fusion); untied heads
            # are stored [D, V] and keep the head-major path
            vm = bool(cfg.tie_embeddings)
            head = params["wte"] if vm else params["head"]
            loss = fused_softmax_cross_entropy(x, head.astype(dtype),
                                               labels,
                                               n_chunks=self.ce_chunks,
                                               int8=self.ce_int8,
                                               vocab_major=vm)
        else:
            head = params["wte"].T if cfg.tie_embeddings \
                else params["head"]
            logits = jnp.einsum("btd,dv->btv", x, head.astype(dtype),
                                preferred_element_type=jnp.float32)
            logits = jax.lax.with_sharding_constraint(
                logits, _spec(self.mesh, "data", "sep", "model"))
            lp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(lp, labels[..., None],
                                     axis=-1)[..., 0]
            loss = -jnp.mean(ll)
        if moe_aux is not None:
            # mean over layers, weighted (GShard's l_aux term)
            loss = loss + self.moe_aux_weight * moe_aux / self.Lps
        return loss

    def _loss_and_grads_1f1b(self, params, input_ids, labels):
        """Full loss+grads via the explicit on-device 1F1B schedule:
        embedding fwd/bwd outside the pipe, blocks + loss head inside
        (distributed/pipeline.pipeline_train_1f1b)."""
        from ..distributed.pipeline import pipeline_train_1f1b
        cfg = self.cfg
        B, T = input_ids.shape
        dtype = cfg.dtype
        M = self.M
        mb = B // M

        def embed(ep):
            return self._embed(ep["wte"], ep["wpe"], input_ids)

        emb_p = {"wte": params["wte"], "wpe": params["wpe"]}
        x, embed_vjp = jax.vjp(embed, emb_p)
        x_micro = x.reshape(M, mb, T, cfg.hidden_size)
        labels_micro = labels.reshape(M, mb, T)

        head_p = {"ln_f_g": params["ln_f_g"], "ln_f_b": params["ln_f_b"]}
        if cfg.tie_embeddings:
            head_p["wte"] = params["wte"]
        else:
            head_p["head"] = params["head"]

        def head_loss(hp, y, lab):
            h = _layer_norm(y, hp["ln_f_g"], hp["ln_f_b"])
            hw = hp["wte"].T if cfg.tie_embeddings else hp["head"]
            logits = jnp.einsum("btd,dv->btv", h, hw.astype(h.dtype),
                                preferred_element_type=jnp.float32)
            # same sharding as _forward_loss's head: vocab over 'model'
            logits = jax.lax.with_sharding_constraint(
                logits, _spec(self.mesh, "data", "sep", "model"))
            lp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(lp, lab[..., None], axis=-1)[..., 0]
            return -jnp.mean(ll)

        if self.moe_experts:
            # MoE+PP composition: the explicit schedule carries the
            # balance-loss side channel (normalized per layer to match
            # the non-pipelined objective)
            stage_fn = self._stage_fn_moe
            aux_w = self.moe_aux_weight / cfg.num_layers
        else:
            stage_fn = self._stage_fn
            aux_w = 0.0
        if self.pipeline_schedule == "1f1b":
            loss, gblocks, ghead, dx_micro = pipeline_train_1f1b(
                stage_fn, head_loss, params["blocks"], head_p,
                x_micro, labels_micro, self.mesh, axis="pipe",
                stage_aux_weight=aux_w,
                stage_has_aux=bool(self.moe_experts))
        else:  # "vpp" / "zb": table-driven on-device engine
            from ..distributed.pipeline_scheduled import \
                pipeline_train_scheduled
            sched = self._get_schedule()
            blocks = params["blocks"]
            if self.V == 1:  # engine expects a leading chunk dim
                blocks = jax.tree.map(lambda a: a[None], blocks)
            loss, gblocks, ghead, dx_micro = pipeline_train_scheduled(
                stage_fn, head_loss, blocks, head_p,
                x_micro, labels_micro, self.mesh, sched, axis="pipe",
                stage_aux_weight=aux_w,
                stage_has_aux=bool(self.moe_experts))
            if self.V == 1:
                gblocks = jax.tree.map(lambda a: a[0], gblocks)

        (demb,) = embed_vjp(dx_micro.reshape(B, T, cfg.hidden_size))
        gwte = demb["wte"].astype(jnp.float32)
        if cfg.tie_embeddings:
            gwte = gwte + ghead["wte"]
        grads = {
            "wte": gwte,
            "wpe": demb["wpe"].astype(jnp.float32),
            "ln_f_g": ghead["ln_f_g"],
            "ln_f_b": ghead["ln_f_b"],
            "blocks": gblocks,
        }
        if not cfg.tie_embeddings:
            grads["head"] = ghead["head"]
        return loss, grads

    def _get_schedule(self):
        """Job table for the 'vpp'/'zb' engines (cached; host-side)."""
        if self._sched_cache is None:
            from ..distributed.pipeline_schedules import (
                InterleavedSchedule, ZeroBubbleSchedule)
            if self.pipeline_schedule == "vpp":
                self._sched_cache = InterleavedSchedule(
                    self.S, self.M, num_chunks=self.V)
            else:
                self._sched_cache = ZeroBubbleSchedule(self.S, self.M)
        return self._sched_cache

    # -- optimizer (fused AdamW, sharded like params) ----------------------
    def _adamw(self, params, grads, opt_state):
        b1, b2 = self.betas
        step = opt_state["step"] + 1
        tf = step.astype(jnp.float32)
        # global-norm clip
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, self.grad_clip / (gnorm + 1e-6))
        step_u32 = step.astype(jnp.uint32)

        lr_mult = jnp.float32(1.0) if self.lr_schedule is None \
            else jnp.asarray(self.lr_schedule(tf), jnp.float32)

        def upd(p, g, m, v, key):
            g = g.astype(jnp.float32) * scale
            m2 = b1 * m.astype(jnp.float32) + (1 - b1) * g
            v2 = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
            mhat = m2 / (1 - b1 ** tf)
            vhat = v2 / (1 - b2 ** tf)
            lr_t = self.lr * lr_mult
            p2 = p.astype(jnp.float32) * (1 - lr_t * self.wd) - \
                lr_t * mhat / (jnp.sqrt(vhat) + 1e-8)
            if self._stoch_round:
                p2 = _stochastic_round_bf16(p2, key)
            return (p2, m2.astype(self.moment_dtype),
                    v2.astype(self.moment_dtype))

        use_fused = self.fused_optimizer
        if use_fused:
            from ..ops.fused_adamw import (fused_adamw_update,
                                           fused_adamw_update8,
                                           fused_adamw_eligible)
            b1f, b2f = float(b1), float(b2)
            inv_bc1 = 1.0 / (1.0 - b1f ** tf)
            inv_bc2 = 1.0 / (1.0 - b2f ** tf)

        _is8 = lambda x: isinstance(x, tuple)  # noqa: E731
        flat_p, tdef = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_m = jax.tree.flatten(opt_state["m"], is_leaf=_is8)[0]
        flat_v = jax.tree.flatten(opt_state["v"], is_leaf=_is8)[0]
        new_p, new_m, new_v = [], [], []
        for i, (p, g, m, v) in enumerate(zip(flat_p, flat_g, flat_m,
                                             flat_v)):
            if _is8(m):
                # int8 moment storage: (q, scale) pairs ride the fused
                # kernel's int8 variant (moment8 implies fused+eligible)
                if not use_fused:
                    # e.g. a moment8 checkpoint resumed on a trainer
                    # built without the fused optimizer (CPU debug):
                    # fail with the diagnosis, not an UnboundLocalError
                    raise RuntimeError(
                        "opt_state carries int8 (q, scale) moment "
                        "pairs but this trainer runs without the "
                        "fused optimizer; rebuild with moment8=True "
                        "on a single-device TPU mesh, or dequantize "
                        "the state via ops.fused_adamw.moment8_unpack")
                p2, mq, msc, vq, vsc = fused_adamw_update8(
                    p, g, m[0], m[1], v[0], v[1], scale, inv_bc1,
                    inv_bc2, step.astype(jnp.int32),
                    lr=float(self.lr), wd=float(self.wd),
                    b1=b1f, b2=b2f, eps=1e-8,
                    stoch_round=self._stoch_round, leaf_id=i,
                    lr_scale=lr_mult)
                new_p.append(p2)
                new_m.append((mq, msc))
                new_v.append((vq, vsc))
                continue
            if use_fused and fused_adamw_eligible(p):
                p2, m2, v2 = fused_adamw_update(
                    p, g, m, v, scale, inv_bc1, inv_bc2,
                    step.astype(jnp.int32),
                    lr=float(self.lr), wd=float(self.wd),
                    b1=b1f, b2=b2f, eps=1e-8,
                    stoch_round=self._stoch_round, leaf_id=i,
                    lr_scale=lr_mult)
                new_p.append(p2)
                new_m.append(m2.astype(self.moment_dtype))
                new_v.append(v2.astype(self.moment_dtype))
                continue
            # rbg keys are cheap to build and the generator is ~10x
            # faster than threefry on TPU (SR needs 16 bits/param/step)
            key = jnp.array([0x5eed, 0xbeef, i, 0], jnp.uint32) \
                .at[3].set(step_u32) if self._stoch_round else None
            p2, m2, v2 = upd(p, g, m, v, key)
            new_p.append(p2)
            new_m.append(m2)
            new_v.append(v2)
        return (jax.tree.unflatten(tdef, new_p),
                {"step": step, "m": jax.tree.unflatten(tdef, new_m),
                 "v": jax.tree.unflatten(tdef, new_v)})

    # -- public step -------------------------------------------------------
    def build_step(self):
        if self._step_fn is not None:
            return self._step_fn

        def step(params, opt_state, input_ids, labels):
            # per-step SR seed for wgrad quantization; int32 multiply
            # wraps, which only mixes the stream (never collapses it
            # the way f32 rounding of big bases would)
            sr_seed = (opt_state["step"].astype(jnp.int32) + 1) \
                * jnp.int32(40503) if self.quant8 == "wgrad" else None
            if self.S > 1 and self.pipeline_schedule in ("1f1b", "vpp",
                                                         "zb"):
                cparams = params if self._stoch_round else jax.tree.map(
                    lambda p: p.astype(self.cfg.dtype), params) \
                    if self.mixed_precision else params
                loss, grads = self._loss_and_grads_1f1b(
                    cparams, input_ids, labels)
            elif self._stoch_round:
                # bf16 masters ARE the compute params — no cast, no
                # second weight copy in HBM
                loss, grads = jax.value_and_grad(self._forward_loss)(
                    params, input_ids, labels, sr_seed)
            elif self.mixed_precision:
                # cast masters -> compute dtype OUTSIDE the diff'd fn so
                # grads materialize at cfg.dtype (AMP-O2 master-weight
                # semantics; halves grad HBM)
                cparams = jax.tree.map(
                    lambda p: p.astype(self.cfg.dtype), params)
                loss, grads = jax.value_and_grad(self._forward_loss)(
                    cparams, input_ids, labels, sr_seed)
            else:
                loss, grads = jax.value_and_grad(self._forward_loss)(
                    params, input_ids, labels, sr_seed)
            params, opt_state = self._adamw(params, grads, opt_state)
            return params, opt_state, loss

        data_spec = _spec(self.mesh, ("data",), None)
        # the program and its cache key are jax.jit(step)'s own; the
        # wrapper only reports the call that compiled (or loaded) it
        self._step_fn = Watched(jax.jit(
            step, donate_argnums=(0, 1),
            in_shardings=(None, None, data_spec, data_spec)),
            kind="train_step")
        return self._step_fn

    def _build_guard(self):
        """Jitted drift probe: relative error of the int8 dgrad (and,
        in wgrad mode, the SR int8 wgrad) on layer 0's qkv matmul with
        the CURRENT weights — ~1% of a step. The 500-step parity runs
        end with wqkv SNR ~1, so the int8 default is watched, not
        assumed (the rounds-1-5 notes (git history before PR 23))."""
        from ..ops.quant_matmul import (quantize_rowwise_fast,
                                        sr_quantize_colwise)
        wgrad_mode = self.quant8 == "wgrad"

        def probe(params, input_ids, seed):
            x = self._embed(params["wte"], params["wpe"], input_ids)
            bp = params["blocks"]["layer_000"] if self._unroll_full \
                else jax.tree.map(lambda a: a[0, 0], params["blocks"])
            h = _layer_norm(x, bp["ln1_g"], bp["ln1_b"])
            w = bp["wqkv"].astype(h.dtype)
            key = jax.random.PRNGKey(seed.astype(jnp.uint32))
            g = jax.random.normal(
                key, h.shape[:-1] + (w.shape[1],)).astype(h.dtype)
            dx_e = jax.lax.dot_general(
                g, w, (((g.ndim - 1,), (1,)), ((), ()))) \
                .astype(jnp.float32)
            gq, gs = quantize_rowwise_fast(g, axis=-1)
            wq, ws = quantize_rowwise_fast(w, axis=1)
            y = jax.lax.dot_general(
                gq, wq, (((g.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
            dx_i = (y.astype(jnp.float32) * gs *
                    jnp.reshape(ws, (1,) * (g.ndim - 1) + (-1,)))
            rel = jnp.linalg.norm(dx_i - dx_e) / \
                (jnp.linalg.norm(dx_e) + 1e-30)
            if wgrad_mode:
                D = h.shape[-1]
                N = w.shape[1]
                h2 = h.reshape(-1, D)
                g2 = g.reshape(-1, N)
                dw_e = jax.lax.dot_general(
                    h2, g2, (((0,), (0,)), ((), ()))) \
                    .astype(jnp.float32)
                si = seed.astype(jnp.int32)
                xq, xs = sr_quantize_colwise(h2, si)
                gq2, gs2 = sr_quantize_colwise(g2, si + 1)
                dwi = jax.lax.dot_general(
                    xq, gq2, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                dw_i = dwi.astype(jnp.float32) * \
                    xs.reshape(D, 1) * gs2
                relw = jnp.linalg.norm(dw_i - dw_e) / \
                    (jnp.linalg.norm(dw_e) + 1e-30)
                rel = jnp.maximum(rel, relw)
            return rel

        return jax.jit(probe)

    def _run_guard(self, input_ids):
        """Measure drift; fall back one int8 tier if it exceeds the
        threshold (wgrad -> dgrad -> exact bf16). Returns the measured
        relative error."""
        if self._guard_fn is None:
            self._guard_fn = self._build_guard()
        seed = self.opt_state["step"].astype(jnp.float32)
        r = float(jax.device_get(
            self._guard_fn(self.params, input_ids, seed)))
        if r > self.int8_guard_threshold:
            ladder = {"wgrad": "dgrad", "dgrad": False, True: False}
            nxt = ladder.get(self.quant8, False)
            self._guard_events.append(
                {"step": int(jax.device_get(self.opt_state["step"])),
                 "rel_err": r, "from": self.quant8, "to": nxt})
            self.quant8 = nxt
            self._step_fn = None   # recompile without the drifted tier
            self._guard_fn = None
        return r

    def guard_events(self):
        """Drift-guard fallback log: [{step, rel_err, from, to}]."""
        return list(self._guard_events)

    def train_step(self, input_ids, labels) -> float:
        fn = self.build_step()
        if isinstance(input_ids, Tensor):
            input_ids = input_ids._data
        if isinstance(labels, Tensor):
            labels = labels._data
        # the HOST's side of the step: placing the batch and enqueuing
        # the program, which returns before the device has finished
        with span("train.step", tokens=int(np.prod(labels.shape))), \
                jax.set_mesh(self.mesh):
            if self.quant8 and self.int8_guard_period and \
                    self._host_step % self.int8_guard_period == 0:
                self._run_guard(jnp.asarray(input_ids))
                fn = self.build_step()  # guard may have recompiled
            self.params, self.opt_state, loss = fn(
                self.params, self.opt_state, input_ids, labels)
        self._host_step += 1
        return loss

    def n_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(self.params))


def _stochastic_round_bf16(x_f32, key):
    """Unbiased fp32 -> bf16 rounding: bf16 is the top 16 bits of f32,
    so adding uniform-[0, 2^16) bits to the f32 representation and
    truncating rounds up with probability exactly equal to the dropped
    fraction (exact stochastic rounding, no special-casing of ulp).

    ``key``: uint32[4] rbg key (hardware bit generator; threefry costs
    ~2x the whole AdamW update at 1.3B params)."""
    bits = jax.lax.bitcast_convert_type(x_f32, jnp.uint32)
    _, r32 = jax.lax.rng_bit_generator(
        key, x_f32.shape, jnp.uint32,
        algorithm=jax.lax.RandomAlgorithm.RNG_DEFAULT)
    y = bits + (r32 & jnp.uint32(0xFFFF))
    # inf/nan inputs: the add could wrap the exponent; keep them verbatim
    y = jnp.where(jnp.isfinite(x_f32), y, bits)
    return jax.lax.bitcast_convert_type(
        (y >> 16).astype(jnp.uint16), jnp.bfloat16)


def _layer_norm(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    v = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - m) * jax.lax.rsqrt(v + eps)
    return (out * g + b).astype(x.dtype)
