"""Llama model family (RMSNorm + RoPE + SwiGLU decoder).

Reference shape: the reference's end-to-end auto-parallel parity test is
a Llama (test/auto_parallel/hybrid_strategy/semi_auto_llama.py:98 —
full model under DPxMPxPP configs with acc-align and save/load). Built
from this framework's layers so it runs eagerly, under jit.to_static,
under dist.to_static/DistModel, and with the fleet TP layer library when
``use_tp`` — mirroring the GPT family's two-path design.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import apply_op
from ._decode_cache import (CacheSpec, cache_attend, check_cache_pos,
                            paged_cache_attend)
from ..nn import functional as F
from ..nn.layer_base import Layer
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "llama_tiny_config", "tp_param_spec"]


# raw_state() param names shardable along their OUTPUT (non-contracted)
# dim under tensor-parallel serving. Output-dim-only sharding is the
# deliberate TP slice that keeps sharded decode provably BITWISE
# token-identical to the single-chip engine: each shard computes full
# contractions over identical operands, collectives are pure data
# movement (all-gather), and no psum ever re-associates a float sum.
# gate/up_proj stay replicated — splitting them would shard
# down_proj's contraction dim and turn it into a partial-sum psum
# (serving/mesh.py, docs/SERVING.md "Multi-chip serving").
_TP_OUT_DIM_PARAMS = ("q_proj.weight", "k_proj.weight", "v_proj.weight",
                      "o_proj.weight", "down_proj.weight",
                      "lm_head.weight")


def tp_param_spec(name: str, shape, tp: int, axis: str = "model"):
    """PartitionSpec for one ``raw_state()`` param under the serving
    engine's tensor-parallel mesh, or None for replicated. Params a
    rule does not cover (norms, embeddings, gate/up_proj, quantized
    weights with their own names) replicate — always correct, just
    unsharded."""
    from jax.sharding import PartitionSpec
    if tp > 1 and name.endswith(_TP_OUT_DIM_PARAMS) \
            and len(shape) == 2 and shape[-1] % tp == 0:
        return PartitionSpec(None, axis)
    return None


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None  # GQA; None = MHA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self):
        return self.num_key_value_heads or self.num_attention_heads


def llama_tiny_config(**kw) -> LlamaConfig:
    kw.setdefault("vocab_size", 128)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("max_position_embeddings", 64)
    return LlamaConfig(**kw)


def _rope_cache(head_dim: int, max_len: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                           / head_dim))
    t = np.arange(max_len, dtype=np.float32)
    freqs = np.outer(t, inv)                      # [T, D/2]
    return np.cos(freqs), np.sin(freqs)


def _apply_rope(x, cos, sin):
    """x [B, T, H, D]; rotate pairs (x0,x1) per RoPE.

    cos/sin are [T, D/2] (shared positions) or [B, T, D/2] (per-row
    positions — the serving engine's decode)."""
    d2 = x.shape[-1] // 2
    x1 = x[..., :d2]
    x2 = x[..., d2:]
    if cos.ndim == 3:
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    else:
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


class LlamaAttention(Layer):
    def __init__(self, cfg: LlamaConfig, use_tp: bool = False,
                 rope_cache=None):
        super().__init__()
        self.cfg = cfg
        H, KV, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        if use_tp:
            from ..distributed.fleet.mp_layers import (
                ColumnParallelLinear, RowParallelLinear)
            self.q_proj = ColumnParallelLinear(cfg.hidden_size, H * D,
                                               gather_output=False,
                                               has_bias=False)
            self.k_proj = ColumnParallelLinear(cfg.hidden_size, KV * D,
                                               gather_output=False,
                                               has_bias=False)
            self.v_proj = ColumnParallelLinear(cfg.hidden_size, KV * D,
                                               gather_output=False,
                                               has_bias=False)
            self.o_proj = RowParallelLinear(H * D, cfg.hidden_size,
                                            input_is_parallel=True,
                                            has_bias=False)
        else:
            self.q_proj = Linear(cfg.hidden_size, H * D, bias_attr=False)
            self.k_proj = Linear(cfg.hidden_size, KV * D,
                                 bias_attr=False)
            self.v_proj = Linear(cfg.hidden_size, KV * D,
                                 bias_attr=False)
            self.o_proj = Linear(H * D, cfg.hidden_size, bias_attr=False)
        if rope_cache is None:  # standalone use; model shares one cache
            cos, sin = _rope_cache(D, cfg.max_position_embeddings,
                                   cfg.rope_theta)
            rope_cache = (jnp.asarray(cos), jnp.asarray(sin))
        self._cos, self._sin = rope_cache

    def forward(self, x, attn_mask=None, cache=None):
        """cache: optional (k_cache, v_cache) Tensors [B, T_past, KV, D];
        when given, ``x`` holds only the NEW tokens and the return is
        (out, (k_cache', v_cache')) — the serving decode path."""
        cfg = self.cfg
        b, t, _ = x.shape
        # cache flavors: len 3 = fixed static buffers (k, v, pos);
        # len 6 = paged pool (k_pool, v_pool, k_scale, v_scale,
        # page_table, pos) — paddle_tpu/serving's paged KV cache;
        # len 4 / len 7 append a per-row write-length `wlen` — the
        # speculative k-token VERIFY flavor (only the first wlen[b]
        # incoming tokens of row b write their k/v)
        static_cache = cache is not None and len(cache) in (3, 4, 6, 7)
        past = cache[0].shape[1] if cache is not None \
            and not static_cache and cache[0] is not None else 0
        if past + t > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {past + t} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings}")
        D = cfg.head_dim
        q = self.q_proj(x)
        k = self.k_proj(x)
        v = self.v_proj(x)
        h_local = q.shape[-1] // D
        kv_local = k.shape[-1] // D
        q = q.reshape([b, t, h_local, D])
        k = k.reshape([b, t, kv_local, D])
        v = v.reshape([b, t, kv_local, D])
        if static_cache:
            # STATIC cache: (k_cache, v_cache, pos) with fixed [B, Tmax]
            # buffers and a (possibly traced) write position — the
            # compile-once serving decode path (one program per step
            # instead of a shape-changing concat per token).
            if attn_mask is not None:
                raise NotImplementedError(
                    "attn_mask with KV cache is not supported; pad-free "
                    "batches only in cached decoding")
            return self._forward_static_cache(x, q, k, v, cache)
        cos, sin = self._cos[past:past + t], self._sin[past:past + t]
        q = apply_op(lambda a: _apply_rope(a, cos, sin), q,
                     _op_name="rope_q")
        k = apply_op(lambda a: _apply_rope(a, cos, sin), k,
                     _op_name="rope_k")
        if cache is not None:
            if cache[0] is not None:  # (None, None) = empty prefill cache
                from ..ops.manipulation import concat
                k = concat([cache[0], k], axis=1)
                v = concat([cache[1], v], axis=1)
            new_cache = (k, v)
        if kv_local != h_local:  # GQA: repeat kv heads
            rep = h_local // kv_local
            k = apply_op(lambda a: jnp.repeat(a, rep, axis=2), k,
                         _op_name="gqa_repeat_k")
            v = apply_op(lambda a: jnp.repeat(a, rep, axis=2), v,
                         _op_name="gqa_repeat_v")
        if cache is not None:
            if attn_mask is not None:
                raise NotImplementedError(
                    "attn_mask with KV cache is not supported; pad-free "
                    "batches only in cached decoding")
            # decoding: new queries attend all cached positions plus the
            # causal prefix of the new block (the XLA sdpa bottom-right-
            # aligns the triangle when Sq < Skv)
            attn = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, training=self.training)
            attn = attn.reshape([b, t, h_local * D])
            return self.o_proj(attn), new_cache
        if attn_mask is not None:
            # combine with causality: a decoder NEVER attends forward,
            # mask or not (a padding mask must not disable the triangle)
            causal = apply_op(
                lambda m: jnp.logical_and(
                    m.astype(bool),
                    jnp.tril(jnp.ones((t, t), bool))[None, None]),
                attn_mask, _op_name="causal_and_mask")
            attn = F.scaled_dot_product_attention(
                q, k, v, attn_mask=causal, training=self.training)
        else:
            attn = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, training=self.training)
        attn = attn.reshape([b, t, h_local * D])
        return self.o_proj(attn)


    def _forward_static_cache(self, x, q, k, v, cache):
        """Fixed-size cache attention: write the new k/v block at ``pos``
        (dynamic_update_slice), attend over the masked full buffer.
        q/k/v arrive reshaped [b, t, heads_local, D]; cache =
        (k_cache [b, Tmax, KV, D], v_cache, pos). ``pos`` is a scalar
        (whole batch at one position — generate()); a [b] vector of
        per-row positions (every row independent — the continuous-
        batching engine, paddle_tpu/serving) goes with pages, or with
        a ``wlen``.

        The 6-tuple flavor routes through paged_cache_attend instead:
        (k_pool, v_pool, k_scale, v_scale, page_table, pos) with
        [num_pages, page, KV, D] pools and a [b, pages_per_seq] int32
        table per row (scales None = model-dtype pages, set = int8
        pages with per-page f32 scales).

        The 4-tuple (k, v, pos, wlen) and 7-tuple (... pos, wlen)
        flavors are the speculative VERIFY forms: per-row [b] write
        lengths gate which of the t incoming tokens write their k/v
        (rejected-draft positions never touch the pools)."""
        t = q.shape[1]
        paged = len(cache) in (6, 7)
        wlen = None
        if paged:
            if len(cache) == 7:
                kp, vp, ksc, vsc, table, pos, wlen = cache
            else:
                kp, vp, ksc, vsc, table, pos = cache
            # t=1: only the START position must be in range — the
            # extend prefill's bucket padding may overshoot the table
            # and is redirected into the trash page by the attend
            per_row = check_cache_pos(
                pos, 1, table.shape[1] * kp.shape[1])
        else:
            if len(cache) == 4:
                k_cache, v_cache, pos, wlen = cache
            else:
                k_cache, v_cache, pos = cache
            # verify flavor: writes past the buffer are index-dropped
            # (cache_attend wlen scatter), so only the START position
            # must be in range, like the paged flavor
            per_row = check_cache_pos(
                pos, 1 if wlen is not None else t, k_cache.shape[1])
        cos_full, sin_full = self._cos, self._sin
        out_dtype = getattr(x, "_data", x).dtype   # the MODEL dtype

        def _rope(q, k, p):
            if wlen is not None:
                # verify / chunked prefill: p + t may run past the rope
                # table for rows near their length cap — a clamped
                # SLICE start would mis-rotate the real leading tokens,
                # so gather per POSITION with a clip that only touches
                # the masked tail (same fix as the paged extend path
                # below). p is [b] (verify) or a scalar (chunk flavor).
                pb = p[:, None] if getattr(p, "ndim", 0) >= 1 else p
                idx = jnp.clip(
                    pb + jnp.arange(t, dtype=jnp.int32)[None],
                    0, cos_full.shape[0] - 1)
                cos, sin = cos_full[idx], sin_full[idx]    # [b, t, D/2]
            elif per_row:
                sl = lambda tbl, pi: jax.lax.dynamic_slice_in_dim(
                    tbl, pi, t)
                cos = jax.vmap(partial(sl, cos_full))(p)   # [b, t, D/2]
                sin = jax.vmap(partial(sl, sin_full))(p)
            elif paged:
                # per-POSITION gather, not dynamic_slice: the paged
                # extend prefill's bucket padding may run p + t past
                # the rope table, and a clamped SLICE start would
                # silently shift the rotation of the real tail tokens.
                # Gathering clamps only the padding rows (whose writes
                # are trash-redirected / overwritten before any read).
                idx = jnp.clip(p + jnp.arange(t, dtype=jnp.int32),
                               0, cos_full.shape[0] - 1)
                cos, sin = cos_full[idx], sin_full[idx]
            else:
                cos = jax.lax.dynamic_slice_in_dim(cos_full, p, t)
                sin = jax.lax.dynamic_slice_in_dim(sin_full, p, t)
            return _apply_rope(q, cos, sin), _apply_rope(k, cos, sin)

        has_wl = wlen is not None
        if paged:
            def f(q, k, v, kp, vp, table, p, *rest):
                p = jnp.asarray(p, jnp.int32)
                if has_wl:
                    wl, rest = jnp.asarray(rest[0], jnp.int32), rest[1:]
                else:
                    wl = None
                qr, kr = _rope(q, k, p)
                ks, vs = rest if rest else (None, None)
                out, kp2, vp2, ks2, vs2 = paged_cache_attend(
                    qr, kr, v, kp, vp, ks, vs, table, p,
                    jnp.dtype(out_dtype), wlen=wl)
                return (out, kp2, vp2, ks2, vs2) if rest \
                    else (out, kp2, vp2)

            args = (q, k, v, kp, vp, table, pos) \
                + ((wlen,) if has_wl else ()) \
                + ((ksc, vsc) if ksc is not None else ())
            res = apply_op(f, *args, _op_name="paged_cache_attn")
            if ksc is not None:
                out, kp2, vp2, ks2, vs2 = res
            else:
                out, kp2, vp2 = res
                ks2, vs2 = None, None
            return self.o_proj(out), (kp2, vp2, ks2, vs2, table,
                                      pos + t)

        def f(q, k, v, kc, vc, p, *rest):
            p = jnp.asarray(p, jnp.int32)
            wl = jnp.asarray(rest[0], jnp.int32) if rest else None
            qr, kr = _rope(q, k, p)
            return cache_attend(qr, kr, v, kc, vc, p, wlen=wl)

        args = (q, k, v, k_cache, v_cache, pos) \
            + ((wlen,) if has_wl else ())
        out, kc2, vc2 = apply_op(f, *args,
                                 _op_name="static_cache_attn")
        return self.o_proj(out), (kc2, vc2, pos + t)


class LlamaMLP(Layer):
    def __init__(self, cfg: LlamaConfig, use_tp: bool = False):
        super().__init__()
        if use_tp:
            from ..distributed.fleet.mp_layers import (
                ColumnParallelLinear, RowParallelLinear)
            self.gate_proj = ColumnParallelLinear(
                cfg.hidden_size, cfg.intermediate_size,
                gather_output=False, has_bias=False)
            self.up_proj = ColumnParallelLinear(
                cfg.hidden_size, cfg.intermediate_size,
                gather_output=False, has_bias=False)
            self.down_proj = RowParallelLinear(
                cfg.intermediate_size, cfg.hidden_size,
                input_is_parallel=True, has_bias=False)
        else:
            self.gate_proj = Linear(cfg.hidden_size,
                                    cfg.intermediate_size,
                                    bias_attr=False)
            self.up_proj = Linear(cfg.hidden_size, cfg.intermediate_size,
                                  bias_attr=False)
            self.down_proj = Linear(cfg.intermediate_size,
                                    cfg.hidden_size, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) *
                              self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, cfg: LlamaConfig, use_tp: bool = False,
                 rope_cache=None):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg, use_tp, rope_cache)
        self.post_attention_layernorm = RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg, use_tp)

    def forward(self, x, attn_mask=None, cache=None):
        if cache is not None:
            a, new_cache = self.self_attn(self.input_layernorm(x),
                                          attn_mask, cache)
            x = x + a
            return x + self.mlp(self.post_attention_layernorm(x)), \
                new_cache
        x = x + self.self_attn(self.input_layernorm(x), attn_mask)
        return x + self.mlp(self.post_attention_layernorm(x))


class LlamaModel(Layer):
    def __init__(self, cfg: LlamaConfig, use_tp: bool = False):
        super().__init__()
        self.config = cfg
        if use_tp:
            from ..distributed.fleet.mp_layers import (
                VocabParallelEmbedding)
            self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                       cfg.hidden_size)
        else:
            self.embed_tokens = Embedding(cfg.vocab_size,
                                          cfg.hidden_size)
        cos, sin = _rope_cache(cfg.head_dim,
                               cfg.max_position_embeddings,
                               cfg.rope_theta)
        rope_cache = (jnp.asarray(cos), jnp.asarray(sin))
        self.layers = LayerList(
            [LlamaDecoderLayer(cfg, use_tp, rope_cache)
             for _ in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, caches=None):
        x = self.embed_tokens(input_ids)
        if caches is not None:
            new_caches = []
            for layer, c in zip(self.layers, caches):
                x, nc = layer(x, attn_mask, c)
                new_caches.append(nc)
            return self.norm(x), new_caches
        for layer in self.layers:
            x = layer(x, attn_mask)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, cfg: LlamaConfig, use_tp: bool = False):
        super().__init__()
        self.config = cfg
        self.llama = LlamaModel(cfg, use_tp)
        if not cfg.tie_word_embeddings:
            self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                  bias_attr=False)

    def forward(self, input_ids, attn_mask=None):
        return self._head(self.llama(input_ids, attn_mask))

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        return F.cross_entropy(
            logits.reshape([-1, self.config.vocab_size]),
            labels.reshape([-1]))

    def _head(self, h):
        if self.config.tie_word_embeddings:
            from ..ops.linalg import matmul
            return matmul(h, self.llama.embed_tokens.weight,
                          transpose_y=True)
        return self.lm_head(h)

    # -- what the serving engine asks of a model -------------------------
    tp_param_spec = staticmethod(tp_param_spec)

    def cached_forward(self, ids, caches):
        return self.llama(ids, None, caches)

    def cache_spec(self) -> CacheSpec:
        cfg = self.config
        # k_proj may be a Linear (weight [in, out]) or a weight-only
        # Int8Linear (wq [in, out] int8) after quantization
        kp = self.llama.layers[0].self_attn.k_proj
        kw = kp.weight if hasattr(kp, "weight") else kp.wq
        return CacheSpec(
            layers=("kv",) * len(self.llama.layers),
            kv_heads=kw.shape[-1] // cfg.head_dim, head_dim=cfg.head_dim,
            dtype=self.llama.embed_tokens.weight._data.dtype,
            max_positions=cfg.max_position_embeddings)

    def generate(self, input_ids, max_new_tokens: int = 16,
                 temperature: float = 0.0, top_p: float = 1.0,
                 use_cache="static"):
        """Greedy / nucleus decoding.

        use_cache:
          - True / "static" (default): compile-once serving path — one
            jitted prefill program + one jitted decode-step program over
            fixed-size KV buffers written at the current position.
          - "dynamic": concat-grown KV cache, one trace per length
            (numerics reference; also used automatically under tracing).
          - False: no cache, full-context recompute per token.
        """
        import paddle_tpu as paddle
        from ..ops.manipulation import concat
        ids = input_ids

        def pick(last):
            if temperature <= 0:
                return apply_op(
                    lambda a: jnp.argmax(a, axis=-1).astype(jnp.int64)[
                        :, None], last, _op_name="greedy")
            probs = F.softmax(last / temperature, axis=-1)
            ps = paddle.full([ids.shape[0]], top_p, dtype="float32")
            return paddle.top_p_sampling(probs, ps)[1]

        if max_new_tokens <= 0:
            return ids
        if not use_cache:
            for _ in range(max_new_tokens):
                nxt = pick(self(ids)[:, -1])
                ids = concat([ids, nxt], axis=1)
            return ids

        if use_cache != "dynamic" and not isinstance(
                ids._data, jax.core.Tracer):
            return self._generate_static(ids, max_new_tokens, pick,
                                         greedy=temperature <= 0)

        # dynamic-cache path (shape grows per step; kept for tracing and
        # as the numerics reference): (None, None) makes each layer seed
        # its cache with ITS local k/v (correct head count and dtype
        # under tensor parallelism too)
        h, caches = self.llama(
            ids, caches=[(None, None)] * len(self.llama.layers))
        nxt = pick(self._head(h[:, -1:])[:, -1])
        ids = concat([ids, nxt], axis=1)
        for _ in range(max_new_tokens - 1):
            h, caches = self.llama(nxt, caches=caches)
            nxt = pick(self._head(h[:, -1:])[:, -1])
            ids = concat([ids, nxt], axis=1)
        return ids

    # -- compile-once serving decode --------------------------------------
    def _cached_step(self, params, buffers, tok_arr, ks, vs, pos):
        """One static-cache model step (shared by the per-step and the
        fused decode programs): tokens in, last-token logits + updated
        fixed-size caches out."""
        from ..framework.tensor import Tensor as _T
        caches = [(_T(k), _T(v), _T(pos)) for k, v in zip(ks, vs)]
        with self.bind_state(params, buffers):
            h, new_caches = self.llama(_T(tok_arr), None, caches)
            logits = self._head(h[:, -1:])
        return (logits._data[:, -1],
                [c[0]._data for c in new_caches],
                [c[1]._data for c in new_caches])

    def _decode_pure(self):
        """One jitted program covering prefill (t=prompt) and decode
        (t=1): runs the static-cache path and returns last-token logits
        plus the updated fixed-size caches (donated)."""
        if getattr(self, "_decode_jit", None) is not None:
            return self._decode_jit

        def pure(params, buffers, ids_arr, ks, vs, pos):
            return self._cached_step(params, buffers, ids_arr, ks, vs,
                                     jnp.asarray(pos))

        self._decode_jit = jax.jit(pure, donate_argnums=(3, 4))
        return self._decode_jit

    def _decode_fused_greedy(self):
        """Prefill + the ENTIRE greedy decode loop as ONE jitted program
        (lax.scan over decode steps). The per-step host loop dispatches
        3 programs/token, which made bs=1 decode dispatch-bound; fused,
        a whole generate() is a single dispatch. ``steps`` is a static arg, so
        jax's own compile cache keys on it."""
        fn = getattr(self, "_decode_fused_jit", None)
        if fn is not None:
            return fn

        def greedy(logits, dtype):
            return jnp.argmax(logits, axis=-1).astype(dtype)[:, None]

        def pure(params, buffers, ids_arr, ks, vs, steps):
            T0 = ids_arr.shape[1]
            last, ks, vs = self._cached_step(params, buffers, ids_arr,
                                             ks, vs, jnp.asarray(0))
            first = greedy(last, ids_arr.dtype)

            def body(carry, _):
                tok, ks, vs, pos = carry
                last, ks, vs = self._cached_step(params, buffers, tok,
                                                 ks, vs, pos)
                nxt = greedy(last, ids_arr.dtype)
                return (nxt, ks, vs, pos + 1), nxt[:, 0]

            _, toks = jax.lax.scan(
                body, (first, ks, vs, jnp.asarray(T0)), None,
                length=steps - 1)
            # [prompt | first generated token | scan-emitted tokens]
            return jnp.concatenate([ids_arr, first, toks.T], axis=1)

        fn = jax.jit(pure, donate_argnums=(3, 4), static_argnums=(5,))
        self._decode_fused_jit = fn
        return fn

    def _generate_static(self, ids, max_new_tokens, pick, greedy=False):
        from ..ops.manipulation import concat
        import paddle_tpu as paddle
        cfg = self.config
        B, T0 = ids.shape
        L = len(self.llama.layers)
        D = cfg.head_dim
        attn0 = self.llama.layers[0].self_attn
        # k_proj may be a Linear (weight [in, out]) or a weight-only
        # Int8Linear (wq [in, out] int8) after quantization
        kp = attn0.k_proj
        kw = kp.weight if hasattr(kp, "weight") else kp.wq
        kv_local = kw.shape[-1] // D
        dtype = self.llama.embed_tokens.weight._data.dtype
        # round the buffer up so nearby generation lengths share programs
        want = T0 + max_new_tokens
        max_len = min(cfg.max_position_embeddings,
                      ((want + 63) // 64) * 64)
        if want > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {want} exceeds "
                f"max_position_embeddings={cfg.max_position_embeddings}")
        params, buffers = self.raw_state()
        ks = [jnp.zeros((B, max_len, kv_local, D), dtype)
              for _ in range(L)]
        vs = [jnp.zeros((B, max_len, kv_local, D), dtype)
              for _ in range(L)]
        from ..framework.tensor import Tensor as _T
        if greedy:
            fused = self._decode_fused_greedy()
            return _T(fused(params, buffers, ids._data, ks, vs,
                            max_new_tokens))
        fn = self._decode_pure()
        last, ks, vs = fn(params, buffers, ids._data, ks, vs, 0)
        nxt = pick(_T(last))
        ids = concat([ids, nxt], axis=1)
        pos = T0
        for _ in range(max_new_tokens - 1):
            last, ks, vs = fn(params, buffers, nxt._data, ks, vs, pos)
            nxt = pick(_T(last))
            ids = concat([ids, nxt], axis=1)
            pos += 1
        return ids
