"""Brumby: a Qwen3-shaped decoder whose every attention is a degree-2
power-retention layer (Manifest AI, arXiv:2507.04239; the equations and
the three forms are ``ops/power_retention.py``'s).

A layer (x ``[T, hidden]``; a = query head, h = a // rep its KV head):

    n  = rmsnorm(x)
    q  = rope(rmsnorm_head(n Wq))      k = rope(rmsnorm_head(n Wk))
    v  = n Wv                          lg = log_sigmoid(n Wg)   (float32)
    x' = x + retention(q, k, v, lg) Wo
    x''= x' + mlp(rmsnorm(x'))

It shares ``LlamaMLP``, the RMS norm, the half-split rope and the head
with ``models/llama.py``. There is no K/V to cache: a slot's whole past
is a float32 state ``S [kv_heads, P, D]`` (in the kernel's layout) and a
normaliser ``z [kv_heads, P]`` a layer, whatever its length, which
``cache_spec`` states and the serving engine holds. The cache flavors a
layer's ``forward`` takes:

- ``None``: the whole sequence, chunked (no state returned);
- ``(None, None, true_len)``: a prefill of one bucket: the ``A`` form for
  the outputs and one state build at its end, positions from
  ``true_len`` on being padding that leaves the state alone; returns the
  new ``(S, z)``;
- ``(S, z, pos, active)``: one token a slot from the slots' states, at
  per-slot positions; slots that are not ``active`` keep their state.

Precision. The feature map squares a product and the output divides by
a sum of such squares, so where the recent scores are all small a layer
amplifies the error of what it is given, and the next layer amplifies
that: on seeded weights (gates centred on 0.5, a memory a few tokens
long) a bfloat16 model read 4.5 times a softmax decoder's logit error
against the float32 reference at 4 layers, and with only the retention
branch made exact still 2.5 times at 8, growing with the depth (PERF.md,
PR 31). So in a bfloat16 model the weights are bfloat16 and the
arithmetic is float32: the residual stream, the norms, the retention
branch, the MLP and the head (bfloat16 logits are a sixtieth of their
standard deviation apart at the top), every product with a weight by
``_split_matmul`` (exact in the bfloat16 weight, 16 bits of the
activation). A decode step is bound by the bytes of weights and state,
which are unchanged; a prefill's matrix products cost twice. In a
float32 model all of it is plain float32.

Not in the catalog's ``config.json`` and therefore assumed (the
benchmark's configuration file lists them): degree 2; the gate
projection ``Wg`` of width ``kv_heads`` with a log-sigmoid; the
sum-of-weights normaliser with epsilon 1e-6; the scale
1/sqrt(head_dim); Qwen3's q/k head norms and rope kept; state and
normaliser held in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.tensor import apply_op
from ..nn.layer_base import Layer
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm
from ..ops import power_retention as pr
from ..ops.grouped_matmul import pieces
from ._decode_cache import CacheSpec
from .llama import LlamaConfig, LlamaMLP, _apply_rope, _rope_cache

__all__ = ["BrumbyModel", "BrumbyForCausalLM", "PowerRetention"]

F32 = jnp.float32


# workspace sizes, not part of the mathematics: rows of the A form
# computed at once in a prefill, keys whose phi is held at once in its
# state build, and the whole-sequence forward's chunk
PREFILL_ROWS = 512
STATE_CHUNK = 256
SEQUENCE_CHUNK = 128


def _split_matmul(x, w, whole: bool = False):
    """``x [..., C]`` float32 times ``w [C, N]`` in float32. A bfloat16
    ``w`` is exact in float32, and the high and the middle eight bits of
    ``x``'s significand are two bfloat16 numbers, so two bfloat16
    products with float32 accumulation give the product to 2^-16 of
    ``x`` without ever holding ``w`` in float32 (a highest-precision
    float32 product would upcast it, and take six passes). The pieces
    are cut with a bit mask, not by rounding to bfloat16 and back: XLA
    may elide such a round trip where excess precision is allowed.
    ``whole``: three pieces, all of ``x``: for a product whose result is
    rounded for storage, where 2^-16 would move it across a rounding
    boundary."""
    if w.dtype != jnp.bfloat16:
        return jnp.matmul(x, w.astype(F32),
                          precision=jax.lax.Precision.HIGHEST)
    out = jnp.einsum("p...c,cn->p...n", pieces(x, whole=whole), w,
                     preferred_element_type=F32)
    return out[0] + out[1] + out[2] if whole else out[0] + out[1]


def _head_norm(x, w, eps):
    """RMS norm over the head size, in float32."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


class PowerRetention(Layer):
    def __init__(self, cfg: LlamaConfig, rope_cache=None):
        super().__init__()
        self.cfg = cfg
        H, KV, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        self.q_proj = Linear(cfg.hidden_size, H * D, bias_attr=False)
        self.k_proj = Linear(cfg.hidden_size, KV * D, bias_attr=False)
        self.v_proj = Linear(cfg.hidden_size, KV * D, bias_attr=False)
        self.g_proj = Linear(cfg.hidden_size, KV, bias_attr=False)
        self.o_proj = Linear(H * D, cfg.hidden_size, bias_attr=False)
        self.q_norm = RMSNorm(D, epsilon=cfg.rms_norm_eps)
        self.k_norm = RMSNorm(D, epsilon=cfg.rms_norm_eps)
        if rope_cache is None:
            cos, sin = _rope_cache(D, cfg.max_position_embeddings,
                                   cfg.rope_theta)
            rope_cache = (jnp.asarray(cos), jnp.asarray(sin))
        self._cos, self._sin = rope_cache

    def forward(self, x, cache=None):
        cfg = self.cfg
        b, t, _ = x.shape
        H, KV, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        eps = cfg.rms_norm_eps
        cos_full, sin_full = self._cos, self._sin
        decode = cache is not None and cache[0] is not None
        if decode and t != 1:
            raise ValueError(
                f"a retention state advances one token a step, got {t}")
        if not decode and t > cos_full.shape[0]:
            raise ValueError(
                f"sequence length {t} exceeds max_position_embeddings="
                f"{cos_full.shape[0]}")

        def f(x, wq, wk, wv, wg, wo, wqn, wkn, *rest):
            # the whole branch in float32 from the normed input on
            x = x.astype(F32)
            lg = jax.nn.log_sigmoid(_split_matmul(x, wg))
            q = _head_norm(_split_matmul(x, wq).reshape(b, t, H, D),
                           wqn.astype(F32), eps)
            k = _head_norm(_split_matmul(x, wk).reshape(b, t, KV, D),
                           wkn.astype(F32), eps)
            v = _split_matmul(x, wv).reshape(b, t, KV, D)
            out = lambda y: _split_matmul(y.reshape(b, t, H * D), wo)
            if decode:
                S, z, pos, active = rest
                pos = jnp.asarray(pos, jnp.int32)
                cos, sin = cos_full[pos][:, None], sin_full[pos][:, None]
                q, k = _apply_rope(q, cos, sin), _apply_rope(k, cos, sin)
                y, S, z = pr.retention_decode(
                    q[:, 0], k[:, 0], v[:, 0], lg[:, 0], S, z, active)
                return out(y), S, z
            cos, sin = cos_full[:t], sin_full[:t]
            q, k = _apply_rope(q, cos, sin), _apply_rope(k, cos, sin)
            if cache is None:
                return out(jax.vmap(lambda *a: pr.retention_chunked(
                    *a, chunk=SEQUENCE_CHUNK)[0])(q, k, v, lg))
            valid = jnp.arange(t) < jnp.asarray(rest[0], jnp.int32)
            y = jax.vmap(lambda *a: pr.retention_attention(
                *a, block=PREFILL_ROWS))(q, k, v, lg)
            S, z = jax.vmap(lambda *a: pr.retention_state(
                *a, valid=valid, chunk=STATE_CHUNK))(
                    k, v, lg)
            return out(y), pr.state_to_layout(S), z

        args = (x,) + tuple(m.weight for m in (
            self.q_proj, self.k_proj, self.v_proj, self.g_proj,
            self.o_proj, self.q_norm, self.k_norm))
        if cache is None:
            return apply_op(f, *args, _op_name="power_retention")
        rest = cache if decode else cache[2:]
        y, S, z = apply_op(f, *args, *rest, _op_name="power_retention")
        return y, (S, z)


class BrumbyDecoderLayer(Layer):
    def __init__(self, cfg: LlamaConfig, rope_cache=None):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)
        self.retention = PowerRetention(cfg, rope_cache)
        self.post_attention_layernorm = RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cache=None):
        """``x``: the residual stream, float32 (the module's Precision);
        ``LlamaMLP``'s SwiGLU on its own weights, in float32."""
        a = self.retention(self.input_layernorm(x), cache)
        new_cache = None
        if cache is not None:
            a, new_cache = a
        x = x + a
        mlp = self.mlp
        x = x + apply_op(
            lambda h, gate, up, down: _split_matmul(
                jax.nn.silu(_split_matmul(h, gate))
                * _split_matmul(h, up), down),
            self.post_attention_layernorm(x), mlp.gate_proj.weight,
            mlp.up_proj.weight, mlp.down_proj.weight, _op_name="swiglu")
        return x if cache is None else (x, new_cache)


class BrumbyModel(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size)
        cos, sin = _rope_cache(cfg.head_dim, cfg.max_position_embeddings,
                               cfg.rope_theta)
        rope_cache = (jnp.asarray(cos), jnp.asarray(sin))
        self.layers = LayerList(
            [BrumbyDecoderLayer(cfg, rope_cache)
             for _ in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, caches=None):
        # the residual stream is float32 whatever the weights' dtype
        x = apply_op(lambda a: a.astype(F32),
                     self.embed_tokens(input_ids), _op_name="cast")
        new_caches = []
        for layer, c in zip(self.layers,
                            caches or [None] * len(self.layers)):
            x = layer(x, c)
            if caches is not None:
                x, nc = x
                new_caches.append(nc)
        h = self.norm(x)
        return h if caches is None else (h, new_caches)


class BrumbyForCausalLM(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.config = cfg
        self.brumby = BrumbyModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                  bias_attr=False)

    def forward(self, input_ids):
        return self._head(self.brumby(input_ids))

    def _head(self, h):
        if self.config.tie_word_embeddings:
            return apply_op(lambda h, w: _split_matmul(h, w.T), h,
                            self.brumby.embed_tokens.weight,
                            _op_name="head")
        return apply_op(_split_matmul, h, self.lm_head.weight,
                        _op_name="head")

    # -- what the serving engine asks of a model -------------------------
    def cached_forward(self, ids, caches):
        return self.brumby(ids, caches)

    def cache_spec(self) -> CacheSpec:
        cfg = self.config
        P = pr.phi_size(cfg.head_dim)
        return CacheSpec(
            layers=("state",) * len(self.brumby.layers),
            kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
            dtype=self.brumby.embed_tokens.weight._data.dtype,
            max_positions=cfg.max_position_embeddings,
            state=(("S", (cfg.kv_heads, P, cfg.head_dim), F32),
                   ("z", (cfg.kv_heads, P), F32)))
