"""Solar Open 2 (upstage/Solar-Open2-250B): a decoder whose layers are, three
to one, Kimi Delta Attention (``ops/kda.py``) and softmax GQA attention
with no rope and an output gate, each followed by a sparse FFN (top-8 of
320 routed experts and one shared expert), served as ONE CHIP'S SHARE of
an expert-parallel deployment: the model is told which experts it holds.

A layer (x ``[T, hidden]``; h = head; d = head size):

    n = rmsnorm(x; w_in)      x'  = x + mixer(n)
    n' = rmsnorm(x'; w_post)  x'' = x' + ffn(n')

*GQA layer* (``gqa_layers``: 0, 4, 8, ...): ``q = n Wq``, ``k = n Wk``,
``v = n Wv``, no position term of any kind;
``a_i^h = softmax_(j<=i)(q_i^h . k_j^(h // rep) / sqrt(d)) v_j^(h // rep)``;
``mixer = (concat_h a^h * sigmoid(n Wgate)) Wo``.

*KDA layer* (every other): ``q~, k~, v~ = n Wq, n Wk, n Wv``; a causal
depthwise convolution of 4 taps along the sequence and SiLU on each;
``q`` and ``k`` L2-normalised a head, ``q`` scaled by ``d^-0.5``;
``g_t = -exp(A_log^h) softplus(n_t Wf1 Wf2 + dt_bias)`` a channel,
``beta_t^h = 2 sigmoid(n_t Wb)``; the delta rule of ``ops/kda.py`` on a
float32 state ``S [d, d]`` a head; ``mixer = (concat_h rmsnorm_head(o^h;
w_o) * sigmoid(n Wg1 Wg2)) Wo``.

*FFN*: ``s = softmax(n' Wr)`` over all the published experts, the 8
largest a token, their weights normalised over the 8;
``ffn = sum_(e chosen and held) w_e swiglu_e(n') + swiglu_shared(n')``.
The experts held are ``first_expert .. first_expert + n_routed_experts -
1`` of ``experts_published``; what the others would add is left out (it
is computed on the chips that hold them), and the partial result goes on.

What the engine holds for it (``cache_spec``): K/V pages for the GQA
layers (in the weights' dtype), and for each KDA layer a slot's state
``S [heads, d, d]`` and the last three inputs of the convolution ``[3,
3 * heads * d]``, both float32. The cache flavors a mixer takes:

- ``None``: the whole sequence (no cache returned);
- a prefill of one bucket: the GQA layer ``(k0, v0, 0)`` (it attends
  within the bucket, an einsum ``ATTENTION_ROWS`` rows at a time against
  the keys at or before them, never scoring ``[heads, t, t]`` at once,
  and returns the bucket's K and V);
  the KDA layer ``(None, None, true_len)`` (the chunked form; positions
  from ``true_len`` on are padding and leave the state alone; returns
  the new ``(S, conv)``);
- a decode step: the GQA layer the paged 6-tuple of
  ``models/_decode_cache`` (``paged_cache_attend``: the live-pages
  kernel on a TPU); the KDA layer ``(S, conv, pos, active)``, one token
  a slot, slots that are not active keeping their state.

Precision, as ``models/brumby.py``: in a bfloat16 model the weights are
bfloat16 and the arithmetic is float32 (the residual stream, the norms,
both mixers, the router, the experts and the head; every product with a
weight by ``_split_matmul`` or, for the experts, ``grouped_matmul``'s
two-piece rows). A router decides by rank: a bfloat16 residual stream
moves a token's 8th and 9th expert past each other on some percent of
the tokens a layer, and such a token's FFN changes by an eighth, which a
logit comparison with the float32 reference does not forgive. **K and V
are cached in the weights' dtype** (``cache_spec().dtype``: bfloat16
pages for a bfloat16 model, as every K/V model of this repo), and what
is stored is DEFINED: the float32 projection rounded once. Every path
attends to the rounded values (a prefill too, so that it sees what a
later decode step reads back), the projections that are rounded are
exact in their input (``_split_matmul(whole=True)``: 2^-16 would move
one element in a few hundred across a rounding boundary, a whole ulp
off), and the decode kernel takes a float32 caller's probabilities in
two bfloat16 parts. The plain reference rounds K and V the same way and
nothing else: with K and V rounded on one side only, an attention
output (a sum of thousands of values, each 2^-9 off) came out 3.3e-3
off, several times the embedding it is added to, and the routers
downstream turned that into swapped experts (served logits 0.02-0.07 of
their deviation off: PERF.md, PR 35).

Not in the catalog's ``config`` and therefore assumed (the benchmark's
configuration file lists them): the KDA mixer as Kimi Linear publishes
it (SiLU after the convolution, no convolution bias, L2-normalised q
and k with epsilon 1e-6, the scale, ``A_log`` a head, ``dt_bias`` a
channel, rank ``kda_rank`` 128 for both low-rank gates, the
sigmoid-gated head norm); the GQA gate as an elementwise sigmoid of its
own projection; no q/k norm in the GQA layers; softmax router scores
with no selection bias and no expert groups; the shared expert added
ungated; ``intermediate_size`` unused (no dense layer); float32 state.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.tensor import apply_op
from ..incubate.moe import expert_share, route_topk
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm
from ..ops import kda
from ..ops.grouped_matmul import pieces
from ._decode_cache import CacheSpec, paged_cache_attend
from .brumby import _head_norm, _split_matmul
from .llama import LlamaConfig, LlamaMLP

__all__ = ["SolarOpen2Config", "SolarOpen2Model", "SolarOpen2ForCausalLM"]

F32 = jnp.float32

# workspace sizes, not part of the mathematics: rows of attention scored
# at once in a prefill, tokens of a KDA chunk (the work inside a chunk
# grows with its length: 32 took 45 ms less of an 8,192-token prefill
# than 64 would by its profile, PERF.md PR 35), tokens whose
# expert assignments are sorted and multiplied at once in a prefill
ATTENTION_ROWS = 256
KDA_CHUNK = 32
EXPERT_TOKENS = 2048
L2_EPS = 1e-6


@dataclasses.dataclass
class SolarOpen2Config:
    """The published keys (``from_dict`` reads a ``config.json``), and
    beside them the share held here: ``n_routed_experts`` experts from
    ``first_expert`` on of ``experts_published`` (None: all of them)."""
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_allow_neg_eigval: bool = True
    kda_rank: int = 128
    tie_word_embeddings: bool = False
    experts_published: Optional[int] = None
    first_expert: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "SolarOpen2Config":
        lin = d.get("linear_attn_config", {})
        kw = {f.name: d[f.name] for f in dataclasses.fields(cls)
              if f.name in d}
        kw["gqa_layers"] = tuple(d["gqa_layers"])
        for key, name in (("num_heads", "linear_num_heads"),
                          ("head_dim", "linear_head_dim"),
                          ("short_conv_kernel_size",
                           "short_conv_kernel_size")):
            if key in lin:
                kw[name] = lin[key]
        return cls(**kw)

    @property
    def router_width(self) -> int:
        return self.experts_published or self.n_routed_experts

    def is_gqa(self, layer: int) -> bool:
        return layer in self.gqa_layers


def _causal_attention(q, k, v):
    """``q [b, t, H, D]`` float32, ``k, v [b, t, KV, D]`` as the cache
    stores them; causal within the block (padding lies after the real
    tokens). ``ATTENTION_ROWS`` rows of the scores at a time against the
    keys at or before them: the ``[H, t, t]`` scores are never whole.

    Against bfloat16 ``k, v`` the float32 side of each product (the
    queries, then the probabilities) goes in as its two bfloat16
    ``pieces`` side by side along the contraction, against ``k`` or
    ``v`` twice over: plain bfloat16 products with float32 accumulation,
    exact in what is stored and good to 2^-16 of the other side. A
    float32 operand beside a bfloat16 one at ``Precision.HIGH`` is not
    that on a TPU: the compiler takes the pair as bfloat16 x bfloat16
    and the queries came out 2^-9 off (PERF.md, PR 35). Float32 ``k,
    v`` (a float32 model) multiply at ``Precision.HIGH``."""
    b, t, H, D = q.shape
    KV = k.shape[2]
    rep, rows = H // KV, min(ATTENTION_ROWS, t)
    split = k.dtype == jnp.bfloat16
    if split:
        cut = lambda a, axis: jnp.concatenate(list(pieces(a)), axis=axis)
        kw = dict(preferred_element_type=F32)
        q, k = cut(q, -1), jnp.concatenate([k, k], axis=-1)
    else:
        kw = dict(precision=jax.lax.Precision.HIGH)
    out = []
    for i0 in range(0, t, rows):
        i1 = min(i0 + rows, t)
        qr = q[:, i0:i1].reshape(b, i1 - i0, KV, rep, -1)
        s = jnp.einsum("bigrd,bjgd->bgrij", qr, k[:, :i1],
                       **kw) / math.sqrt(D)
        seen = jnp.arange(i1)[None, :] <= jnp.arange(i0, i1)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        vs = v[:, :i1]
        if split:
            p, vs = cut(p, -1), jnp.concatenate([vs, vs], axis=1)
        out.append(jnp.einsum("bgrij,bjgd->bigrd", p, vs,
                              **kw).reshape(b, i1 - i0, H, D))
    return jnp.concatenate(out, axis=1)


class SolarGQAttention(Layer):
    """Softmax GQA attention with no position term and an output gate."""

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        C = cfg.hidden_size
        H, KV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        self.q_proj = Linear(C, H * D, bias_attr=False)
        self.k_proj = Linear(C, KV * D, bias_attr=False)
        self.v_proj = Linear(C, KV * D, bias_attr=False)
        self.g_proj = Linear(C, H * D, bias_attr=False)
        self.o_proj = Linear(H * D, C, bias_attr=False)

    def forward(self, x, cache=None):
        cfg = self.cfg
        b, t, _ = x.shape
        H, KV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        paged = cache is not None and len(cache) == 6
        if cache is not None and not paged and (
                len(cache) != 3 or cache[2] != 0):
            raise ValueError(
                "a GQA layer takes a from-scratch prefill (k, v, 0) or "
                f"the paged 6-tuple, got {len(cache)} elements")

        def f(x, wq, wk, wv, wg, wo, *rest):
            x = x.astype(F32)
            q = _split_matmul(x, wq).reshape(b, t, H, D)
            # K and V are what the cache keeps: rounded to the weights'
            # dtype on every path, attended within a prefill as a later
            # decode step will read them
            kept = lambda w: _split_matmul(x, w, whole=True).astype(
                w.dtype).reshape(b, t, KV, D)
            k, v = kept(wk), kept(wv)
            gate = jax.nn.sigmoid(_split_matmul(x, wg))
            out = lambda a: _split_matmul(a.reshape(b, t, H * D) * gate,
                                          wo)
            if paged:
                kp, vp, table, pos = rest
                a, kp, vp, _, _ = paged_cache_attend(
                    q, k, v, kp, vp, None, None, table,
                    jnp.asarray(pos, jnp.int32), jnp.dtype(F32))
                return out(a), kp, vp
            a = _causal_attention(q, k, v)
            if cache is None:
                return out(a)
            return out(a), k, v

        args = (x,) + tuple(m.weight for m in (
            self.q_proj, self.k_proj, self.v_proj, self.g_proj,
            self.o_proj))
        if cache is None:
            return apply_op(f, *args, _op_name="gqa_attention")
        if paged:
            kp, vp, _, _, table, pos = cache
            y, kp, vp = apply_op(f, *args, kp, vp, table, pos,
                                 _op_name="gqa_attention")
            return y, (kp, vp, None, None)
        y, k, v = apply_op(f, *args, _op_name="gqa_attention")
        return y, (k, v)


def _inverse_softplus(x):
    return x + np.log(-np.expm1(-x))


class KimiDeltaAttention(Layer):
    """The KDA mixer (module docstring)."""

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        C, H, D = cfg.hidden_size, cfg.linear_num_heads, \
            cfg.linear_head_dim
        R, taps = cfg.kda_rank, cfg.short_conv_kernel_size
        lin = lambda i, o: Linear(i, o, bias_attr=False)
        self.q_proj, self.k_proj, self.v_proj = (lin(C, H * D)
                                                 for _ in range(3))
        self.o_proj = lin(H * D, C)
        self.f_a_proj, self.f_b_proj = lin(C, R), lin(R, H * D)
        self.g_a_proj, self.g_b_proj = lin(C, R), lin(R, H * D)
        self.b_proj = lin(C, H)
        # a tap's weight of the order of 1 / sqrt(taps): the convolution
        # keeps the size of what it is given
        conv = lambda: self.create_parameter(
            [taps, H * D],
            default_initializer=I.Normal(0.0, taps ** -0.5))
        self.q_conv, self.k_conv, self.v_conv = conv(), conv(), conv()
        # as Kimi Linear initialises them: exp(A_log) over 1 .. 16, the
        # step softplus(dt_bias) over 1e-3 .. 1e-1 (a memory of tens to
        # thousands of tokens)
        self.A_log = self.create_parameter(
            [H], default_initializer=I.Assign(
                np.log(np.linspace(1.0, 16.0, H)).astype(np.float32)))
        self.dt_bias = self.create_parameter(
            [H * D], default_initializer=I.Assign(_inverse_softplus(
                np.geomspace(1e-3, 1e-1, H * D)).astype(np.float32)))
        self.o_norm = RMSNorm(D, epsilon=cfg.rms_norm_eps)

    def forward(self, x, cache=None):
        cfg = self.cfg
        b, t, _ = x.shape
        H, D = cfg.linear_num_heads, cfg.linear_head_dim
        taps, eps = cfg.short_conv_kernel_size, cfg.rms_norm_eps
        two = 2.0 if cfg.kda_allow_neg_eigval else 1.0
        decode = cache is not None and cache[0] is not None
        if decode and t != 1:
            raise ValueError(
                f"a KDA state advances one token a step, got {t}")

        def f(x, wq, wk, wv, wo, wf1, wf2, wg1, wg2, wb, cq, ck, cv,
              a_log, dt_bias, w_on, *rest):
            x = x.astype(F32)
            pre = jnp.concatenate([_split_matmul(x, w)
                                   for w in (wq, wk, wv)], axis=-1)
            taps_w = jnp.concatenate([cq, ck, cv], axis=-1).astype(F32)
            if decode:
                S, conv, pos, active = rest
                window = jnp.concatenate([conv, pre], axis=1)
                mixed = jnp.sum(window * taps_w[None], axis=1,
                                keepdims=True)
                conv = jnp.where(active[:, None, None], window[:, 1:],
                                 conv)
            else:
                window = jnp.pad(pre, ((0, 0), (taps - 1, 0), (0, 0)))
                mixed = sum(window[:, j:j + t] * taps_w[j]
                            for j in range(taps))
            q, k, v = (a.reshape(b, t, H, D) for a in jnp.split(
                jax.nn.silu(mixed), 3, axis=-1))
            unit = lambda a: a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
            q, k = unit(q) * D ** -0.5, unit(k)
            low = lambda w1, w2: _split_matmul(_split_matmul(x, w1), w2)
            g = -jnp.exp(a_log.astype(F32))[:, None] * jax.nn.softplus(
                low(wf1, wf2) + dt_bias.astype(F32)).reshape(b, t, H, D)
            beta = two * jax.nn.sigmoid(_split_matmul(x, wb))
            gate = jax.nn.sigmoid(low(wg1, wg2)).reshape(b, t, H, D)
            out = lambda o: _split_matmul(
                (_head_norm(o, w_on.astype(F32), eps) * gate).reshape(
                    b, t, H * D), wo)
            if decode:
                o, S = kda.kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                      beta[:, 0], S, active)
                return out(o[:, None]), S, conv
            if cache is None:
                return out(jax.vmap(lambda *a: kda.kda_chunked(
                    *a, chunk=KDA_CHUNK)[0])(q, k, v, g, beta))
            true_len = jnp.asarray(rest[0], jnp.int32)
            valid = jnp.arange(t) < true_len
            o, S = jax.vmap(lambda *a: kda.kda_chunked(
                *a, chunk=KDA_CHUNK, valid=valid))(q, k, v, g, beta)
            # the last taps - 1 real inputs: window row i is input
            # i - (taps - 1)
            conv = jax.lax.dynamic_slice_in_dim(window, true_len,
                                                taps - 1, axis=1)
            return out(o), S, conv

        args = (x,) + tuple(m.weight for m in (
            self.q_proj, self.k_proj, self.v_proj, self.o_proj,
            self.f_a_proj, self.f_b_proj, self.g_a_proj, self.g_b_proj,
            self.b_proj)) + (self.q_conv, self.k_conv, self.v_conv,
                             self.A_log, self.dt_bias, self.o_norm.weight)
        if cache is None:
            return apply_op(f, *args, _op_name="kda")
        rest = cache if decode else cache[2:]
        y, S, conv = apply_op(f, *args, *rest, _op_name="kda")
        return y, (S, conv)


class SolarSparseMLP(Layer):
    """The routed experts held here and the shared expert."""

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        C, F, E = (cfg.hidden_size, cfg.moe_intermediate_size,
                   cfg.n_routed_experts)
        if cfg.first_expert + E > cfg.router_width:
            raise ValueError(
                f"experts {cfg.first_expert}..{cfg.first_expert + E - 1} "
                f"are not among the router's {cfg.router_width}")
        if not cfg.norm_topk_prob:
            raise NotImplementedError(
                "norm_topk_prob false: the weights of the chosen "
                "experts are normalised over them")
        self.router = Linear(C, cfg.router_width, bias_attr=False)
        stack = lambda i, o: self.create_parameter(
            [E, i, o], default_initializer=I.Normal(
                0.0, math.sqrt(2.0 / (i + o))))
        self.experts_gate = stack(C, F)
        self.experts_up = stack(C, F)
        self.experts_down = stack(F, C)
        self.shared_expert = LlamaMLP(LlamaConfig(
            hidden_size=C, intermediate_size=F * cfg.n_shared_experts))

    def forward(self, x, decode: bool = False):
        """``x [b, t, C]`` float32; returns the layer's result and the
        assignments each held expert got, ``[experts held]`` int32."""
        cfg = self.cfg
        b, t, C = x.shape
        k = cfg.num_experts_per_tok
        name = "expert_gmm_decode" if decode else "expert_gmm_prefill"

        def f(x, wr, eg, eu, ed, sg, su, sd):
            x = x.astype(F32).reshape(-1, C)
            w, idx = route_topk(x, wr, k)
            w = w * cfg.routed_scaling_factor
            share = lambda xs, ws, ix: expert_share(
                xs, ws, ix, eg, eu, ed, cfg.first_expert, name=name)
            T = x.shape[0]
            if T <= EXPERT_TOKENS or T % EXPERT_TOKENS:
                y, counts = share(x, w, idx)
            else:
                part = lambda a: a.reshape(-1, EXPERT_TOKENS, a.shape[-1])
                y, counts = jax.lax.map(lambda a: share(*a),
                                        (part(x), part(w), part(idx)))
                y, counts = y.reshape(T, C), counts.sum(0)
            y = y + _split_matmul(jax.nn.silu(_split_matmul(x, sg))
                                  * _split_matmul(x, su), sd)
            return y.reshape(b, t, C), counts

        s = self.shared_expert
        return apply_op(f, x, self.router.weight, self.experts_gate,
                        self.experts_up, self.experts_down,
                        s.gate_proj.weight, s.up_proj.weight,
                        s.down_proj.weight, _op_name="sparse_mlp")


class SolarDecoderLayer(Layer):
    def __init__(self, cfg: SolarOpen2Config, gqa: bool):
        super().__init__()
        self.gqa = gqa
        self.input_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)
        if gqa:
            self.self_attn = SolarGQAttention(cfg)
        else:
            self.kda = KimiDeltaAttention(cfg)
        self.post_attention_layernorm = RMSNorm(
            cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.mlp = SolarSparseMLP(cfg)

    def forward(self, x, cache=None):
        """``x``: the residual stream, float32. Returns it, the mixer's
        new cache (with a cache) and the FFN's assignment counts."""
        mixer = self.self_attn if self.gqa else self.kda
        a = mixer(self.input_layernorm(x), cache)
        new_cache = None
        if cache is not None:
            a, new_cache = a
        x = x + a
        # one token a slot from a cache is a decode step: the grouped
        # product is named and tiled for it
        decode = cache is not None and x.shape[1] == 1
        y, counts = self.mlp(self.post_attention_layernorm(x), decode)
        return x + y, new_cache, counts


class SolarOpen2Model(Layer):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.config = cfg
        self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = LayerList(
            [SolarDecoderLayer(cfg, cfg.is_gqa(i))
             for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        # the last forward's assignment counts, a layer: [layers, experts
        # held] int32 (traced values inside an engine program)
        self.expert_counts = None

    def forward(self, input_ids, caches=None):
        # the residual stream is float32 whatever the weights' dtype
        x = apply_op(lambda a: a.astype(F32),
                     self.embed_tokens(input_ids), _op_name="cast")
        new_caches, counts = [], []
        for layer, c in zip(self.layers,
                            caches or [None] * len(self.layers)):
            x, nc, n = layer(x, c)
            new_caches.append(nc)
            counts.append(n)
        self.expert_counts = apply_op(lambda *a: jnp.stack(a), *counts,
                                      _op_name="stack")
        h = self.norm(x)
        return h if caches is None else (h, new_caches)


class SolarOpen2ForCausalLM(Layer):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.config = cfg
        self.solar = SolarOpen2Model(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                  bias_attr=False)

    def forward(self, input_ids):
        return self._head(self.solar(input_ids))

    def _head(self, h):
        if self.config.tie_word_embeddings:
            return apply_op(lambda h, w: _split_matmul(h, w.T), h,
                            self.solar.embed_tokens.weight,
                            _op_name="head")
        return apply_op(_split_matmul, h, self.lm_head.weight,
                        _op_name="head")

    # -- what the serving engine asks of a model -------------------------
    def cached_forward(self, ids, caches):
        return self.solar(ids, caches)

    def step_counters(self) -> dict:
        """What the last ``cached_forward`` counted, by name, an int32 a
        layer each: an engine's decode program returns it beside the
        logits, and the engine publishes each name's sum."""
        counts = self.solar.expert_counts._data       # [layers, held]
        return {"experts_hit": jnp.sum(counts > 0, axis=1, dtype=jnp.int32),
                "expert_tokens": jnp.sum(counts, axis=1, dtype=jnp.int32),
                "experts_held": jnp.full(counts.shape[:1], counts.shape[1],
                                         jnp.int32)}

    def cache_spec(self) -> CacheSpec:
        cfg = self.config
        H, D = cfg.linear_num_heads, cfg.linear_head_dim
        return CacheSpec(
            layers=tuple("kv" if layer.gqa else "state"
                         for layer in self.solar.layers),
            kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            dtype=self.solar.embed_tokens.weight._data.dtype,
            max_positions=cfg.max_position_embeddings,
            state=(("S", (H, D, D), F32),
                   ("conv", (cfg.short_conv_kernel_size - 1, 3 * H * D),
                    F32)))
