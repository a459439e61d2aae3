"""Plain reference of the Solar-Open2 configuration (3 KDA layers to 1
gated GQA layer with no rope, a sparse FFN in every layer), in
``jax.numpy``; the benchmark's own copy of
``paddle_tpu/models/solar_reference.py``, in blocks so that 12,288
positions fit beside 6.6 GB of weights.

Float32, ``default_matmul_precision("highest")``, no cache, no batching,
no kernel, no chunk, no sort: one sequence, a layer at a time, each
layer's weights upcast as they are reached. Softmax attention ``ROWS``
rows of the scores at a time (``[64, ROWS, T]`` floats, 403 MB at 12,288
positions); the delta rule a token at a time under ``lax.scan``; the
experts held one after the other, each over every token and weighted by
what the router gave it there (0 where the token did not choose it: an
expert costs a product over all the tokens, which a reference may
afford); the head ``reference_brumby.ROWS`` positions at a time. A layer
(x ``[T, C]``; h = head; d = head size):

    n = rmsnorm(x; w_in)      x'  = x + mixer(n)
    n' = rmsnorm(x'; w_post)  x'' = x' + ffn(n')

GQA layer:  q, k, v = n Wq, n Wk, n Wv (no position term); k and v as
            a deployment's cache keeps them, rounded to the weights'
            dtype (what is STORED is part of the configuration, as the
            bfloat16 weights are; every product stays float32)
            a_i^h = softmax_(j<=i)(q_i^h . k_j^(h // rep) / sqrt(d)) v_j^(h // rep)
            mixer = (concat_h a^h * sigmoid(n Wgate)) Wo
KDA layer:  q, k, v = silu(conv4(n Wq)), silu(conv4(n Wk)), silu(conv4(n Wv))
            q = q / |q| * d^-0.5      k = k / |k|        (a head; |.|^2 + 1e-6)
            g_t = -exp(A_log^h) softplus(n_t Wf1 Wf2 + dt_bias)
            beta_t^h = 2 sigmoid(n_t Wb)
            S' = diag(exp(g_t)) S_(t-1);  u_t = beta_t (v_t - S'^T k_t)
            S_t = S' + k_t u_t^T;         o_t = S_t^T q_t
            mixer = (concat_h rmsnorm_head(o^h; w_o) * sigmoid(n Wg1 Wg2)) Wo
FFN:        s = softmax(n' Wr) over all the router's experts; E_t the 8
            largest; w_te = s_te / sum_(e' in E_t) s_te'
            ffn = sum_(e in E_t, held) w_te swiglu_e(n'_t) + swiglu_shared(n'_t)

``held`` are experts ``first_expert ..`` as many as the weights have: the
chip's share, as the program computes it. It reads the program's
parameter arrays and nothing else of it. The limits are
``reference.py``'s, as they stand.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .reference import F32, _MATMUL, _f32, _rms
from .reference_brumby import _head

ROWS = 128        # rows of the attention scores computed at once
L2_EPS = 1e-6     # under the root of q's and k's norms (assumed)


def _attention(q, k, v):
    """q [T, H, d]; k, v [T, KV, d]; T a multiple of ROWS or under it."""
    T, H, d = q.shape
    KV = k.shape[1]
    rows = min(ROWS, T)
    j = jnp.arange(T)

    def block(x):
        qb, i0 = x                              # [rows, KV, rep, d]
        s = jnp.einsum("igrd,jgd->grij", qb, k) / math.sqrt(d)
        seen = j[None, :] <= (i0 + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("grij,jgd->igrd", p, v)

    a = jax.lax.map(block, (q.reshape(-1, rows, KV, H // KV, d),
                            jnp.arange(T // rows) * rows))
    return a.reshape(T, H * d)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                   "precision"))
def _gqa_layer(x, lp, heads, kv_heads, eps, precision="f32"):
    # rounded, and still float32 to the compiler: a product of a
    # float32 operand with one it knows for bfloat16 is not float32
    # on a TPU
    stored = jnp.finfo(lp["k"].dtype)
    kept = lambda a: jax.lax.reduce_precision(a, stored.nexp, stored.nmant)
    lp = _f32(lp)
    mm = _MATMUL[precision]
    T = x.shape[0]
    d = lp["q"].shape[1] // heads
    n = _rms(x, lp["ln1"], eps)
    a = _attention(mm(n, lp["q"]).reshape(T, heads, d),
                   kept(mm(n, lp["k"])).reshape(T, kv_heads, d),
                   kept(mm(n, lp["v"])).reshape(T, kv_heads, d))
    return x + mm(a * jax.nn.sigmoid(mm(n, lp["g"])), lp["o"])


def _conv_silu(x, w):
    """``y_t = sum_j w_j x_(t - taps + 1 + j)``, zero before the
    sequence, then SiLU; ``x [T, C]``, ``w [taps, C]``."""
    taps = w.shape[0]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[j] * xp[j:j + x.shape[0]]
                           for j in range(taps)))


@partial(jax.jit, static_argnames=("heads", "eps", "two", "precision"))
def _kda_layer(x, lp, heads, eps, two, precision="f32"):
    lp = _f32(lp)
    mm = _MATMUL[precision]
    T = x.shape[0]
    d = lp["q"].shape[1] // heads
    n = _rms(x, lp["ln1"], eps)
    head = lambda a: a.reshape(T, heads, d)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                  + L2_EPS)
    q = unit(head(_conv_silu(mm(n, lp["q"]), lp["cq"]))) * d ** -0.5
    k = unit(head(_conv_silu(mm(n, lp["k"]), lp["ck"])))
    v = head(_conv_silu(mm(n, lp["v"]), lp["cv"]))
    g = -jnp.exp(lp["A_log"])[:, None] * head(jax.nn.softplus(
        mm(mm(n, lp["f1"]), lp["f2"]) + lp["dt_bias"]))
    beta = two * jax.nn.sigmoid(mm(n, lp["b"]))

    def token(S, t):
        qt, kt, vt, gt, bt = t
        S = jnp.exp(gt)[:, :, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), F32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid(mm(mm(n, lp["g1"]), lp["g2"]))
    o = _rms(o, lp["on"], eps).reshape(T, heads * d)
    return x + mm(o * gate, lp["o"])


@partial(jax.jit, static_argnames=("top_k", "first_expert", "eps",
                                   "precision"))
def _ffn(x, lp, top_k, first_expert, eps, precision="f32"):
    mm = _MATMUL[precision]
    n = _rms(x, lp["ln2"].astype(F32), eps)
    s = jax.nn.softmax(mm(n, lp["router"].astype(F32)), axis=-1)
    w, chosen = jax.lax.top_k(s, top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    swiglu = lambda gate, up, down: mm(
        jax.nn.silu(mm(n, gate.astype(F32))) * mm(n, up.astype(F32)),
        down.astype(F32))

    def expert(e, out):
        mine = jnp.sum(jnp.where(chosen == first_expert + e, w, 0.0),
                       axis=-1)
        return out + mine[:, None] * swiglu(lp["eg"][e], lp["eu"][e],
                                            lp["ed"][e])

    out = jax.lax.fori_loop(0, lp["eg"].shape[0], expert,
                            jnp.zeros_like(x))
    return x + out + swiglu(lp["sg"], lp["su"], lp["sd"])


_COMMON = {"ln1": "input_layernorm.weight",
           "ln2": "post_attention_layernorm.weight",
           "router": "mlp.router.weight", "eg": "mlp.experts_gate",
           "eu": "mlp.experts_up", "ed": "mlp.experts_down",
           "sg": "mlp.shared_expert.gate_proj.weight",
           "su": "mlp.shared_expert.up_proj.weight",
           "sd": "mlp.shared_expert.down_proj.weight"}
_GQA = {"q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
        "v": "self_attn.v_proj.weight", "g": "self_attn.g_proj.weight",
        "o": "self_attn.o_proj.weight"}
_KDA = {"q": "kda.q_proj.weight", "k": "kda.k_proj.weight",
        "v": "kda.v_proj.weight", "o": "kda.o_proj.weight",
        "f1": "kda.f_a_proj.weight", "f2": "kda.f_b_proj.weight",
        "g1": "kda.g_a_proj.weight", "g2": "kda.g_b_proj.weight",
        "b": "kda.b_proj.weight", "cq": "kda.q_conv", "ck": "kda.k_conv",
        "cv": "kda.v_conv", "A_log": "kda.A_log",
        "dt_bias": "kda.dt_bias", "on": "kda.o_norm.weight"}


def forward(params, ids, tokens, *, layers: int, gqa_layers, heads: int,
            kv_heads: int, linear_heads: int, top_k: int,
            first_expert: int, eps: float, neg_eigval: bool = True,
            precision: str = "f32"):
    """One sequence ``ids`` (its length under ``ROWS`` or a multiple of
    it and of ``reference_brumby.ROWS``) through the reference: at each
    position the gap of ``tokens[i]`` under the best logit, and the best
    token. ``precision`` is that of every matrix product with a weight:
    ``"f32"`` the reference, ``"int8"`` the CONTROL's."""
    with jax.default_matmul_precision("highest"):
        x = params["solar.embed_tokens.weight"][np.asarray(ids)
                                                ].astype(F32)
        for li in range(layers):
            get = lambda names: {k: params[f"solar.layers.{li}.{name}"]
                                 for k, name in names.items()}
            common = get(_COMMON)
            if li in gqa_layers:
                x = _gqa_layer(x, dict(get(_GQA), ln1=common["ln1"]),
                               heads, kv_heads, eps, precision)
            else:
                x = _kda_layer(x, dict(get(_KDA), ln1=common["ln1"]),
                               linear_heads, eps,
                               2.0 if neg_eigval else 1.0, precision)
            x = _ffn(x, common, top_k, first_expert, eps, precision)
        return _head(x, params["solar.norm.weight"],
                     params["lm_head.weight"], jnp.asarray(tokens), eps,
                     precision)


def served_gaps(params, prompt, outputs, *, pad_to: int = 0,
                control: bool = False, **model) -> np.ndarray:
    """``reference.llama_served_gaps`` for this model: for each served
    token of one finished request, how far the reference's logit of it
    lies under the reference's best at that position, in standard
    deviations of the position's logits; one forward over prompt +
    outputs, padded at its end to a multiple of ``pad_to`` (what
    follows a position cannot reach it). ``control=True`` reads
    instead, at the same positions, the gap of the token the int8
    forward puts first: it has to fail the cell's limits."""
    ids = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(outputs, np.int64)])
    first, n = len(prompt) - 1, len(ids) - 1
    pad = np.zeros(-n % pad_to if pad_to else 0, np.int64)
    inputs = np.concatenate([ids[:-1], pad])
    tokens = np.concatenate([ids[1:], pad])
    if control:
        _, tokens = forward(params, inputs, tokens, precision="int8",
                            **model)
    gaps, _ = forward(params, inputs, tokens, **model)
    return np.asarray(gaps, np.float64)[first:n]
