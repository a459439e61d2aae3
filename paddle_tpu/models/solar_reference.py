"""The plain reference of ``models/solar.py``: the forward pass written
from the layers' equations and not from ``ops/kda.py``,
``ops/grouped_matmul.py`` or ``incubate/moe.py``.

``jax.numpy``, float32, ``default_matmul_precision("highest")``: one
sequence, no cache, no batching, no kernel, no chunk, no sort; the delta
rule a token at a time under ``lax.scan``, softmax attention over the
whole ``[T, T]`` scores, every chosen expert of a token computed one at a
time from a gather of its three matrices. A layer (x ``[T, C]``; h =
head; d = head size):

    n = rmsnorm(x; w_in)      x'  = x + mixer(n)
    n' = rmsnorm(x'; w_post)  x'' = x' + ffn(n')

GQA layer:  q, k, v = n Wq, n Wk, n Wv (no position term); k and v as
            the cache keeps them, rounded to the weights' dtype;
            a_i^h = softmax_(j<=i)(q_i^h . k_j^(h // rep) / sqrt(d)) v_j^(h // rep)
            mixer = (concat_h a^h * sigmoid(n Wgate)) Wo
KDA layer:  q, k, v = silu(conv4(n Wq)), silu(conv4(n Wk)), silu(conv4(n Wv))
            q = q / |q| * d^-0.5      k = k / |k|        (a head; |.|^2 + 1e-6)
            g_t = -exp(A_log^h) softplus(n_t Wf1 Wf2 + dt_bias)
            beta_t^h = 2 sigmoid(n_t Wb)
            S' = diag(exp(g_t)) S_(t-1);  u_t = beta_t (v_t - S'^T k_t)
            S_t = S' + k_t u_t^T;         o_t = S_t^T q_t
            mixer = (concat_h rmsnorm_head(o^h; w_o) * sigmoid(n Wg1 Wg2)) Wo
FFN:        s = softmax(n' Wr) over all the router's experts; E_t the k
            largest; w_te = s_te / sum_(e' in E_t) s_te'
            ffn = sum_(e in E_t, held) w_te swiglu_e(n'_t) + swiglu_shared(n'_t)

``held`` are experts ``first_expert .. first_expert + (experts in
params) - 1``: given all of them the reference is the uncut model, given
a share it leaves out what the others would add, as the program does.
Parameters by their ``raw_state()`` names. The tests hold the model's
paths to it (whole sequence; prefill into pages and states, then decode
from them); the benchmark carries its own copy, in blocks so that 12,288
positions fit (``chipbench/reference_solar.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["solar_logits", "solar_ffn"]

F32 = jnp.float32
L2_EPS = 1e-6       # under the root of q's and k's norms (assumed)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _gqa(n, p, heads, kv_heads, stored):
    T = n.shape[0]
    d = p["self_attn.q_proj.weight"].shape[1] // heads
    rep = heads // kv_heads
    fi = jnp.finfo(stored)
    kept = lambda a: jax.lax.reduce_precision(
        a, fi.nexp, fi.nmant).reshape(T, kv_heads, d)
    q = (n @ p["self_attn.q_proj.weight"]).reshape(T, heads, d)
    k = kept(n @ p["self_attn.k_proj.weight"])
    v = kept(n @ p["self_attn.v_proj.weight"])
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("ihd,jhd->hij", q, k) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, axis=-1), v)
    gate = jax.nn.sigmoid(n @ p["self_attn.g_proj.weight"])
    return (a.reshape(T, heads * d) * gate) @ p["self_attn.o_proj.weight"]


def _conv_silu(x, w):
    """``x [T, C]``, ``w [taps, C]``: ``y_t = sum_j w_j x_(t - taps + 1
    + j)`` (what lies before the sequence is zero), then SiLU."""
    taps = w.shape[0]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[j] * xp[j:j + x.shape[0]]
                           for j in range(taps)))


def _kda(n, p, heads, eps, neg_eigval):
    T = n.shape[0]
    d = p["kda.q_proj.weight"].shape[1] // heads
    head = lambda a: a.reshape(T, heads, d)
    q = head(_conv_silu(n @ p["kda.q_proj.weight"], p["kda.q_conv"]))
    k = head(_conv_silu(n @ p["kda.k_proj.weight"], p["kda.k_conv"]))
    v = head(_conv_silu(n @ p["kda.v_proj.weight"], p["kda.v_conv"]))
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                  + L2_EPS)
    q, k = unit(q) * d ** -0.5, unit(k)
    g = -jnp.exp(p["kda.A_log"])[:, None] * head(jax.nn.softplus(
        n @ p["kda.f_a_proj.weight"] @ p["kda.f_b_proj.weight"]
        + p["kda.dt_bias"]))
    beta = (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(
        n @ p["kda.b_proj.weight"])

    def token(S, x):
        qt, kt, vt, gt, bt = x              # [heads, d], bt [heads]
        S = jnp.exp(gt)[:, :, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), F32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid(n @ p["kda.g_a_proj.weight"]
                          @ p["kda.g_b_proj.weight"])
    o = _rms(o, p["kda.o_norm.weight"], eps).reshape(T, heads * d)
    return (o * gate) @ p["kda.o_proj.weight"]


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def solar_ffn(n, p, *, top_k: int, first_expert: int = 0,
              scale: float = 1.0):
    """The FFN of one layer over ``n [T, C]``: a token's chosen experts
    one at a time, each from a gather of its matrices, those not among
    the ``p["mlp.experts_*"]`` held left out; the shared expert once."""
    s = jax.nn.softmax(n @ p["mlp.router.weight"], axis=-1)
    w, chosen = jax.lax.top_k(s, top_k)
    w = scale * w / jnp.sum(w, axis=-1, keepdims=True)
    held = p["mlp.experts_gate"].shape[0]
    out = _swiglu(n, p["mlp.shared_expert.gate_proj.weight"],
                  p["mlp.shared_expert.up_proj.weight"],
                  p["mlp.shared_expert.down_proj.weight"])

    def one_token(y, e, we):
        """``y [C]`` through its expert ``e`` (0 where it is not held)."""
        local = e - first_expert
        here = (local >= 0) & (local < held)
        i = jnp.clip(local, 0, held - 1)
        part = _swiglu(y, p["mlp.experts_gate"][i], p["mlp.experts_up"][i],
                       p["mlp.experts_down"][i])
        return jnp.where(here, we, 0.0) * part

    for j in range(top_k):
        out = out + jax.vmap(one_token)(n, chosen[:, j], w[:, j])
    return out


def solar_logits(params, ids, cfg):
    """Float32 logits ``[len(ids), vocab held]`` of the one sequence
    ``ids`` under ``params`` (``SolarOpen2ForCausalLM.raw_state()``
    names); ``cfg`` a ``SolarOpen2Config``."""
    f32 = lambda a: jnp.asarray(a).astype(F32)
    eps = cfg.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        x = f32(params["solar.embed_tokens.weight"])[jnp.asarray(ids)]
        for li in range(cfg.num_hidden_layers):
            pre = f"solar.layers.{li}."
            p = {name[len(pre):]: f32(a) for name, a in params.items()
                 if name.startswith(pre)}
            n = _rms(x, p["input_layernorm.weight"], eps)
            if cfg.is_gqa(li):
                x = x + _gqa(n, p, cfg.num_attention_heads,
                             cfg.num_key_value_heads, jnp.asarray(
                                 params[pre + "self_attn.k_proj.weight"]
                             ).dtype)
            else:
                x = x + _kda(n, p, cfg.linear_num_heads, eps,
                             cfg.kda_allow_neg_eigval)
            n = _rms(x, p["post_attention_layernorm.weight"], eps)
            x = x + solar_ffn(n, p, top_k=cfg.num_experts_per_tok,
                              first_expert=cfg.first_expert,
                              scale=cfg.routed_scaling_factor)
        return _rms(x, f32(params["solar.norm.weight"]), eps) \
            @ f32(params["lm_head.weight"])
