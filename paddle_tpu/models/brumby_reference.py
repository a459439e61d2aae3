"""The plain reference of ``models/brumby.py``: the forward pass written
from the layer's equations and not from ``ops/power_retention.py``.

``jax.numpy``, float32, ``default_matmul_precision("highest")``, the
``A`` form: no state, no cache, no batching, no kernel, no blocks; one
sequence, every ``[T, T]`` weight matrix whole. A layer (x ``[T, C]``;
a = query head, h = a // rep its KV head; sums over j <= i):

    n   = rmsnorm(x; w_in)
    q   = rope(rmsnorm_head(n Wq; w_qn))     k = rope(rmsnorm_head(n Wk; w_kn))
    v   = n Wv                               lg = -softplus(-(n Wg))
    A_ij^a = ((q_i^a . k_j^h) / sqrt(D))^2 * exp(sum_{m=j+1..i} lg_m^h)
    y_i^a  = sum_j A_ij^a v_j^h / (sum_j A_ij^a + 1e-6)
    x'  = x + concat_a(y^a) Wo
    x'' = x' + Wdown(silu(Wgate n') * Wup n'),   n' = rmsnorm(x'; w_post)

then the final norm and the untied head. Parameters by their
``raw_state()`` names. The tests hold the model's three paths to it
(whole sequence, prefill into a state, decode from states); the
benchmark carries its own copy, in blocks so that 4096 positions fit
(``chipbench/reference_brumby.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["brumby_logits"]

F32 = jnp.float32
EPS = 1e-6          # the normaliser's, assumed (see models/brumby.py)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """x [T, H, D]: rotate pairs (x[i], x[i + D/2]) by pos * theta^(-2i/D)."""
    T, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _retention(q, k, v, lg):
    """q [T, H, D]; k, v [T, KV, D]; lg [T, KV]."""
    T, H, D = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    cum = jnp.repeat(jnp.cumsum(lg, axis=0), rep, axis=1)      # [T, H]
    s = jnp.einsum("ihd,jhd->hij", q, k) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((T, T), bool))[None]
    decay = jnp.exp(jnp.where(
        causal, cum.T[:, :, None] - cum.T[:, None, :], -jnp.inf))
    A = s * s * decay
    return jnp.einsum("hij,jhd->ihd", A, v) \
        / (A.sum(-1).T[:, :, None] + EPS)


def _layer(x, p, heads, kv_heads, eps, theta):
    T = x.shape[0]
    d = p["retention.q_proj.weight"].shape[1] // heads
    n = _rms(x, p["input_layernorm.weight"], eps)
    q = _rope(_rms((n @ p["retention.q_proj.weight"]).reshape(
        T, heads, d), p["retention.q_norm.weight"], eps), theta)
    k = _rope(_rms((n @ p["retention.k_proj.weight"]).reshape(
        T, kv_heads, d), p["retention.k_norm.weight"], eps), theta)
    v = (n @ p["retention.v_proj.weight"]).reshape(T, kv_heads, d)
    lg = -jax.nn.softplus(-(n @ p["retention.g_proj.weight"]))
    x = x + _retention(q, k, v, lg).reshape(T, heads * d) \
        @ p["retention.o_proj.weight"]
    n = _rms(x, p["post_attention_layernorm.weight"], eps)
    return x + (jax.nn.silu(n @ p["mlp.gate_proj.weight"])
                * (n @ p["mlp.up_proj.weight"])) \
        @ p["mlp.down_proj.weight"]


def brumby_logits(params, ids, *, layers: int, heads: int, kv_heads: int,
                  eps: float, theta: float):
    """Float32 logits ``[len(ids), vocab]`` of the one sequence ``ids``
    under ``params`` (``BrumbyForCausalLM.raw_state()`` names)."""
    f32 = lambda a: jnp.asarray(a).astype(F32)
    with jax.default_matmul_precision("highest"):
        x = f32(params["brumby.embed_tokens.weight"])[np.asarray(ids)]
        for li in range(layers):
            pre = f"brumby.layers.{li}."
            p = {name[len(pre):]: f32(a) for name, a in params.items()
                 if name.startswith(pre)}
            x = _layer(x, p, heads, kv_heads, eps, theta)
        return _rms(x, f32(params["brumby.norm.weight"]), eps) \
            @ f32(params["lm_head.weight"])
