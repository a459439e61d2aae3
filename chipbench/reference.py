"""Plain references: the published forward passes in ``jax.numpy``.

Float32, ``default_matmul_precision("highest")`` (on a TPU a float32
matmul otherwise runs in bf16 passes), no kernels, no cache, no
batching: one sequence at a time, one layer at a time, each layer's
weights upcast as it is reached, so a 7.5 GB bf16 model is never held
twice. They read the program's parameter arrays and nothing else of it.

Tolerances, with their reasons, are at the bottom.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _layer_norm(x, g, b, eps):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean((x - m) ** 2, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def _causal_attention(q, k, v):
    """q [T, H, d]; k, v [T, H, d] (already repeated for GQA)."""
    T, _, d = q.shape
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


# -- GPT (Brown et al. 2020 / GPT-2 block: pre-LN, learned positions,
# gelu, tied head) as GPTSpmdTrainer lays its parameters out ----------

@partial(jax.jit, static_argnames=("num_heads",))
def _gpt_layer(x, bp, num_heads):
    bp = _f32(bp)
    T, D = x.shape
    h = _layer_norm(x, bp["ln1_g"], bp["ln1_b"], 1e-5)
    qkv = (h @ bp["wqkv"] + bp["bqkv"]).reshape(
        T, 3, num_heads, D // num_heads)
    a = _causal_attention(qkv[:, 0], qkv[:, 1], qkv[:, 2])
    x = x + a.reshape(T, D) @ bp["wproj"] + bp["bproj"]
    h = _layer_norm(x, bp["ln2_g"], bp["ln2_b"], 1e-5)
    a = jax.nn.gelu(h @ bp["win"] + bp["bin"], approximate=True)
    return x + a @ bp["wout"] + bp["bout"]


@jax.jit
def _gpt_embed(wte, wpe, ids):
    return wte.astype(F32)[ids] + wpe.astype(F32)[:ids.shape[0]]


@jax.jit
def _gpt_nll_sum(x, g, b, wte, labels):
    h = _layer_norm(x, g.astype(F32), b.astype(F32), 1e-5)
    lp = jax.nn.log_softmax(h @ wte.astype(F32).T, axis=-1)
    return -jnp.sum(jnp.take_along_axis(lp, labels[:, None], axis=-1))


def gpt_layers(blocks):
    """The trainer's ``params["blocks"]`` as a list of per-layer dicts:
    the per-layer pytree ("layer_000": {...}) of ``layer_unroll="full"``
    or the stacked [stages, layers, ...] arrays of the scanned layout."""
    if any(k.startswith("layer_") for k in blocks):
        return [blocks[k] for k in sorted(blocks)]
    S, L = blocks["wqkv"].shape[:2]
    return [jax.tree.map(lambda a: a[s, l], blocks)
            for s in range(S) for l in range(L)]


def gpt_loss(params, ids, labels, num_heads: int) -> float:
    """Mean token cross-entropy of the batch ``ids``/``labels``
    ([B, T] ints) under ``params``."""
    ids, labels = np.asarray(ids), np.asarray(labels)
    layers = gpt_layers(params["blocks"])
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for b in range(ids.shape[0]):
            x = _gpt_embed(params["wte"], params["wpe"], ids[b])
            for bp in layers:
                x = _gpt_layer(x, bp, num_heads)
            total += float(_gpt_nll_sum(
                x, params["ln_f_g"], params["ln_f_b"], params["wte"],
                labels[b]))
    return total / ids.size


# -- Llama-style decoder (Mistral-7B: RMSNorm, rotary positions in the
# half-split convention of the published code, grouped-query attention,
# SwiGLU, untied head), parameters by their ``raw_state()`` names ------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """x [T, H, d]: rotate pairs (x[i], x[i + d/2]) by pos * theta^(-2i/d)."""
    T, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta"))
def _llama_layer(x, lp, heads, kv_heads, eps, theta):
    lp = _f32(lp)
    T, D = x.shape
    d = lp["q"].shape[1] // heads
    h = _rms(x, lp["ln1"], eps)
    q = _rope((h @ lp["q"]).reshape(T, heads, d), theta)
    k = _rope((h @ lp["k"]).reshape(T, kv_heads, d), theta)
    v = (h @ lp["v"]).reshape(T, kv_heads, d)
    rep = heads // kv_heads
    a = _causal_attention(q, jnp.repeat(k, rep, axis=1),
                          jnp.repeat(v, rep, axis=1))
    x = x + a.reshape(T, heads * d) @ lp["o"]
    h = _rms(x, lp["ln2"], eps)
    return x + (jax.nn.silu(h @ lp["gate"]) * (h @ lp["up"])) @ lp["down"]


@partial(jax.jit, static_argnames=("eps",))
def _llama_logits(x, norm, head, eps):
    return _rms(x, norm.astype(F32), eps) @ head.astype(F32)


_LLAMA_LEAVES = {
    "ln1": "input_layernorm.weight", "q": "self_attn.q_proj.weight",
    "k": "self_attn.k_proj.weight", "v": "self_attn.v_proj.weight",
    "o": "self_attn.o_proj.weight",
    "ln2": "post_attention_layernorm.weight",
    "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
    "down": "mlp.down_proj.weight"}


def llama_logits(params, ids, *, layers: int, heads: int, kv_heads: int,
                 eps: float, theta: float, last: int):
    """Float32 logits [last, vocab] at the last ``last`` positions of
    the one sequence ``ids`` under ``params`` (name -> array)."""
    ids = np.asarray(ids)
    with jax.default_matmul_precision("highest"):
        x = params["llama.embed_tokens.weight"][ids].astype(F32)
        for li in range(layers):
            lp = {k: params[f"llama.layers.{li}.{name}"]
                  for k, name in _LLAMA_LEAVES.items()}
            x = _llama_layer(x, lp, heads, kv_heads, eps, theta)
        return np.asarray(_llama_logits(
            x[-last:], params["llama.norm.weight"],
            params["lm_head.weight"], eps))


# -- tolerances ---------------------------------------------------------
# Training: |trainer loss - reference loss| / reference loss at the
# first step, before any update. The committed recipe runs the block
# matmuls on the int8 MXU (per-row round-to-nearest: ~2^-8 relative
# noise a product, averaging out over 2048-wide contractions) over bf16
# activations. PR 25 measured 1.0e-4 and 2.0e-4 on the chip on two seeds
# (PERF.md Findings); the bound is five times the larger. A dropped
# layer, a wrong mask or an untied head moves the loss by percents.
GPT_LOSS_RTOL = 1e-3

# Serving: at each generated position the reference's logit of the token
# the engine chose may lie this far (in units of the standard deviation
# of that position's reference logits) under the reference's largest
# logit. Greedy tokens of a bf16 model differ from a float32 one's only
# where the top logits nearly tie: bf16 carries 8 bits, and its error
# over 16 layers stays a small fraction of the spread between logits,
# while a wrong position, mask, head grouping or cache page moves the
# chosen token's logit by about a standard deviation or more. A scan of
# the check over 24 seeds on the chip (my chip run, PR 25) read 0.0 on
# 16 of them (the engine chose the reference's own largest logit at all
# 32 positions) and at most 0.0217 on the other 8; the bound is about
# five times that.
LLAMA_LOGIT_TOL_STD = 0.1
