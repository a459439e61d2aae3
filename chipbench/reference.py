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


def _int8_matmul(x, w):
    """``x @ w`` as an int8 MXU computes it: rows of ``x`` and columns
    of ``w`` rounded to 127 levels of their largest magnitude, an int32
    dot, the two scales multiplied back. The control of a bfloat16
    configuration (see ``llama_logits``), nothing a cell is timed on."""
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
    sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0 + 1e-30
    xq = jnp.round(x / sx).astype(jnp.int8)
    wq = jnp.round(w / sw).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(F32) * sx * sw


_MATMUL = {"f32": jnp.matmul, "int8": _int8_matmul}


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta",
                                   "precision"))
def _llama_layer(x, lp, heads, kv_heads, eps, theta, precision="f32"):
    lp = _f32(lp)
    mm = _MATMUL[precision]
    T, D = x.shape
    d = lp["q"].shape[1] // heads
    h = _rms(x, lp["ln1"], eps)
    q = _rope(mm(h, lp["q"]).reshape(T, heads, d), theta)
    k = _rope(mm(h, lp["k"]).reshape(T, kv_heads, d), theta)
    v = mm(h, lp["v"]).reshape(T, kv_heads, d)
    rep = heads // kv_heads
    a = _causal_attention(q, jnp.repeat(k, rep, axis=1),
                          jnp.repeat(v, rep, axis=1))
    x = x + mm(a.reshape(T, heads * d), lp["o"])
    h = _rms(x, lp["ln2"], eps)
    return x + mm(jax.nn.silu(mm(h, lp["gate"])) * mm(h, lp["up"]),
                  lp["down"])


@partial(jax.jit, static_argnames=("eps", "precision"))
def _llama_logits(x, norm, head, eps, precision="f32"):
    return _MATMUL[precision](_rms(x, norm.astype(F32), eps),
                              head.astype(F32))


@jax.jit
def _gaps(logits, tokens):
    """How far, at each position, the logit of ``tokens[i]`` lies under
    the largest, in standard deviations of that position's logits."""
    chosen = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return (jnp.max(logits, axis=1) - chosen) / jnp.std(logits, axis=1)


_LLAMA_LEAVES = {
    "ln1": "input_layernorm.weight", "q": "self_attn.q_proj.weight",
    "k": "self_attn.k_proj.weight", "v": "self_attn.v_proj.weight",
    "o": "self_attn.o_proj.weight",
    "ln2": "post_attention_layernorm.weight",
    "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
    "down": "mlp.down_proj.weight"}


def llama_logits(params, ids, *, layers: int, heads: int, kv_heads: int,
                 eps: float, theta: float, precision: str = "f32"):
    """Float32 logits [len(ids), vocab], on the device, of the one
    sequence ``ids`` under ``params`` (name -> array: the benchmark's
    own draw from the seed). ``precision`` is that of every matrix
    product: ``"f32"`` the reference, ``"int8"`` the CONTROL's, the
    precision one below the configuration's bfloat16."""
    with jax.default_matmul_precision("highest"):
        x = params["llama.embed_tokens.weight"][np.asarray(ids)
                                                ].astype(F32)
        for li in range(layers):
            lp = {k: params[f"llama.layers.{li}.{name}"]
                  for k, name in _LLAMA_LEAVES.items()}
            x = _llama_layer(x, lp, heads, kv_heads, eps, theta, precision)
        return _llama_logits(x, params["llama.norm.weight"],
                             params["lm_head.weight"], eps, precision)


def llama_served_gaps(params, prompt, outputs, *, pad_to: int = 0,
                      control: bool = False, **model) -> np.ndarray:
    """One finished request against the plain reference: for each served
    token, how far the reference's logit of it lies under the
    reference's best at that position (``_gaps``; 0 = the reference
    would have served it). One forward over prompt + outputs, padded at
    its end to a multiple of ``pad_to`` (under a causal mask what
    follows a position cannot reach it), so that a run compiles one
    program for all its requests.

    ``control=True`` reads instead, at the same positions, the gap of
    the token that the int8 forward puts first: what a server computing
    one precision below the configuration would have served. It has to
    fail the cell's limit and is never what a cell is held to."""
    ids = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(outputs, np.int64)])
    first, n = len(prompt) - 1, len(ids) - 1
    pad = np.zeros(-n % pad_to if pad_to else 0, np.int64)
    # logits at position p predict token p + 1
    inputs = np.concatenate([ids[:-1], pad])
    tokens = jnp.asarray(np.concatenate([ids[1:], pad]))
    logits = llama_logits(params, inputs, **model)
    if control:
        tokens = jnp.argmax(llama_logits(
            params, inputs, precision="int8", **model), axis=1)
    return np.asarray(_gaps(logits, tokens), np.float64)[first:n]


# -- tolerances ---------------------------------------------------------
# Training: |trainer loss - reference loss| / reference loss at the
# first step, before any update. The committed recipe runs the block
# matmuls on the int8 MXU (per-row round-to-nearest: ~2^-8 relative
# noise a product, averaging out over 2048-wide contractions) over bf16
# activations. PR 25 measured 1.0e-4 and 2.0e-4 on the chip on two seeds
# (PERF.md Findings); the bound is five times the larger. A dropped
# layer, a wrong mask or an untied head moves the loss by percents.
GPT_LOSS_RTOL = 1e-3

# Serving: at each served position of a sample of the requests a window
# finished, how far the reference's logit of the served token lies under
# the reference's largest logit, in units of the standard deviation of
# that position's reference logits (``llama_served_gaps``). Greedy tokens
# of a bf16 model differ from a float32 one's only where the top logits
# nearly tie. Two numbers of those gaps are held, each for what it
# separates (every reading, with its seed: PERF.md, Findings, PR 29; my
# chip runs at the cells' own size, 12 requests and 1,300-2,400 tokens a
# run):
#
# the WIDEST gap is what one wrong token moves. Sound runs read 0.0126 to
# 0.0559 (95 runs); one token in two hundred altered where the engine
# samples it reads 3.83 to 5.55 (``chipbench.planted``, 6 seeds). The
# int8 control reads 0.0917 to 0.170 (17 runs), under three times the
# sound runs' largest: the widest gap is a maximum and grows with the
# noise itself, so the control is not what it is held against.
LLAMA_LOGIT_TOL_STD = 0.1
# the MEAN gap over all the tokens compared is what a lower precision
# moves (the share of positions that flip grows with the noise and so
# does the size of each gap). Sound runs read 4.9e-5 to 5.50e-4 (95
# runs on 40 seeds, both traffics); the int8 control 1.49e-3 to 7.70e-3
# (17 runs), 2.7 times the sound runs' largest at the least and 13 times
# the same run's own reading or more. The limit stands 1.8 times over
# the one and 1.5 times under the other (PERF.md says what was tried to
# part them further).
LLAMA_LOGIT_MEAN_TOL_STD = 0.001
