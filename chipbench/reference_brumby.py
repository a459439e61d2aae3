"""Plain reference of the Brumby configuration: the published shape
(Qwen3-14B's) with every attention a degree-2 power-retention layer, in
``jax.numpy``; the benchmark's own copy of
``paddle_tpu/models/brumby_reference.py``, in blocks so that 4096
positions fit beside 8.4 GB of weights.

Float32, ``default_matmul_precision("highest")``, no state, no cache, no
batching, no kernel: one sequence, a layer at a time, each layer's
weights upcast as it is reached; the ``A`` form, ``ROWS`` rows of it at
a time (``[heads, ROWS, T]`` floats, 335 MB at 4096 positions), and the
head ``ROWS`` positions at a time (the whole ``[4096, 151936]`` logits
would be 2.5 GB beside a 3.1 GB float32 head). A layer (x ``[T, C]``;
a = query head, h = a // rep its KV head; sums over j <= i):

    n   = rmsnorm(x; w_in)
    q   = rope(rmsnorm_head(n Wq; w_qn))     k = rope(rmsnorm_head(n Wk; w_kn))
    v   = n Wv                               lg = -softplus(-(n Wg))
    A_ij^a = ((q_i^a . k_j^h) / sqrt(D))^2 * exp(sum_{m=j+1..i} lg_m^h)
    y_i^a  = sum_j A_ij^a v_j^h / (sum_j A_ij^a + 1e-6)
    x'  = x + concat_a(y^a) Wo
    x'' = x' + Wdown(silu(Wgate n') * Wup n'),   n' = rmsnorm(x'; w_post)

It reads the program's parameter arrays and nothing else of it. The
limits are ``reference.py``'s, as they stand.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .reference import F32, _MATMUL, _f32, _gaps, _rms, _rope

EPS = 1e-6     # the normaliser's (assumed: the configuration file)
ROWS = 512     # rows of A, and positions of the head, computed at once

_LEAVES = {
    "ln1": "input_layernorm.weight", "q": "retention.q_proj.weight",
    "k": "retention.k_proj.weight", "v": "retention.v_proj.weight",
    "g": "retention.g_proj.weight", "o": "retention.o_proj.weight",
    "qn": "retention.q_norm.weight", "kn": "retention.k_norm.weight",
    "ln2": "post_attention_layernorm.weight",
    "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
    "down": "mlp.down_proj.weight"}


def _retention(q, k, v, lg):
    """q [T, H, D]; k, v [T, KV, D]; lg [T, KV]; T a multiple of ROWS
    or under it."""
    T, H, D = q.shape
    KV = k.shape[1]
    rows = min(ROWS, T)
    cum = jnp.cumsum(lg, axis=0)
    j = jnp.arange(T)

    def block(x):
        qb, cb, i0 = x                  # [rows, KV, rep, D], [rows, KV]
        s = jnp.einsum("ihrd,jhd->hrij", qb, k) / math.sqrt(D)
        causal = j[None, :] <= (i0 + jnp.arange(rows))[:, None]
        decay = jnp.exp(jnp.where(
            causal[None], cb.T[:, :, None] - cum.T[:, None, :], -jnp.inf))
        A = s * s * decay[:, None]
        return jnp.einsum("hrij,jhd->ihrd", A, v) \
            / (jnp.moveaxis(A.sum(-1), -1, 0)[..., None] + EPS)

    y = jax.lax.map(block, (q.reshape(-1, rows, KV, H // KV, D),
                            cum.reshape(-1, rows, KV),
                            jnp.arange(T // rows) * rows))
    return y.reshape(T, H * D)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta",
                                   "precision"))
def _layer(x, lp, heads, kv_heads, eps, theta, precision="f32"):
    lp = _f32(lp)
    mm = _MATMUL[precision]
    T = x.shape[0]
    d = lp["q"].shape[1] // heads
    n = _rms(x, lp["ln1"], eps)
    q = _rope(_rms(mm(n, lp["q"]).reshape(T, heads, d), lp["qn"], eps),
              theta)
    k = _rope(_rms(mm(n, lp["k"]).reshape(T, kv_heads, d), lp["kn"],
                   eps), theta)
    v = mm(n, lp["v"]).reshape(T, kv_heads, d)
    lg = -jax.nn.softplus(-mm(n, lp["g"]))
    x = x + mm(_retention(q, k, v, lg), lp["o"])
    n = _rms(x, lp["ln2"], eps)
    return x + mm(jax.nn.silu(mm(n, lp["gate"])) * mm(n, lp["up"]),
                  lp["down"])


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, head, tokens, eps, precision="f32"):
    """``ROWS`` positions at a time: the gap of ``tokens`` under the
    best logit (``reference._gaps``) and the token the head puts
    first."""
    head, norm = head.astype(F32), norm.astype(F32)
    rows = min(ROWS, x.shape[0])

    def block(xt):
        logits = _MATMUL[precision](_rms(xt[0], norm, eps), head)
        return _gaps(logits, xt[1]), jnp.argmax(logits, axis=1)

    gaps, best = jax.lax.map(block, (x.reshape(-1, rows, x.shape[1]),
                                     tokens.reshape(-1, rows)))
    return gaps.reshape(-1), best.reshape(-1)


def forward(params, ids, tokens, *, layers: int, heads: int,
            kv_heads: int, eps: float, theta: float,
            precision: str = "f32"):
    """One sequence ``ids`` (its length under ``ROWS`` or a multiple of
    it) through the reference: at each position the gap of
    ``tokens[i]`` under the best logit, and the best token.
    ``precision`` is that of every matrix product with a weight:
    ``"f32"`` the reference, ``"int8"`` the CONTROL's."""
    with jax.default_matmul_precision("highest"):
        x = params["brumby.embed_tokens.weight"][np.asarray(ids)
                                                 ].astype(F32)
        for li in range(layers):
            lp = {k: params[f"brumby.layers.{li}.{name}"]
                  for k, name in _LEAVES.items()}
            x = _layer(x, lp, heads, kv_heads, eps, theta, precision)
        return _head(x, params["brumby.norm.weight"],
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
