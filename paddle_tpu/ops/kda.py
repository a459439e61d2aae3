"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692): the gated
delta rule with a decay a channel.

For one sequence and one head, with a state ``S [K, V]`` (key x value),
``q_t, k_t [K]``, ``v_t [V]``, a log decay ``g_t [K] <= 0`` (``alpha_t =
exp(g_t)``) and a step size ``beta_t``:

    S'  = diag(alpha_t) S_(t-1)          u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T                 o_t = S_t^T q_t

What normalises ``q`` and ``k`` and what makes ``g`` and ``beta`` is the
model's (``models/solar.py``); this module is the rule in three forms
that agree:

- ``kda_recurrent``: a token at a time under ``lax.scan``;
- ``kda_chunked``: ``chunk`` tokens at a time, matrix products inside a
  chunk and the state between chunks (a prefill). With ``G_t`` the sum of
  ``g`` over the chunk's tokens up to ``t`` and ``S_0`` the state the
  chunk is entered with:

      A_ts = beta_t sum_c k_tc k_sc exp(G_tc - G_sc)   (s < t, else 0)
      U    = (I + A)^-1 diag(beta) (V - (K * exp(G)) S_0)
      o_t  = (q_t * exp(G_t))^T S_0
             + sum_(s<=t) ((q_t * exp(G_t - G_s)) . k_s) u_s
      S_C  = diag(exp(G_C)) S_0 + sum_s (k_s * exp(G_C - G_s)) u_s^T

  Every exponent is formed as a difference ``G_t - G_s`` with ``s <= t``,
  so it is never positive: ``exp(-G_s)`` alone overflows where a channel
  decays fast;
- ``kda_decode``: one token a slot over a batch of slots' states, on one
  TPU device with a Pallas kernel (``kda_decode`` on the device's
  per-operation line) that streams each active slot's state through VMEM
  once and writes it back in place; slots that are not active are not
  touched.

Everything is float32 with ``Precision.HIGHEST`` products: the state is a
long sum, and ``(I + A)^-1`` carries what it is given through a chunk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_recurrent", "kda_chunked", "kda_decode"]

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_einsum = functools.partial(jnp.einsum, precision=_HI,
                            preferred_element_type=F32)


def _zero_state(H: int, K: int, V: int):
    return jnp.zeros((H, K, V), F32)


def kda_recurrent(q, k, v, g, beta, state=None):
    """A token at a time: ``q, k, g [T, H, K]``, ``v [T, H, V]``,
    ``beta [T, H]``; returns ``o [T, H, V]`` and the state ``[H, K, V]``
    after the last token."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None] * S
        u = bt[:, None] * (vt - _einsum("hkv,hk->hv", S, kt))
        S = S + kt[..., None] * u[:, None, :]
        return S, _einsum("hkv,hk->hv", S, qt)

    S0 = _zero_state(q.shape[1], q.shape[2], v.shape[2]) \
        if state is None else state
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o, S


# chunks whose state-free part is computed at once; its workspace is their
# pairwise decays ``[chunks, C, C, H, K]``: 64 x 128 channels x 32 x 32 x
# 8 chunks is 268 MB of float32
_GROUP_ELEMENTS = 1 << 26


def _inside_chunks(q, k, v, G, beta):
    """What a chunk needs that does not depend on the state it is
    entered with, for a group of chunks at once: ``q, k, G [n, C, H, K]``,
    ``v [n, C, H, V]``, ``beta [n, C, H]``. Returns ``qk [n, H, C, C]``
    (``(q_t * exp(G_t - G_s)) . k_s`` for ``s <= t``), and ``(I + A)^-1
    diag(beta)`` applied to ``V`` (``[n, H, C, V]``) and to ``K * exp(G)``
    (``[n, H, C, K]``)."""
    C, V = q.shape[1], v.shape[-1]
    tri = jnp.tril(jnp.ones((C, C), bool))[None, :, :, None, None]
    # W[t, s] = k_s * exp(G_t - G_s) for s <= t, a channel
    W = k[:, None] * jnp.exp(jnp.where(tri, G[:, :, None] - G[:, None],
                                       -jnp.inf))
    kk = _einsum("nthc,ntshc->nhts", k, W)
    qk = _einsum("nthc,ntshc->nhts", q, W)
    bh = jnp.moveaxis(beta, 1, 2)[..., None]              # [n, H, C, 1]
    lower = jnp.eye(C, dtype=F32) + bh * kk * jnp.tril(
        jnp.ones((C, C), F32), -1)
    rhs = bh * jnp.concatenate([jnp.moveaxis(v, 1, 2),
                                jnp.moveaxis(k * jnp.exp(G), 1, 2)], -1)
    with jax.default_matmul_precision("highest"):
        sol = jax.scipy.linalg.solve_triangular(
            lower, rhs, lower=True, unit_diagonal=True)
    return qk, sol[..., :V], sol[..., V:]


def kda_chunked(q, k, v, g, beta, chunk: int = 64, state=None,
                valid=None):
    """The chunked form (module docstring) over any ``T``: ``o [T, H,
    V]`` and the state after the last VALID position. ``valid [T]``
    marks the real positions of a padded bucket: a position that is not
    valid has decay 1, step 0 and no key, and leaves the state alone.

    ``U = U' - W' S_0`` with ``U' = (I + A)^-1 diag(beta) V`` and ``W' =
    (I + A)^-1 diag(beta) (K * exp(G))``, neither of which knows the
    state: they, and the products inside a chunk, are computed for many
    chunks at once; only three products a chunk wait for the state."""
    T, H, K = q.shape
    V = v.shape[2]
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    C = min(chunk, T)
    pad = -T % C
    valid = jnp.ones(T, bool) if valid is None else valid
    pads = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    split = lambda a: pads(a).reshape(-1, C, *a.shape[1:])
    q, k, v, g, beta, valid = (split(a)
                               for a in (q, k, v, g, beta, valid))
    N = q.shape[0]
    n = max(1, min(N, _GROUP_ELEMENTS // (C * C * H * K)))
    while N % n:
        n -= 1

    def one(S, x):
        qg, kd, decay, qk, Uq, Wq = x
        U = Uq - _einsum("hck,hkv->hcv", Wq, S)
        o = _einsum("thk,hkv->thv", qg, S) \
            + _einsum("hts,hsv->thv", qk, U)
        S = decay[..., None] * S + _einsum("shk,hsv->hkv", kd, U)
        return S, o

    def group(S, x):
        """``n`` chunks: what needs no state for all of them at once,
        then the state through them one after the other."""
        q, k, v, g, beta, real = x
        # a position that is not real: decay 1, step 0, no key
        k = jnp.where(real[..., None, None], k, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
        G = jnp.cumsum(jnp.where(real[..., None, None], g, 0.0), axis=1)
        last = G[:, -1]                                   # [n, H, K]
        return jax.lax.scan(one, S, (
            q * jnp.exp(G), k * jnp.exp(last[:, None] - G),
            jnp.exp(last)) + _inside_chunks(q, k, v, G, beta))

    S0 = _zero_state(H, K, V) if state is None else state
    S, o = jax.lax.scan(group, S0, tuple(
        a.reshape(N // n, n, *a.shape[1:])
        for a in (q, k, v, g, beta, valid)))
    return o.reshape(-1, H, V)[:T], S


# -- one-token decode over a batch of slots -----------------------------

def _decode_kernel(slots_ref, n_ref, cols_ref, rows_ref, s_ref, o_ref,
                   so_ref):
    """One (slot, block of heads): each head's ``[K, V]`` state streams
    through VMEM once. ``cols`` holds, a head, the decay, the key and
    the query down the sublanes (the key index), one lane each; ``rows``
    the value and the step size along the lanes (the value index). Grid
    steps past the active slots repeat the last block and do nothing."""
    del slots_ref

    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        hb = s_ref.shape[1]
        for i in range(hb):
            a = cols_ref[0, 0, :, 3 * i:3 * i + 1]            # [K, 1]
            k = cols_ref[0, 0, :, 3 * i + 1:3 * i + 2]
            q = cols_ref[0, 0, :, 3 * i + 2:3 * i + 3]
            v = rows_ref[0, 0, i:i + 1, :]                    # [1, V]
            b = rows_ref[0, 0, hb + i:hb + i + 1, :]
            s = a * s_ref[0, i]
            u = b * (v - jnp.sum(s * k, axis=0, keepdims=True))
            s = s + k * u
            so_ref[0, i] = s
            o_ref[0, 0, i:i + 1, :] = jnp.sum(s * q, axis=0,
                                              keepdims=True)


def _head_block(H: int, target: int = 16) -> int:
    hb = min(H, target)
    while H % hb:
        hb -= 1
    return hb


def _decode_pallas(alpha, k, q, v, beta, S, active, interpret: bool):
    """``alpha, k, q [B, H, K]``, ``v [B, H, V]``, ``beta [B, H]``, ``S
    [B, H, K, V]``; the outputs ``[B, H, V]`` (rows of slots that are
    not active are not written) and the state, updated in place."""
    B, H, K, V = S.shape
    hb = _head_block(H)
    nb = H // hb
    # a head's three key-indexed vectors side by side on the lanes, the
    # key index on the sublanes: [B, nb, K, 3 * hb]
    cols = jnp.stack([alpha, k, q], axis=-1).reshape(B, nb, hb, K, 3)
    cols = jnp.moveaxis(cols, 3, 2).reshape(B, nb, K, 3 * hb)
    rows = jnp.concatenate(
        [v.reshape(B, nb, hb, V),
         jnp.broadcast_to(beta.reshape(B, nb, hb, 1), (B, nb, hb, V))],
        axis=2)                                        # [B, nb, 2hb, V]
    # active slots first; at least one grid step computes, so that the
    # block every later step repeats has been filled (a slot that is
    # not active arrives with decay 1, step 0 and no key: its state is
    # rewritten as it was)
    order = jnp.argsort(jnp.logical_not(active), stable=True) \
        .astype(jnp.int32)
    n = jnp.maximum(jnp.sum(active), 1).astype(jnp.int32).reshape(1)

    def at(b, j, slots, n):
        live = b < n[0]
        return (slots[jnp.minimum(b, n[0] - 1)],
                jnp.where(live, j, nb - 1), 0, 0)

    state = pl.BlockSpec((1, hb, K, V), at)
    o, S = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, nb),
            in_specs=[pl.BlockSpec((1, 1, K, 3 * hb), at),
                      pl.BlockSpec((1, 1, 2 * hb, V), at), state],
            out_specs=[pl.BlockSpec((1, 1, hb, V), at), state]),
        out_shape=[jax.ShapeDtypeStruct((B, nb, hb, V), F32),
                   jax.ShapeDtypeStruct(S.shape, F32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * hb * K * V * 4 + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=7 * B * H * K * V, transcendentals=0,
            bytes_accessed=2 * B * H * K * V * 4),
        name="kda_decode", interpret=interpret,
    )(order, n, cols, rows, S)
    return o.reshape(B, H, V), S


def kda_decode(q, k, v, g, beta, S, active, kernel=None):
    """One token a slot: ``q, k, g [B, H, K]``, ``v [B, H, V]``, ``beta
    [B, H]``, ``S [B, H, K, V]`` float32, ``active [B]``. Returns ``o
    [B, H, V]`` (zero for slots that are not active) and the new state:
    that of slots that are not active is unchanged. ``kernel``: the
    Pallas kernel (``True``; interpreted on the CPU) or ``jax.numpy``
    (``False``); by default the kernel where one un-partitioned TPU
    program is traced."""
    from .pallas_ops import _interpret, single_device_tpu
    on = active[:, None, None]
    alpha = jnp.where(on, jnp.exp(g.astype(F32)), 1.0)
    k = jnp.where(on, k.astype(F32), 0.0)
    beta = jnp.where(active[:, None], beta.astype(F32), 0.0)
    q, v = q.astype(F32), v.astype(F32)
    if single_device_tpu() if kernel is None else kernel:
        # the kernel serves and is never differentiated (see
        # power_retention.retention_decode)
        o, S = _decode_pallas(*jax.lax.stop_gradient(
            (alpha, k, q, v, beta, S)), active, _interpret())
    else:
        S = alpha[..., None] * S
        u = beta[..., None] * (v - _einsum("bhkv,bhk->bhv", S, k))
        S = S + k[..., None] * u[:, :, None, :]
        o = _einsum("bhkv,bhk->bhv", S, q)
    return jnp.where(on, o, 0.0), S
