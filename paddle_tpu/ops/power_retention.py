"""Degree-2 power retention (Manifest AI, arXiv:2507.04239): gated linear
attention whose feature map is the symmetric square of the head vector.

For one sequence, per query head ``a`` with KV head ``h = a // rep``
(all sums over ``j <= i``; ``lg <= 0`` is the log of a sigmoid gate a KV
head and position):

    A_ij = ((q_i . k_j) / sqrt(D))^2 * exp(sum_{m=j+1..i} lg_m)
    y_i  = sum_j A_ij v_j / (sum_j A_ij + EPS)

``phi(u)`` holds the D(D+1)/2 distinct products ``u_r u_s`` (off-diagonal
ones times sqrt 2), so that ``phi(a) . phi(b) == (a . b)^2`` exactly and
the same layer is a recurrence with a fixed-size state a KV head:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        [P, D],  P = D(D+1)/2
    z_t = g_t z_{t-1} + phi(k_t)              [P]
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + D * EPS)

(the 1/D of the scale moved to the epsilon). Three forms that agree:
``retention_attention`` (the ``A`` form, rows in blocks) with
``retention_state`` (the state after the last position: a prefill),
``retention_chunked`` (inside a chunk the ``A`` form, across chunks the
state) and ``retention_recurrent`` (a token at a time);
``retention_decode`` advances a batch of slots' states by one token,
on one TPU device with a Pallas kernel that reads and writes each
active slot's state once, in place.

Everything here is float32 with ``Precision.HIGHEST`` products: a square
doubles a product's relative error, and the state is a long sum.

The order of ``phi``: pairs by cyclic offset ``o``, entry ``o * D + i``
is ``(i, (i + o) % D)`` for ``o < D/2``, and the last ``D/2`` entries are
``(i, i + D/2)``: every entry of one offset is one lane roll away from
the vector itself, which is what the kernel computes it by.

The state's layout on the device (``state_to_layout``): the same
``P x D`` numbers with each offset's ``[D (pair), D (value)]`` block
transposed, so that the kernel finds the pair index on the lanes, where
``phi`` of one token is a row, and the value index on the sublanes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["EPS", "phi", "phi_size", "retention_attention",
           "retention_state", "retention_chunked", "retention_recurrent",
           "retention_decode", "state_to_layout", "state_from_layout"]

EPS = 1e-6
SQRT2 = math.sqrt(2.0)
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_einsum = functools.partial(jnp.einsum, precision=_HI,
                            preferred_element_type=F32)


def phi_size(d: int) -> int:
    return d * (d + 1) // 2


def phi(u):
    """``[..., D] -> [..., D(D+1)/2]``, D even, in the offset order of
    the module docstring."""
    d = u.shape[-1]
    if d % 2:
        raise ValueError(f"phi needs an even head size, got {d}")
    h = d // 2
    uu = jnp.concatenate([u, u], axis=-1)
    rows = [u * u]
    rows += [SQRT2 * u * uu[..., o:o + d] for o in range(1, h)]
    rows.append(SQRT2 * u[..., :h] * u[..., h:])
    return jnp.concatenate(rows, axis=-1)


def state_to_layout(S):
    """``[..., P, D]`` (pair-major: ``S[p, d] = sum w phi(k)[p] v[d]``)
    to the device layout of the same shape. Row ``o * D + d``, lane
    ``i`` holds pair ``(o, i)``, value ``d``; the half offset's ``D/2``
    rows hold values ``r`` and ``r + D/2`` in their lane halves."""
    *lead, P, d = S.shape
    h = d // 2
    main = jnp.swapaxes(S[..., :h * d, :].reshape(*lead, h, d, d), -1, -2)
    tail = S[..., h * d:, :].reshape(*lead, h, 2, h)      # [i, half, r]
    tail = jnp.moveaxis(tail, -3, -1)                     # [half, r, i]
    tail = jnp.swapaxes(tail, -3, -2).reshape(*lead, h, d)  # [r, half*i]
    return jnp.concatenate([main.reshape(*lead, h * d, d), tail], axis=-2)


def state_from_layout(L):
    """The inverse of ``state_to_layout``."""
    *lead, P, d = L.shape
    h = d // 2
    main = jnp.swapaxes(L[..., :h * d, :].reshape(*lead, h, d, d), -1, -2)
    tail = L[..., h * d:, :].reshape(*lead, h, 2, h)      # [r, half, i]
    tail = jnp.swapaxes(tail, -3, -2)                     # [half, r, i]
    tail = jnp.moveaxis(tail, -1, -3).reshape(*lead, h, d)  # [i, half*r]
    return jnp.concatenate([main.reshape(*lead, h * d, d), tail], axis=-2)


def _heads(q, k):
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    if Hq % Hkv:
        raise ValueError(f"{Hq} query heads over {Hkv} KV heads")
    return T, Hkv, Hq // Hkv, D


def _zero_state(Hkv: int, D: int):
    P = phi_size(D)
    return jnp.zeros((Hkv, P, D), F32), jnp.zeros((Hkv, P), F32)


def _masked(lg, k, valid):
    """Positions that are padding leave the state alone: gate 1 and no
    key."""
    if valid is None:
        return lg, k
    return (jnp.where(valid[:, None], lg, 0.0),
            jnp.where(valid[:, None, None], k, 0.0))


def retention_recurrent(q, k, v, lg, state=None):
    """A token at a time: ``q [T, Hq, D]``, ``k, v [T, Hkv, D]``,
    ``lg [T, Hkv]``; returns ``y [T, Hq, D]`` and the state ``(S
    [Hkv, P, D] pair-major, z [Hkv, P])`` after the last token."""
    T, Hkv, rep, D = _heads(q, k)
    q, k, v, lg = (a.astype(F32) for a in (q, k, v, lg))

    def step(carry, x):
        S, z = carry
        qt, kt, vt, lgt = x
        g, pk = jnp.exp(lgt), phi(kt)
        S = g[:, None, None] * S + pk[:, :, None] * vt[:, None, :]
        z = g[:, None] * z + pk
        pq = phi(qt).reshape(Hkv, rep, -1)
        num = _einsum("hrp,hpd->hrd", pq, S)
        den = _einsum("hrp,hp->hr", pq, z)
        return (S, z), (num / (den[..., None] + D * EPS)).reshape(-1, D)

    carry = _zero_state(Hkv, D) if state is None else state
    carry, y = jax.lax.scan(step, carry, (q, k, v, lg))
    return y, carry


def _decay(ci, cj, mask):
    """``exp(ci - cj)`` where ``mask``, else 0; ``ci [I, H]``, ``cj
    [J, H]`` cumulative log gates -> ``[H, I, J]``."""
    diff = ci.T[:, :, None] - cj.T[:, None, :]
    return jnp.exp(jnp.where(mask[None], diff, -jnp.inf))


def retention_attention(q, k, v, lg, block: int = 512):
    """The ``A`` form over one whole sequence, ``block`` rows at a time
    (the ``[Hq, block, T]`` weights are the workspace); no state. Rows
    past a prompt's end see the padding, rows before it do not."""
    T, Hkv, rep, D = _heads(q, k)
    q, k, v, lg = (a.astype(F32) for a in (q, k, v, lg))
    block = min(block, T)
    pad = -T % block
    qh = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, Hkv, rep, D)
    cum = jnp.cumsum(lg, axis=0)
    ci = jnp.pad(cum, ((0, pad), (0, 0))).reshape(-1, block, Hkv)
    j = jnp.arange(T)

    def rows(x):
        qb, cb, i0 = x
        s = _einsum("ihrd,jhd->hrij", qb, k) / math.sqrt(D)
        mask = j[None, :] <= (i0 + jnp.arange(block))[:, None]
        A = s * s * _decay(cb, cum, mask)[:, None]
        num = _einsum("hrij,jhd->ihrd", A, v)
        den = jnp.moveaxis(A.sum(-1), -1, 0)
        return num / (den[..., None] + EPS)

    y = jax.lax.map(rows, (qh, ci, jnp.arange(qh.shape[0]) * block))
    return y.reshape(-1, Hkv * rep, D)[:T]


def retention_state(k, v, lg, valid=None, chunk: int = 256):
    """The state after the last position, pair-major: ``phi`` of
    ``chunk`` keys at a time is the workspace. ``valid [T]`` marks the
    real positions of a padded bucket."""
    T, Hkv, D = k.shape
    k, v, lg = (a.astype(F32) for a in (k, v, lg))
    lg, k = _masked(lg, k, valid)
    cum = jnp.cumsum(lg, axis=0)
    w = jnp.exp(cum[-1][None] - cum)
    chunk = min(chunk, T)
    pad = -T % chunk
    kw = jnp.pad(k, ((0, pad), (0, 0), (0, 0))).reshape(-1, chunk, Hkv, D)
    vc = jnp.pad(v, ((0, pad), (0, 0), (0, 0))).reshape(-1, chunk, Hkv, D)
    wc = jnp.pad(w, ((0, pad), (0, 0))).reshape(-1, chunk, Hkv)

    def add(carry, x):
        S, z = carry
        kc, vv, ww = x
        pk = phi(kc) * ww[..., None]
        return (S + _einsum("jhp,jhd->hpd", pk, vv), z + pk.sum(0)), None

    (S, z), _ = jax.lax.scan(add, _zero_state(Hkv, D), (kw, vc, wc))
    return S, z


def retention_chunked(q, k, v, lg, chunk: int = 128, state=None,
                      valid=None):
    """Inside a chunk the ``A`` form, across chunks the state: ``y
    [T, Hq, D]`` and the state after the last position, for any ``T``
    (the last chunk is padded with positions that are not ``valid``)."""
    T, Hkv, rep, D = _heads(q, k)
    q, k, v, lg = (a.astype(F32) for a in (q, k, v, lg))
    chunk = min(chunk, T)
    pad = -T % chunk
    valid = jnp.ones(T, bool) if valid is None else valid
    valid = jnp.pad(valid, (0, pad))
    q, k, v = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (q, k, v))
    lg, k = _masked(jnp.pad(lg, ((0, pad), (0, 0))), k, valid)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(carry, x):
        S, z = carry
        qc, kc, vc, lc = x
        qc = qc.reshape(chunk, Hkv, rep, D)
        cum = jnp.cumsum(lc, axis=0)
        s = _einsum("ihrd,jhd->hrij", qc, kc)
        A = s * s * _decay(cum, cum, tri)[:, None]
        pq, dq = phi(qc), jnp.exp(cum)[:, :, None]
        num = _einsum("hrij,jhd->ihrd", A, vc) \
            + dq[..., None] * _einsum("ihrp,hpd->ihrd", pq, S)
        den = jnp.moveaxis(A.sum(-1), -1, 0) \
            + dq * _einsum("ihrp,hp->ihr", pq, z)
        y = num / (den[..., None] + D * EPS)
        pk = phi(kc) * jnp.exp(cum[-1][None] - cum)[..., None]
        g = jnp.exp(cum[-1])
        S = g[:, None, None] * S + _einsum("jhp,jhd->hpd", pk, vc)
        z = g[:, None] * z + pk.sum(0)
        return (S, z), y.reshape(chunk, Hkv * rep, D)

    split = lambda a: a.reshape(-1, chunk, *a.shape[1:])
    carry = _zero_state(Hkv, D) if state is None else state
    carry, y = jax.lax.scan(one, carry,
                            (split(q), split(k), split(v), split(lg)))
    return y.reshape(-1, Hkv * rep, D)[:T], carry


# -- one-token decode over a batch of slots -----------------------------

def _decode_kernel(slots_ref, n_ref, q_ref, kvg_ref, s_ref, y_ref, so_ref):
    """One (slot, KV head): the state streams through VMEM once. Rows
    ``o * D + d``, lanes ``i`` (``state_to_layout``): each offset's
    block is scaled by the gate, gains ``v[d] * phi(k)[o, i]`` and is
    written back; ``acc[a][d, i]`` gathers ``S[d, i] * phi(q_a)[o, i]``
    over the offsets and is summed over the lanes at the end. ``phi`` of
    a token is a lane roll of its row away. Grid steps past the active
    slots repeat the last block and do nothing."""
    del slots_ref

    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        rep, D = q_ref.shape[-2:]
        h = D // 2
        Q = q_ref[0, 0]                                   # [rep, D]
        K, G = kvg_ref[0, 0, 0:1, :], kvg_ref[0, 0, 2:3, :]
        # the value on the sublanes: Vc[d, i] = v[d]
        Vc = jnp.broadcast_to(kvg_ref[0, 0, 1:2, :], (D, D)).T

        def offset(o, r0, acc, scale, shift):
            pk = scale * K * pltpu.roll(K, shift, 1)
            pq = scale * Q * pltpu.roll(Q, shift, 1)
            s = G * s_ref[0, 0, pl.ds(r0, D), :] + Vc * pk
            so_ref[0, 0, pl.ds(r0, D), :] = s
            return tuple(acc[a] + s * pq[a:a + 1, :] for a in range(rep))

        acc = offset(0, 0, (jnp.zeros((D, D), F32),) * rep, 1.0, 0)
        acc = jax.lax.fori_loop(
            1, h, lambda o, acc: offset(
                o, pl.multiple_of(o * D, D), acc, SQRT2, D - o), acc)
        # the half offset: pairs (i, i + D/2), D/2 rows whose lane halves
        # hold the values r and r + D/2
        low = jax.lax.broadcasted_iota(jnp.int32, (h, D), 1) < h
        pk = SQRT2 * K * pltpu.roll(K, h, 1)
        pq = SQRT2 * Q * pltpu.roll(Q, h, 1)
        s = G * s_ref[0, 0, h * D:h * D + h, :] \
            + jnp.where(low, Vc[:h], Vc[h:]) * pk
        so_ref[0, 0, h * D:h * D + h, :] = s
        for a in range(rep):
            t = s * pq[a:a + 1, :]
            full = acc[a] + jnp.concatenate(
                [jnp.where(low, t, 0.0), jnp.where(low, 0.0, t)], axis=0)
            # lanes to sublanes, so that the sum over the pairs leaves
            # the value index on the lanes of the output row
            y_ref[0, 0, a:a + 1, :] = jnp.sum(full.T, axis=0,
                                              keepdims=True)


def _decode_pallas(q, kvg, S, active, interpret: bool):
    """``q [B, Hkv, rep, D]``, ``kvg [B, Hkv, 3, D]`` (key, value, gate
    on every lane), ``S [B, Hkv, P, D]`` in the device layout; the
    numerators ``[B, Hkv, rep, D]`` (rows of slots that are not active
    are not written) and the state, updated in place."""
    B, Hkv, rep, D = q.shape
    P = S.shape[2]
    # active slots first; at least one grid step computes, so that the
    # block every later step repeats has been filled (a slot that is
    # not active arrives with gate 1 and no key: its state is rewritten
    # as it was)
    order = jnp.argsort(jnp.logical_not(active), stable=True) \
        .astype(jnp.int32)
    n = jnp.maximum(jnp.sum(active), 1).astype(jnp.int32).reshape(1)

    def at(b, hh, slots, n):
        live = b < n[0]
        return (slots[jnp.minimum(b, n[0] - 1)],
                jnp.where(live, hh, Hkv - 1), 0, 0)

    small = lambda rows: pl.BlockSpec((1, 1, rows, D), at)
    state = pl.BlockSpec((1, 1, P, D), at)
    y, S = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, Hkv),
            in_specs=[small(rep), small(3), state],
            out_specs=[small(rep), state]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, F32),
                   jax.ShapeDtypeStruct(S.shape, F32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_decode_vmem_bytes(P, D)),
        cost_estimate=pl.CostEstimate(
            flops=(3 + 2 * rep) * B * Hkv * P * D, transcendentals=0,
            bytes_accessed=2 * B * Hkv * P * D * 4),
        name="retention_decode", interpret=interpret,
    )(order, n, q, kvg, S)
    return y, S


def _decode_vmem_bytes(P: int, D: int) -> int:
    """A whole state block in and out, each double-buffered, and room
    for the accumulators."""
    return 4 * P * D * 4 + (16 << 20)


def retention_decode(q, k, v, lg, S, z, active, kernel=None):
    """One token a slot: ``q [B, Hq, D]``, ``k, v [B, Hkv, D]``, ``lg
    [B, Hkv]``; ``S [B, Hkv, P, D]`` in the device layout and ``z
    [B, Hkv, P]``, float32; ``active [B]``. Returns ``y [B, Hq, D]``
    (zero for slots that are not active) and the new ``S`` and ``z``:
    those of slots that are not active are unchanged. ``kernel``: the
    Pallas kernel (``True``; interpreted on the CPU) or ``jax.numpy``
    (``False``); by default the kernel where one un-partitioned TPU
    program is traced."""
    from .pallas_ops import _interpret, single_device_tpu
    B, Hq, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    on = active[:, None]
    g = jnp.where(on, jnp.exp(lg.astype(F32)), 1.0)
    k = jnp.where(on[..., None], k.astype(F32), 0.0)
    v = jnp.where(on[..., None], v.astype(F32), 0.0)
    qh = q.astype(F32).reshape(B, Hkv, rep, D)
    pk, pq = phi(k), phi(qh)
    z = g[..., None] * z + pk
    den = _einsum("bhrp,bhp->bhr", pq, z)
    if single_device_tpu() if kernel is None else kernel:
        kvg = jnp.stack([k, v, jnp.broadcast_to(g[..., None], k.shape)],
                        axis=2)
        # the kernel serves and is never differentiated: with its inputs
        # held constant, a caller that traces for gradients (the
        # framework's op dispatch does whenever a parameter is
        # trainable) does not reach for a derivative the kernel has not
        num, S = _decode_pallas(*jax.lax.stop_gradient((qh, kvg, S)),
                                active, _interpret())
    else:
        Sm = state_from_layout(S)
        Sm = g[..., None, None] * Sm + pk[..., None] * v[:, :, None, :]
        num = _einsum("bhrp,bhpd->bhrd", pq, Sm)
        S = state_to_layout(Sm)
    y = num / (den[..., None] + D * EPS)
    return jnp.where(on[..., None], y.reshape(B, Hq, D), 0.0), S, z
