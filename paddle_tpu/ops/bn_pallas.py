"""Pallas training BatchNorm for NCHW activations.

Why: on v5e, XLA's BN reduce/apply fusions sustain only ~150-250 GB/s
against the ~660 GB/s the in-house Pallas kernels reach (measured:
the rounds-1-5 notes (git history before PR 23) round-5 ResNet ledger; the 98.8
ms ResNet-50 step
carries ~93 ms of such fusions). BatchNorm is pure streaming work, so
the fix is the same one fused_adamw applied to the optimizer: hand
Pallas the whole pass. Four kernels, each one read (+ at most one
write) of the activation:

  fwd:  K1 per-channel sum/sumsq (accumulated over the batch grid axis)
        -> tiny XLA math on [C] -> K2 scale/shift apply (+ optional
        fused relu)
  bwd:  K3 per-channel sum(dy), sum(dy*x) -> tiny XLA -> K4
        dx = A[c]*dy + B[c]*x + D[c] (the BN backward collapsed to a
        per-channel FMA over dy and x)

Layout contract: x is [N, C, spatial...] (NCHW/NCDHW); kernels view it
as [N, C, S] with S = prod(spatial) as the (whole-dim) lane axis, so S
needs no 128 alignment. Reference analog: the reference's cuDNN-backed
``batch_norm`` training kernels (paddle/phi/kernels/gpu/batch_norm_*).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bn_train", "bn_train_eligible"]


def _stats_kernel(x_ref, s1_ref, s2_ref):
    n = pl.program_id(1)
    xf = x_ref[...].astype(jnp.float32)              # [bn, bc, S]
    s1 = jnp.sum(xf, axis=(0, 2))[None, :, None]
    s2 = jnp.sum(xf * xf, axis=(0, 2))[None, :, None]

    @pl.when(n == 0)
    def _init():
        s1_ref[...] = s1
        s2_ref[...] = s2

    @pl.when(n > 0)
    def _acc():
        s1_ref[...] += s1
        s2_ref[...] += s2


def _apply_kernel(x_ref, sc_ref, sh_ref, y_ref, *, relu):
    xf = x_ref[...].astype(jnp.float32)              # [bn, bc, S]
    y = xf * sc_ref[...] + sh_ref[...]
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[...] = y.astype(y_ref.dtype)


def _gsum_kernel(dy_ref, x_ref, sdy_ref, sdyx_ref):
    n = pl.program_id(1)
    dyf = dy_ref[...].astype(jnp.float32)
    xf = x_ref[...].astype(jnp.float32)
    a = jnp.sum(dyf, axis=(0, 2))[None, :, None]
    b = jnp.sum(dyf * xf, axis=(0, 2))[None, :, None]

    @pl.when(n == 0)
    def _init():
        sdy_ref[...] = a
        sdyx_ref[...] = b

    @pl.when(n > 0)
    def _acc():
        sdy_ref[...] += a
        sdyx_ref[...] += b


def _dx_kernel(dy_ref, x_ref, a_ref, b_ref, d_ref, dx_ref):
    dyf = dy_ref[...].astype(jnp.float32)
    xf = x_ref[...].astype(jnp.float32)
    dx = dyf * a_ref[...] + xf * b_ref[...] + d_ref[...]
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _pick_bc(C: int, S: int) -> int:
    # largest channel tile whose (bc, S) f32 face stays ~1 MB: small-
    # spatial deep layers take the WHOLE channel dim (fewer grid steps
    # — a (1, bc, S) block design measured grid-overhead-bound there)
    for bc in (C, 512, 256, 128, 64, 32, 16, 8):
        if C % bc == 0 and bc * S * 4 <= (1 << 19):
            return bc
    return 0


def _pick_bn(N: int, bc: int, S: int) -> int:
    for bn in (32, 16, 8, 4, 2):
        if N % bn == 0 and bn * bc * S * 4 <= (1 << 20):
            return bn
    return 1


def _grids(x3):
    N, C, S = x3.shape
    bc = _pick_bc(C, S)
    bn = _pick_bn(N, bc, S)
    blk = pl.BlockSpec((bn, bc, S), lambda j, n: (n, j, 0))
    cblk = pl.BlockSpec((1, bc, 1), lambda j, n: (0, j, 0))
    # batch-blocks innermost: the [C]-sized accumulator blocks are
    # revisited on CONSECUTIVE grid steps, the pattern Pallas TPU
    # keeps in VMEM
    return (C // bc, N // bn), blk, cblk


@functools.partial(jax.jit, static_argnums=(1,))
def _stats_call(x3, interpret):
    N, C, S = x3.shape
    grid, blk, cblk = _grids(x3)
    s1, s2 = pl.pallas_call(
        _stats_kernel, grid=grid,
        in_specs=[blk], out_specs=[cblk, cblk],
        out_shape=[jax.ShapeDtypeStruct((1, C, 1), jnp.float32)] * 2,
        compiler_params=_params(),
        interpret=interpret)(x3)
    return s1.reshape(C), s2.reshape(C)


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _apply_call(x3, scale, shift, relu, interpret):
    N, C, S = x3.shape
    grid, blk, cblk = _grids(x3)
    return pl.pallas_call(
        functools.partial(_apply_kernel, relu=relu), grid=grid,
        in_specs=[blk, cblk, cblk], out_specs=[blk],
        out_shape=[jax.ShapeDtypeStruct((N, C, S), x3.dtype)],
        compiler_params=_params(),
        interpret=interpret)(x3, scale.reshape(1, C, 1),
                             shift.reshape(1, C, 1))[0]


@functools.partial(jax.jit, static_argnums=(2,))
def _gsum_call(dy3, x3, interpret):
    N, C, S = x3.shape
    grid, blk, cblk = _grids(x3)
    sdy, sdyx = pl.pallas_call(
        _gsum_kernel, grid=grid,
        in_specs=[blk, blk], out_specs=[cblk, cblk],
        out_shape=[jax.ShapeDtypeStruct((1, C, 1), jnp.float32)] * 2,
        compiler_params=_params(),
        interpret=interpret)(dy3, x3)
    return sdy.reshape(C), sdyx.reshape(C)


@functools.partial(jax.jit, static_argnums=(5,))
def _dx_call(dy3, x3, a, b, d, interpret):
    N, C, S = x3.shape
    grid, blk, cblk = _grids(x3)
    return pl.pallas_call(
        _dx_kernel, grid=grid,
        in_specs=[blk, blk, cblk, cblk, cblk], out_specs=[blk],
        out_shape=[jax.ShapeDtypeStruct((N, C, S), dy3.dtype)],
        compiler_params=_params(),
        interpret=interpret)(dy3, x3, a.reshape(1, C, 1),
                             b.reshape(1, C, 1), d.reshape(1, C, 1))[0]


def bn_train_eligible(x) -> bool:
    """4-D+ [N, C, spatial...] with a Pallas-block-compatible C."""
    if x.ndim < 3:
        return False
    C = x.shape[1]
    S = 1
    for s in x.shape[2:]:
        S *= s
    # C % 8: stay on sublane-aligned channel tiles (hardware-verified
    # geometry); every shipped vision net satisfies it
    return C % 8 == 0 \
        and _pick_bc(C, S) != 0 \
        and x.shape[0] >= 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def bn_train(x, gamma, beta, eps=1e-5, relu=False, interpret=False):
    """Training BatchNorm over [N, C, spatial...]: returns
    (y, batch_mean, batch_var). mean/var are emitted for the caller's
    running-stats update and are NOT differentiated through (the
    standard BN-train contract). ``relu`` fuses max(y, 0) into the
    apply pass; its backward masks on y > 0."""
    y, mean, var, _ = _fwd_core(x, gamma, beta, eps, relu, interpret)
    return y, mean, var


def _fwd_core(x, gamma, beta, eps, relu, interpret):
    N, C = x.shape[0], x.shape[1]
    S = x.size // (N * C)
    x3 = x.reshape(N, C, S)
    s1, s2 = _stats_call(x3, interpret)
    n = N * S
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    g = jnp.ones((C,), jnp.float32) if gamma is None \
        else gamma.astype(jnp.float32)
    b = jnp.zeros((C,), jnp.float32) if beta is None \
        else beta.astype(jnp.float32)
    scale = g * rstd
    shift = b - mean * scale
    y = _apply_call(x3, scale, shift, relu, interpret).reshape(x.shape)
    return y, mean, var, rstd


def _bn_fwd(x, gamma, beta, eps, relu, interpret):
    y, mean, var, rstd = _fwd_core(x, gamma, beta, eps, relu, interpret)
    res = (x, gamma, beta, mean, rstd, y if relu else None)
    return (y, mean, var), res


def _bn_bwd(eps, relu, interpret, res, cts):
    x, gamma, beta, mean, rstd, y = res
    dy = cts[0]   # mean/var cotangents are zero by contract
    N, C = x.shape[0], x.shape[1]
    S = x.size // (N * C)
    if relu:
        # mask through the fused relu: dY/dpre = [y > 0]
        dy = jnp.where(y > 0, dy, jnp.zeros((), dy.dtype))
    dy3 = dy.reshape(N, C, S)
    x3 = x.reshape(N, C, S)
    sdy, sdyx = _gsum_call(dy3, x3, interpret)
    n = N * S
    g = jnp.ones((C,), jnp.float32) if gamma is None \
        else gamma.astype(jnp.float32)
    # dgamma = sum(dy * xhat) = rstd * (sum(dy x) - mu sum(dy))
    dgamma = rstd * (sdyx - mean * sdy)
    dbeta = sdy
    # dx = g*rstd*(dy - mean_dy - xhat*mean(dy*xhat))
    #    = A*dy + B*x + D with per-channel A, B, D
    m1 = sdy / n
    m2 = dgamma / n          # mean(dy * xhat)
    A = g * rstd
    B = -g * rstd * rstd * m2
    D = -A * m1 - B * mean
    dx = _dx_call(dy3, x3, A, B, D, interpret).reshape(x.shape) \
        .astype(x.dtype)
    dg = None if gamma is None else dgamma.astype(gamma.dtype)
    db = None if beta is None else dbeta.astype(beta.dtype)
    return dx, dg, db


bn_train.defvjp(_bn_fwd, _bn_bwd)
