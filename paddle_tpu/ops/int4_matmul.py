"""Pallas int4 weight-only matmul: unpack + dequant fused into the dot.

The XLA lowering of unpack->dequant->matmul materializes the bf16
weight copy in HBM every call, which DESTROYS the bandwidth win decode
exists for (measured 62 tok/s bs1 vs 329 bf16 — the rounds-1-5 notes (git
history before PR 23)
round-5 int4 ledger). This kernel reads the PACKED uint8 nibbles
[K/2, N] straight from HBM, unpacks and scales in VMEM registers, and
feeds the MXU — HBM cost stays 0.5 B/weight.

Packing layout (pack_rows_int4): nibble pair (hi, lo) holds original
rows (k, k + K/2), so the kernel needs NO interleave — it computes
``y = x[:, :K/2] @ W_hi + x[:, K/2:] @ W_lo`` (two dots, one
accumulator). Per-group scales (group size divides K/2) broadcast to
rows in-register.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pack_rows_int4", "quantize_int4_rows", "int4_matmul"]


def quantize_int4_rows(w: np.ndarray, group: int = 128):
    """[K, N] float -> (q int8-valued [-7,7] [K, N],
    scales f32 [K//group, N]), symmetric per (group, out-column)."""
    K, N = w.shape
    if K % group:
        raise ValueError(f"K {K} % group {group} != 0")
    g = K // group
    wg = w.reshape(g, group, N).astype(np.float32)
    scale = np.abs(wg).max(axis=1) / 7.0
    scale = np.where(scale == 0.0, 1.0, scale)
    q = np.clip(np.round(wg / scale[:, None, :]), -7, 7)
    return q.reshape(K, N).astype(np.int8), scale.astype(np.float32)


def pack_rows_int4(q: np.ndarray) -> np.ndarray:
    """[K, N] int4-valued -> uint8 [K/2, N]: row k in the HIGH nibble,
    row k + K/2 in the LOW nibble (halves layout — the kernel's two
    half-dots need no interleave)."""
    K = q.shape[0]
    if K % 2:
        raise ValueError("K must be even")
    u = (q.astype(np.int16) + 8).astype(np.uint8)
    return ((u[:K // 2] << 4) | u[K // 2:]).astype(np.uint8)


def _kernel(x_ref, p_ref, s_ref, o_ref, *, group, out_dtype, cdtype):
    # x [Bb, K]; p [K/2, Nb] packed; s [G, Nb]; o [Bb, Nb]
    Bb, K = x_ref.shape
    half = K // 2
    Nb = p_ref.shape[1]
    # Mosaic cannot legalize shifts on i8 vectors (arith.shrui) —
    # widen to i32 for the nibble arithmetic, it stays in registers
    p = p_ref[...].astype(jnp.int32)
    hi = ((p >> 4) - 8).astype(cdtype)           # rows 0..K/2
    lo = ((p & 0xF) - 8).astype(cdtype)          # rows K/2..K
    s = s_ref[...].astype(jnp.float32)           # [G, Nb]
    x = x_ref[...].astype(cdtype)
    gh = half // group                           # groups per half

    # y = sum_g (x_g @ q_g) * s_g: per-group dots with the scale
    # applied to the SMALL [Bb, Nb] partial output — scaling the
    # W-sized block per row measured ~2x slower (VPU-bound) than the
    # int8 path it was supposed to beat. The group loop is UNROLLED in
    # python (gh is static, <=22): Mosaic has no dynamic_slice on TC.
    acc = jnp.zeros((Bb, Nb), jnp.float32)
    for g in range(gh):
        r = slice(g * group, (g + 1) * group)
        acc = acc + jax.lax.dot(
            x[:, r], hi[r, :],
            preferred_element_type=jnp.float32) * s[g]
        acc = acc + jax.lax.dot(
            x[:, half + g * group:half + (g + 1) * group], lo[r, :],
            preferred_element_type=jnp.float32) * s[gh + g]
    o_ref[...] = acc.astype(out_dtype)


def int4_matmul(x, packed, scales, group: int = 128,
                block_n: int = 256, block_b: int = 256,
                interpret=None):
    """``x [B, K] @ dequant(packed [K/2, N], scales [K//group, N])``
    with the unpack fused in VMEM; rows and columns both blocked so
    decode (B<=32) AND prefill (B=bs*seq) shapes fit scoped VMEM."""
    B, K = x.shape
    N = packed.shape[1]
    if (K // 2) % group:
        # the kernel's halves layout assigns whole scale groups to each
        # nibble half; a group straddling the half boundary would be
        # silently dropped/mis-scaled
        raise ValueError(
            f"group {group} must divide K//2 = {K // 2} "
            f"(pick a group size with group | K/2)")
    if packed.shape[0] != K // 2:
        raise ValueError(
            f"packed rows {packed.shape[0]} != K//2 = {K // 2}")
    if scales.shape != (K // group, N):
        raise ValueError(
            f"scales shape {scales.shape} != {(K // group, N)}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    cdtype = jnp.float32 if interpret else jnp.bfloat16
    bn = min(block_n, N)
    while N % bn:
        bn //= 2
    bb = min(block_b, B)
    while B % bb:
        bb //= 2
    grid = (B // bb, N // bn)
    kernel = functools.partial(_kernel, group=group,
                               out_dtype=x.dtype, cdtype=cdtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K // 2, bn), lambda i, j: (0, j)),
            pl.BlockSpec((K // group, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, packed, scales)
