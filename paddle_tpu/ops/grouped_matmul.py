"""Grouped matrix product: rows sorted by group, one matrix a group.

``grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G])`` multiplies
rows ``offset[g] .. offset[g + 1] - 1`` of ``lhs`` by ``rhs[g]``; rows
past ``sum(group_sizes)`` belong to no group and come back as whatever
the buffer held: the caller masks them (a pass over the result to zero
them is a pass it does not need). It is
the product an expert layer needs once its assignments are sorted by
expert (``incubate/moe.expert_share``): work and weight traffic follow
the rows that are there, not ``G`` matrices a row, and an empty group's
matrix is never read.

On one TPU device it is a Pallas kernel in the manner of
``jax.experimental.pallas.ops.tpu.megablox`` (whose
``make_group_metadata`` it uses as shipped): the grid runs over column
tiles and, inside, over the (row tile, group) pairs that hold a row, a
number known only on the device (a dynamic grid bound); a row tile that
two groups share is visited once for each, and a mask keeps for each
visit the rows of its group. The contraction is not tiled: a ``[K, tn]``
tile of one group's matrix is fetched once a visit. The kernel is given
a ``name`` a program (``expert_gmm_decode``, ``expert_gmm_prefill``), so
that a device trace parts them.

Arithmetic: float32 rows, float32 accumulation. Against bfloat16
matrices the rows are cut into two bfloat16 pieces (the high and the
middle eight bits of the significand, by a bit mask: ``pieces``, which
``models/brumby._split_matmul`` shares) and both pieces meet each tile,
so a matrix is read once and the product is exact in the matrix and good
to 2^-16 of the row; any other dtype is multiplied as float32 at
``Precision.HIGHEST``. Elsewhere (the CPU, a program traced for a mesh)
it is ``jax.lax.ragged_dot`` in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul", "pieces", "use_kernel"]

F32, BF16 = jnp.float32, jnp.bfloat16
_HI = jax.lax.Precision.HIGHEST
# the rows of one visit (a smaller product is one tile of its rows)
ROW_TILE = 128
# a fetched tile of a group's matrix, in bytes: two of them are in
# flight beside two row tiles and two output tiles
_RHS_TILE_BYTES = 5 * 1024 * 1024 + 512 * 1024


def pieces(x, exact: bool = True, whole: bool = False):
    """``[M, K]`` float32 as ``[P, M, K]``: two bfloat16 pieces whose
    sum is the row to 16 bits (``whole``: three, whose sum is the row),
    or the row itself. A caller that gathers its rows may cut them
    first and gather the pieces."""
    if not exact:
        return x[None]
    bits = jax.lax.bitcast_convert_type
    top = lambda a: bits(bits(a, jnp.uint32) & jnp.uint32(0xFFFF0000),
                         F32)
    high = top(x)
    rest = x - high
    if whole:
        return jnp.stack([high, top(rest), rest - top(rest)]).astype(BF16)
    return jnp.stack([high, rest]).astype(BF16)


def _column_tile(K: int, N: int, itemsize: int) -> int:
    """The widest multiple of 128 that divides ``N`` and keeps a ``[K,
    tn]`` tile under ``_RHS_TILE_BYTES`` (``N`` itself where it has no
    such divisor: a test's small shapes)."""
    fits = [t for t in range(128, N + 1, 128)
            if N % t == 0 and K * t * itemsize <= _RHS_TILE_BYTES]
    return max(fits) if fits else N


def _kernel(offsets_ref, gids_ref, mids_ref, lhs_ref, rhs_ref, out_ref,
            *, tm: int):
    t = pl.program_id(1)
    g = gids_ref[t]
    rhs = rhs_ref[...]
    acc = None
    for p in range(lhs_ref.shape[0]):
        part = jax.lax.dot_general(
            lhs_ref[p], rhs, (((1,), (0,)), ((), ())),
            preferred_element_type=F32,
            precision=None if rhs.dtype == BF16 else _HI)
        acc = part if acc is None else acc + part
    row = mids_ref[t] * tm + jax.lax.broadcasted_iota(
        jnp.int32, acc.shape, 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    # a tile that two groups share is visited by one after the other and
    # stays in VMEM between the visits: each keeps what is not its own
    out_ref[...] = jnp.where(mine, acc, out_ref[...])


def _pallas(lhs, rhs, group_sizes, tm: int, name: str, interpret: bool):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
        make_group_metadata
    P, M, K = lhs.shape
    G, _, N = rhs.shape
    tn = _column_tile(K, N, rhs.dtype.itemsize)
    (offsets, gids, mids), visits = make_group_metadata(
        group_sizes=group_sizes, m=M, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=G,
        visit_empty_groups=False)
    vmem = 2 * (P * tm * K * lhs.dtype.itemsize
                + K * tn * rhs.dtype.itemsize + tm * tn * 4) + (8 << 20)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # no visit at all (no row of any group) would leave the
            # grid empty: one visit whose mask keeps nothing
            grid=(N // tn, jnp.maximum(visits, 1)),
            in_specs=[
                pl.BlockSpec((P, tm, K),
                             lambda n, t, o, g, m: (0, m[t], 0)),
                pl.BlockSpec((None, K, tn),
                             lambda n, t, o, g, m: (g[t], 0, n))],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, t, o, g, m: (m[t], n))),
        out_shape=jax.ShapeDtypeStruct((M, N), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        name=name, interpret=interpret,
    )(offsets, gids, mids, lhs, rhs)


def use_kernel(kernel=None) -> bool:
    """Whether ``grouped_matmul`` runs its Pallas kernel here."""
    from .pallas_ops import single_device_tpu
    return single_device_tpu() if kernel is None else kernel


def grouped_matmul(lhs, rhs, group_sizes, *, name: str = "grouped_matmul",
                   kernel=None):
    """``lhs [M, K]`` float32 rows sorted by group (or their ``pieces``,
    ``[P, M, K]``), ``rhs [G, K, N]``, ``group_sizes [G]`` int32 with
    ``sum <= M``; returns ``[M, N]`` float32 (module docstring).
    ``kernel``: the Pallas kernel (``True``; interpreted on the CPU) or
    ``ragged_dot`` (``False``); by default the kernel where one
    un-partitioned TPU program is traced."""
    from .pallas_ops import _interpret
    group_sizes = group_sizes.astype(jnp.int32)
    if not use_kernel(kernel):
        lhs = lhs.astype(F32).sum(0) if lhs.ndim == 3 else lhs.astype(F32)
        return jax.lax.ragged_dot(lhs, rhs.astype(F32), group_sizes,
                                  precision=_HI,
                                  preferred_element_type=F32)
    parts = lhs if lhs.ndim == 3 else pieces(lhs.astype(F32),
                                             rhs.dtype == BF16)
    M = parts.shape[1]
    tm = min(ROW_TILE, -(-M // 8) * 8)
    parts = jnp.pad(parts, ((0, 0), (0, -M % tm), (0, 0)))
    # the kernel serves and is never differentiated (see
    # power_retention.retention_decode)
    return _pallas(*jax.lax.stop_gradient((parts, rhs, group_sizes)),
                   tm, name, _interpret())[:M]
