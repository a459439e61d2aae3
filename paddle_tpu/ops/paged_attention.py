"""Length-aware paged attention for the serving decode step: one new
token a slot against that slot's LIVE pages of a paged K/V pool.

``models/_decode_cache.paged_cache_attend`` gathers every slot's whole
page table into a dense ``[B, max_len, KV, D]`` copy and contracts over
``max_len`` whatever the slot's length. This kernel reads pages
``0 .. pos[b] // page`` of slot ``b`` and nothing else: one grid step a
slot, a loop over its live pages with double-buffered page copies from
the pool in HBM (the next page, or the next slot's first, is in flight
while this one is scored), online softmax in float32 scratch.

The pool is read in the layout the engine keeps it in, ``[num_pages,
page, KV, D]``: on the device its tiles lie over ``(KV, D)``, so any
view that puts one head's positions side by side is a copy of the pool.
A page is scored as its ``page * KV`` rows of ``D`` (position-major,
head-minor) against ALL query heads at once, and a constant mask keeps
for each query head the rows of its own KV head (GQA folded, no
head-repeated copy; the masked products are MXU work that costs no
extra weight loads). Positions beyond ``pos[b]`` in the last page are
masked the same way.

Arithmetic: float32 queries against the cache with float32
accumulation. Against a bfloat16 cache the query is split into three
bfloat16 parts whose products with the cache are exact, so the MXU runs
bfloat16 passes and nothing of the query is rounded away; any other
cache dtype is upcast and multiplied at ``Precision.HIGHEST``. Softmax
statistics are float32; probabilities enter the value product in the
cache's dtype (as the einsum path's do) and accumulate in float32,
except for a caller that asks a float32 result of a bfloat16 cache (a
model whose arithmetic is float32 over bfloat16 storage): its
probabilities go in as two bfloat16 parts, so that only what is stored
is rounded.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_ops
from .grouped_matmul import pieces
from .pallas_ops import NEG_INF

__all__ = ["paged_decode_attention", "kernel_fits"]

F32, BF16 = jnp.float32, jnp.bfloat16
_HI = jax.lax.Precision.HIGHEST


def kernel_fits(head_dim: int) -> bool:
    """Whether Mosaic takes the pool as it lies (the interpreter takes
    any): a page copy is a slice of the pool, and its last axis has to
    fill whole 128-lane tiles."""
    return head_dim % 128 == 0


def _kernel(table_ref, pos_ref, first_ref,          # scalar prefetch
            q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, m_ref, l_ref, acc_ref,
            *, heads: int, per_seq: int):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    _, page, kv, D = kbuf.shape
    rows = page * kv
    rep = heads // kv
    pos = pos_ref[b]
    first = first_ref[b]          # live pages of the slots before b
    n = first_ref[b + 1] - first  # and of this one

    def copies(slot, i, buf):
        pid = table_ref[slot * per_seq + i]
        return (pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[buf],
                                      sem.at[1, buf]))

    def start(slot, i, buf):
        for c in copies(slot, i, buf):
            c.start()

    @pl.when(b == 0)
    def _():
        start(0, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # row r of a page is position r // kv of KV head r % kv; query head
    # h reads the rows of head h // rep
    row = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, rows), 0)
    own = (row % kv) == (head // rep)
    exact = k_hbm.dtype == BF16

    def body(i, _):
        buf = (first + i) % 2

        @pl.when(i + 1 < n)
        def _():
            start(b, i + 1, 1 - buf)

        @pl.when(jnp.logical_and(i + 1 == n, b + 1 < nb))
        def _():
            start(b + 1, 0, 1 - buf)

        ck, cv = copies(b, i, buf)
        ck.wait()
        k = kbuf[buf].reshape(rows, D)
        nt = (((1,), (1,)), ((), ()))
        if exact:
            s = jax.lax.dot_general(q_ref[0], k, nt,
                                    preferred_element_type=F32)
            s = s[:heads] + s[heads:2 * heads] + s[2 * heads:]
        else:
            s = jax.lax.dot_general(q_ref[0], k.astype(F32), nt,
                                    precision=_HI,
                                    preferred_element_type=F32)
        live = (pos - i * page + 1) * kv        # rows at or before pos
        s = jnp.where(jnp.logical_and(own, row < live), s, NEG_INF)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                  keepdims=True)
        m_ref[...] = m_new
        cv.wait()
        v = vbuf[buf].reshape(rows, D)
        if exact and o_ref.dtype == F32:
            # a float32 caller: the probabilities as two bfloat16 parts
            # (2^-16 of each), both against the page as it lies; the
            # high part is cut with a bit mask, which no compiler takes
            # for a round trip it may drop
            bits = jax.lax.bitcast_convert_type
            hi = bits(bits(p, jnp.uint32) & jnp.uint32(0xFFFF0000), F32)
            pv = jnp.dot(jnp.concatenate([hi, p - hi], axis=0).astype(BF16),
                         v, preferred_element_type=F32)
            pv = pv[:heads] + pv[heads:]
        else:
            pv = jnp.dot(p.astype(v.dtype), v,
                         preferred_element_type=F32,
                         precision=None if exact else _HI)
        acc_ref[...] = alpha * acc_ref[...] + pv
        return 0

    jax.lax.fori_loop(0, n, body, 0)
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _split3(x):
    """``x`` (float32) as three bfloat16 parts that sum to it."""
    hi = x.astype(BF16)
    r = x - hi.astype(F32)
    mid = r.astype(BF16)
    lo = (r - mid.astype(F32)).astype(BF16)
    return hi, mid, lo


def paged_decode_attention(q, kp, vp, table, pos, out_dtype):
    """``q [B, H, D]`` float32 (position-encoded), the pools ``kp, vp
    [num_pages, page, KV, D]`` with the new token already written,
    ``table [B, pages_per_seq]`` and the new token's position ``pos
    [B]``. Returns ``[B, H, D]`` in ``out_dtype``: softmax over
    positions ``0 .. pos[b]`` of slot ``b``. A slot that is not active
    arrives with ``pos 0`` and a table of the trash page: it reads one
    page. Interpreted where the backend is not a TPU."""
    return _attention(q, kp, vp, table, pos, out_dtype,
                      pallas_ops._interpret())


# jitted so that a model's layers share one trace and one Mosaic
# lowering of the kernel: sixteen separate ones are seconds of every
# set-up, before the compile cache is even asked
@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _attention(q, kp, vp, table, pos, out_dtype, interpret: bool):
    B, H, D = q.shape
    _, page, KV, _ = kp.shape
    per_seq = table.shape[1]
    q = q.astype(F32) * (1.0 / math.sqrt(D))
    if kp.dtype == BF16:
        # [B, 3H, D]; for a float32 caller the parts are cut with bit
        # masks (grouped_matmul.pieces): inside a larger program the
        # TPU compiler may drop ``_split3``'s round trips through
        # bfloat16 as excess precision, and the query is then 2^-9 off
        q = jnp.concatenate(
            list(pieces(q, whole=True)) if out_dtype == F32
            else _split3(q), axis=1)
    pos = pos.astype(jnp.int32)
    n = jnp.minimum(pos // page, per_seq - 1) + 1          # live pages
    first = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(n)])
    pool = pl.BlockSpec(memory_space=pl.ANY)
    row = lambda rows: pl.BlockSpec((1, rows, D),
                                    lambda b, *_: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, heads=H, per_seq=per_seq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[row(q.shape[1]), pool, pool],
            out_specs=row(H),
            scratch_shapes=[
                pltpu.VMEM((2, page, KV, D), kp.dtype),
                pltpu.VMEM((2, page, KV, D), vp.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, 1), F32), pltpu.VMEM((H, 1), F32),
                pltpu.VMEM((H, D), F32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode_attention", interpret=interpret,
    )(table.reshape(-1).astype(jnp.int32), pos, first.astype(jnp.int32),
      q, kp, vp)
