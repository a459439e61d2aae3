"""Dynamic-quantized int8 matmuls for TPU training: forward, dgrad, wgrad.

The v5e MXU runs int8 x int8 -> int32 at twice the bf16 rate (393 TOP/s
against 197 TFLOP/s published; the block dots of the flagship step
measure 79-96% of that, PERF.md section 5). Five recipes, each a
``custom_vjp`` over the same forward, differing in how much of the
backward is also int8:

  ``int8_linear``          forward int8 (per-row activation scales,
                           per-column weight scales, symmetric, dynamic,
                           no calibration; dequant fused as the epilogue);
                           dgrad and wgrad exact in the input dtype (a
                           straight-through estimator w.r.t. the rounding).
  ``int8_linear_dgrad8``   + the activation gradient on the int8 MXU
                           (round-to-nearest per-row scales on the
                           cotangent and on w's contraction dim).
  ``int8_linear_all8``     + the WEIGHT gradient int8 too: both operands
                           quantized along the token axis with STOCHASTIC
                           rounding, so each quantization is unbiased and
                           the noise integrates to zero in Adam's moments
                           instead of drifting. This is the flagship's
                           recipe (``GPTSpmdTrainer(quant8="wgrad")``).
  ``int8_gelu_linear_all8``, ``int8_ln_linear_all8``
                           ``all8`` with the producer (gelu, LayerNorm)
                           computed inside the quantize kernels.

The quantizers are single-pass Pallas kernels on a single-device TPU
program and XLA elsewhere (the same arithmetic; the SR quantizers
draw another random stream there). The weight-gradient
contraction ``dequant(xq^T @ gq)`` contracts the major axis of both
operands, a form XLA's dot fusion runs at anything between a third and
86% of the int8 peak; where the Pallas quantizer runs, the left operand
is quantized straight into [K, M] and the contraction is the plain
``[K, M] @ [M, N]`` of the forward dots (``_wgrad_form``, counted per
traced site in ``ptpu_int8_wgrad_sites_total{form}``). Either form
accumulates exactly in int32 and applies the same f32 scales in the
same order: the bits do not depend on the form.

Reference behavior analog: the reference's QAT fake-quant linear
(python/paddle/nn/quant/qat/linear.py) simulates int8 in fp32; this is
the TPU-native real-int8 version that engages the int8 MXU path.
Loss parity of each recipe: the rounds-1-5 notes (git history before
PR 23) and ``benchmarks/parity_int8.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_ops import single_device_tpu

__all__ = ["int8_linear", "int8_linear_dgrad8", "int8_linear_all8",
           "int8_gelu_linear_all8", "int8_ln_linear_all8",
           "int8_dot_dequant",
           "quantize_rowwise", "quantize_rowwise_fast",
           "ln_quantize_rowwise", "sr_quantize_colwise",
           "sr_quantize_colwise_ln", "site_seed"]


def site_seed(seed, site: int):
    """The (layer, site) SR-stream derivation used by EVERY int8 block
    matmul: layer seeds arrive 16 apart (_layer_seeds), so seed*8+site
    keeps streams distinct; int32 wrap just mixes. One definition —
    _mm's closure and the fused gelu site both call this."""
    import jax.numpy as _jnp
    s = _jnp.int32(1) if seed is None else seed
    return s * _jnp.int32(8) + _jnp.int32(site)


def quantize_rowwise(x, axis):
    """Symmetric int8 quantization along ``axis``: returns (q, scale)
    with x ~= q * scale, scale shaped like x with ``axis`` size 1."""
    # one hoisted upcast: the amax pass and the cast pass share the f32
    # view instead of each materializing their own convert (dtype-
    # discipline pass, round 6 — XLA usually CSEs this, but the jaxpr
    # should not rely on it)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


# ---------------------------------------------------------------------------
# single-pass Pallas quantize
# ---------------------------------------------------------------------------
# XLA lowers quantize_rowwise to two passes over x in HBM: a reduce
# fusion for amax, then an elementwise fusion that re-reads x to scale
# and cast. The row fits in VMEM, so a Pallas kernel does amax + scale
# in ONE read of x — quantize passes were ~12 ms of the 411 ms flagship
# step (the rounds-1-5 notes (git history before PR 23) round-3 decomposition),
# roughly half of
# which is the second read this kernel removes.

def _apply_act(x, act):
    """Producer-fused activation inside the quantize kernels: the
    activation's own HBM write + the quantizer's re-read disappear
    (round-5 lever d: ~27 ms of gelu+rowq+colq passes on the GPT step
    touch the same [6144, 8192] tensor three times without this)."""
    if act is None:
        return x
    if act == "gelu":
        # tanh-approximate gelu, matching jax.nn.gelu(approximate=True)
        c = jnp.float32(0.7978845608028654)      # sqrt(2/pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))
    raise ValueError(f"unsupported fused act {act!r}")


def _rowq_kernel(x_ref, q_ref, s_ref, *, act=None):
    x = _apply_act(x_ref[...].astype(jnp.float32), act)    # [bm, K]
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax) / 127.0
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127) \
        .astype(jnp.int8)
    s_ref[...] = scale


def _colq_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                     # [K, bn]
    amax = jnp.max(jnp.abs(x), axis=0, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax) / 127.0
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127) \
        .astype(jnp.int8)
    s_ref[...] = scale


def _pick_block(rows: int, row_bytes: int, budget: int = 2 << 20) -> int:
    for b in (512, 256, 128, 64, 32, 16, 8):
        if rows % b == 0 and b * row_bytes <= budget:
            return b
    return 0


@functools.partial(jax.jit, static_argnums=(1, 2))
def _rowq_call(x2, interpret, act=None):
    M, K = x2.shape
    bm = _pick_block(M, K * x2.dtype.itemsize)
    kernel = pl.pallas_call(
        functools.partial(_rowq_kernel, act=act), grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, K), jnp.int8),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32)],
        interpret=interpret)
    return kernel(x2)


@functools.partial(jax.jit, static_argnums=(1,))
def _colq_call(x2, interpret):
    K, N = x2.shape
    bn = _pick_block(N, K * x2.dtype.itemsize)
    kernel = pl.pallas_call(
        _colq_kernel, grid=(N // bn,),
        in_specs=[pl.BlockSpec((K, bn), lambda j: (0, j))],
        out_specs=[pl.BlockSpec((K, bn), lambda j: (0, j)),
                   pl.BlockSpec((1, bn), lambda j: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((K, N), jnp.int8),
                   jax.ShapeDtypeStruct((1, N), jnp.float32)],
        interpret=interpret)
    return kernel(x2)


def quantize_rowwise_fast(x, axis, interpret=None, act=None):
    """quantize_rowwise with a single-pass Pallas kernel where the
    layout permits (TPU backend, lane-aligned reduced dim, divisible
    row count); falls back to the XLA version otherwise. ``act``
    applies a producer-fused activation (see _apply_act) before
    quantizing — one read of x instead of act-write + quantize-read."""
    def _fallback(x, axis):
        if act is not None:
            # f32 like the Pallas kernel, so the two paths quantize
            # the same values (bit-identical across eligibility)
            x = _apply_act(x.astype(jnp.float32), act).astype(x.dtype)
        return quantize_rowwise(x, axis)
    if interpret is None:
        if not single_device_tpu():
            return _fallback(x, axis)
        interpret = False
    axis = axis % x.ndim
    if axis == x.ndim - 1:
        lead = x.shape[:-1]
        K = x.shape[-1]
        M = 1
        for s in lead:
            M *= s
        if K % 128 == 0 and _pick_block(M, K * x.dtype.itemsize):
            q, s = _rowq_call(x.reshape(M, K), interpret, act)
            return q.reshape(x.shape), s.reshape(lead + (1,))
    elif axis == 0 and x.ndim == 2 and act is None:
        K, N = x.shape
        if N % 128 == 0 and K % 8 == 0 \
                and _pick_block(N, K * x.dtype.itemsize):
            return _colq_call(x, interpret)
    return _fallback(x, axis)


# ---------------------------------------------------------------------------
# producer-fused LayerNorm -> quantize (round-5 lever a)
# ---------------------------------------------------------------------------
# The qkv and ffn1 matmuls consume LayerNorm outputs. Unfused, each site
# pays: LN reads x + writes h, then the rowq kernel re-reads h — three
# HBM passes over a [6144, 2048] activation, twice per layer per
# execution (forward + remat recompute). LN is row-wise and the rowq
# kernel already holds full rows in VMEM, so stats + normalize + scale
# + amax + cast collapse into ONE read of the pre-LN activation. The
# wgrad SR column kernel cannot compute row stats from its column
# blocks, so the row kernel also emits mean/rstd ([M,1] f32 — 24 KB at
# the flagship shape) for the backward to reuse.

_LN_EPS = 1e-5


def _rowq_ln_kernel(x_ref, g_ref, b_ref, q_ref, s_ref, m_ref, r_ref):
    x = x_ref[...].astype(jnp.float32)                     # [bm, K]
    m = jnp.mean(x, axis=1, keepdims=True)
    xc = x - m
    v = jnp.mean(xc * xc, axis=1, keepdims=True)
    r = jax.lax.rsqrt(v + _LN_EPS)
    h = xc * r * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(h), axis=1, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax) / 127.0
    q_ref[...] = jnp.clip(jnp.round(h / scale), -127, 127) \
        .astype(jnp.int8)
    s_ref[...] = scale
    m_ref[...] = m
    r_ref[...] = r


@functools.partial(jax.jit, static_argnums=(3,))
def _rowq_ln_call(x2, g, b, interpret):
    M, K = x2.shape
    bm = _pick_block(M, K * x2.dtype.itemsize)
    kernel = pl.pallas_call(
        _rowq_ln_kernel, grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                  pl.BlockSpec((1, K), lambda i: (0, 0)),
                  pl.BlockSpec((1, K), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, K), jnp.int8),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32)],
        interpret=interpret)
    return kernel(x2, g.reshape(1, K), b.reshape(1, K))


def _ln_stats(x2):
    xf = x2.astype(jnp.float32)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    v = jnp.var(xf, axis=-1, keepdims=True)
    return m, jax.lax.rsqrt(v + _LN_EPS)


def ln_quantize_rowwise(x2, g, b, interpret=None):
    """LayerNorm + symmetric per-row int8 quantize of [M, K] in one
    pass: returns (q, scale, mean, rstd). The stats make the backward's
    column-quantize of LN(x) possible without re-deriving them from
    full rows (see sr_quantize_colwise_ln)."""
    M, K = x2.shape
    if interpret is None and single_device_tpu():
        interpret = False             # else fall through to XLA
    if interpret is not None and K % 128 == 0 \
            and _pick_block(M, K * x2.dtype.itemsize):
        return _rowq_ln_call(x2, g, b, interpret)
    m, r = _ln_stats(x2)
    h = (x2.astype(jnp.float32) - m) * r \
        * g.astype(jnp.float32) + b.astype(jnp.float32)
    q, s = quantize_rowwise(h, axis=-1)
    return q, s, m, r


def _sr_cast_ln_kernel(seed_ref, x_ref, m_ref, r_ref, g_ref, b_ref,
                       sc_ref, q_ref):
    # Tiled SR cast with the column scale precomputed: a whole-column
    # one-pass variant (amax in-kernel) needs the full [M, bn] block
    # plus an f32 LN temp resident, which blows the 16M scoped-vmem
    # budget at the flagship [6144, 2048] (the non-LN colq kernel fit
    # with 343K to spare; +h does not). Splitting amax out to one XLA
    # reduce fusion costs a second bf16 read of x but keeps the
    # in-kernel hardware PRNG (the XLA SR path would write+read a full
    # uint32 rng buffer per operand — the bigger tax).
    from jax.experimental.pallas import tpu as pltpu
    x = x_ref[...].astype(jnp.float32)
    h = (x - m_ref[...]) * r_ref[...] \
        * g_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    # Mosaic caps prng_seed at 2 values: fold the 2-D grid id into one
    pltpu.prng_seed(seed_ref[0], pl.program_id(0) * pl.num_programs(1)
                    + pl.program_id(1))
    bits = pltpu.prng_random_bits(h.shape).astype(jnp.uint32)
    f = jax.lax.bitcast_convert_type(
        jnp.uint32(0x3F800000) | (bits >> 9), jnp.float32)
    q_ref[...] = jnp.clip(jnp.floor(h / sc_ref[...] + (f - 1.0)),
                          -127, 127).astype(jnp.int8)


@functools.partial(jax.jit, static_argnums=(6,))
def _sr_colq_ln_pallas(x2, m, r, g, b, seed_i, interpret):
    M, C = x2.shape
    gf = g.astype(jnp.float32).reshape(1, C)
    bf = b.astype(jnp.float32).reshape(1, C)
    h_for_amax = (x2.astype(jnp.float32) - m) * r * gf + bf
    amax = jnp.max(jnp.abs(h_for_amax), axis=0, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax) / 127.0
    bm = _pick_block(M, 256 * 4)
    bn = 256 if C % 256 == 0 else 128
    kernel = pl.pallas_call(
        _sr_cast_ln_kernel, grid=(M // bm, C // bn),
        in_specs=[pl.BlockSpec(memory_space=pltpu_smem()),
                  pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((M, C), jnp.int8)],
        interpret=interpret)
    (q,) = kernel(seed_i.reshape(1), x2, m, r,
                  g.reshape(1, C), b.reshape(1, C), scale)
    return q, scale


def sr_quantize_colwise_ln(x2, m, r, g, b, seed_i):
    """Unbiased int8 column quantize of LN(x2) given precomputed row
    stats; one read of the PRE-LN activation instead of an LN pass plus
    a re-read of its output."""
    M, C = x2.shape
    if single_device_tpu() \
            and C % 128 == 0 and _pick_block(M, 256 * 4):
        return _sr_colq_ln_pallas(x2, m, r, g, b, seed_i, False)
    h = ((x2.astype(jnp.float32) - m) * r
         * g.astype(jnp.float32) + b.astype(jnp.float32))
    return _sr_colq_xla(h, seed_i)


def int8_dot_dequant(aq, a_scale, bq, b_scale, dims, out_dtype=None):
    """int8 dot_general + f32 dequant. ``dims`` = (a_axes, b_axes)
    contraction dims; scales must already broadcast against the
    result. The ONE quantized-matmul core shared by the block matmuls
    and the CE head (three call paths, one arithmetic). ``out_dtype``
    folds the final downcast into the dequant epilogue so the fusion
    writes the consumer dtype directly instead of an f32 buffer plus a
    separate convert (dtype-discipline pass, round 6); scale math stays
    f32 either way."""
    y = jax.lax.dot_general(aq, bq, (dims, ((), ())),
                            preferred_element_type=jnp.int32)
    out = y.astype(jnp.float32) * a_scale * b_scale
    return out if out_dtype is None else out.astype(out_dtype)


def _int8_matmul(x, w):
    """x [..., K] @ w [K, N] with int8 MXU math, output in x.dtype."""
    xq, xs = quantize_rowwise_fast(x, axis=-1)     # [..., 1]
    wq, ws = quantize_rowwise_fast(w, axis=0)      # [1, N]
    return int8_dot_dequant(xq, xs, wq, ws, ((x.ndim - 1,), (0,)),
                            out_dtype=x.dtype)


@jax.custom_vjp
def int8_linear(x, w):
    """Forward int8 x int8 matmul; backward exact in the input dtype."""
    return _int8_matmul(x, w)


def _fwd(x, w):
    return _int8_matmul(x, w), (x, w)


def _bwd(res, g):
    x, w = res
    # dgrad/wgrad in bf16: gradients have too much dynamic range for
    # naive per-row int8, and the optimizer's moment estimates would
    # see the quantization noise twice
    dx = jax.lax.dot_general(g, w, (((g.ndim - 1,), (1,)), ((), ())))
    k = x.ndim - 1
    dw = jax.lax.dot_general(
        x, g, ((tuple(range(k)), tuple(range(k))), ((), ())))
    return dx.astype(x.dtype), dw.astype(w.dtype)


int8_linear.defvjp(_fwd, _bwd)


@jax.custom_vjp
def int8_linear_dgrad8(x, w):
    """Like int8_linear but the ACTIVATION gradient (dgrad) also runs on
    the int8 MXU: per-row scales on the incoming cotangent, per-row
    scales on w's contraction dim. The WEIGHT gradient stays exact bf16
    — it feeds the optimizer's moment estimates directly, where
    quantization noise integrates over steps."""
    return _int8_matmul(x, w)


def _fwd8(x, w):
    return _int8_matmul(x, w), (x, w)


def _bwd8(res, g):
    x, w = res
    # dx = g [..., N] @ w.T [N, K], both sides int8-quantized along N
    gq, gs = quantize_rowwise_fast(g, axis=-1)       # [..., 1]
    wq, ws = quantize_rowwise_fast(w, axis=1)        # [K, 1]
    y = jax.lax.dot_general(gq, wq, (((g.ndim - 1,), (1,)), ((), ())),
                            preferred_element_type=jnp.int32)
    dx = (y.astype(jnp.float32) * gs *
          jnp.reshape(ws, (1,) * (g.ndim - 1) + (-1,)))
    k = x.ndim - 1
    dw = jax.lax.dot_general(
        x, g, ((tuple(range(k)), tuple(range(k))), ((), ())))
    return dx.astype(x.dtype), dw.astype(w.dtype)


int8_linear_dgrad8.defvjp(_fwd8, _bwd8)


# ---------------------------------------------------------------------------
# int8 wgrad with stochastic rounding (round 4)
# ---------------------------------------------------------------------------
# The weight gradient dw[k,n] = sum_m x[m,k] g[m,n] contracts the token
# axis. Round-to-nearest int8 there would feed a persistent, data-
# correlated bias straight into Adam's moments; stochastic rounding
# makes each quantization UNBIASED (E[q*s] = value), so over steps the
# wgrad noise integrates to zero like SGD noise instead of drifting.
# Streams are decorrelated per (step, layer, site, operand) via the
# seed, drawn in-kernel from the TPU hardware PRNG (no HBM rng buffer —
# the XLA lowering would write+read a full uint32 buffer per operand).

def _colq_sr_kernel(seed_ref, x_ref, q_ref, s_ref, *, act=None,
                    transposed=False):
    from jax.experimental.pallas import tpu as pltpu
    x = _apply_act(x_ref[...].astype(jnp.float32), act)    # [M, bn]
    amax = jnp.max(jnp.abs(x), axis=0, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax) / 127.0
    pltpu.prng_seed(seed_ref[0], pl.program_id(0))
    bits = pltpu.prng_random_bits(x.shape).astype(jnp.uint32)
    f = jax.lax.bitcast_convert_type(
        jnp.uint32(0x3F800000) | (bits >> 9), jnp.float32)
    q = jnp.clip(jnp.floor(x / scale + (f - 1.0)), -127, 127)
    # the transpose runs on the f32 tile, before the cast: same values
    # and the same random stream, written as [bn, M]
    q_ref[...] = (q.T if transposed else q).astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _sr_colq_pallas(x2, seed_i, interpret, act=None, transposed=False):
    """Column-wise (per output channel) symmetric int8 SR quantize of
    [M, C] in ONE read of x: full-column blocks (M x 128 lanes) hold
    the whole reduction in VMEM, so amax, SR bits, and the cast happen
    in a single pass — the XLA lowering is a convert+abs+reduce pass
    PLUS a re-reading cast pass (~33 ms/step of abs_reduce fusions on
    the GPT-1.3B step before this kernel). ``transposed`` writes the
    int8 values as [C, M] (the scales stay [1, C]): the layout the
    weight gradient's left operand wants (``_wgrad_form``), at the cost
    of the plain kernel (0.056 against 0.055 ms at [6144, 2048], 0.343
    against 0.318 at [6144, 8192])."""
    M, C = x2.shape
    # f32 temps are M*bn*4 and several are live at once (x, bits, u,
    # q-pre-cast) plus double-buffered IO: ~4.5 copies must fit the
    # 16M scoped-vmem budget
    bn = 256 if (C % 256 == 0 and M * 256 * 4 * 9 // 2 <= (15 << 20)) \
        else 128
    q_block, q_shape = (pl.BlockSpec((bn, M), lambda j: (j, 0)), (C, M)) \
        if transposed else (pl.BlockSpec((M, bn), lambda j: (0, j)), (M, C))
    kernel = pl.pallas_call(
        functools.partial(_colq_sr_kernel, act=act, transposed=transposed),
        grid=(C // bn,),
        in_specs=[pl.BlockSpec(memory_space=pltpu_smem()),
                  pl.BlockSpec((M, bn), lambda j: (0, j))],
        out_specs=[q_block, pl.BlockSpec((1, bn), lambda j: (0, j))],
        out_shape=[jax.ShapeDtypeStruct(q_shape, jnp.int8),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)],
        interpret=interpret)
    return kernel(seed_i.reshape(1), x2)


def pltpu_smem():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.SMEM


def _sr_colq_xla(x2, seed_i, act=None):
    """Portable SR column quantize (CPU tests / ineligible layouts)."""
    if act is not None:
        x2 = _apply_act(x2.astype(jnp.float32), act)
    amax = jnp.max(jnp.abs(x2.astype(jnp.float32)), axis=0,
                   keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax) / 127.0
    key = jax.random.fold_in(jax.random.PRNGKey(0),
                             seed_i.astype(jnp.uint32))
    u = jax.random.uniform(key, x2.shape, jnp.float32)
    q = jnp.clip(jnp.floor(x2.astype(jnp.float32) / scale + u),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _sr_colq_pallas_ok(M: int, C: int) -> bool:
    """Whether ``sr_quantize_colwise`` runs its Pallas kernel on an
    [M, C] operand: a single-device TPU program, lane-aligned columns,
    and a full-column block that fits the scoped VMEM."""
    return single_device_tpu() \
        and C % 128 == 0 and M % 8 == 0 \
        and M * 128 * 4 * 9 // 2 <= (15 << 20)


def sr_quantize_colwise(x2, seed_i, act=None, transposed=False):
    """Unbiased int8 quantize of [M, C] with per-column scales;
    ``act`` fuses an activation before quantization (one read);
    ``transposed`` returns the int8 values as [C, M]."""
    if _sr_colq_pallas_ok(*x2.shape):
        return _sr_colq_pallas(x2, seed_i, False, act, transposed)
    q, s = _sr_colq_xla(x2, seed_i, act)
    return (q.T if transposed else q), s


# ---------------------------------------------------------------------------
# the int8 weight-gradient contraction (PR 28)
# ---------------------------------------------------------------------------
# dw[K, N] = dequant(xq[M, K]^T @ gq[M, N]) contracts the MAJOR axis
# (tokens) of both operands. XLA's dot fusion runs that form at 82-86% of
# the int8 peak at its best and at 34.5% at its worst: in the flagship
# step (v5e, M=6144) ffn1's [2048, 8192] gradient took 1.519 ms against
# 0.608 for ffn2's [8192, 2048], the same two operands in the other
# order, and alone in a program the two shapes trade places (0.64 and
# 1.54 ms); swapping the operands in the step changes nothing (1.516 ms).
# What the forward and dgrad dots run at 90-93% everywhere is the plain
# [K, M] @ [M, N]. So the left operand's SR quantize kernel writes its
# int8 values transposed (form ``km``; an in-graph transpose would only
# be folded back into the dot's layout): in the step 0.548 / 0.425 /
# 0.560 ms a call at ffn1 / qkv / ffn2 against 1.519 / 0.478 / 0.608.
# The arithmetic never changes: the same quantized values, an exact int32
# accumulation (M * 127^2 < 2^31), then int32 -> f32, times the K-side
# scale, times the N-side scale, then the cast, so both forms give the
# same bits. The form is chosen from the operands' shapes and the
# program's device count, at trace time, and counted there.

def _wgrad_form(M: int, K: int) -> str:
    """The form of the contraction for the weight gradient of a [K, N]
    matrix over M tokens: ``km`` (left operand quantized straight into
    [K, M]) where the Pallas SR quantize kernel runs on it and M fills
    whole lanes of its transposed block; ``kn`` (XLA's token-major dot)
    elsewhere. N has no say: ``km`` measured faster at every ratio the
    flagship has (N = 3K, N = 4K, K = 4N); PERF.md section 6, PR 28."""
    return "km" if _sr_colq_pallas_ok(M, K) and M % 128 == 0 else "kn"


def _wgrad_int8(xq, xs, gq, gs, out_dtype, form):
    """``dequant(xq^T @ gq)`` [K, N] of SR-quantized operands: ``gq``
    [M, N] with column scales ``gs``; ``xq`` is [M, K] in form ``kn``
    and [K, M] in form ``km`` (column scales ``xs`` either way). Counts
    the traced site under its form."""
    from ..observability.registry import default_registry
    default_registry().counter(
        "ptpu_int8_wgrad_sites_total",
        "int8 weight-gradient sites traced, by the form of the "
        "contraction their shapes chose",
        labels=("form",)).labels(form=form).inc()
    K, N = xs.size, gs.size
    return int8_dot_dequant(
        xq, xs.reshape(K, 1), gq, gs.reshape(1, N),
        ((1,) if form == "km" else (0,), (0,)), out_dtype=out_dtype)


def _wgrad_all8(x2, g2, seed, out_dtype, act=None):
    """The SR int8 weight gradient of ``act(x2)`` [M, K] and ``g2``
    [M, N]: both quantized along the tokens, streams decorrelated per
    operand from the site's ``seed``."""
    base = jnp.asarray(seed, jnp.int32) * jnp.int32(1000003)
    form = _wgrad_form(*x2.shape)
    xq, xs = sr_quantize_colwise(x2, base + jnp.int32(7919), act,
                                 transposed=form == "km")
    gq, gs = sr_quantize_colwise(g2, base + jnp.int32(104729))
    return _wgrad_int8(xq, xs, gq, gs, out_dtype, form)


@jax.custom_vjp
def int8_linear_all8(x, w, seed):
    """int8 MXU matmul on all three step matmuls: forward and dgrad as
    in ``int8_linear_dgrad8``; wgrad ALSO int8, with stochastic-rounding
    quantization along the token axis (unbiased — see module note).
    ``seed`` is a traced int32 scalar decorrelating SR streams per
    (step, microbatch, layer, site); int32 wrap-around only mixes the
    stream, it never collapses distinct seeds onto each other the way
    f32 rounding of large bases would. Its cotangent is float0."""
    del seed
    return _int8_matmul(x, w)


def _fwd_all8(x, w, seed):
    return _int8_matmul(x, w), (x, w, seed)


def _bwd_all8(res, g):
    x, w, seed = res
    # dgrad: int8 per-row, as int8_linear_dgrad8
    gq, gs = quantize_rowwise_fast(g, axis=-1)
    wq, ws = quantize_rowwise_fast(w, axis=1)
    y = jax.lax.dot_general(gq, wq, (((g.ndim - 1,), (1,)), ((), ())),
                            preferred_element_type=jnp.int32)
    dx = (y.astype(jnp.float32) * gs *
          jnp.reshape(ws, (1,) * (g.ndim - 1) + (-1,)))
    # wgrad: int8 with SR quantization along the contraction (tokens)
    dw = _wgrad_all8(x.reshape(-1, x.shape[-1]),
                     g.reshape(-1, g.shape[-1]), seed, w.dtype)  # [K,N]
    import numpy as np
    return (dx.astype(x.dtype), dw,
            np.zeros((), jax.dtypes.float0))


int8_linear_all8.defvjp(_fwd_all8, _bwd_all8)


@jax.custom_vjp
def int8_gelu_linear_all8(x, w, seed):
    """``int8_linear_all8(gelu(x), w, seed)`` with the gelu computed
    INSIDE the quantize kernels (round-5 lever d): x here is the
    PRE-activation (the saved ffn1 residual). Forward and wgrad each
    read x once and never materialize the bf16 gelu output; dgrad
    chains through gelu' outside (one fused elementwise)."""
    del seed
    return _int8_matmul_gelu(x, w)


def _int8_matmul_gelu(x, w):
    xq, xs = quantize_rowwise_fast(x, axis=-1, act="gelu")
    wq, ws = quantize_rowwise_fast(w, axis=0)
    return int8_dot_dequant(xq, xs, wq, ws, ((x.ndim - 1,), (0,)),
                            out_dtype=x.dtype)


def _fwd_gelu_all8(x, w, seed):
    return _int8_matmul_gelu(x, w), (x, w, seed)


def _bwd_gelu_all8(res, g):
    x, w, seed = res
    # dgrad w.r.t. a = gelu(x): int8 per-row, as int8_linear_all8
    gq, gs = quantize_rowwise_fast(g, axis=-1)
    wq, ws = quantize_rowwise_fast(w, axis=1)
    y = jax.lax.dot_general(gq, wq, (((g.ndim - 1,), (1,)), ((), ())),
                            preferred_element_type=jnp.int32)
    da = (y.astype(jnp.float32) * gs *
          jnp.reshape(ws, (1,) * (g.ndim - 1) + (-1,)))
    # chain through gelu' (tanh approximation, matching _apply_act)
    _, gelu_vjp = jax.vjp(
        lambda t: jax.nn.gelu(t.astype(jnp.float32), approximate=True),
        x)
    dx = gelu_vjp(da)[0]
    # wgrad: SR int8 of a = gelu(x), fused in the colq kernel
    dw = _wgrad_all8(x.reshape(-1, x.shape[-1]),
                     g.reshape(-1, g.shape[-1]), seed, w.dtype,
                     act="gelu")
    import numpy as np
    return (dx.astype(x.dtype), dw,
            np.zeros((), jax.dtypes.float0))


int8_gelu_linear_all8.defvjp(_fwd_gelu_all8, _bwd_gelu_all8)


def _int8_matmul_ln(x, g_ln, b_ln, w):
    lead, K = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, K)
    q, s, m, r = ln_quantize_rowwise(x2, g_ln, b_ln)
    wq, ws = quantize_rowwise_fast(w, axis=0)
    y = int8_dot_dequant(q, s, wq, ws, ((1,), (0,)),
                         out_dtype=x.dtype)
    return y.reshape(lead + (w.shape[1],)), m, r


def _env_fuse_bwd_colq() -> bool:
    import os
    return os.environ.get("PTPU_FUSE_BWD_COLQ", "0") \
        not in ("0", "", "false")


def int8_ln_linear_all8(x, g_ln, b_ln, w, seed, fuse_bwd_colq=None):
    """``int8_linear_all8(layer_norm(x, g_ln, b_ln), w, seed)`` with
    the LayerNorm computed INSIDE the quantize kernels (round-5 lever
    a): x is the PRE-LN residual stream. Forward and wgrad each read x
    once and never materialize the bf16 LN output; the backward chains
    the LN vjp outside (one fused elementwise + row reductions) and
    returns real gradients for g_ln/b_ln.

    ``fuse_bwd_colq`` (ADVICE r5 — was the dead module constant
    _FUSE_BWD_COLQ): True computes the wgrad column quantize of LN(x)
    from the forward's saved [M,1] mean/rstd stats
    (sr_quantize_colwise_ln — two reads of the pre-LN x, no h buffer);
    False re-materializes h once (shared with the LN vjp) and runs the
    plain one-pass colq kernel, and the [M,1] stats are NOT saved as
    residuals at all. None defers to env PTPU_FUSE_BWD_COLQ; the
    trainer threads its own knob (GPTSpmdTrainer(fuse_bwd_colq=...))."""
    if fuse_bwd_colq is None:
        fuse_bwd_colq = _env_fuse_bwd_colq()
    return _int8_ln_linear_all8(bool(fuse_bwd_colq), x, g_ln, b_ln, w,
                                seed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _int8_ln_linear_all8(fuse_bwd_colq, x, g_ln, b_ln, w, seed):
    del seed
    return _int8_matmul_ln(x, g_ln, b_ln, w)[0]


def _fwd_ln_all8(fuse_bwd_colq, x, g_ln, b_ln, w, seed):
    y, m, r = _int8_matmul_ln(x, g_ln, b_ln, w)
    # the [M,1] stats are residuals ONLY for the fused-bwd-colq branch;
    # when it is off they would be dead saves (ADVICE r5)
    stats = (m, r) if fuse_bwd_colq else None
    return y, (x, g_ln, b_ln, w, seed, stats)


def _bwd_ln_all8(fuse_bwd_colq, res, gy):
    x, g_ln, b_ln, w, seed, stats = res
    K = x.shape[-1]
    N = gy.shape[-1]
    # dgrad w.r.t. h = LN(x): int8 per-row, as int8_linear_all8
    gq, gs = quantize_rowwise_fast(gy, axis=-1)
    wq, ws = quantize_rowwise_fast(w, axis=1)
    y = jax.lax.dot_general(gq, wq, (((gy.ndim - 1,), (1,)), ((), ())),
                            preferred_element_type=jnp.int32)
    da = (y.astype(jnp.float32) * gs *
          jnp.reshape(ws, (1,) * (gy.ndim - 1) + (-1,)))
    # LN vjp via jax.vjp on the bf16 cotangent — replays the exact
    # graph the unfused path's autodiff built. A hand-written f32 vjp
    # from the saved stats measured +23.6 ms/step: the f32 [M, K]
    # cotangent feeds three row reductions XLA cannot fuse into one
    # pass, while this form fuses like any other LN backward.
    def _ref_ln(xx, gg, bb):
        xf = xx.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        va = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(va + _LN_EPS)
        return (out * gg + bb).astype(xx.dtype)

    h, ln_vjp = jax.vjp(_ref_ln, x, g_ln, b_ln)
    dx, dg_ln, db_ln = ln_vjp(da.astype(x.dtype))
    # wgrad: SR int8 of h = LN(x). fuse_bwd_colq=True computes the LN
    # inside the colq path (amax pass + tiled SR cast, two reads of x,
    # no h buffer) from the saved stats; False materializes h once
    # (shared with the vjp above) and runs the plain one-pass colq
    # kernel — the bwd then matches the unfused path op-for-op (A/B
    # isolation knob).
    g2 = gy.reshape(-1, N)
    if fuse_bwd_colq:
        m, r = stats
        base = jnp.asarray(seed, jnp.int32) * jnp.int32(1000003)
        hq, hs = sr_quantize_colwise_ln(x.reshape(-1, K), m, r,
                                        g_ln, b_ln,
                                        base + jnp.int32(7919))
        gq2, gs2 = sr_quantize_colwise(g2, base + jnp.int32(104729))
        dw = _wgrad_int8(hq, hs, gq2, gs2, w.dtype, "kn")
    else:
        dw = _wgrad_all8(h.reshape(-1, K), g2, seed, w.dtype)
    import numpy as np
    return (dx, dg_ln, db_ln, dw,
            np.zeros((), jax.dtypes.float0))


_int8_ln_linear_all8.defvjp(_fwd_ln_all8, _bwd_ln_all8)
