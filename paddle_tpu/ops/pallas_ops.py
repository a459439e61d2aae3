"""Pallas TPU kernels: flash attention (fwd + bwd) with custom VJP.

Replaces the reference's FlashAttention-2 CUDA integration
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu via dynload of
the external flashattn repo; cutlass memory_efficient_attention under
kernels/fusion/cutlass/). TPU-native: blockwise online-softmax attention
written in Pallas — q blocks stream against k/v blocks in VMEM with fp32
accumulators on the MXU; backward follows the standard dq/dk/dv two-pass
recomputation using saved logsumexp. Layout is paddle's
[batch, seq, heads, head_dim] at the API boundary, [B*H, S, D] inside.

On non-TPU backends the kernels run under ``interpret=True`` (tests), and
nn.functional falls back to fused-XLA attention anyway.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # VPU lane width: scalar-per-row carries live as [bq, 128]


def _choose_block(seq_len: int, target: int = 0,
                  which: str = "") -> int:
    """Block size for one kernel axis. Env overrides, most specific
    wins: PTPU_FLASH_BWD_BQ/_BWD_BK beat PTPU_FLASH_BQ/_BK beat the
    all-four fallback PTPU_FLASH_BLOCK — the fwd and bwd kernels have
    different reuse patterns, so their optima differ (the step-level
    sweep lives in benchmarks/).

    Default (round-5 step-level sweeps, the rounds-1-5 notes (git history
    before PR 23)): 1024 blocks
    everywhere — at S=1024 fwd+bwd all-1024 measures 348 ms/step vs
    373 at the old 512 default (fewer grid steps, no online-softmax
    carry rescaling, the PV matmul's contraction grows with the
    block), and the S=2048 re-sweep with SEPARATE fwd/bwd knobs also
    prefers 1024 (407 vs 419 ms/step; the r4 '512 wins at 2048'
    result was an artifact of the single shared knob)."""
    import os
    if target <= 0:
        target = min(seq_len, 1024)
    names = {"fwd_q": ("PTPU_FLASH_BQ",),
             "fwd_k": ("PTPU_FLASH_BK",),
             "bwd_q": ("PTPU_FLASH_BWD_BQ", "PTPU_FLASH_BQ"),
             "bwd_k": ("PTPU_FLASH_BWD_BK", "PTPU_FLASH_BK")}
    for name in names.get(which, ()) + ("PTPU_FLASH_BLOCK",):
        raw = os.environ.get(name, "")
        if raw:
            try:
                override = int(raw)
            except ValueError:
                override = 0
            if override >= 1:  # invalid/sentinel values keep default
                target = override
                break
    b = min(target, seq_len)
    while seq_len % b:
        b //= 2
    return max(b, 1)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def single_device_tpu() -> bool:
    """Whether an un-partitioned Pallas kernel may be emitted here: the
    backend is a TPU and the program is traced for ONE device. Under
    GSPMD a pallas_call is an opaque custom call the partitioner would
    replicate, so multi-device meshes keep the partitionable XLA path.
    The device count is the mesh the step is traced under
    (``jax.set_mesh``; none = jit's single default device), never the
    host's chip count: a one-chip trainer on a four-chip host keeps its
    kernels."""
    return (jax.default_backend() == "tpu"
            and jax.sharding.get_abstract_mesh().size <= 1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# Mosaic-native structure: the k/v block index is a GRID axis (innermost,
# 'arbitrary'), so block DMAs double-buffer automatically while the MXU
# works; the online-softmax carry (acc, m, l) persists in VMEM scratch
# across the innermost axis. Causal masking touches only diagonal blocks
# and strictly-upper blocks are skipped entirely.

def _fa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref, *, bq, bk, nk, causal, scale,
                   id_axes=(1, 2)):
    qi = pl.program_id(id_axes[0])
    j = pl.program_id(id_axes[1])
    j_last = jnp.minimum(((qi + 1) * bq - 1) // bk, nk - 1) if causal \
        else nk - 1
    run = j <= j_last if causal else True

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(run)
    def _body():
        q = q_ref[0]  # [bq, d] bf16: MXU takes bf16 in, accumulates fp32
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            # mask only when this block straddles the diagonal
            diag = (j + 1) * bk - 1 > qi * bq

            @pl.when(diag)
            def _():
                iq = qi * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                ik = j * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                s_ref_val = jnp.where(iq >= ik, s, NEG_INF)
                _online_update(s_ref_val, v, acc_ref, m_ref, l_ref)

            @pl.when(jnp.logical_not(diag))
            def _():
                _online_update(s, v, acc_ref, m_ref, l_ref)
        else:
            _online_update(s, v, acc_ref, m_ref, l_ref)

    @pl.when(j == j_last)
    def _finish():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[:, :1] + jnp.log(l_safe)[:, None]) \
            .astype(jnp.float32)


def _online_update(s, v, acc_ref, m_ref, l_ref):
    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)


def _fa_forward(q, k, v, causal, scale, bq, bk):
    BH, S, D = q.shape
    nk = S // bk
    grid = (BH, S // bq, nk)
    kernel = functools.partial(_fa_fwd_kernel, bq=bq, bk=bk, nk=nk,
                               causal=causal, scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="fa_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _fa_bwd_dkdv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc,
                        *, bq, bk, nq, causal, scale, id_axes=(1, 2)):
    ki = pl.program_id(id_axes[0])
    i = pl.program_id(id_axes[1])
    i_start = (ki * bk) // bq if causal else 0
    run = i >= i_start if causal else True

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(run)
    def _body():
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]
        q = q_ref[0]  # [bq, d]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            iq = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            ik = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(iq >= ik, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])  # [bq, bk]
        pb = p.astype(do.dtype)
        dv_acc[...] += jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                      dq_ref, dq_acc, *, bq, bk, nk, causal, scale,
                      id_axes=(1, 2)):
    qi = pl.program_id(id_axes[0])
    j = pl.program_id(id_axes[1])
    j_last = jnp.minimum(((qi + 1) * bq - 1) // bk, nk - 1) if causal \
        else nk - 1
    run = j <= j_last if causal else True

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            iq = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            ik = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(iq >= ik, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(k.dtype)
        dq_acc[...] += jax.lax.dot(ds, k,
                                   preferred_element_type=jnp.float32)

    @pl.when(j == j_last)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_backward(res, g, causal, scale, bq, bk):
    q, k, v, out, lse = res
    BH, S, D = q.shape
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1)[..., None]  # [BH, S, 1] (lane-dim, see fwd)
    interp = _interpret()
    nq, nk = S // bq, S // bk
    seq_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkdv_kernel, bq=bq, bk=bk, nq=nq,
                          causal=causal, scale=scale),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=seq_params,
        interpret=interp,
        name="fa_bwd_dkdv",
    )(k, v, q, g, lse, delta)
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, bq=bq, bk=bk, nk=nk,
                          causal=causal, scale=scale),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=seq_params,
        interpret=interp,
        name="fa_bwd_dq",
    )(q, g, lse, delta, k, v)[0]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API: [B, S, H, D] layout with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bshd(q, k, v, causal, scale):
    return _flash_fwd_rule(q, k, v, causal, scale)[0]


def _pack(x):
    B, S, H, D = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(B * H, S, D)


def _unpack(x, B, H):
    BH, S, D = x.shape
    return jnp.swapaxes(x.reshape(B, H, S, D), 1, 2)


def _flash_fwd_rule(q, k, v, causal, scale):
    B, S, H, D = q.shape
    bq = _choose_block(S, which="fwd_q")
    bk = _choose_block(S, which="fwd_k")
    qp, kp, vp = _pack(q), _pack(k), _pack(v)
    out, lse = _fa_forward(qp, kp, vp, causal, scale, bq, bk)
    # named so remat policies can keep the flash residuals and skip the
    # whole forward-kernel recompute in the backward pass
    # (models/gpt.py "save_dots" saves these alongside matmul outputs)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return _unpack(out, B, H), (qp, kp, vp, out, lse, B, H, bq, bk)


def _flash_bwd_rule(causal, scale, res, g):
    qp, kp, vp, out, lse, B, H, _, _ = res  # fwd blocks: not reused
    S = qp.shape[1]
    bq, bk = (_choose_block(S, which="bwd_q"),
              _choose_block(S, which="bwd_k"))
    gp = _pack(g)
    dq, dk, dv = _fa_backward((qp, kp, vp, out, lse), gp, causal, scale,
                              bq, bk)
    return (_unpack(dq, B, H), _unpack(dk, B, H), _unpack(dv, B, H))


_flash_bshd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention_fwd(q, k, v, causal=False, scale=None):
    """Fused attention on [batch, seq, heads, head_dim] arrays."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_bshd(q, k, v, causal, scale)


# ---------------------------------------------------------------------------
# ring attention (context parallelism over a mesh axis)
# ---------------------------------------------------------------------------

def ulysses_attention(q, k, v, mesh, axis: str = "sep",
                      causal: bool = False, scale=None,
                      manual_axes=None, use_flash: Optional[bool] = None,
                      in_spec=None):
    """DeepSpeed-Ulysses attention: sequence-sharded activations are
    all-to-all'd into head-sharded full-sequence blocks, attended
    locally, and all-to-all'd back.

    The reference has NO long-context mechanism (SURVEY.md P8 — absent);
    with ring_attention below this is the TPU-native superset. vs ring:
    per-chip kv memory drops to S*(H/n)*D (heads split) instead of the
    gathered S*H*D, comm is two all-to-alls riding ICI, and causal
    masking is the plain triangle since every rank sees the full
    sequence for its head subset. Layout [B, S, H, D], S sharded over
    ``axis``; requires num_heads % n == 0.

    ``manual_axes``: mesh axes to go manual in the shard_map (defaults
    to {axis}); pass ALL mesh axis names to run the Pallas flash kernel
    inside (Mosaic requires a fully-manual region). ``in_spec``:
    override the activation PartitionSpec when batch/head dims are also
    sharded (e.g. P('data','sep','model',None) in the hybrid trainer)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    from jax.sharding import PartitionSpec as P
    axes = set(manual_axes) if manual_axes is not None else {axis}
    if use_flash is None:
        use_flash = (jax.default_backend() == "tpu" and
                     axes == set(mesh.axis_names))

    def per_rank(ql, kl, vl):
        # [B, S/n, H_loc, D] -> [B, S, H_loc/n, D]
        def fwd(x):
            return jax.lax.all_to_all(x, axis, split_axis=2,
                                      concat_axis=1, tiled=True)

        qg, kg, vg = fwd(ql), fwd(kl), fwd(vl)
        if use_flash:
            out = _flash_bshd(qg, kg, vg, causal, scale)
        else:
            out = _dense_bshd(qg, kg, vg, causal, scale)
        return jax.lax.all_to_all(out, axis, split_axis=1,
                                  concat_axis=2, tiled=True)

    spec = in_spec if in_spec is not None else P(None, axis, None, None)
    fn = jax.shard_map(per_rank, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, axis_names=axes, check_vma=False)
    return fn(q, k, v)


def _dense_bshd(q, k, v, causal, scale):
    """Plain fused-XLA attention on [B, S, H, D] (fp32 softmax accum)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        S_q, S_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((S_q, S_k), bool), k=S_k - S_q)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ring_attention(q, k, v, mesh, axis: str = "sep", causal: bool = False,
                   scale=None):
    """Exact attention with the sequence sharded over ``axis``.

    The reference has NO long-context mechanism (SURVEY.md P8 — absent);
    this is the TPU-native superset: k/v blocks rotate around the ring via
    ``ppermute`` while each rank accumulates its queries' online softmax —
    peak memory per chip is O(S/N), comm is overlapped block-by-block over
    ICI. Layout [B, S, H, D] global view; S sharded over ``axis``.

    Differentiable with O(S/N) residual memory: a custom VJP saves only
    the local q/k/v blocks, output, and logsumexp; the backward pass
    re-rotates k/v (flash-attention-style recomputation) while dk/dv
    partial sums travel the ring with their blocks back to the owner —
    jax's default scan autodiff would instead save every rotated block
    (the full sequence per chip), defeating ring attention's point.
    """
    from jax.sharding import PartitionSpec as P

    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    N = mesh.shape[axis]
    perm = [(i, (i + 1) % N) for i in range(N)]

    @jax.custom_vjp
    def per_rank(ql, kl, vl):
        return _ring_fwd(ql, kl, vl)[0]

    def _block_scores(qf, kb, rank, src_rank, Sl):
        s = jnp.einsum("bqhd,bkhd->bqhk", qf,
                       kb.astype(jnp.float32)) * scale
        if causal:
            iq = rank * Sl + jax.lax.broadcasted_iota(
                jnp.int32, (Sl, Sl), 0)
            ik = src_rank * Sl + jax.lax.broadcasted_iota(
                jnp.int32, (Sl, Sl), 1)
            s = jnp.where((iq >= ik)[None, :, None, :], s, NEG_INF)
        return s

    def _ring_fwd(ql, kl, vl):
        rank = jax.lax.axis_index(axis)
        B, Sl, H, D = ql.shape
        qf = ql.astype(jnp.float32)
        acc = jnp.zeros((B, Sl, H, D), jnp.float32)
        m = jnp.full((B, Sl, H), NEG_INF, jnp.float32)
        l = jnp.zeros((B, Sl, H), jnp.float32)

        def step(carry, t):
            acc, m, l, kb, vb = carry
            src_rank = (rank - t) % N  # whose k/v block we hold now
            s = _block_scores(qf, kb, rank, src_rank, Sl)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqhk,bkhd->bqhd", p, vb.astype(jnp.float32))
            kb2 = jax.lax.ppermute(kb, axis, perm)
            vb2 = jax.lax.ppermute(vb, axis, perm)
            return (acc_new, m_new, l_new, kb2, vb2), None

        (acc, m, l, _, _), _ = jax.lax.scan(
            step, (acc, m, l, kl, vl), jnp.arange(N))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = (acc / l_safe[..., None]).astype(ql.dtype)
        lse = m + jnp.log(l_safe)
        return out, lse

    def fwd_rule(ql, kl, vl):
        out, lse = _ring_fwd(ql, kl, vl)
        return out, (ql, kl, vl, out, lse)

    def bwd_rule(res, g):
        ql, kl, vl, out, lse = res
        rank = jax.lax.axis_index(axis)
        B, Sl, H, D = ql.shape
        qf = ql.astype(jnp.float32)
        gf = g.astype(jnp.float32)
        delta = jnp.sum(out.astype(jnp.float32) * gf, axis=-1)  # [B,S,H]
        dq = jnp.zeros((B, Sl, H, D), jnp.float32)

        def step(carry, t):
            dq, kb, vb, dkb, dvb = carry
            src_rank = (rank - t) % N
            s = _block_scores(qf, kb, rank, src_rank, Sl)
            p = jnp.exp(s - lse[..., None])           # [B,Sq,H,Sk]
            dp = jnp.einsum("bqhd,bkhd->bqhk", gf,
                            vb.astype(jnp.float32))
            ds = p * (dp - delta[..., None]) * scale
            dq = dq + jnp.einsum("bqhk,bkhd->bqhd", ds,
                                 kb.astype(jnp.float32))
            dkb = dkb + jnp.einsum("bqhk,bqhd->bkhd", ds, qf)
            dvb = dvb + jnp.einsum("bqhk,bqhd->bkhd", p, gf)
            # k/v grads travel WITH their blocks; after N hops both are
            # back at the owner rank with every rank's contribution
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            dkb = jax.lax.ppermute(dkb, axis, perm)
            dvb = jax.lax.ppermute(dvb, axis, perm)
            return (dq, kb, vb, dkb, dvb), None

        zeros = jnp.zeros((B, Sl, H, D), jnp.float32)
        (dq, _, _, dk, dv), _ = jax.lax.scan(
            step, (dq, kl, vl, zeros, zeros), jnp.arange(N))
        return (dq.astype(ql.dtype), dk.astype(kl.dtype),
                dv.astype(vl.dtype))

    per_rank.defvjp(fwd_rule, bwd_rule)

    spec = P(None, axis, None, None)
    fn = jax.shard_map(per_rank, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, axis_names={axis}, check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# fused-layout flash attention: [B, S, H*D] activations, zero relayouts
# ---------------------------------------------------------------------------
# The packed [B*H, S, D] API above needs a (B,S,H,D)->(B,H,S,D)
# transpose on every input/output — ~34 ms/step of pure relayout in the
# GPT-1.3B profile. These wrappers read each head's slice DIRECTLY from
# the qkv matmul's natural [B, S, H*D] layout via BlockSpec index maps
# (head = a grid axis selecting a column block), so q/k/v/out never
# change layout between the projection matmuls and the kernel. lse
# keeps the [B*H, S, 1] shape via a computed (b*H + h) index map.

def _fa_backward_hsplit(res, g, H, causal, scale, bq, bk):
    q, k, v, out, lse = res
    B, S, HD = q.shape
    D = HD // H
    delta_full = out.astype(jnp.float32) * g.astype(jnp.float32)
    # per-head delta: sum each head's D-column block -> [B*H, S, 1]
    delta = jnp.sum(delta_full.reshape(B, S, H, D), axis=-1)
    delta = jnp.moveaxis(delta, -1, 1).reshape(B * H, S, 1)
    interp = _interpret()
    nq, nk = S // bq, S // bk
    seq4 = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))
    lse_spec_q = pl.BlockSpec((1, bq, 1),
                              lambda b, h, j, i: (b * H + h, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkdv_kernel, bq=bq, bk=bk, nq=nq,
                          causal=causal, scale=scale, id_axes=(2, 3)),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bk, D), lambda b, h, j, i: (b, j, h)),
            pl.BlockSpec((1, bk, D), lambda b, h, j, i: (b, j, h)),
            pl.BlockSpec((1, bq, D), lambda b, h, j, i: (b, i, h)),
            pl.BlockSpec((1, bq, D), lambda b, h, j, i: (b, i, h)),
            lse_spec_q,
            lse_spec_q,
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, h, j, i: (b, j, h)),
            pl.BlockSpec((1, bk, D), lambda b, h, j, i: (b, j, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, HD), q.dtype),
            jax.ShapeDtypeStruct((B, S, HD), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=seq4,
        interpret=interp,
        name="fa_bwd_dkdv",
    )(k, v, q, g, lse, delta)
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, bq=bq, bk=bk, nk=nk,
                          causal=causal, scale=scale, id_axes=(2, 3)),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((1, bq, D), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((1, bq, 1),
                         lambda b, h, i, j: (b * H + h, i, 0)),
            pl.BlockSpec((1, bq, 1),
                         lambda b, h, i, j: (b * H + h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, h, i, j: (b, j, h)),
            pl.BlockSpec((1, bk, D), lambda b, h, i, j: (b, j, h)),
        ],
        out_specs=[pl.BlockSpec((1, bq, D),
                                lambda b, h, i, j: (b, i, h))],
        out_shape=[jax.ShapeDtypeStruct((B, S, HD), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=seq4,
        interpret=interp,
        name="fa_bwd_dq",
    )(q, g, lse, delta, k, v)[0]
    return dq, dk, dv


def _fa_forward_qkvpacked(qkv, H, causal, scale, bq, bk):
    """Forward directly from the projection output [B, S, 3*H*D]:
    q/k/v are the same array with BlockSpec column offsets 0/H/2H."""
    B, S, HD3 = qkv.shape
    D = HD3 // (3 * H)
    nq, nk = S // bq, S // bk
    kernel = functools.partial(_fa_fwd_kernel, bq=bq, bk=bk, nk=nk,
                               causal=causal, scale=scale,
                               id_axes=(2, 3))
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((1, bk, D),
                         lambda b, h, i, j: (b, j, H + h)),
            pl.BlockSpec((1, bk, D),
                         lambda b, h, i, j: (b, j, 2 * H + h)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((1, bq, 1),
                         lambda b, h, i, j: (b * H + h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * D), qkv.dtype),
            jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="fa_fwd",
    )(qkv, qkv, qkv)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flash_qkvpacked(qkv, H, causal, scale):
    return _flash_qkvpacked_fwd(qkv, H, causal, scale)[0]


def _flash_qkvpacked_fwd(qkv, H, causal, scale):
    S = qkv.shape[1]
    bq = _choose_block(S, which="fwd_q")
    bk = _choose_block(S, which="fwd_k")
    out, lse = _fa_forward_qkvpacked(qkv, H, causal, scale, bq, bk)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (qkv, out, lse, bq, bk)


def _flash_qkvpacked_bwd(H, causal, scale, res, g):
    qkv, out, lse, _, _ = res  # fwd blocks: not reused by the bwd
    S = qkv.shape[1]
    bq, bk = (_choose_block(S, which="bwd_q"),
              _choose_block(S, which="bwd_k"))
    HD = out.shape[-1]
    q = qkv[..., :HD]
    k = qkv[..., HD:2 * HD]
    v = qkv[..., 2 * HD:]
    dq, dk, dv = _fa_backward_hsplit((q, k, v, out, lse), g, H, causal,
                                     scale, bq, bk)
    return (jnp.concatenate([dq, dk, dv], axis=-1),)


_flash_qkvpacked.defvjp(_flash_qkvpacked_fwd, _flash_qkvpacked_bwd)


def flash_attention_qkv_fused(qkv, num_heads, causal=False, scale=None):
    """Fused attention straight off the qkv projection output
    [batch, seq, 3*heads*head_dim]; returns [batch, seq, heads*head_dim]
    with no relayout or slicing on the forward path.

    head_dim must be a multiple of 128 (Mosaic lane constraint on the
    column blocks — checked here because interpret mode does not)."""
    if qkv.shape[-1] % (3 * num_heads):
        raise ValueError(
            f"last dim {qkv.shape[-1]} is not 3*num_heads*head_dim "
            f"(num_heads={num_heads})")
    head_dim = qkv.shape[-1] // (3 * num_heads)
    if head_dim % 128:
        raise ValueError(
            f"head_dim {head_dim} must be a multiple of 128 for the "
            f"fused-layout kernel; use flash_attention_fwd instead")
    if scale is None:
        scale = 1.0 / math.sqrt(head_dim)
    return _flash_qkvpacked(qkv, num_heads, causal, scale)
