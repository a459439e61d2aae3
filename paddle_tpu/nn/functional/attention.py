"""Attention functionals.

Reference surface: python/paddle/nn/functional/flash_attention.py:195
(flash_attention), :593 (flash_attn_unpadded), :976
(scaled_dot_product_attention) — backed there by the FlashAttention-2 CUDA
library (phi/kernels/gpu/flash_attn_kernel.cu).

TPU-native: a fused Pallas flash-attention kernel (paddle_tpu.ops.pallas_ops)
when available, with an XLA fallback that relies on XLA's softmax(QK)V
fusion. Layout is paddle's [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework import random as rnd
from ...framework.tensor import Tensor, apply_op

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "ring_attention", "ulysses_attention"]


def _sdpa_xla(q, k, v, mask, causal, dropout_p, key, scale=None):
    # q,k,v: [B, S, H, D] -> compute in [B, H, S, D]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # fp32 softmax accumulation (flash-attn numerics)
    logits = jnp.einsum("bhsd,bhtd->bhst", qh, kh,
                        preferred_element_type=jnp.float32) * s
    if causal:
        sq, skv = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        logits = jnp.where(cm, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(
            probs.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """paddle layout [batch_size, seq_len, num_heads, head_dim]."""
    drop = dropout_p if training else 0.0
    rkey = rnd.op_key(query, key, value) if drop > 0.0 else None

    use_pallas = (attn_mask is None and drop == 0.0 and
                  _pallas_eligible(query, key))
    if use_pallas:
        from ...ops.pallas_ops import flash_attention_fwd
        return apply_op(
            lambda q, k, v: flash_attention_fwd(q, k, v, causal=is_causal),
            query, key, value, _op_name="flash_attention")

    if drop > 0.0:
        if attn_mask is not None:
            return apply_op(
                lambda q, k, v, m, rk:
                    _sdpa_xla(q, k, v, m, is_causal, drop, rk),
                query, key, value, attn_mask, rkey, _op_name="sdpa")
        return apply_op(
            lambda q, k, v, rk: _sdpa_xla(q, k, v, None, is_causal, drop,
                                          rk),
            query, key, value, rkey, _op_name="sdpa")
    if attn_mask is not None:
        return apply_op(
            lambda q, k, v, m: _sdpa_xla(q, k, v, m, is_causal, drop, None),
            query, key, value, attn_mask, _op_name="sdpa")
    return apply_op(
        lambda q, k, v: _sdpa_xla(q, k, v, None, is_causal, drop, None),
        query, key, value, _op_name="sdpa")


def _pallas_eligible(q, k) -> bool:
    import jax
    if jax.default_backend() != "tpu":
        return False
    d = q.shape[-1]
    s = q.shape[1]
    # the kernel assumes square self-attention (Sq == Skv); cached
    # decode with Sq < Skv must take the XLA path
    return (d in (64, 128, 256) and s % 128 == 0
            and k.shape[1] == s)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """python/paddle/nn/functional/flash_attention.py:195 signature."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False, training=True,
                        name=None):
    """Varlen attention: computed by segment-masked dense attention.

    Inputs are packed [total_tokens, heads, dim] with cu_seqlens prefix
    sums (reference :593). The mask reconstruction keeps it one fused XLA
    attention instead of a per-sequence loop.
    """
    def f(q, k, v, cu_q, cu_k):
        total_q = q.shape[0]
        total_k = k.shape[0]
        seg_q = jnp.searchsorted(cu_q, jnp.arange(total_q), side="right") - 1
        seg_k = jnp.searchsorted(cu_k, jnp.arange(total_k), side="right") - 1
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(total_q) - jnp.take(cu_q, seg_q)
            pos_k = jnp.arange(total_k) - jnp.take(cu_k, seg_k)
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        logits = jnp.einsum("qhd,khd->hqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask[None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        probs = jnp.where(mask[None], probs, 0.0)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = apply_op(f, query, key, value, cu_seqlens_q, cu_seqlens_k,
                   _op_name="flash_attn_unpadded")
    return out, None


def ulysses_attention(query, key, value, mesh=None, axis: str = "sep",
                      causal: bool = False, name=None):
    """All-to-all (DeepSpeed-Ulysses) sequence-parallel attention over a
    mesh axis; the sibling of ring_attention for long-context scaling
    (see ops.pallas_ops.ulysses_attention). Requires heads % axis_size
    == 0; seq dim of the inputs sharded over ``axis``."""
    from ...distributed.process_mesh import get_mesh
    from ...ops.pallas_ops import ulysses_attention as _ulysses
    if mesh is None:
        pmesh = get_mesh()
        if pmesh is None:
            return scaled_dot_product_attention(query, key, value,
                                                is_causal=causal)
        mesh = pmesh.jax_mesh()
    elif hasattr(mesh, "jax_mesh"):
        mesh = mesh.jax_mesh()
    return apply_op(lambda q, k, v: _ulysses(q, k, v, mesh, axis, causal),
                    query, key, value, _op_name="ulysses_attention")


def ring_attention(query, key, value, mesh=None, axis: str = "sep",
                   causal: bool = False, name=None):
    """Context-parallel exact attention over a mesh axis (long-context
    path; see ops.pallas_ops.ring_attention). Accepts Tensors with the
    seq dim sharded over ``axis``."""
    from ...distributed.process_mesh import get_mesh
    from ...ops.pallas_ops import ring_attention as _ring
    if mesh is None:
        pmesh = get_mesh()
        if pmesh is None:
            return scaled_dot_product_attention(query, key, value,
                                                is_causal=causal)
        mesh = pmesh.jax_mesh()
    elif hasattr(mesh, "jax_mesh"):
        mesh = mesh.jax_mesh()
    return apply_op(lambda q, k, v: _ring(q, k, v, mesh, axis, causal),
                    query, key, value, _op_name="ring_attention")
