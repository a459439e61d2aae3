"""Normalization functionals (reference: python/paddle/nn/functional/norm.py;
phi batch_norm/layer_norm kernels + SPMD rules spmd_rules/layer_norm.cc).

batch_norm updates running stats through the Tensor façade's functional
mutation — stats tensors are rebound, never mutated, so the op stays
jit-safe when stats are carried explicitly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.tensor import Tensor, apply_op, no_grad

__all__ = ["batch_norm", "layer_norm", "group_norm", "instance_norm",
           "local_response_norm", "rms_norm"]


def _update_running_stats(running_mean, running_var, m_t, v_t,
                          momentum, x, ch_axis):
    # paddle momentum convention: running = momentum*running +
    # (1-momentum)*batch, var unbiased by n/(n-1)
    if getattr(m_t, "_data", None) is None:
        # static-graph capture: the batch stats are lazy Variables with
        # no concrete value. Static programs carry stats explicitly
        # (module docstring) — the eager in-place EMA has no meaning
        # at capture time and used to crash on _data=None here.
        return
    with no_grad():
        n = x.size // x.shape[ch_axis]
        unbiased = v_t._data * (n / max(n - 1, 1))
        running_mean._data = (momentum * running_mean._data +
                              (1 - momentum) * m_t._data).astype(
            running_mean._data.dtype)
        running_var._data = (momentum * running_var._data +
                             (1 - momentum) * unbiased).astype(
            running_var._data.dtype)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    use_batch = training and not use_global_stats

    if use_batch:
        # Pallas streaming BN (ops/bn_pallas.py), OPT-IN via
        # FLAGS_bn_pallas and default OFF: measured SLOWER than XLA's
        # BN fusions on v5e NCHW shapes (165-220 vs 263-395 GB/s — the
        # unaligned spatial lane dim defeats Pallas block DMA; XLA
        # re-layouts globally and wins; the rounds-1-5 notes (git history
        # before PR 23) round-5).
        # Kept: the custom_vjp collapses BN backward to a per-channel
        # FMA, and C-minor layouts (point clouds, 3-D voxels with
        # aligned S) may flip the verdict per-model.
        from ...framework.flags import flag_value
        from ...ops.pallas_ops import single_device_tpu
        pallas_ok = False
        if flag_value("FLAGS_bn_pallas") and ch_axis == 1 \
                and x.ndim >= 3 \
                and getattr(x, "_data", None) is not None \
                and single_device_tpu():
            # _data is None for static-graph Variables (lazy capture):
            # those must fall through to apply_op's _lazy_cls dispatch
            from ...ops.bn_pallas import bn_train, bn_train_eligible
            pallas_ok = bn_train_eligible(x._data)
        if pallas_ok:
            args = [a for a in (x, weight, bias) if a is not None]
            nw = len(args) - 1

            def f_pallas(a, *wb):
                w_ = wb[0] if weight is not None else None
                b_ = wb[nw - 1] if bias is not None else None
                return bn_train(a, w_, b_, epsilon)

            f_pallas._direct_custom_vjp = True
            out, m_t, v_t = apply_op(f_pallas, *args,
                                     _op_name="batch_norm")
            _update_running_stats(running_mean, running_var, m_t, v_t,
                                  momentum, x, ch_axis)
            return out
        # compute batch stats; update running stats (paddle momentum
        # convention: running = momentum*running + (1-momentum)*batch)
        def stats(a):
            # ONE fused pass: sum and sum-of-squares reduce together
            # (jnp.mean + jnp.var is TWO reads of the activation — at
            # ResNet-50 bs256 that is gigabytes per step), f32
            # accumulation regardless of activation dtype
            af = a.astype(jnp.float32)
            n = a.size // a.shape[ch_axis]
            s1 = jnp.sum(af, axis=axes)
            s2 = jnp.sum(af * af, axis=axes)
            m = s1 / n
            v = jnp.maximum(s2 / n - m * m, 0.0)
            return m, v
        m_t, v_t = apply_op(stats, x, _op_name="bn_stats")
        _update_running_stats(running_mean, running_var, m_t, v_t,
                              momentum, x, ch_axis)
        mean_used, var_used = m_t, v_t
    else:
        mean_used, var_used = running_mean, running_var

    shape = [1] * x.ndim
    shape[ch_axis] = x.shape[ch_axis]

    def f(a, m, v, *wb):
        # fold (m, v, gamma, beta) into per-CHANNEL f32 scale/shift
        # (C-sized math, free), then one elementwise FMA over the
        # activation with the OUTPUT back in a.dtype — the old
        # ``(a - m_f32) * inv`` promoted the whole activation to f32,
        # doubling the write traffic of every BN in the network
        inv = jax.lax.rsqrt(v.astype(jnp.float32) + epsilon)
        i = 0
        if weight is not None:
            scale = wb[i].astype(jnp.float32) * inv
            i += 1
        else:
            scale = inv
        shift = -m.astype(jnp.float32) * scale
        if bias is not None:
            shift = shift + wb[i].astype(jnp.float32)
        out = (a.astype(jnp.float32) * scale.reshape(shape)
               + shift.reshape(shape))
        return out.astype(a.dtype)

    args = [x, mean_used, var_used]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(f, *args, _op_name="batch_norm")


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    ns = normalized_shape if isinstance(normalized_shape, (list, tuple)) \
        else [normalized_shape]
    axes = tuple(range(x.ndim - len(ns), x.ndim))

    from ... import decomposition as _dec
    decomp = _dec.active("layer_norm")

    def f(a, *wb):
        # fp32 accumulation for bf16 inputs (matches reference fp16/bf16
        # layer_norm numerics: compute in fp32, cast back)
        af = a.astype(jnp.float32)
        if decomp:
            # primitive rule: mean/sub/mul/rsqrt only (no jnp.var fused
            # form); weight/bias applied below as in the fused path
            out = _dec.get_rule("layer_norm")(af, epsilon=epsilon,
                                              axes=axes)
        else:
            m = jnp.mean(af, axis=axes, keepdims=True)
            v = jnp.var(af, axis=axes, keepdims=True)
            out = (af - m) * jax.lax.rsqrt(v + epsilon)
        out = out.astype(a.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i]
            i += 1
        if bias is not None:
            out = out + wb[i]
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(f, *args, _op_name="layer_norm")


def rms_norm(x, weight=None, epsilon=1e-6, begin_norm_axis=-1, name=None):
    """RMSNorm (reference exposes fused rms_norm via incubate
    python/paddle/incubate/nn/functional/fused_rms_norm.py)."""
    axis = begin_norm_axis if begin_norm_axis >= 0 else x.ndim + begin_norm_axis
    axes = tuple(range(axis, x.ndim))

    def f(a, *w):
        af = a.astype(jnp.float32)
        ms = jnp.mean(jnp.square(af), axis=axes, keepdims=True)
        out = (af * jax.lax.rsqrt(ms + epsilon)).astype(a.dtype)
        if w:
            out = out * w[0]
        return out

    args = [x] + ([weight] if weight is not None else [])
    return apply_op(f, *args, _op_name="rms_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    def f(a, *wb):
        n, c = a.shape[0], a.shape[1]
        spatial = a.shape[2:]
        g = a.reshape(n, num_groups, c // num_groups, *spatial)
        axes = tuple(range(2, g.ndim))
        m = jnp.mean(g, axis=axes, keepdims=True)
        v = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - m) * jax.lax.rsqrt(v + epsilon)).reshape(a.shape)
        shape = [1, c] + [1] * len(spatial)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(f, *args, _op_name="group_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9,
                  epsilon=1e-5, data_format="NCHW", name=None):
    axes = tuple(range(2, x.ndim))

    def f(a, *wb):
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + epsilon)
        c = a.shape[1]
        shape = [1, c] + [1] * (a.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply_op(f, *args, _op_name="instance_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def f(a):
        sq = jnp.square(a)
        half = size // 2
        c = a.shape[1]
        pad_cfg = [(0, 0)] * a.ndim
        pad_cfg[1] = (half, size - half - 1)
        padded = jnp.pad(sq, pad_cfg)
        acc = jnp.zeros_like(a)
        for i in range(size):
            acc = acc + jax.lax.slice_in_dim(padded, i, i + c, axis=1)
        return a / jnp.power(k + alpha * acc, beta)
    return apply_op(f, x, _op_name="local_response_norm")
