"""Weight-only int4 serving layers: per-group scales, two weights/byte.

Reference analog: the weight_only_quant int4 pass family under
paddle/fluid/inference (analysis_predictor.h int8/int4 story) and
llm.int4-style serving. Storage is EXPLICIT uint8 nibble packing
(ops/int4_matmul.pack_rows_int4 halves layout) consumed by the fused
Pallas unpack-matmul kernel; per-GROUP symmetric scales along the
contraction dim hold accuracy at 4-bit. NOTE the measured verdict
(the rounds-1-5 notes (git history before PR 23) round-5): on v5e the VPU
unpack cost exceeds
the halved-HBM saving, so int4 decode is SLOWER than the int8-MXU
path — these layers earn their keep on memory capacity (2x model per
chip), not latency.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor, apply_op
from ..nn.layer_base import Layer

from ..ops.int4_matmul import (  # noqa: F401  (re-exports)
    pack_rows_int4, quantize_int4_rows)

__all__ = ["Int4Linear", "weight_only_int4", "quantize_int4_rows",
           "pack_rows_int4"]


class Int4Linear(Layer):
    """Weight-only int4 linear: weights stored as PACKED uint8 nibble
    pairs (0.5 B/weight in HBM, packing is explicit), unpacked + dequantized
    INSIDE the Pallas matmul kernel (ops/int4_matmul.py). A plain XLA
    unpack lowering materializes the bf16 weight copy per call and
    measured 5x SLOWER than bf16 decode — the fused kernel is the
    whole point."""

    def __init__(self, source, group: int = 128):
        super().__init__()
        from ..ops.int4_matmul import pack_rows_int4, quantize_int4_rows
        w = np.asarray(source.weight.numpy())      # [in, out]
        if (w.shape[0] // 2) % group:
            # halves packing needs group | K/2; fall back to a group
            # size that divides (still int4, coarser scaling)
            group = int(np.gcd(w.shape[0] // 2, group))
        q, scale = quantize_int4_rows(w, group)
        self.group = group
        self._in, self._out = w.shape
        self.register_buffer("wq",
                             Tensor(jnp.asarray(pack_rows_int4(q))))
        self.register_buffer("w_scale",
                             Tensor(jnp.asarray(scale, jnp.float32)))
        self.bias = source.bias

    def forward(self, x):
        from ..ops.int4_matmul import int4_matmul
        in_f, out_f = self._in, self._out
        group = self.group

        def f(x, wq, ws, b):
            lead = x.shape[:-1]
            x2 = x.reshape((-1, in_f))
            y = int4_matmul(x2, wq, ws, group=group)
            y = y.reshape(lead + (out_f,))
            if b is not None:
                y = y + b.astype(y.dtype)
            return y.astype(x.dtype)

        args = [x, self.wq, self.w_scale,
                self.bias if self.bias is not None else None]
        if isinstance(x, Tensor):
            # inference-only layer (like the reference's weight-only
            # pass output): the Pallas kernel has no vjp, so the call
            # never records on the tape
            from ..framework.tensor import no_grad
            with no_grad():
                return apply_op(f, *args, _op_name="int4_linear")
        return f(x, getattr(self.wq, "_data", self.wq),
                 getattr(self.w_scale, "_data", self.w_scale),
                 getattr(self.bias, "_data", self.bias)
                 if self.bias is not None else None)


def weight_only_int4(model: Layer, group: int = 128,
                     min_features: int = 256,
                     inplace: bool = True) -> Layer:
    """Swap every big-enough nn.Linear for Int4Linear (see
    weight_only_int8 — same traversal, half the weight bytes)."""
    from ..nn.layer.common import Linear
    from ._swap import swap_layers

    def factory(child):
        if isinstance(child, Linear):
            w = child.weight
            if min(w.shape) >= min_features and \
                    w.shape[0] % group == 0:
                return Int4Linear(child, group)
        return None

    return swap_layers(model, factory, inplace=inplace)
