"""Shared KV-cache attention for serving decode.

``cache_attend`` is the pure-jax routine of every causal LM's
fixed-buffer path (llama RoPE attention, gpt learned-position
attention): write the new k/v block into the fixed ``[B, Tmax, KV, D]``
buffers at the write position and attend over the causally masked full
buffer. Its write position ``p`` is a SCALAR, the whole batch at one
position (``dynamic_update_slice``): the synchronized ``generate()``
decode and every whole-prompt prefill. ``paged_cache_attend`` (below)
is what the serving engine's per-row programs run. Both lower to the
same einsum contraction, so per-row results are bitwise identical to
the scalar path's, which is what makes the serving engine's greedy
outputs token-identical to ``generate()``'s.

Both also take an optional per-row write length ``wlen`` (``[B]``
int32; ``cache_attend`` then takes a per-row ``[B]`` position too) —
the SPECULATIVE-VERIFY contract: row ``b`` carries ``wlen[b]`` real
tokens (the last emitted token + its draft window) followed by
``t - wlen[b]`` padding, and only the real tokens write their k/v
(token ``j``'s write is DROPPED when ``j >= wlen[b]`` — out-of-range
scatter index in the fixed buffers, trash-page redirect in the
pages), so padded lanes can never clobber live positions
or run past a row's budget. Reads are untouched: position ``j`` still
attends causally over everything ``<= pos + j``, so the per-position
outputs for ``j < wlen[b]`` are bitwise what a sequential
one-token-at-a-time decode would have computed — the greedy-identity
proof obligation of speculative decoding (paddle_tpu/serving engine,
``speculative=True``). NOTE: draft tokens the verifier then REJECTS
are within ``wlen`` and DO write — their k/v is garbage sitting at
positions >= the new write position. That is safe for the same reason
stale tails have always been safe here (the causal mask hides
positions beyond the current length, and each later step overwrites a
position right before first attending it), but it means decode-written
pages/rows must never be shared or indexed, and the serving engine's
page rollback only returns OVER-ALLOCATED pages, it does not (and need
not) scrub accepted-range pages.

TENSOR-PARALLEL serving note (serving/mesh.py): both attends are
mesh-safe by construction when the cache buffers/pools shard on their
``kv_heads`` axis — every einsum batches over that axis (GQA groups
fold into the per-kv-head contraction instead of crossing it), the
softmax reduces over positions, and the write scatter indexes only
batch/position dims, so no arithmetic ever crosses kv-heads and GSPMD
partitioning preserves BITWISE identity with the single-chip program.
The serving engine relies on this for its sharded token-identity law.

``paged_cache_attend`` is the PAGE-TABLE flavor of the same attention:
instead of one ``[B, Tmax, KV, D]`` row per sequence, k/v live in a
shared pool of fixed-size pages ``[num_pages, page, KV, D]``
and each row carries a static ``[B, pages_per_seq]`` int32 page table.
Writes scatter the new tokens through the table (flat position ``f``
lands in page ``table[b, f // page]`` at offset ``f % page``); reads
gather the row's pages back into a ``[B, pages_per_seq * page, KV, D]``
view and run the IDENTICAL masked einsum as ``cache_attend`` — when
``pages_per_seq * page == Tmax`` the contraction shapes match the
fixed-buffer path exactly, which is what keeps paged greedy decode
token-identical to ``generate()``. Optional int8 storage keeps the
pools in int8 with per-page f32 scales (one scale per page slot ×
position × kv-head, absmax over head_dim) and dequantizes inside the
attend.

The token-identity contracts above are stated for the EINSUM. The
one-token-a-row case on the model-dtype pools (the engine's decode
program) has a second implementation, the Pallas kernel of
``ops/paged_attention.py``, which reads a row's live pages only and is
what one TPU device runs: the same softmax over the same positions with
the query at float32, accumulated a page at a time (online softmax), so
it agrees with the einsum to float32 rounding (on bfloat16 pools: to
the bfloat16 rounding of a probability), not bitwise. Everything else
keeps the einsum: extend, prefill and chunk (``t > 1``), speculative
verify (``wlen``), int8 pages, a program traced under a mesh (the
sharded token-identity law rests on the einsum), ``cache_attend`` and
every CPU run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import paged_attention, pallas_ops
from ..utils.compile_cache import note_fact

__all__ = ["CacheSpec", "cache_attend", "check_cache_pos",
           "paged_cache_attend", "quantize_kv_page"]


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What a causal LM asks the serving engine to hold for it between
    steps (its ``cache_spec()``), a slot: ``layers`` says it layer by
    layer, and a model may mix the two kinds.

    - ``"kv"``: K and V by position, ``[positions, kv_heads,
      head_dim]`` each, in ``dtype``; the engine holds them in pages
      and the layer's cache tuples are ``models/_decode_cache``'s;
    - ``"state"``: fixed-size arrays whatever the length, ``state``
      naming each with its shape a slot and its dtype (the same for
      every state layer); a prefill builds them (cache ``(None, None,
      true_len)``), a decode step reads and rewrites them (``(*arrays,
      pos, active)``).

    A softmax decoder is ``("kv",) * n``, an attention-free one
    ``("state",) * n``."""
    layers: Tuple[str, ...]
    kv_heads: int
    head_dim: int
    dtype: Any                  # the model's
    max_positions: int
    state: Tuple[Tuple[str, Tuple[int, ...], Any], ...] = ()

    def __post_init__(self):
        odd = set(self.layers) - {"kv", "state"}
        if odd or not self.layers:
            raise ValueError(f"layers of kind {sorted(odd)}: a layer "
                             f"keeps 'kv' or 'state'")
        if ("state" in self.layers) != bool(self.state):
            raise ValueError("state layers and their arrays go together")

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    def count(self, kind: str) -> int:
        return sum(1 for k in self.layers if k == kind)


def check_cache_pos(pos, t: int, Tmax: int) -> bool:
    """Validate a static-cache write position against the buffer and
    classify it: returns per_row (True when ``pos`` is a [B] vector).

    When the position is concrete (not under a jax trace), a write past
    the buffer fails HERE with a diagnosis — dynamic_update_slice would
    otherwise silently clamp and corrupt the cache tail."""
    pos_data = getattr(pos, "_data", pos)
    per_row = getattr(pos_data, "ndim", 0) >= 1
    concrete = pos if isinstance(pos, int) else (
        None if isinstance(pos_data, jax.core.Tracer)
        else int(np.asarray(pos_data).max()))
    if concrete is not None and concrete + t > Tmax:
        raise ValueError(
            f"static cache overflow: pos {concrete} + {t} new "
            f"tokens exceeds cache length {Tmax}")
    return per_row


def cache_attend(qr, kr, v, kc, vc, p, wlen=None):
    """Masked fixed-buffer cache attention.

    qr: [B, t, H, D] position-encoded queries; kr/v: [B, t, KV, D] new
    keys (position-encoded) / values; kc/vc: [B, Tmax, KV, D] cache
    buffers; p: int32 write position — a scalar, or with ``wlen`` also
    [B]. ``wlen`` ([B] int32): only the first ``wlen[b]`` incoming
    tokens of row ``b`` write their k/v (speculative verify, chunked
    prefill — see module docstring); None = every token writes, the
    whole batch at one position. GQA folds the
    query-group dim into the einsum against kv-head caches instead of
    materializing a head-repeated cache copy.

    Returns (out [B, t, H*D], kc', vc').
    """
    b, t, h, D = qr.shape
    kv = kr.shape[2]
    rep = h // kv
    Tmax = kc.shape[1]
    if wlen is not None:
        # a scalar position with wlen is the CHUNKED-PREFILL flavor
        # (one row at one position, a real-token count gating the
        # padded tail): broadcast it onto the per-row path
        p = jnp.broadcast_to(jnp.asarray(p, jnp.int32), (b,))
        # write-masked scatter: token j of row b lands at p[b]+j only
        # when j < wlen[b] AND in range; everything else gets index
        # Tmax and mode="drop" discards it (a clamped
        # dynamic_update_slice would smear masked/overflowing writes
        # over the live tail instead)
        idx = p[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        ok = (jnp.arange(t, dtype=jnp.int32)[None, :]
              < wlen[:, None]) & (idx < Tmax)
        widx = jnp.where(ok, idx, Tmax)
        bidx = jnp.arange(b)[:, None]
        kc = kc.at[bidx, widx].set(kr.astype(kc.dtype), mode="drop")
        vc = vc.at[bidx, widx].set(v.astype(vc.dtype), mode="drop")
        qpos = p[:, None] + jnp.arange(t)[None, :]            # [B, t]
        mask = jnp.arange(Tmax)[None, None, :] <= qpos[:, :, None]
        maskx = mask[:, None, None]                    # [B,1,1,t,Tmax]
    else:
        if jnp.ndim(p):
            raise ValueError(
                "per-row cache positions need per-row write lengths: "
                "pass the 4-tuple cache (k, v, pos, wlen)")
        kc = jax.lax.dynamic_update_slice(
            kc, kr.astype(kc.dtype), (0, p, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            vc, v.astype(vc.dtype), (0, p, 0, 0))
        qpos = p + jnp.arange(t)[:, None]                     # [t, 1]
        kpos = jnp.arange(Tmax)[None, :]                      # [1, Tmax]
        mask = kpos <= qpos                          # causal over buffer
        maskx = mask[None, None, None]                 # [1,1,1,t,Tmax]
    note_fact("attend", "einsum")
    qg = qr.reshape(b, t, kv, rep, D)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk",
                        qg.astype(jnp.float32),
                        kc.astype(jnp.float32)) / (D ** 0.5)
    scores = jnp.where(maskx, scores, -1e30)
    # cast back to the CACHE dtype (the model dtype), not qr.dtype:
    # RoPE's float32 cos/sin tables promote a bf16 q to f32, and
    # keying on qr.dtype would upcast the whole value cache + output
    # to f32 on the bf16 decode path
    probs = jax.nn.softmax(scores, axis=-1).astype(vc.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, vc)
    return out.reshape(b, t, h * D), kc, vc


def quantize_kv_page(x):
    """Symmetric int8 quantization of a k/v block ``[..., KV, D]``:
    per-(position, kv-head) absmax over head_dim. Returns (int8 values,
    f32 scales ``[..., KV]``). The scale floor keeps all-zero rows
    (never-written page tails) from dividing by zero."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(pool_rows, scale_rows):
    return pool_rows.astype(jnp.float32) * scale_rows[..., None]


def paged_cache_attend(qr, kr, v, kp, vp, ks, vs, table, p,
                       out_dtype, wlen=None, kernel=None):
    """Masked paged-pool cache attention (see module docstring).

    qr: [B, t, H, D] position-encoded queries; kr/v: [B, t, KV, D] new
    keys/values; kp/vp: [num_pages, page, KV, D] pools (int8 when
    ks/vs scales are given, else the model dtype); ks/vs: per-page f32
    scales [num_pages, page, KV] or None; table: [B, pages_per_seq]
    int32 page table (rows of inactive lanes must point at the
    reserved trash page 0); p: int32 write position, scalar or [B];
    ``wlen`` ([B] int32): only the first ``wlen[b]`` incoming tokens
    of row ``b`` write (speculative verify — masked writes land in the
    trash page); None = every token writes. ``kernel``: for one token
    a row on model-dtype pools, the live-pages Pallas kernel (``True``;
    interpreted on the CPU) or the einsum (``False``); by default the
    kernel where one un-partitioned TPU program is traced and Mosaic
    takes the shapes. The kernel has no gradient rule: the engine's
    programs trace under ``no_grad``, and so does any other caller
    that can reach it.

    Returns (out [B, t, H*D], kp', vp', ks', vs').
    """
    b, t, h, D = qr.shape
    kv = kr.shape[2]
    rep = h // kv
    page = kp.shape[1]
    Tmax = table.shape[1] * page
    pv = jnp.asarray(p, jnp.int32)
    if pv.ndim == 0:
        pv = jnp.broadcast_to(pv, (b,))
    qpos = pv[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    # bucket-padded writes (the shared-prefix extend prefill pads its
    # token block) can run past the table: redirect them into the
    # reserved trash page 0 — the gather clamp would otherwise smear
    # them over a REAL page at a wrong offset
    w_ok = qpos < Tmax
    if wlen is not None:
        w_ok = w_ok & (jnp.arange(t, dtype=jnp.int32)[None, :]
                       < wlen[:, None])
    pidx = jnp.minimum(qpos // page, table.shape[1] - 1)
    pid = jnp.where(w_ok,
                    jnp.take_along_axis(table, pidx, axis=1),
                    0)                                       # [B, t]
    off = jnp.where(w_ok, qpos % page, 0)
    quant = ks is not None
    live_pages = t == 1 and wlen is None and not quant and (
        pallas_ops.single_device_tpu() and paged_attention.kernel_fits(D)
        if kernel is None else kernel)
    if quant:
        kq, ksc = quantize_kv_page(kr)
        vq, vsc = quantize_kv_page(v)
        kp = kp.at[pid, off].set(kq)
        vp = vp.at[pid, off].set(vq)
        ks = ks.at[pid, off].set(ksc)
        vs = vs.at[pid, off].set(vsc)
    else:
        kp = kp.at[pid, off].set(kr.astype(kp.dtype))
        vp = vp.at[pid, off].set(v.astype(vp.dtype))
    if live_pages:
        # the new token is in the pool: it attends to itself
        note_fact("attend", "paged_kernel")
        out = paged_attention.paged_decode_attention(
            qr[:, 0], kp, vp, table, pv, out_dtype)
        return out.reshape(b, 1, h * D), kp, vp, ks, vs
    note_fact("attend", "einsum")
    # gather the row's pages into one [B, Tmax] attend view; with
    # pages_per_seq * page == Tmax this is value-identical to a fixed
    # buffer, so the einsum below matches cache_attend's
    gather = lambda pool: pool[table].reshape(
        b, Tmax, *pool.shape[2:])
    kc = _dequant(gather(kp), gather(ks)) if quant else gather(kp)
    mask = jnp.arange(Tmax)[None, None, :] <= qpos[:, :, None]
    maskx = mask[:, None, None]                    # [B,1,1,t,Tmax]
    qg = qr.reshape(b, t, kv, rep, D)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk",
                        qg.astype(jnp.float32),
                        kc.astype(jnp.float32)) / (D ** 0.5)
    scores = jnp.where(maskx, scores, -1e30)
    if quant:
        vc = _dequant(gather(vp), gather(vs)).astype(out_dtype)
        probs = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    else:
        # bf16 non-shared token-identity contract: same probs dtype
        # and same value einsum as cache_attend
        vc = gather(vp)
        probs = jax.nn.softmax(scores, axis=-1)
        if jnp.dtype(out_dtype).itemsize > vc.dtype.itemsize:
            # a float32 caller of a bfloat16 pool (a model whose
            # arithmetic is float32 over bfloat16 storage): the
            # probabilities stay whole, only what is stored is rounded
            vc = vc.astype(probs.dtype)
        else:
            probs = probs.astype(vc.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, vc)
    return (out.reshape(b, t, h * D).astype(out_dtype),
            kp, vp, ks, vs)
