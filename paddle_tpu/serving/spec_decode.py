"""Self-speculative draft proposal: n-gram / prompt-lookup decoding.

The draft side of speculative decoding without a second model: each
request's own token history (prompt + generated tokens) is indexed by
n-gram, and when the current suffix n-gram has occurred before, the
tokens that FOLLOWED that earlier occurrence are proposed as the next
draft window. On repetitive traffic — code, templated chat, extraction
over a quoted document, or any greedy loop that falls into a cycle —
the continuation after a repeated n-gram is very often the same
continuation again, so the verify program accepts several tokens per
weight pass. On non-repetitive traffic the proposer simply finds no
match and the engine runs that row at k=1 inside the same compiled
verify program (the fallback costs no extra compile and no extra host
round-trip).

Pure host-side and deterministic by construction: proposals are a
function of the token history alone (no RNG, no clock), which is what
keeps speculative greedy decoding replayable — and lets the chaos
harness treat drafts as part of the seeded episode.

State is per-request and incremental (each call only indexes the
tokens appended since the last call), so the per-step cost is O(new
tokens x ngram span), not O(history). The engine releases a request's
state when its slot is evicted (finish, deadline, cancel, disconnect)
and prunes to the surviving in-flight set after ``recover()`` — the
no-leak law for proposer state is audited by the chaos invariants.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

__all__ = ["NgramProposer", "DraftModelProposer"]

_EMPTY = np.zeros((0,), np.int64)


class NgramProposer:
    """Prompt-lookup draft proposer over per-request token history.

    ``ngram`` is the longest suffix n-gram matched (the proposer backs
    off to shorter n-grams down to ``min_ngram`` — a single repeated
    token already drafts on a 1-gram); ``max_draft`` caps the proposed
    window (the engine passes ``spec_k - 1``). Matching prefers the
    longest n-gram, and within one n-gram length the MOST RECENT
    earlier occurrence (recency tracks the local pattern of the
    sequence better than the first occurrence).
    """

    def __init__(self, ngram: int = 2, max_draft: int = 3,
                 min_ngram: int = 1):
        if ngram < 1:
            raise ValueError(f"ngram must be >= 1, got {ngram}")
        if not 1 <= min_ngram <= ngram:
            raise ValueError(
                f"min_ngram must be in [1, ngram={ngram}], got "
                f"{min_ngram}")
        if max_draft < 0:
            raise ValueError(
                f"max_draft must be >= 0, got {max_draft}")
        self.ngram = int(ngram)
        self.min_ngram = int(min_ngram)
        self.max_draft = int(max_draft)
        # rid -> {"done": processed history length,
        #         "maps": {n: {ngram tuple: last end position}}}
        self._state: Dict[int, dict] = {}

    # -- state lifecycle (engine hooks) --------------------------------
    def release(self, rid: int) -> None:
        """Drop one request's index (slot eviction: finish, deadline,
        cancel, disconnect)."""
        self._state.pop(rid, None)

    def retain(self, rids: Iterable[int]) -> None:
        """Keep only the given requests' indexes (``recover()`` prunes
        to the rebuilt in-flight set; ``drain()`` passes ())."""
        keep = set(rids)
        for rid in [r for r in self._state if r not in keep]:
            del self._state[rid]

    def tracked(self) -> list:
        """Rids with live index state (the no-leak audit surface)."""
        return sorted(self._state)

    def unwind(self, rid: int) -> None:
        """Discard one request's partial state after a mid-step draft
        fault; the next proposal re-indexes from scratch. For the
        n-gram proposer the index is derived purely from confirmed
        history, so this is just a release."""
        self._state.pop(rid, None)

    # -- proposal ------------------------------------------------------
    def _update(self, st: dict, ids: np.ndarray) -> None:
        """Index every n-gram ENDING strictly before the final
        position (the suffix about to be looked up must only match
        EARLIER occurrences), resuming from the last processed
        length."""
        end = len(ids) - 1               # exclusive bound on ngram end
        maps = st["maps"]
        for n in range(self.min_ngram, self.ngram + 1):
            m = maps[n]
            for i in range(max(n - 1, st["done"]), end):
                m[tuple(int(t) for t in ids[i - n + 1:i + 1])] = i
        st["done"] = end

    def propose(self, rid: int, ids: np.ndarray,
                max_tokens: Optional[int] = None) -> np.ndarray:
        """Draft up to ``max_tokens`` (default ``max_draft``) next
        tokens for the sequence ``ids`` (prompt + generated so far).
        Returns an int64 array, possibly empty (no match -> the engine
        falls back to k=1 for this row)."""
        want = self.max_draft if max_tokens is None \
            else min(int(max_tokens), self.max_draft)
        L = int(len(ids))
        if want < 1 or L < self.min_ngram + 1:
            return _EMPTY
        st = self._state.get(rid)
        if st is None or st["done"] > L - 1:
            # unknown rid, or history SHRANK (adoption/replay edge):
            # rebuild from scratch — correctness over cleverness
            st = {"done": 0,
                  "maps": {n: {} for n in
                           range(self.min_ngram, self.ngram + 1)}}
            self._state[rid] = st
        self._update(st, ids)
        for n in range(min(self.ngram, L - 1), self.min_ngram - 1, -1):
            key = tuple(int(t) for t in ids[L - n:])
            pos = st["maps"][n].get(key)
            if pos is not None:
                draft = ids[pos + 1:pos + 1 + want]
                if len(draft):
                    return np.asarray(draft, np.int64)
        return _EMPTY


class DraftModelProposer:
    """Small-draft-model proposer: a tiny GPT-family causal LM drafts
    the next ``max_draft`` tokens autoregressively, sharing the
    serving stack's cache/program machinery — ONE compiled draft
    program (a window-``W`` write-masked forward over fixed buffers,
    the 4-tuple cache of ``models/_decode_cache``) over a
    slot-mirrored per-layer
    ``[max_slots, max_len, H, D]`` KV pool. The engine admits, evicts
    and recovers proposer state in lockstep with its own slots
    (release/retain below), so the no-leak law that audits the n-gram
    index audits this pool too.

    Position discipline (what makes drafting restart-safe without an
    unwind protocol): ``_state[rid]["n"]`` counts CONFIRMED tokens
    whose KV writes are final. Every proposal first catches the draft
    cache up to the full confirmed history — re-feeding from
    ``min(n, L-1)`` so the returned logits are always fresh — then
    chains wlen=1 forwards for the draft tokens. Draft-chain writes
    land at positions >= L and are simply overwritten by the next
    catch-up (re-feeding a confirmed token over an identical prefix is
    bitwise idempotent, and the causal scope never reads past the
    cursor), so a rejected draft, a faulted step, or a retried step
    needs no cache rollback here. Proposals are a deterministic
    function of (weights, history) for greedy requests — the
    token-identity law holds whatever the draft model predicts, since
    the k-wide verify program only ever accepts tokens equal to the
    target's own greedy chain.
    """

    def __init__(self, model, max_slots: int, max_len: int,
                 max_draft: int = 3):
        from .engine import _ModelAdapter      # circular at import time
        if max_slots < 1:
            raise ValueError(
                f"max_slots must be >= 1, got {max_slots}")
        if max_draft < 0:
            raise ValueError(
                f"max_draft must be >= 0, got {max_draft}")
        self.adapter = _ModelAdapter(model)
        if self.adapter.max_positions < max_len:
            raise ValueError(
                f"draft model supports {self.adapter.max_positions} "
                f"positions < engine max_len={max_len}; speculation "
                "must cover the full target horizon")
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.max_draft = int(max_draft)
        # window of the ONE compiled program: wide enough to chain a
        # full draft (wlen=1 calls) and to batch catch-up ingestion
        self.window = max(1, self.max_draft + 1)
        self._params, self._buffers = self.adapter.model.raw_state()
        # trace-time compile counter; the owning engine rebinds this
        # to its own trace_counts dict so draft compiles surface as
        # trace_counts["draft"] next to decode/verify
        self.trace_counts = {"draft": 0}
        self.registry = None      # the owning engine's, for the count
        self._jit = None
        self._ks = self._vs = None             # lazy [S, T, H, D] pools
        # rid -> {"slot": draft-pool slot, "n": confirmed tokens whose
        # KV writes are final}; insertion-ordered for tracked()
        self._state: Dict[int, dict] = {}
        self._free = list(range(self.max_slots - 1, -1, -1))

    # -- state lifecycle (engine hooks, NgramProposer-compatible) ------
    def release(self, rid: int) -> None:
        st = self._state.pop(rid, None)
        if st is not None:
            self._free.append(st["slot"])

    def retain(self, rids: Iterable[int]) -> None:
        keep = set(rids)
        for rid in [r for r in self._state if r not in keep]:
            self.release(rid)

    def tracked(self) -> list:
        return sorted(self._state)

    def unwind(self, rid: int) -> None:
        """Drop one request's draft state after a mid-step fault that
        fired BEFORE any forward ran (pool contents untouched): the
        next proposal re-ingests the confirmed history from scratch."""
        self.release(rid)

    def reset(self) -> None:
        """Drop ALL draft state AND the KV pools (lazily re-allocated).
        The recovery hammer for a draft forward that failed with
        donated pools in flight — the donation contract means the
        arrays may be poisoned, exactly the engine-side failure mode
        ``ServingEngine.recover()`` handles for the target pools."""
        self._state.clear()
        self._free = list(range(self.max_slots - 1, -1, -1))
        self._ks = self._vs = None

    def free_slots(self) -> int:
        return len(self._free)

    # -- the ONE compiled draft program --------------------------------
    def _pools(self):
        if self._ks is None:
            import jax.numpy as jnp
            ad = self.adapter
            shape = (self.max_slots, self.max_len, ad.kv_heads,
                     ad.head_dim)
            self._ks = [jnp.zeros(shape, ad.dtype)
                        for _ in range(ad.num_layers)]
            self._vs = [jnp.zeros(shape, ad.dtype)
                        for _ in range(ad.num_layers)]
        return self._ks, self._vs

    def _draft_fn(self):
        """THE draft program (compiled once): a [max_slots, window]
        write-masked forward at per-slot positions — the engine's
        verify program over the proposer's own row-a-slot buffers,
        without the acceptance rule. wlen=1
        calls chain draft tokens; wlen=w calls batch catch-up
        ingestion of confirmed history. Same program either way —
        compile count 1, trace-count asserted."""
        if self._jit is not None:
            return self._jit
        import jax
        import jax.numpy as jnp
        from ..framework.tensor import Tensor
        from ..utils.compile_cache import Watched, note_trace
        ad = self.adapter

        def ptpu_draft(params, buffers, toks, pos, active, wlen, ks, vs):
            self.trace_counts["draft"] += 1
            note_trace("draft")
            pos_eff = jnp.where(active, pos, 0).astype(jnp.int32)
            wl_eff = jnp.where(active, wlen, 0).astype(jnp.int32)
            caches = [(k, v, pos_eff, wl_eff)
                      for k, v in zip(ks, vs)]
            with ad.model.bind_state(params, buffers):
                h, new_caches = ad.call(Tensor(toks), caches)
                logits = ad.head(h)._data        # [S, W, vocab]
            logits = jnp.where(active[:, None, None], logits, 0.0)
            ks2 = [getattr(c[0], "_data", c[0]) for c in new_caches]
            vs2 = [getattr(c[1], "_data", c[1]) for c in new_caches]
            return logits, ks2, vs2

        self._jit = Watched(
            jax.jit(ptpu_draft, donate_argnums=self._donate_idx(6, 7)),
            self.registry)
        return self._jit

    @staticmethod
    def _donate():
        """Donation flag + the pool argument indices, mirroring
        ServingEngine._donate: CPU skips donation (tests monkeypatch
        this to simulate the TPU donated-pool failure mode)."""
        import jax
        return () if jax.default_backend() == "cpu" else (6, 7)

    def _donate_idx(self, *idx):
        return idx if self._donate() else ()

    def _forward(self, slot: int, toks, pos: int, wlen: int):
        """One window forward for ONE slot; returns the np logits row
        [window, vocab] for that slot."""
        S, W = self.max_slots, self.window
        tok_block = np.zeros((S, W), np.int64)
        tok_block[slot, :len(toks)] = np.asarray(toks, np.int64)
        pos_v = np.full((S,), 0, np.int32)
        pos_v[slot] = pos
        active = np.zeros((S,), bool)
        active[slot] = True
        wl = np.zeros((S,), np.int32)
        wl[slot] = wlen
        ks, vs = self._pools()
        logits, self._ks, self._vs = self._draft_fn()(
            self._params, self._buffers, tok_block, pos_v, active,
            wl, ks, vs)
        return np.asarray(logits[slot])

    # -- proposal ------------------------------------------------------
    def _ensure(self, rid: int) -> Optional[dict]:
        st = self._state.get(rid)
        if st is None:
            if not self._free:
                return None                    # degrade to k=1
            st = {"slot": self._free.pop(), "n": 0}
            self._state[rid] = st
        return st

    def _catch_up(self, st: dict, ids: np.ndarray) -> Optional[np.ndarray]:
        """Ingest confirmed history into the draft cache up to
        ``len(ids)``; returns the logits row predicting token
        ``len(ids)`` (None when the history overruns the pool).
        ``n`` advances only after each successful forward, so a fault
        mid-catch-up leaves a consistent shorter prefix."""
        L = int(len(ids))
        if L > self.max_len:
            return None
        if st["n"] > L - 1:
            st["n"] = 0                        # history shrank: rebuild
        start = min(st["n"], L - 1)            # re-feed last token so
        out = None                             # logits are fresh
        while start < L:
            w = min(self.window, L - start)
            out = self._forward(st["slot"], ids[start:start + w],
                                start, w)[w - 1]
            start += w
            st["n"] = max(st["n"], start)
        return out

    def propose(self, rid: int, ids: np.ndarray,
                max_tokens: Optional[int] = None) -> np.ndarray:
        """Greedy draft chain: argmax of the draft model's own
        sequential predictions. Same signature/return contract as
        NgramProposer.propose."""
        toks, _ = self._propose(rid, ids, max_tokens, None, None)
        return toks

    def propose_sampled(self, rid: int, ids: np.ndarray,
                        max_tokens: Optional[int], params,
                        rng) -> Tuple[np.ndarray, List[np.ndarray]]:
        """Sampled draft chain for rejection-sampling acceptance:
        draft token j is DRAWN from the draft distribution q_j
        (sampling.sampling_dist under the request's own params/rng),
        and every q_j is returned so ``_emit_verified`` can compute
        min(1, p/q) and the residual. Lossless speculative sampling
        requires drafts sampled from the very q used in the ratio."""
        return self._propose(rid, ids, max_tokens, params, rng)

    def _propose(self, rid, ids, max_tokens, params, rng):
        from .sampling import sampling_dist
        want = self.max_draft if max_tokens is None \
            else min(int(max_tokens), self.max_draft)
        L = int(len(ids))
        if want < 1 or L < 1 or L >= self.max_len:
            return _EMPTY, []
        st = self._ensure(rid)
        if st is None:
            return _EMPTY, []
        logits = self._catch_up(st, np.asarray(ids, np.int64))
        if logits is None:
            return _EMPTY, []
        draft, qs = [], []
        for j in range(want):
            if params is None:
                t = int(np.argmax(logits))
            else:
                q = sampling_dist(logits, params)
                t = int(rng.choice(q.size, p=q))
                qs.append(q)
            draft.append(t)
            pos = L + j
            if j + 1 >= want or pos >= self.max_len:
                break
            # speculative feed: writes at positions >= confirmed n,
            # overwritten by the next catch-up — no unwind needed
            logits = self._forward(st["slot"], [t], pos, 1)[0]
        return np.asarray(draft, np.int64), qs
