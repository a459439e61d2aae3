"""The one general traffic generator: data files in, work out.

Every seed gets the same set of sizes and gaps in another order, so
that the seed changes the order of the work and not its amount: a mix
is a block of ``block`` quantile points of each distribution, and each
successive block of requests is one seeded permutation of it.
"""
from __future__ import annotations

import math
import queue
import threading
from typing import Iterator, List, Tuple

import numpy as np

from . import seeding


def zipf_cdf(vocab: int, exponent: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    return np.cumsum(p / p.sum())


class BatchStream:
    """Fresh training batches from a seeded host stream, produced by a
    background thread as an input pipeline is: tokens from a Zipf
    unigram over the vocabulary (rank -> token id by a fixed
    permutation), labels the ids shifted by one."""

    def __init__(self, vocab: int, batch: int, seq: int, traffic: dict,
                 seed: int):
        self._cdf = zipf_cdf(vocab, float(traffic["zipf_exponent"]))
        self._perm = seeding.host_rng(
            traffic["vocab_permutation_seed"], 0).permutation(vocab)
        self._shape = (batch, seq + 1)
        self._rng = seeding.host_rng(seed, 1)
        self._q: queue.Queue = queue.Queue(
            maxsize=int(traffic.get("prefetch", 4)))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._produce,
                                        daemon=True)
        self._thread.start()

    def _produce(self) -> None:
        while not self._stop.is_set():
            ranks = np.searchsorted(self._cdf,
                                    self._rng.random(self._shape))
            toks = self._perm[np.minimum(ranks, len(self._perm) - 1)
                              ].astype(np.int32)
            item = (np.ascontiguousarray(toks[:, :-1]),
                    np.ascontiguousarray(toks[:, 1:]))
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        return self._q.get()

    def close(self) -> None:
        self._stop.set()
        self._thread.join()


def log_uniform_grid(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` midpoint quantiles of the log-uniform law on [lo, hi]."""
    u = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
                   ).astype(np.int64)


def truncated_exponential_grid(mean: float, lo: int, hi: int,
                               n: int) -> np.ndarray:
    """``n`` midpoint quantiles of the exponential law cut to [lo, hi]
    whose mean is ``mean``: the law of largest entropy for a published
    mean between published limits, so nothing but those is chosen. The
    scale is found by bisection on the cut law's own mean."""
    if not lo < mean < (lo + hi) / 2:
        raise ValueError(f"mean {mean} outside ({lo}, {(lo + hi) / 2})")
    w = float(hi - lo)

    def cut_mean(scale):
        return lo + scale - w / math.expm1(w / scale)

    a, b = 1e-3 * w, 1e3 * w
    for _ in range(200):
        mid = math.sqrt(a * b)
        a, b = (mid, b) if cut_mean(mid) < mean else (a, mid)
    scale = math.sqrt(a * b)
    u = (np.arange(n) + 0.5) / n
    return np.rint(lo - scale * np.log1p(-u * -math.expm1(-w / scale))
                   ).astype(np.int64)


def length_grid(spec: dict, n: int) -> np.ndarray:
    """A traffic file's ``prompt_tokens`` or ``output_tokens`` as ``n``
    quantile points: ``{"log_uniform": [lo, hi]}`` or
    ``{"truncated_exponential": {"mean": m, "min": lo, "max": hi}}``."""
    (law, arg), = spec.items()
    if law == "log_uniform":
        return log_uniform_grid(arg[0], arg[1], n)
    if law == "truncated_exponential":
        return truncated_exponential_grid(
            float(arg["mean"]), int(arg["min"]), int(arg["max"]), n)
    raise ValueError(f"no length law {law!r}")


def exponential_grid(n: int) -> np.ndarray:
    """``n`` midpoint quantiles of the unit exponential law, scaled to
    mean exactly 1: a block of Poisson gaps that always spans ``n``."""
    g = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    return g / g.mean()


class RequestMix:
    """Prompt and output lengths, token contents and (open loop) gaps of
    a serving mix, from its traffic file and the seed."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.block = n = int(traffic["block"])
        self.prompt_lengths = length_grid(traffic["prompt_tokens"], n)
        # prompts and outputs are paired by one fixed permutation, so a
        # block holds the same (prompt, output) pairs under every seed
        self._outputs = length_grid(traffic["output_tokens"], n)[
            seeding.host_rng(traffic["pairing_seed"], 0).permutation(n)]
        # a tokenizer's first id: every prompt of a deployment starts
        # with it, so every request shares a one-token prefix
        self.bos = traffic.get("bos_token_id")
        self._gaps = None
        if "rate_rps" in traffic:
            self._gaps = exponential_grid(n) / float(traffic["rate_rps"])
        self._vocab = int(vocab)
        self._order = seeding.host_rng(seed, 2)
        self._tokens = seeding.host_rng(seed, 3)
        self._gap_order = seeding.host_rng(seed, 4)
        self.mean_output = float(self._outputs.mean())

    def requests(self) -> Iterator[Tuple[np.ndarray, int]]:
        """(prompt token ids, max_new_tokens), for ever."""
        while True:
            for i in self._order.permutation(self.block):
                ids = self._tokens.integers(
                    1, self._vocab, int(self.prompt_lengths[i]),
                    dtype=np.int64)
                if self.bos is not None:
                    ids[0] = self.bos
                yield ids, int(self._outputs[i])

    def arrivals(self, start: float) -> Iterator[float]:
        """Due times from ``start`` on, for ever (open loop)."""
        t = start
        while True:
            for i in self._gap_order.permutation(self.block):
                t += float(self._gaps[i])
                yield t

    def truncation(self, n: int) -> List[float]:
        """Fractions in (0, 1] for the outputs of the ``n`` requests in
        flight when a run starts: a server is joined mid-stream, so the
        first requests are as if partly done and do not end together."""
        return list((seeding.host_rng(0, 5).permutation(n) + 1.0) / n)
