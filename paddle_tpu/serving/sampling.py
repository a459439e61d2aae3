"""Token sampling for the serving engine.

A greedy token is chosen where its logits are; a sampled one on the
host. Continuous batching needs a host round-trip every step (EOS
detection, admission and eviction), and what the trip carries follows
from the batch: the decode program returns, beside its ``[slots,
vocab]`` logits (the model's dtype), their argmax a slot (``[slots]``
int32), and a decode step whose active rows are ALL greedy
(``temperature <= 0``) fetches that vector alone. A step with one row
that samples (``temperature > 0``) fetches the logits and every row of
it is chosen here, as the first token of a prefill, extend or chunk
always is: a sampled token is drawn from the request's own seeded
NumPy stream, which no device sampler could replay token for token,
and one compiled decode program serves every sampling configuration.

Either way every token passes through ``sample_token``, one call a row
(the seam where tests and the benchmark's planted fault see, and alter,
a token as it is produced): a greedy step hands it an ``ArgmaxRow``,
the row as it stayed on the device.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

__all__ = ["ArgmaxRow", "SamplingParams", "sample_token",
           "sampling_dist"]


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling configuration.

    temperature <= 0 means greedy (argmax); top_k == 0 means no top-k
    truncation. ``seed`` pins the request's private RNG stream so a
    replayed trace reproduces token-for-token.
    """
    temperature: float = 0.0
    top_k: int = 0
    seed: Optional[int] = None

    def validate(self):
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")


class ArgmaxRow:
    """One logits row that stayed on the device: its length and its
    argmax (lowest index on a tie, a NaN counts as the maximum, as
    ``np.argmax``) are here; its values cross only for who asks
    (``np.asarray(row)``: tests do, the engine never)."""
    __slots__ = ("_logits", "_slot", "argmax")

    def __init__(self, logits, slot: int, argmax: int):
        self._logits, self._slot, self.argmax = logits, slot, argmax

    def __len__(self) -> int:
        return self._logits.shape[-1]

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._logits[self._slot], dtype=dtype)


def sampling_dist(logits: np.ndarray,
                  params: SamplingParams) -> np.ndarray:
    """The [vocab] float64 distribution ``sample_token`` draws from.

    Exposed for speculative rejection sampling: acceptance needs the
    target (and draft) probabilities of the drafted token, and the
    residual distribution on rejection, under the SAME
    temperature/top-k transform the plain path uses — anything else
    breaks the distribution-parity law vs k=1 decoding. Requires
    temperature > 0 (greedy is a point mass; callers use argmax).
    """
    z = logits.astype(np.float64) / params.temperature
    if 0 < params.top_k < z.size:
        kth = np.partition(z, -params.top_k)[-params.top_k]
        z = np.where(z >= kth, z, -np.inf)
    z -= z.max()
    p = np.exp(z)
    p /= p.sum()
    return p


def sample_token(logits: np.ndarray, params: SamplingParams,
                 rng: np.random.RandomState) -> int:
    """Pick one token id from a [vocab] logits row. An ``ArgmaxRow``
    serves greedy requests only: the engine fetches the logits for a
    step with any row that samples."""
    if isinstance(logits, ArgmaxRow):
        if params.temperature > 0:
            raise ValueError(
                "an ArgmaxRow holds a row's argmax alone: a request "
                f"with temperature {params.temperature} needs its logits")
        return logits.argmax
    if params.temperature <= 0:
        return int(np.argmax(logits))
    p = sampling_dist(logits, params)
    return int(rng.choice(p.size, p=p))
