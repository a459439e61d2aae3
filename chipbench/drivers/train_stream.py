"""Driver ``train_stream``: train on a fresh seeded batch every step.

Set-up: the first batch's loss under the plain reference, the first
step (which compiles or loads the step program) and the warm-up steps.
Window: steps are dispatched one ahead of the device — step i+1 is
enqueued, then the loss of step i is fetched — so the device never
waits for the host and the host never runs more than one step ahead;
the window closes with the ``device_get`` of the last loss, and every
token counted belongs to a step that completed inside it.
"""
from __future__ import annotations

import math
import time

from .. import reference
from ..setup_marks import mark
from ..traffic_gen import BatchStream

clock = time.perf_counter

# what the selftest lays over the cell's traffic file
TINY = {"trace_seconds": 0.3}


def _steps(system, stream, tracer, seconds, losses, ends=None):
    """Dispatch steps for ``seconds``; returns (steps, elapsed) with
    the device drained at the end. ``ends`` takes the clock at each
    loss fetched (a step's end as the host sees it)."""
    import jax
    t0 = clock()
    n, pending = 0, None
    while True:
        with tracer.span("next_batch"):
            ids, labels = stream.next()
        loss = system.step(ids, labels)
        n += 1
        if pending is not None:
            with tracer.span("device_get"):
                losses.append(float(jax.device_get(pending)))
            if ends is not None:
                ends.append(clock())
        pending = loss
        if clock() - t0 >= seconds:
            break
    with tracer.span("device_get"):
        losses.append(float(jax.device_get(pending)))
    return n, clock() - t0


def run(system, traffic: dict, seed: int, seconds: float, tracer) -> dict:
    import jax
    stream = BatchStream(system.vocab, system.batch, system.seq, traffic,
                         seed)
    try:
        ids, labels = stream.next()
        ref = system.reference_loss(ids, labels)
        mark("reference_loss")
        losses = [float(jax.device_get(system.step(ids, labels)))]
        mark("first_step")
        first_err = abs(losses[0] - ref) / abs(ref)
        for _ in range(int(traffic["warmup_steps"])):
            ids, labels = stream.next()
            losses.append(float(jax.device_get(
                system.step(ids, labels))))
        mark("warm_steps")
        programs0 = system.programs()
        traced = tracer.seconds if tracer.seconds else 0.0
        t_window = clock()
        ends = []
        steps, elapsed = _steps(system, stream, tracer,
                                seconds - traced, losses, ends)
        if traced:
            tracer.start()
            n_tr, _ = _steps(system, stream, tracer, traced, losses)
            tracer.stop(n_tr)
    finally:
        stream.close()
    tokens = steps * system.batch * system.seq
    rate = tokens / elapsed / system.chips
    # whether a slow run is slow in every step or stalls in a few
    gaps = sorted(b - a for a, b in zip(ends, ends[1:]))
    step_profile = {
        "median_ms": gaps[len(gaps) // 2] * 1e3, "max_ms": gaps[-1] * 1e3,
        "over_1.05_median": sum(g > 1.05 * gaps[len(gaps) // 2]
                                for g in gaps)} if gaps else {}
    k = min(10, len(losses) // 2)
    nonfinite = sum(not math.isfinite(x) for x in losses)
    compared = [
        {"name": "first_loss_rel_err", "value": first_err,
         "limit": getattr(system, "loss_rtol", reference.GPT_LOSS_RTOL)},
        {"name": "nonfinite_losses", "value": nonfinite, "limit": 0},
        # the mean of the last ten losses over that of the first ten
        {"name": "last_over_first_losses",
         "value": sum(losses[-k:]) / sum(losses[:k]), "limit": 1.0},
    ]
    return {
        "t_window": t_window,
        "attempted": steps,
        "failed": nonfinite,
        "compared": compared,
        "notes": {"first_loss": losses[0], "reference_loss": ref,
                  "first_loss_rel_err": first_err,
                  "last_loss": losses[-1], "steps": steps,
                  "step_profile": step_profile,
                  "losses_head": losses[:5], "losses_tail": losses[-5:]},
        "end_to_end": {"train_tokens_per_s": rate},
        "obs": {
            "host": {"step_ms": elapsed / steps * 1e3,
                     # forward and backward: 6 operations a parameter
                     # a token
                     "model_flops_per_s_per_chip":
                         6.0 * system.n_params * rate,
                     "window_s": elapsed},
            "counters": {
                "compiles_in_window": system.programs() - programs0},
            "samples": {},
            "registry": {},
        },
    }
