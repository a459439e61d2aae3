"""Driver ``open_loop``: independent users; requests fall due on a
seeded Poisson schedule at the traffic file's fixed ``rate_rps`` and are
timed from when they were due."""
from __future__ import annotations

from .. import serving_loop

# what the selftest lays over the cell's traffic file
TINY = {"prompt_tokens": {"log_uniform": [8, 64]},
        "output_tokens": {"log_uniform": [4, 24]},
        "block": 8, "ramp_seconds": 0.3, "rate_rps": 20.0,
        "initial_inflight": 2, "drain_seconds": 2,
        "verify_requests": 4, "trace_seconds": 0.3}


def run(system, traffic: dict, seed: int, seconds: float, tracer) -> dict:
    return serving_loop.drive(
        system, traffic, seed, seconds, tracer,
        lambda mix, start: serving_loop.OpenSource(mix.arrivals(start)),
        initial_inflight=int(traffic["initial_inflight"]))
