"""Driver ``closed_loop``: ``clients`` callers, each sending its next
request when its last one has ended (an offline or overloaded server)."""
from __future__ import annotations

from .. import serving_loop

# what the selftest lays over the cell's traffic file
TINY = {"prompt_tokens": {"log_uniform": [8, 64]},
        "output_tokens": {"log_uniform": [4, 24]},
        "clients": 8, "block": 8, "ramp_seconds": 0.3,
        "verify_requests": 4, "trace_seconds": 0.3}


def run(system, traffic: dict, seed: int, seconds: float, tracer) -> dict:
    clients = int(traffic["clients"])
    return serving_loop.drive(
        system, traffic, seed, seconds, tracer,
        lambda mix, start: serving_loop.ClosedSource(clients, start),
        initial_inflight=0)
