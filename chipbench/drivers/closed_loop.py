"""Driver ``closed_loop``: ``clients`` callers, each sending its next
request when its last one has ended (an offline or overloaded server)."""
from __future__ import annotations

from .. import serving_loop


def run(system, traffic: dict, seed: int, seconds: float, tracer) -> dict:
    clients = int(traffic["clients"])
    return serving_loop.drive(
        system, traffic, seed, seconds, tracer,
        lambda mix, start: serving_loop.ClosedSource(clients, start),
        initial_inflight=0)
