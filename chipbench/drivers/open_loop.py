"""Driver ``open_loop``: independent users; requests fall due on a
seeded Poisson schedule at the traffic file's fixed ``rate_rps`` and are
timed from when they were due."""
from __future__ import annotations

from .. import serving_loop


def run(system, traffic: dict, seed: int, seconds: float, tracer) -> dict:
    return serving_loop.drive(
        system, traffic, seed, seconds, tracer,
        lambda mix, start: serving_loop.OpenSource(mix.arrivals(start)),
        initial_inflight=int(traffic["initial_inflight"]))
