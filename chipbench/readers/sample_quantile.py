"""A quantile of the raw samples a driver collected in the window
(numpy's linear interpolation), times ``scale``."""
import numpy as np


def read(args: dict, obs: dict):
    xs = obs["samples"].get(args["samples"])
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64),
                               100.0 * args["q"])) * args.get("scale", 1.0)


SELFTEST_CASE = ({"samples": "lat", "q": 0.9, "scale": 1000.0},
                 {"samples": {"lat": [0.001 * i for i in range(101)]}},
                 90.0)
