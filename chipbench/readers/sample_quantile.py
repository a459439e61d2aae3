"""A quantile of the raw samples a driver collected in the window
(numpy's linear interpolation), times ``scale``."""
import numpy as np


def read(args: dict, obs: dict):
    xs = obs["samples"].get(args["samples"])
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64),
                               100.0 * args["q"])) * args.get("scale", 1.0)
