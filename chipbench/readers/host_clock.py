"""A scalar the driver took on the host's clock, times ``scale``."""


def read(args: dict, obs: dict):
    v = obs["host"].get(args["key"])
    return None if v is None else v * args.get("scale", 1.0)


SELFTEST_CASE = ({"key": "step_ms"}, {"host": {"step_ms": 320.0}}, 320.0)
