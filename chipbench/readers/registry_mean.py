"""Mean of a registry histogram over the window: the change of its sum
over the change of its count, times ``scale``."""


def read(args: dict, obs: dict):
    h = obs["registry"].get(args["family"])
    if not h or not h["count"]:
        return None
    return h["sum"] / h["count"] * args.get("scale", 1.0)


SELFTEST_CASE = ({"family": "fam", "scale": 1000.0},
                 {"registry": {"fam": {"sum": 3.0, "count": 60}}}, 50.0)
