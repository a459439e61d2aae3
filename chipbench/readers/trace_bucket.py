"""Device self time of one step-budget bucket, ms a step."""


def read(args: dict, obs: dict):
    tr = obs.get("trace")
    if not tr or not tr["steps"]:
        return None
    return tr["buckets_ms_per_step"].get(args["bucket"])


# of the recorded fixture (fixtures/mini_step.xplane.pb)
SELFTEST_CASE = ({"bucket": "quantize"}, {}, 1.25)
