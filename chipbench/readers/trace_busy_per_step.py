"""Device busy time in the traced window over the steps in it, ms."""


def read(args: dict, obs: dict):
    tr = obs.get("trace")
    if not tr or not tr["steps"]:
        return None
    return tr["busy_s"] * 1e3 / tr["steps"]


# of the recorded fixture: busy 22 ms over 2 steps
SELFTEST_CASE = ({}, {}, 11.0)
