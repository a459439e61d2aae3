"""Share of the traced window in which no operation ran on the device."""


def read(args: dict, obs: dict):
    tr = obs.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


# of the recorded fixture: busy 22 ms of a 25 ms window
SELFTEST_CASE = ({}, {}, 100 * (1 - 0.022 / 0.025))
