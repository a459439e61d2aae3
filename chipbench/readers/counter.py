"""A count the driver read from the program, as it is."""


def read(args: dict, obs: dict):
    return obs["counters"].get(args["key"])


SELFTEST_CASE = ({"key": "compiles_in_window"},
                 {"counters": {"compiles_in_window": 0}}, 0)
