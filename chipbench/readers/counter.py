"""A count the driver read from the program, as it is."""


def read(args: dict, obs: dict):
    return obs["counters"].get(args["key"])
