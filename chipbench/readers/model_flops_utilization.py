"""6 x parameters x tokens/s (a chip) over a peak, in percent: the
end-to-end rate times a constant, not a kernel's roofline share."""


def read(args: dict, obs: dict):
    h = obs["host"]
    if "n_params" not in h or "tokens_per_s_per_chip" not in h:
        return None
    return 100.0 * 6.0 * h["n_params"] * h["tokens_per_s_per_chip"] \
        / obs["peaks"][args["peak"]]
