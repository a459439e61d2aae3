"""The whole step's share of a peak, in percent: the model's operations
a second a chip, as the cell's driver counted them over the window
(``obs["host"]["model_flops_per_s_per_chip"]``: training 6 x parameters
x tokens/s, serving 2 x the parameters each prompt and served token
passes through), over ``args["peak"]``. The end-to-end rate times
constants, not a kernel's roofline share."""


def read(args: dict, obs: dict):
    rate = obs["host"].get("model_flops_per_s_per_chip")
    if rate is None:
        return None
    return 100.0 * rate / obs["peaks"][args["peak"]]


SELFTEST_CASE = ({"peak": "bf16_flops"},
                 {"host": {"model_flops_per_s_per_chip": 6e9 * 19700.0}},
                 60.0)
