"""100 * (1 - busy / window): the share of the traced window in which no
operation ran on the chip, meaned over the chips."""


def read(params, obs):
    t = obs.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_mean_s"] / t["window_s"])
