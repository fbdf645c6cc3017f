"""``counters[num] / units[den]`` (or ``/ counters[den]``) over the
window: a count made by the program, e.g. host-to-device bytes per row.
``params["when"]`` = ``"setup"`` reads the set-up's counters instead;
without ``den`` the count itself is the value."""


def read(params, obs):
    counters = obs["setup_counters" if params.get("when") == "setup" else "counters"]
    if params["num"] not in counters:
        return None
    num = counters[params["num"]]
    den = params.get("den")
    if den is None:
        return float(num)
    d = obs["units"].get(den, counters.get(den))
    if not d:
        return None
    return float(num) / float(d)
