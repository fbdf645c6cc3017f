"""``counters[num] / units[den]`` (or ``/ counters[den]``) over the
window: a count made by the program, e.g. host-to-device bytes per row.
``params["when"]`` = ``"setup"`` reads the set-up's counters instead;
without ``den`` the count itself is the value.

A ratio of two COUNTERS whose window moved neither (a window of
placement hits stages no row) is read over the set-up's counters where
they moved: the placement such a window trains on was made there, and
the ratio is that placement's (``hostdata.staged_row_share`` in
``lr-a9a.fit`` and ``lr-criteo.fit`` since PR 54). A count over the
window's units never falls back: no work of the window is a real 0."""


def read(params, obs):
    if params.get("when") == "setup":
        return _ratio(params, obs["setup_counters"], obs)
    value = _ratio(params, obs["counters"], obs)
    den = params.get("den")
    if (value is None and den is not None and den not in obs["units"]
            and not obs["counters"].get(params["num"])):
        return _ratio(params, obs.get("setup_counters") or {}, obs)
    return value


def _ratio(params, counters, obs):
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
