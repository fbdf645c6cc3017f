"""100 * (time from a span's start to its first substantial device
operation, ``trace.LEAD_FLOOR`` of its longest) / (the span's length), over the spans named
``params["span"]``: the host's share before the chip gets work
(``hostdata.prep_share.fit``, ``fusion.feed_share.transform``)."""


def read(params, obs):
    t = obs.get("trace")
    if not t:
        return None
    sel = [s for s in t["spans"]
           if s["name"] == params["span"] and s["lead_s"] is not None]
    length = sum(s["end_s"] - s["start_s"] for s in sel)
    if not sel or length <= 0:
        return None
    return 100.0 * sum(s["lead_s"] for s in sel) / length
