"""Device milliseconds of ``_prepare_cell`` alone (the pubkey gather
and the G1 aggregation of each set's keys, then its RLC ladder) per
1,000 signature sets committed in the window."""


def read(run):
    t, c = run["trace"], run["counters"]
    if t is None or not c.get("sets_committed"):
        return None
    s = t.programs_s(("_prepare_cell",))
    if s is None:
        return None
    return s * 1e3 / (c["sets_committed"] / 1e3)
