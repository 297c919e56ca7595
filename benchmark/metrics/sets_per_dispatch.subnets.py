"""Signature sets answered per device dispatch of the verification
service in the window (its ``verified`` + ``rejected`` counters over its
``dispatches``)."""


def read(run):
    c = run["counters"]
    if not c.get("dispatches"):
        return None
    return c["verified_sets"] / c["dispatches"]
