"""Kilobytes (1,000 bytes) the state's device subsystems pushed to the
chip per state root in the window, from the device ledger's h2d counters
of device_tree, registry_mirror, packed_cache and staging."""


def read(run):
    c = run["counters"]
    if not c.get("roots"):
        return None
    return c["h2d_bytes"] / 1e3 / c["roots"]
