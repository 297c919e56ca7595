"""Milliseconds a batch waits on its signature verdict after its host
work (the ``sig_join`` stage of ``chain_segment_stage_seconds_total``):
the device BLS time that the transition and the boundary root left
unhidden, per batch in the window."""


def read(run):
    c = run["counters"]
    stages = c.get("chain_segment_stage_seconds_total")
    if not stages or "sig_join" not in stages or not c.get("batches"):
        return None
    return stages["sig_join"] * 1e3 / c["batches"]
