"""Host milliseconds of the batched window's state transition per block
in the window: the ``apply`` stage of
``chain_segment_stage_seconds_total`` (every block's slots, epoch
transitions and operations, and the per-block snapshots), over the
window's blocks."""


def read(run):
    c = run["counters"]
    stages = c.get("chain_segment_stage_seconds_total")
    if not stages or "apply" not in stages or not c.get("blocks"):
        return None
    return stages["apply"] * 1e3 / c["blocks"]
