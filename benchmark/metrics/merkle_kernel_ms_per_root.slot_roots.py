"""Device milliseconds of the Merkle programs per state root in the
window: the summed device durations of the programs below (the ``XLA
Modules`` events of the chip's trace, by jitted name), over the roots."""

PROGRAMS = (
    "scatter_propagate_body",   # DeviceTree dirty-row scatter + path re-hash
    "_levels_body",             # full level rebuild
    "body",                     # repack + rebuild of an adopted column
    "_chunk_roots_natural_impl",
    "merkleize", "merkle_level", "mix_in_length",
)


def read(run):
    t, c = run["trace"], run["counters"]
    if t is None or not c.get("roots"):
        return None
    s = t.programs_s(PROGRAMS)
    if s is None:
        return None
    return s * 1e3 / c["roots"]
