"""Device milliseconds of the BLS verify programs per 1,000 signature
sets committed in the window: the summed device durations of the
programs below (the ``XLA Modules`` events of the chip's trace, by
jitted name), over the sets of the batches committed on the device
path.  The program list is the gossip reader's."""

PROGRAMS = (
    "_prepare_cell",            # pubkey gather, G1 aggregation, RLC ladder
    "hash_g2_kernel_call",      # hash-to-curve
    "sigma_kernel_call",        # signature-side RLC fold
    "_sigma_point", "_sigma_add", "_sigma_block",
    "_cell_bad",
    "_miller_cell",             # fused Miller loop + lane fold
    "_fold_pair",               # product fold
    "_finalize_call_body",      # final exponentiation
    "_combine_verdict",
)


def read(run):
    t, c = run["trace"], run["counters"]
    if t is None or not c.get("sets_committed"):
        return None
    s = t.programs_s(PROGRAMS)
    if s is None:
        return None
    return s * 1e3 / (c["sets_committed"] / 1e3)
