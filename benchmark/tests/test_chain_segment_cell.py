"""Whole runs of the range-sync cell on the CPU at a small registry, with
the chip check skipped and the host BLS backend in the device's place: a
sound run comes out correct, and each fault planted under the timed path
comes out not correct — a batch verdict flipped where it is produced, a
batch with its last block dropped, a head state changed behind the
chain's back, and a device verify that aggregates only each set's first
key.  The cell runs on one chip, so there is no exchange to leave out."""

import secrets

import pytest

import run as R

CELL = ["--workload", "mainnet-1m-rangesync.full_block_batches",
        "--seed", "3000000031", "--seconds", "1"]
# 2^11 validators (one committee of 64 a slot), one window batch, a
# smaller reference sample.
SMALL = {"config": {"deployment": {"validators": 2048},
                    "service": {"backend": "python"}},
         "mix": {"window_hint_s": 1,
                 "reference_sample": {"attestation": 2, "sync": 1,
                                      "proposal": 1, "randao": 1}}}


def _run(*extra):
    randbits = secrets.randbits
    try:
        return R.run(CELL + list(extra), require_tpu=False, overrides=SMALL)
    finally:
        secrets.randbits = randbits


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    c = out["counters"]
    assert c["batches"] >= 1 and c["blocks"] == 64 * c["batches"]
    assert c["sets_committed"] == out["attempted"] and out["failed"] == 0
    assert out["metrics"]["sets_per_s"]["value"] > 0
    assert set(c["chain_segment_stage_seconds_total"]) >= {
        "apply", "boundary_root", "sig_join", "commit"}
    assert R.load_reader("transition_host_ms_per_block.batches")(out) > 0
    assert R.load_reader("sig_wait_ms_per_batch.batches")(out) > 0


@pytest.mark.parametrize("fault,check", [
    ("flipped_verdict", "wrong_outcomes"),
    ("dropped_block", "wrong_outcomes"),
    ("wrong_head_root", "root_mismatches"),
    ("first_key", "wrong_outcomes"),
])
def test_broken_runs_are_not_correct(fault, check):
    out = _run("--fault", fault)
    assert not out["correct"]
    assert out["checks"][check]["value"] > 0


def test_short_window_is_not_correct():
    """A window that runs out of generated batches before ``--seconds``
    is reported, not passed off as a rate over a shorter window."""
    out = R.run(["--workload", CELL[1], "--seed", CELL[3],
                 "--seconds", "100000"], require_tpu=False, overrides=SMALL)
    assert not out["correct"]
    assert out["checks"]["window_short"]["value"] == 1
    assert out["counters"]["window_exhausted"]
