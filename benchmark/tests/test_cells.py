"""Whole runs of both cells on the CPU at small sizes, with the chip
check skipped: a sound run comes out correct, and the control and each
fault that a cell can have, planted under the timed path, come out not
correct.

The state cell's faults: a root call that returns the state's previous
root (state unchanged), half of the slot's participation writes left out,
and a root altered where it is produced; its control lands each slot's
participation writes after the slot's root.  The gossip cell's faults: a
verdict flipped where it is produced, and half of each batch left out;
its control sets every random-linear-combination scalar to 1.  Neither
cell runs on more than one chip, so there is no exchange to leave out.
"""

import secrets

import pytest

import run as R

STATE = ["--workload", "mainnet-1m-default.slot_roots",
         "--seed", "3000000017", "--seconds", "2"]
STATE_SMALL = {"config": {"deployment": {"validators": 4096}},
               "mix": {"warmup_slots": 2, "reference_sample": 3}}

GOSSIP = ["--workload", "mainnet-1m-allsubnets.subnet_attestations",
          "--seed", "3000000021", "--seconds", "3"]
# 4 committees of 8 on 2 s slots, batches of 8 that dispatch when full,
# each slot's swapped pair in the second half of its first batch, the host
# BLS backend in the device's place.
GOSSIP_SMALL = {
    "config": {"deployment": {"validators": 16384, "subnets_subscribed": 4,
                              "seconds_per_slot": 2},
               "service": {"backend": "python", "max_batch": 8,
                           "slo_ms": 60000}},
    "mix": {"arrivals": {"offset_s": 0.2, "spread_s": 0.3},
            "invalid_pair_ranks": [4, 7], "reference_sample": 2}}


def _run(argv, small, *extra):
    randbits = secrets.randbits
    try:
        return R.run(argv + list(extra), require_tpu=False, overrides=small)
    finally:
        secrets.randbits = randbits


def test_state_cell_sound_run_is_correct():
    out = _run(STATE, STATE_SMALL)
    assert out["correct"], out["checks"]
    assert out["metrics"]["slot_root_p95_ms"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("broken", [
    ("--control", "lagged_writes"),
    ("--fault", "stale_root"),
    ("--fault", "half_writes"),
    ("--fault", "altered_answer"),
], ids=lambda b: b[1])
def test_state_cell_broken_runs_are_not_correct(broken):
    out = _run(STATE, STATE_SMALL, *broken)
    assert not out["correct"]
    assert out["checks"]["root_mismatches"]["value"] > 0


def test_gossip_cell_sound_run_is_correct():
    out = _run(GOSSIP, GOSSIP_SMALL)
    assert out["correct"], out["checks"]
    c = out["counters"]
    assert c["splits"] >= 1 and c["sets_in_window"] > 0
    assert out["metrics"]["sets_per_s"]["value"] > 0
    # Load the service did not reach in the window is not a failure.
    assert out["failed"] == 0 and out["attempted"] == c["due"] > 0
    share = R.load_reader("answered_in_window_pct.subnets")(out)
    assert 0 < share <= 100


@pytest.mark.parametrize("broken", [
    ("--control", "rlc_off"),
    ("--fault", "altered_answer"),
    ("--fault", "half_batch"),
], ids=lambda b: b[1])
def test_gossip_cell_broken_runs_are_not_correct(broken):
    out = _run(GOSSIP, GOSSIP_SMALL, *broken)
    assert not out["correct"]
    assert out["checks"]["wrong_verdicts"]["value"] > 0
    assert out["failed"] >= out["checks"]["wrong_verdicts"]["value"]


def test_trace_run_reports_per_layer_metrics_only():
    out = _run(STATE, STATE_SMALL, "--trace", "1")
    assert out["correct"]
    assert "slot_root_p95_ms" not in out["metrics"]
    assert out["metrics"]["h2d_kb_per_root.slot_roots"]["value"] > 0
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert "breakdown" in out
