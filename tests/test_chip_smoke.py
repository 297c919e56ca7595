"""``chip_smoke.py`` off the chip: its contract without a TPU, its phase
accounting, and its phases at small sizes against their oracles.

The smoke's phases take their sizes as arguments, so the state-root
phase (device-resident state vs the hashlib SSZ fold kept in the script)
and the fork-choice / pack phase (jit vs numpy vs host engines, the jit
engines forced on) run here on the CPU at a few thousand validators.
"""

import json

import numpy as np
import pytest

import chip_smoke as CS

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_no_tpu_exits_nonzero_without_result(monkeypatch, capsys):
    # main() pins the fast path to 0 in the environment; monkeypatch
    # restores the caller's value afterwards.
    monkeypatch.setenv("LIGHTHOUSE_TPU_HOST_FASTPATH_MAX", "4")
    assert CS.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


def test_compile_clock_counts_nested_spans_once():
    clock = CS.CompileClock()
    t0 = CS.time.monotonic() - 10.0
    event = "/jax/core/compile/backend_compile_duration"
    clock._spans["compile_s"] += [(t0 + 1, t0 + 4), (t0 + 2, t0 + 3),
                                  (t0 + 6, t0 + 7)]
    clock.listen("/jax/some/other_event", 5.0)
    got = clock.since(t0)
    assert got["compile_s"] == pytest.approx(4.0, abs=1e-3)
    assert got["trace_s"] == 0.0
    # A span that began before the phase counts only from its start.
    assert clock.since(t0 + 2)["compile_s"] == pytest.approx(3.0, abs=1e-3)
    clock.listen(event, 0.5)
    assert clock.since(t0)["compile_s"] == pytest.approx(4.5, abs=1e-2)


def test_phase_line_records_failure(capsys):
    clock = CS.CompileClock()
    with pytest.raises(AssertionError):
        with CS.Phase("demo", clock, CPU) as p:
            p.out["n"] = 3
            CS.check(False, "mismatch")
    row = json.loads(capsys.readouterr().out.strip())
    assert row["phase"] == "demo" and row["device"] == CPU
    assert row["ok"] is False and "mismatch" in row["error"]
    assert row["n"] == 3 and {"wall_s", "trace_s", "lower_s",
                              "compile_s"} <= set(row)


def test_state_root_phase_small(capsys):
    """2^10 MAINNET validators, device-resident, cold + 2 warm roots —
    every root equal to the script's hashlib fold."""
    with CS.Phase("state_root", CS.CompileClock(), CPU) as p:
        CS.phase_state_root(p, seed=3, log2_n=10, rounds=2)
    assert p.out["ok"] and p.out["validators"] == 1024
    assert len(p.out["warm_root_s"]) == 2 and p.out["state_dispatches"] > 0
    assert json.loads(capsys.readouterr().out)["ok"] is True


def test_hashlib_merkle_root_matches_package():
    from lighthouse_tpu.ops.merkle import merkleize_host

    rng = np.random.default_rng(5)
    for k, depth in ((1, 0), (1, 3), (3, 2), (5, 4), (8, 3), (0, 2)):
        chunks = rng.integers(0, 256, (k, 32), dtype=np.uint8)
        want = merkleize_host([c.tobytes() for c in chunks], 1 << depth)
        assert CS.merkle_root(chunks, depth) == want


def test_fork_choice_and_pack_rounds_on_jit_engines(monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_FORKCHOICE_JIT", "1")
    monkeypatch.setenv("LIGHTHOUSE_TPU_PACK_JIT", "1")
    with CS.Phase("fork_choice_pack", CS.CompileClock(), CPU) as p:
        CS.phase_fork_choice_pack(p, seed=1, n_nodes=24,
                                  n_validators=1 << 9, n_cands=128,
                                  limit=16)
    assert p.out["fork_choice"]["engine"] == "jit"
    assert p.out["pack"]["selected"] == 16
