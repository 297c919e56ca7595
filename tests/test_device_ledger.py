"""Device ledger (ISSUE 15): per-subsystem attribution, concurrent
accounting, snapshot/delta consistency, watermark monotonicity, the
legacy RESIDENCY_STATS view, the warm-slot zero-pull invariant on a
materialized state, and the /lighthouse/device HTTP scoreboard.

Everything quick-tier: merkle-scale jitted programs only (seconds on
CPU), fake BLS backend, no pairing-scale compiles.
"""

import gc
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from lighthouse_tpu.common.device_ledger import (LEDGER, MiB,
                                                 SUBSYSTEMS,
                                                 WARM_SLOT_BUDGET,
                                                 evaluate_budget)
from lighthouse_tpu.common import tracing


# ---------------------------------------------------------------------------
# Core accounting
# ---------------------------------------------------------------------------


def test_subsystem_attribution_isolation():
    base = LEDGER.snapshot()["subsystems"]
    LEDGER.note_transfer("h2d", 100, subsystem="bls")
    LEDGER.note_transfer("d2h", 50, subsystem="slasher")
    with LEDGER.attribute("packed_cache"):
        LEDGER.note_transfer("h2d", 7)          # ambient wins
        with LEDGER.attribute("registry_mirror"):
            LEDGER.note_transfer("h2d", 3)      # innermost wins
        LEDGER.note_transfer("h2d", 2)
    LEDGER.note_transfer("h2d", 11)             # no context → device_tree
    snap = LEDGER.snapshot()["subsystems"]

    def d(sub, key):
        return snap[sub][key] - base[sub][key]

    assert d("bls", "h2d_bytes") == 100
    assert d("slasher", "d2h_bytes") == 50
    assert d("packed_cache", "h2d_bytes") == 9
    assert d("registry_mirror", "h2d_bytes") == 3
    assert d("device_tree", "h2d_bytes") == 11
    assert d("packed_cache", "h2d_ops") == 2
    # explicit beats ambient
    with LEDGER.attribute("packed_cache"):
        LEDGER.note_transfer("h2d", 5, subsystem="kzg")
    snap = LEDGER.snapshot()["subsystems"]
    assert snap["kzg"]["h2d_bytes"] - base["kzg"]["h2d_bytes"] == 5


def test_unknown_subsystem_rejected():
    with pytest.raises(AssertionError):
        LEDGER.note_transfer("h2d", 1, subsystem="warp_drive")
    with pytest.raises(AssertionError):
        with LEDGER.attribute("warp_drive"):
            pass


def test_concurrent_thread_accounting_exact():
    base = LEDGER.snapshot()["subsystems"]["bls"]
    n_threads, per = 8, 500

    def worker():
        for _ in range(per):
            LEDGER.note_transfer("h2d", 3, subsystem="bls")
            LEDGER.note_dispatch("bls", 0.5)

    ts = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = LEDGER.snapshot()["subsystems"]["bls"]
    assert snap["h2d_bytes"] - base["h2d_bytes"] == 3 * n_threads * per
    assert snap["h2d_ops"] - base["h2d_ops"] == n_threads * per
    assert snap["dispatches"] - base["dispatches"] == n_threads * per
    assert snap["dispatch_wall_ms"] - base["dispatch_wall_ms"] == \
        pytest.approx(0.5 * n_threads * per)


def test_ambient_context_is_thread_local():
    seen = {}

    def worker():
        seen["other"] = LEDGER.ambient()

    with LEDGER.attribute("kzg"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert LEDGER.ambient() == "kzg"
    assert seen["other"] is None


# ---------------------------------------------------------------------------
# Slot-delta ring
# ---------------------------------------------------------------------------


def test_slot_delta_consistency():
    LEDGER.mark_slot(9001)
    LEDGER.note_transfer("h2d", 111, subsystem="bls")
    LEDGER.note_transfer("d2h", 22, subsystem="fork_choice")
    LEDGER.mark_slot(9002)          # closes 9001
    LEDGER.mark_slot(9002)          # idempotent per slot value
    LEDGER.note_transfer("h2d", 5, subsystem="bls")
    deltas = {d["slot"]: d["subsystems"] for d in LEDGER.slot_deltas()}
    assert deltas[9001]["bls"]["h2d_bytes"] == 111
    assert deltas[9001]["bls"]["h2d_ops"] == 1
    assert deltas[9001]["fork_choice"]["d2h_bytes"] == 22
    # the open slot's delta is visible separately
    cur = LEDGER.current_slot_delta()
    assert cur["bls"]["h2d_bytes"] == 5
    LEDGER.mark_slot(9003)
    deltas = {d["slot"]: d["subsystems"] for d in LEDGER.slot_deltas()}
    assert deltas[9002]["bls"]["h2d_bytes"] == 5
    # quiet interval records nothing
    LEDGER.mark_slot(9004)
    assert 9003 not in {d["slot"] for d in LEDGER.slot_deltas()}


def test_slot_ring_bounded():
    for s in range(20000, 20000 + LEDGER.max_slots + 10):
        LEDGER.note_transfer("h2d", 1, subsystem="bls")
        LEDGER.mark_slot(s)
    assert len(LEDGER.slot_deltas()) <= LEDGER.max_slots


# ---------------------------------------------------------------------------
# Residency watermarks
# ---------------------------------------------------------------------------


def test_watermark_monotonic_and_release():
    before = LEDGER.snapshot()["subsystems"]["slasher"]
    tok = LEDGER.residency("slasher")
    tok.set(1000)
    tok.set(400)            # shrink: resident follows, high-water holds
    snap = LEDGER.snapshot()["subsystems"]["slasher"]
    assert snap["resident_bytes"] - before["resident_bytes"] == 400
    assert snap["hbm_high_water_bytes"] >= \
        before["resident_bytes"] + 1000
    tok.set(600)
    tok.release()
    tok.release()           # idempotent
    snap2 = LEDGER.snapshot()["subsystems"]["slasher"]
    assert snap2["resident_bytes"] == before["resident_bytes"]
    assert snap2["hbm_high_water_bytes"] == snap["hbm_high_water_bytes"]


def test_track_releases_on_gc():
    class Owner:
        pass

    before = LEDGER.snapshot()["subsystems"]["kzg"]["resident_bytes"]
    o = Owner()
    LEDGER.track(o, "kzg", 12345)
    assert LEDGER.snapshot()["subsystems"]["kzg"]["resident_bytes"] \
        == before + 12345
    del o
    gc.collect()
    assert LEDGER.snapshot()["subsystems"]["kzg"]["resident_bytes"] \
        == before


def test_gc_finalizer_release_reentrant_under_ledger_lock():
    """A tracked owner can be collected while THIS thread already holds
    the ledger lock (any allocation inside a locked section may trigger
    GC, and weakref.finalize then runs release -> _adjust_resident on
    the same thread).  The lock must be reentrant or the process
    self-deadlocks — observed wedging tier-1 inside mark_slot's
    slot-base rebuild.  Run the reentrant release on a worker thread so
    a regression fails the test instead of hanging the suite."""
    tok = LEDGER.residency("replay")
    tok.set(4096)

    def reenter():
        with LEDGER._lock:          # the locked section in progress
            tok.release()           # the GC finalizer's call shape

    t = threading.Thread(target=reenter, daemon=True)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), \
        "ResidencyToken.release deadlocked against the held ledger lock"
    assert LEDGER.snapshot()["subsystems"]["replay"]["resident_bytes"] == 0


def test_reset_reseeds_live_tokens():
    """reset() zeroes history but re-seeds residency from live tokens —
    a device object created before the reset must not under-report
    afterwards (its later set() deltas land on the re-seeded base)."""
    tok = LEDGER.residency("registry_mirror")
    tok.set(1000)
    LEDGER.reset()
    row = LEDGER.snapshot()["subsystems"]["registry_mirror"]
    assert row["resident_bytes"] == 1000
    assert row["hbm_high_water_bytes"] == 1000
    tok.set(1050)   # delta applies on the re-seeded base
    row = LEDGER.snapshot()["subsystems"]["registry_mirror"]
    assert row["resident_bytes"] == 1050
    tok.release()
    assert LEDGER.snapshot()["subsystems"]["registry_mirror"][
        "resident_bytes"] == 0


def test_envelope_owns_dispatch_accounting():
    """A device path that self-accounts (kzg pairing / direct XLA
    verify) must count ONCE when called through the resilience
    envelope — the envelope suppresses the inner seam and records the
    dispatch itself, including across the watchdog's worker thread."""
    from lighthouse_tpu.beacon_chain.verification_service import (
        ResilienceEnvelope)

    def device_fn():
        LEDGER.note_dispatch("kzg", 5.0)   # the inner self-account
        return True

    for deadline in (None, 2.0):           # inline AND watchdog thread
        base = LEDGER.snapshot()["subsystems"]
        env = ResilienceEnvelope("ledger_sup_kzg", retries=0,
                                 deadline_s=deadline)
        out, path = env.call(device_fn, None)
        assert out is True and path == "device"
        snap = LEDGER.snapshot()["subsystems"]
        total = sum(snap[s]["dispatches"] - base[s]["dispatches"]
                    for s in SUBSYSTEMS)
        assert total == 1, (deadline, total)
        # and it's the envelope's (kzg family), not the inner 5 ms
        assert snap["kzg"]["dispatches"] - base["kzg"]["dispatches"] == 1


# ---------------------------------------------------------------------------
# Legacy RESIDENCY_STATS view
# ---------------------------------------------------------------------------


def test_legacy_view_is_ledger_backed_and_rebases():
    from lighthouse_tpu.ops.device_tree import (RESIDENCY_STATS,
                                                reset_residency_stats,
                                                note_push, note_pull,
                                                residency_snapshot)
    reset_residency_stats()
    assert residency_snapshot() == {
        "bytes_pushed": 0, "bytes_pulled": 0,
        "scatters": 0, "rebuilds": 0, "materializes": 0}
    note_push(64)                   # no context → device_tree
    with LEDGER.attribute("packed_cache"):
        note_pull(32)
    LEDGER.note_event("scatters", subsystem="registry_mirror")
    snap = residency_snapshot()
    assert snap["bytes_pushed"] == 64
    assert snap["bytes_pulled"] == 32
    assert snap["scatters"] == 1
    # BLS/KZG/slasher/staging traffic is ledger-only — the legacy view
    # keeps its pre-ledger meaning (tree/registry/packed/fork-choice).
    LEDGER.note_transfer("h2d", 10 ** 6, subsystem="bls")
    LEDGER.note_transfer("h2d", 10 ** 6, subsystem="staging")
    assert residency_snapshot()["bytes_pushed"] == 64
    assert RESIDENCY_STATS["bytes_pushed"] == 64
    reset_residency_stats()
    assert residency_snapshot()["bytes_pushed"] == 0


# ---------------------------------------------------------------------------
# Warm-slot budget
# ---------------------------------------------------------------------------


def test_budget_evaluation_flags_violation():
    deltas = [
        {"slot": 5, "subsystems": {
            "packed_cache": {"h2d_bytes": 100, "h2d_ops": 1,
                             "d2h_bytes": 0, "d2h_ops": 0}}},
        {"slot": 6, "subsystems": {
            "staging": {"h2d_bytes": 1, "h2d_ops": 1,
                        "d2h_bytes": 0, "d2h_ops": 0}}},
    ]
    out = evaluate_budget(deltas)
    assert not out["ok"]
    bad = [r for r in out["rows"] if not r["ok"]]
    assert [(r["subsystem"], r["direction"]) for r in bad] == \
        [("staging", "h2d")]
    assert bad[0]["violations"] == [6]
    assert bad[0]["worst_slot"] == 6
    assert 0 < out["attainment"] < 1


def test_budget_vacuous_on_empty_window():
    out = evaluate_budget([])
    assert out["ok"] and out["attainment"] == 1.0


def test_budget_covers_every_subsystem():
    assert set(WARM_SLOT_BUDGET) == set(SUBSYSTEMS)


def test_sustained_scoreboard_exports_budget_row():
    from lighthouse_tpu.testing.sustained_load import run_sustained
    board = run_sustained(slots=4, slot_s=0.15, n_validators=16, seed=1)
    db = board["device_budget"]
    assert db["ok"] is True and db["violations"] == []
    assert db["attainment"] == 1.0
    assert board["attainment"]["device_transfer_budget"] == 1.0
    assert board["loss"]["zero_loss"]


# ---------------------------------------------------------------------------
# Stage source + tracing attribution
# ---------------------------------------------------------------------------


def test_device_ledger_stage_source_registered():
    LEDGER.note_transfer("h2d", 77, subsystem="kzg")
    snap = tracing.stage_split("device_ledger")
    assert snap.get("kzg_h2d_bytes", 0) >= 77
    # counters, not phase decompositions: no bare *_ms keys that the
    # record_stages layout would misread as sequential spans
    assert not any(k.endswith("_ms") for k in snap)


# ---------------------------------------------------------------------------
# The six device subsystems attribute where they run (CPU/fake backend)
# ---------------------------------------------------------------------------


def _mk_state(n: int):
    from lighthouse_tpu.types.chain_spec import ForkName
    from lighthouse_tpu.types.factory import spec_types
    from lighthouse_tpu.types.presets import MAINNET
    from lighthouse_tpu.types.validators import ValidatorRegistry

    rng = np.random.default_rng(7)
    T = spec_types(MAINNET)
    state = T.state_cls(ForkName.CAPELLA)()
    reg = ValidatorRegistry(n)
    reg._n = n
    reg.init_columns(
        pubkey=rng.integers(0, 256, (n, 48), dtype=np.uint8),
        withdrawal_credentials=rng.integers(0, 256, (n, 32),
                                            dtype=np.uint8),
        effective_balance=np.full(n, 32 * 10 ** 9, dtype=np.uint64))
    state.validators = reg
    state.balances = np.full(n, 32 * 10 ** 9, dtype=np.uint64)
    state.previous_epoch_participation = np.zeros(n, dtype=np.uint8)
    state.current_epoch_participation = np.zeros(n, dtype=np.uint8)
    state.inactivity_scores = np.zeros(n, dtype=np.uint64)
    return state


def test_warm_slot_zero_pull_invariant():
    """A materialized state's WARM root pulls nothing and pushes only
    the dirty rows — the invariant the warm-slot budget encodes."""
    from lighthouse_tpu.types.device_state import materialize_state

    state = _mk_state(64)
    assert materialize_state(state)
    state.tree_hash_root()
    base = {s: dict(r) for s, r
            in LEDGER.snapshot()["subsystems"].items()}
    idx = np.arange(4)
    state.balances[idx] = np.uint64(1)
    state.validators.wcol("effective_balance")[idx] = np.uint64(2)
    state.tree_hash_root()
    snap = LEDGER.snapshot()["subsystems"]
    for sub in ("device_tree", "registry_mirror", "packed_cache",
                "staging"):
        assert snap[sub]["d2h_bytes"] == base[sub]["d2h_bytes"], sub
    pushed = sum(snap[s]["h2d_bytes"] - base[s]["h2d_bytes"]
                 for s in ("device_tree", "registry_mirror",
                           "packed_cache"))
    assert 0 < pushed < 64 * 1024  # dirty rows, not a re-stage
    assert snap["staging"]["h2d_bytes"] == base["staging"]["h2d_bytes"]


def test_all_device_subsystems_attribute():
    """Each of the six device subsystems reports nonzero attribution
    from its own driver (CPU backend: merkle-scale compiles only)."""
    from lighthouse_tpu.fork_choice import (DeviceProtoArrayForkChoice,
                                            EXEC_OPTIMISTIC)
    from lighthouse_tpu.fork_choice.proto_array import ZERO_ROOT
    from lighthouse_tpu.ops.device_tree import DeviceTree
    from lighthouse_tpu.slasher.device_spans import DeviceSpanPlane
    from lighthouse_tpu.beacon_chain.verification_service import (
        ResilienceEnvelope)
    from lighthouse_tpu.types.device_state import materialize_state

    base = {s: dict(r) for s, r
            in LEDGER.snapshot()["subsystems"].items()}

    # device_tree
    DeviceTree.from_host_leaves(np.zeros((8, 8), np.uint32))
    # registry_mirror + packed_cache
    state = _mk_state(32)
    assert materialize_state(state)
    state.tree_hash_root()
    # slasher
    plane = DeviceSpanPlane(64, history=64)
    plane.ingest(plane.group([(1, 2, np.array([3, 5]))]))
    # fork_choice (jit engine — the device mirror pushes/pulls)
    def root(i):
        return bytes([i]) + b"\x00" * 31
    pa = DeviceProtoArrayForkChoice(engine="jit")
    pa.on_block(slot=0, root=root(0), parent_root=ZERO_ROOT,
                state_root=root(0), justified_epoch=1,
                justified_root=root(0), finalized_epoch=1,
                finalized_root=root(0),
                execution_status=EXEC_OPTIMISTIC)
    pa.on_block(slot=1, root=root(1), parent_root=root(0),
                state_root=root(1), justified_epoch=1,
                justified_root=root(0), finalized_epoch=1,
                finalized_root=root(0),
                execution_status=EXEC_OPTIMISTIC)
    deltas = pa.compute_deltas(np.full(4, 32 * 10 ** 9, np.uint64))
    pa.apply_score_changes(deltas, (1, root(0)), (1, root(0)),
                           ZERO_ROOT, 0, 10)
    # bls (the envelope dispatch seam — fake "device" fn)
    env = ResilienceEnvelope("ledger_test_bls", retries=0)
    env.call(lambda: True, None)

    snap = LEDGER.snapshot()["subsystems"]

    def moved(sub):
        r, b = snap[sub], base[sub]
        return (r["h2d_bytes"] - b["h2d_bytes"]
                + r["d2h_bytes"] - b["d2h_bytes"]
                + r["dispatches"] - b["dispatches"])

    for sub in ("bls", "device_tree", "registry_mirror", "packed_cache",
                "fork_choice", "slasher"):
        assert moved(sub) > 0, sub
    # watermarks: every resident subsystem left a high-water mark
    for sub in ("device_tree", "registry_mirror", "packed_cache",
                "fork_choice", "slasher"):
        assert snap[sub]["hbm_high_water_bytes"] > 0, sub


# ---------------------------------------------------------------------------
# /lighthouse/device HTTP route
# ---------------------------------------------------------------------------


@pytest.fixture
def api_server():
    from lighthouse_tpu.api import HttpApiServer
    from lighthouse_tpu.beacon_chain import BeaconChain
    from lighthouse_tpu.crypto import bls as B
    from lighthouse_tpu.store import HotColdDB
    from lighthouse_tpu.testing.harness import StateHarness
    from lighthouse_tpu.types.presets import MINIMAL

    B.set_backend("fake")
    h = StateHarness(n_validators=16, preset=MINIMAL)
    hdr = h.state.latest_block_header.copy()
    hdr.state_root = h.state.tree_hash_root()
    chain = BeaconChain(store=HotColdDB.memory(h.preset, h.spec, h.T),
                        genesis_state=h.state.copy(),
                        genesis_block_root=hdr.tree_hash_root(),
                        preset=h.preset, spec=h.spec, T=h.T)
    srv = HttpApiServer(chain)
    srv.start()
    yield h, chain, srv
    srv.stop()
    B.set_backend("python")


def _get(srv, path):
    req = urllib.request.Request(f"http://127.0.0.1:{srv.port}{path}")
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_device_route_empty_ledger(api_server):
    """A fresh node answers with an all-zero scoreboard (attainment
    vacuously 1.0) — the route never 500s on an empty ledger."""
    _h, _chain, srv = api_server
    LEDGER.reset()
    code, body = _get(srv, "/lighthouse/device")
    assert code == 200
    data = body["data"]
    assert data["enabled"] is True
    assert set(data["subsystems"]) == set(SUBSYSTEMS)
    for row in data["subsystems"].values():
        assert row["h2d_bytes"] == 0 and row["resident_bytes"] == 0
    assert data["slots"] == []
    assert data["budget"]["evaluation"]["ok"] is True
    assert data["budget"]["evaluation"]["attainment"] == 1.0


def test_http_device_route_after_slot(api_server):
    """After a processed slot the scoreboard carries per-subsystem
    attribution and the per-slot delta ring keyed like the trace ring."""
    h, chain, srv = api_server
    LEDGER.reset()
    chain.per_slot_task(1)
    signed = h.build_block(slot=1)
    h.apply_block(signed)
    chain.process_block(signed, is_timely=True)
    LEDGER.note_transfer("h2d", 4096, subsystem="bls")  # in-slot traffic
    chain.per_slot_task(2)  # closes slot 1's delta

    code, body = _get(srv, "/lighthouse/device")
    assert code == 200
    data = body["data"]
    # host-backend verifies are NOT device dispatches by design — the
    # in-slot traffic shows in the transfer axis instead
    assert data["subsystems"]["bls"]["h2d_bytes"] >= 4096
    slots = {d["slot"]: d["subsystems"] for d in data["slots"]}
    assert 1 in slots and slots[1]["bls"]["h2d_bytes"] >= 4096
    assert "bytes_per_slot" in data["budget"]
    assert data["budget"]["evaluation"]["slots_checked"] >= 1


def test_http_device_route_skips_cold_slots(api_server):
    """A materialize inside a slot marks it cold: the HTTP budget view
    skips it (listed, not silent) instead of reporting a fresh node's
    staging as a warm-path violation; the raw delta row still carries
    the bytes."""
    _h, chain, srv = api_server
    LEDGER.reset()
    chain.per_slot_task(11)
    LEDGER.note_transfer("h2d", 10 * MiB, subsystem="staging")
    LEDGER.note_event("materializes", subsystem="packed_cache")
    chain.per_slot_task(12)

    code, body = _get(srv, "/lighthouse/device")
    assert code == 200
    ev = body["data"]["budget"]["evaluation"]
    assert ev["ok"] is True
    assert ev["cold_slots_skipped"] == [11]
    slots = {d["slot"]: d for d in body["data"]["slots"]}
    assert slots[11]["cold"] is True
    assert slots[11]["subsystems"]["staging"]["h2d_bytes"] == 10 * MiB
    # the drill's default evaluation (include_cold=True) DOES flag it
    from lighthouse_tpu.common.device_ledger import evaluate_budget
    strict = evaluate_budget(body["data"]["slots"])
    assert strict["ok"] is False
