"""Headline benchmark: batched BLS aggregate-verify + registry Merkleization
on TPU — the north-star metrics (`BASELINE.md` Target table).

Primary metric: ``verify_signature_sets`` throughput through the fused
device pipeline (pubkey-table gather → hash-to-curve kernel → prepare →
Miller → product fold → on-device final exponentiation; ONE host sync per
call), on **1024 aggregate signature sets** (BASELINE row 1's workload):
64 distinct messages, 2^14 distinct pubkeys (16 signers per set) — nothing
about the crypto is memoised away (VERDICT r3 weak #8): message
hash-to-curve runs on-device every call; the device pubkey table is the
``validator_pubkey_cache.rs`` role and is reported warm AND cold.

Also measured (BASELINE rows 2-5 + latency tier):

- ``single_set_verify_ms`` — one proposer-signature set (the gossip-block
  check, `block_verification.py`), routed through the native C++ host
  pairing for tiny batches (``tpu_backend._host_fastpath_max``, n≤4 sets
  by default); LIGHTHOUSE_TPU_HOST_FASTPATH_MAX=0 keeps the device path.
- ``fast_aggregate_verify_512x256_ms`` — 256 sets × 512 shared pubkeys
  (sync-committee shape, BASELINE row 4).
- ``registry_htr_ms`` — fused-Pallas `hash_tree_root` of a 2^21-validator
  registry vs a 40 ns/hash single-SHA-NI-core estimate.
- ``state_root_cold_ms`` / ``state_root_incremental_ms`` — full
  `BeaconState` root at 2^20 validators, cold and after 100-validator
  mutations (reference: `tree_hash_cache.rs`).  The cold build streams
  its columns through the chunked push pipeline; ``push_overlap_ms`` is
  the transfer time the overlap hid behind on-device reduction (and
  ``state_root_cold_push_ms`` is only what remained on the critical
  path); ``leaf_push_wait_ms``/``leaf_push_overlap_ms`` are the same
  split for the non-registry big-field leaf pushes
  (``merkle_levels_device``).
- ``state_root_device_resident`` — the device-resident counterpart: one
  ``materialize_state`` push makes HBM the source of truth, then warm
  roots are timed clean and at 0.1% / 1% / 10% dirty fractions with
  bytes-pushed-per-root (≈ 0 clean; ∝ dirty rows otherwise — the cold
  row's 5+ s re-stage is eliminated from the warm path, not overlapped).
- ``block_transition_ms`` / ``block_transition_atts_per_s`` — Capella
  block with 128 attestations applied to a 2^14-validator mainnet state,
  per-phase (BASELINE row 3; `lcli/src/transition_blocks.rs:229`),
  through the batched attestation path.
- ``epoch_transition_ms`` — single-pass epoch processing at 2^20
  validators with per-stage timings (context / justification /
  inactivity / rewards / registry / slashings / effective-balance) plus
  ``epoch_transition_stepwise_ms`` (the oracle path) and
  ``epoch_shuffle_ms`` (whole-epoch committee shuffle).
- ``op_pool_pack_100k_ms`` — max-cover packing over 100k pooled
  attestations (BASELINE row 5).
- ``trace_overhead`` — the block row with slot-scope tracing off vs on
  (ISSUE 9 acceptance: an enabled tracer costs <1% on the block
  transition; min-of-several interleaved, re-measured once on a miss,
  reported as a boolean — rc stays 0 either way).
- ``slasher_update_1m_ms`` — slasher min/max span-plane ingest for a
  batch of attestations over a 2^20-validator registry (VERDICT r4 #9).
- ``kzg_batch_verify_ms`` — Deneb blob-sidecar batch verification
  (6 mainnet-width blobs): device barycentric evaluation + 2 Miller
  lanes per blob + one shared final exponentiation, with per-stage
  timings (``kzg_eval_ms`` / ``kzg_pairing_ms`` / ...).
- ``pipeline_host_prep_ms`` / ``pipeline_dispatches`` — host
  marshalling time and staged sub-batch dispatches per headline BLS
  batch, read from the cumulative ``bls_pipeline_host_prep_seconds``
  histogram across the timing loop.

The run needs the chip: when JAX finds no TPU it exits 1 before any row
runs — no row is ever re-run on the CPU under a device metric name.

CLI: ``--list`` prints the row names; ``--only ROW[,ROW…]`` runs a
subset (the per-row incremental emission is unchanged, but
``BENCH_LATEST.json`` is left untouched so a subset run never guts the
regression baseline).  A full run writes per-row snapshots to
``BENCH_LATEST.json.tmp`` and renames over ``BENCH_LATEST.json`` once
at end of run — a killed run cannot leave a truncated artifact.

``vs_baseline`` compares against a **native single-core blst estimate** of
0.7 ms/set for ``verify_multiple_aggregate_signatures`` (1 Miller loop +
G2 RLC scalar-mul + share of final exp per set; supranational's published
figures put a full 2-pairing verify at ~1.2 ms/core).  The reference
parallelises with rayon, so divide by core count for multi-core.

Output protocol (VERDICT r4 weak #2 — resilient to its own compile
costs): every sub-benchmark prints its own JSON line **as it completes**
and flushes, so a driver timeout costs only the rows that never ran.  On
success the LAST line printed is the combined headline row
``{"metric": "bls_batch_verify_1024_sets", "value": N, "unit": "sets/s",
"vs_baseline": N, ...}`` carrying every sub-row — a driver that keeps
only the final line still gets everything.  A wall-clock budget
(``BENCH_BUDGET_S``, default 3600 s) is checked between rows; when
exceeded, remaining rows are skipped (recorded in ``skipped``) and the
combined line prints immediately.  Each row is independently
exception-guarded: a failing row records an ``error`` field, the
remaining rows still run, and the process exits 1.
"""

from __future__ import annotations

import faulthandler
import json
import os
import signal
import sys
import time
import traceback

faulthandler.register(signal.SIGUSR1, file=sys.stderr)

import numpy as np

BLST_EST_MS_PER_SET = 0.7      # single-core native estimate (see docstring)
BLOCK_SIGS_MODELED_RATE = 1964.9  # measured flagship sets/s (BENCH r5) —
#   the single-chip modeled-device rate of the block_with_sigs row
DEVICE_ROOT_MODELED_MS = 15.48  # measured device-resident incremental
#   state root (BENCH r5 state_root_incremental_ms) — the per-slot
#   device program the serial replay oracle pays and the batched
#   window collapses to ONE boundary launch.
BLOCK_SIGS_MESH_RATE = 9900.0  # projected 8-chip mesh-sharded sets/s
#   (dryrun_multichip stage model, BENCH r5) — the sharded path the
#   block batch actually dispatches through on a pod
NATIVE_NS_PER_HASH = 40.0      # single SHA-NI core, 64-byte message
N_SETS = 1024                  # BASELINE row 1: 1024 attestation sets
KEYS_PER_SET = 16              # → 2^14 distinct pubkeys
N_MSGS = 64                    # distinct messages (≥ one per committee)
REG_LOG2 = 21                  # registry Merkle scale
STATE_LOG2 = 20                # incremental state-root scale
RUNS = 3

BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "3600"))
_T_START = time.monotonic()


def _emit(row: dict) -> None:
    print(json.dumps(row), flush=True)


def _bls_bench() -> dict:
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.common.metrics import REGISTRY
    from lighthouse_tpu.crypto import tpu_backend as TB  # noqa (registers)
    from lighthouse_tpu.crypto.fields import R

    tpu = bls._BACKENDS["tpu"]

    breaker_mark = _breaker_attribution("bls")
    t_setup = time.perf_counter()
    sk_ints = [0x10000 + 7 * i for i in range(N_SETS * KEYS_PER_SET)]
    sks = [bls.SecretKey(v) for v in sk_ints]
    pks = [k.public_key() for k in sks]
    msgs = [b"att-data-%03d" % i for i in range(N_MSGS)]
    sets = []
    for i in range(N_SETS):
        keys = pks[i * KEYS_PER_SET:(i + 1) * KEYS_PER_SET]
        vals = sk_ints[i * KEYS_PER_SET:(i + 1) * KEYS_PER_SET]
        m = msgs[i % N_MSGS]
        # Aggregate-of-16 signature == signature under the summed secret.
        agg = bls.SecretKey(sum(vals) % R).sign(m)
        sets.append(bls.SignatureSet(agg, list(keys), m))
    setup_s = time.perf_counter() - t_setup

    # Correctness gates (also warms kernels + uploads the pubkey table).
    t0 = time.perf_counter()
    if not tpu.verify_signature_sets(sets):
        raise RuntimeError("valid batch rejected")
    cold_ms = (time.perf_counter() - t0) * 1e3
    bad = list(sets)
    bad[17] = bls.SignatureSet(sets[17].signature, sets[18].signing_keys,
                               sets[17].message)
    if tpu.verify_signature_sets(bad):
        raise RuntimeError("tampered batch accepted")

    # The staged executor's cumulative host-prep histogram, diffed
    # across the timing loop: marshalling per headline batch.
    prep = REGISTRY.histogram("bls_pipeline_host_prep_seconds")
    _b, _c, prep_n0, prep_s0 = prep.snapshot()
    ts = []
    for _ in range(RUNS):
        t0 = time.perf_counter()
        if not tpu.verify_signature_sets(sets):
            raise RuntimeError("valid batch rejected in timing loop")
        ts.append(time.perf_counter() - t0)
    best = min(ts)
    _b, _c, prep_n1, prep_s1 = prep.snapshot()

    # Latency tier: one single-key set (gossip proposer-signature shape).
    single = [bls.SignatureSet(sks[0].sign(msgs[0]), [pks[0]], msgs[0])]
    if not tpu.verify_signature_sets(single):
        raise RuntimeError("single set rejected")
    t0 = time.perf_counter()
    tpu.verify_signature_sets(single)
    single_ms = (time.perf_counter() - t0) * 1e3

    # BASELINE row 4: fast_aggregate_verify, 512 shared pubkeys × 256 msgs.
    # The shared-key collapse (one aggregation + 2 Miller lanes for the
    # whole committee) makes this the CHEAPEST per-set shape; a tampered
    # gate guards the fast path's correctness, and one STAGE_TIMINGS run
    # attributes the total to aggregate-keys / HTC / RLC-fold / Miller+
    # final-exp (the attribution run pays per-stage syncs, so the
    # throughput number comes from the untimed run).
    fam = [b"sync-comm-%03d" % i for i in range(256)]
    fkeys = pks[:512]
    fsum = sum(sk_ints[:512]) % R
    fsets = [bls.SignatureSet(bls.SecretKey(fsum).sign(m), list(fkeys), m)
             for m in fam]
    if not tpu.verify_signature_sets(fsets):
        raise RuntimeError("fast-aggregate batch rejected")
    fbad = list(fsets)
    fbad[3] = bls.SignatureSet(fsets[4].signature, fsets[3].signing_keys,
                               fsets[3].message)
    if tpu.verify_signature_sets(fbad):
        raise RuntimeError("tampered fast-aggregate batch accepted")
    t0 = time.perf_counter()
    tpu.verify_signature_sets(fsets)
    fam_ms = (time.perf_counter() - t0) * 1e3
    TB.STAGE_TIMINGS = True
    try:
        # The attribution branch dispatches DIFFERENT programs than the
        # untimed path (eager sigma folds + a standalone tail jit), so
        # the first pass pays their trace/compile inside the fenced
        # spans — throw it away and record the warm second pass.
        tpu.verify_signature_sets(fsets)
        tpu.verify_signature_sets(fsets)
        from lighthouse_tpu.common import tracing
        fam_stages = tracing.stage_split("fast_agg")
    finally:
        TB.STAGE_TIMINGS = False

    sets_per_s = N_SETS / best
    out = {
        "sets_per_s": round(sets_per_s, 1),
        "ms_per_set": round(best * 1e3 / N_SETS, 3),
        "batch_ms": round(best * 1e3, 1),
        "batch_cold_ms": round(cold_ms, 1),
        "distinct_messages": N_MSGS,
        "distinct_pubkeys": N_SETS * KEYS_PER_SET,
        "single_set_verify_ms": round(single_ms, 2),
        "fast_aggregate_verify_512x256_ms": round(fam_ms, 1),
        "fast_aggregate_ms_per_set": round(fam_ms / 256, 3),
        "fast_aggregate_stage_split": fam_stages,
        "bls_setup_s": round(setup_s, 1),
        **_breaker_attribution("bls", breaker_mark),
    }
    if prep_n1 > prep_n0:
        out.update({
            "pipeline_dispatches": (prep_n1 - prep_n0) / RUNS,
            "pipeline_host_prep_ms":
                round((prep_s1 - prep_s0) * 1e3 / RUNS, 1),
        })
    return out


def _registry_htr_bench() -> dict:
    from lighthouse_tpu.types.validators import ValidatorRegistry

    n = 1 << REG_LOG2
    rng = np.random.default_rng(0)
    reg = ValidatorRegistry(n)
    reg._n = n
    reg.init_columns(
        pubkey=rng.integers(0, 256, (n, 48), dtype=np.uint8),
        withdrawal_credentials=rng.integers(0, 256, (n, 32), dtype=np.uint8),
        effective_balance=rng.integers(0, 2**35, n).astype(np.uint64),
        slashed=np.zeros(n, dtype=bool),
        activation_eligibility_epoch=rng.integers(0, 2**20, n).astype(np.uint64),
        activation_epoch=rng.integers(0, 2**20, n).astype(np.uint64),
        exit_epoch=rng.integers(0, 2**20, n).astype(np.uint64),
        withdrawable_epoch=rng.integers(0, 2**20, n).astype(np.uint64))
    from lighthouse_tpu.types.validators import (
        registry_device_columns, registry_root_device)

    limit = 1 << 40
    # Production shape: the registry columns are HBM-resident (SURVEY §7
    # hard-part 3); the root is ONE fused dispatch (record mini-trees
    # swallowed by the Pallas chunk reduction).  Correctness of this path
    # vs the host-spec fold is asserted in tests/test_merkle_kernel.py.
    import jax
    cols = registry_device_columns(reg)
    jax.block_until_ready(cols)
    registry_root_device(cols, n, limit)  # warm the compile
    ts = []
    for _ in range(RUNS):
        t0 = time.perf_counter()
        registry_root_device(cols, n, limit)
        ts.append((time.perf_counter() - t0) * 1e3)
    best = min(ts)
    # record trees: 8n hashes (incl. pubkey pre-hash); registry tree: n-1.
    hashes = 8 * n + (n - 1) + 40
    native_ms = hashes * NATIVE_NS_PER_HASH * 1e-6
    return {
        "registry_htr_ms": round(best, 1),
        "registry_htr_vs_native_1core": round(native_ms / best, 2),
        "registry_native_1core_est_ms": round(native_ms, 1),
    }


def _incremental_state_root_bench() -> dict:
    from lighthouse_tpu.types.presets import MAINNET
    from lighthouse_tpu.types.factory import spec_types
    from lighthouse_tpu.types.chain_spec import ForkName
    from lighthouse_tpu.types.validators import ValidatorRegistry

    n = 1 << STATE_LOG2
    rng = np.random.default_rng(1)
    T = spec_types(MAINNET)
    state = T.state_cls(ForkName.CAPELLA)()
    reg = ValidatorRegistry(n)
    reg._n = n
    reg.init_columns(
        pubkey=rng.integers(0, 256, (n, 48), dtype=np.uint8),
        withdrawal_credentials=rng.integers(0, 256, (n, 32), dtype=np.uint8),
        effective_balance=np.full(n, 32 * 10**9, dtype=np.uint64))
    state.validators = reg
    state.balances = np.full(n, 32 * 10**9, dtype=np.uint64)
    state.previous_epoch_participation = np.zeros(n, dtype=np.uint8)
    state.current_epoch_participation = np.zeros(n, dtype=np.uint8)
    state.inactivity_scores = np.zeros(n, dtype=np.uint64)

    # Warm the cold-path jit (the first call in a process pays its
    # compile — a per-process artifact, not the algorithm), then time a
    # GENUINE cache-less cold build.
    from lighthouse_tpu.ops import merkle_kernel as MK
    state.tree_hash_root()
    state.__dict__.pop("_thc", None)
    MK.reset_push_stats()  # leaf-push totals for THIS cold build only
    t0 = time.perf_counter()
    state.tree_hash_root()
    cold_ms = (time.perf_counter() - t0) * 1e3
    idx = rng.choice(n, 100, replace=False)
    ts = []
    for r in range(RUNS):
        state.validators.wcol("effective_balance")[idx] -= np.uint64(r + 1)
        state.balances[idx] -= np.uint64(r + 1)
        t0 = time.perf_counter()
        state.tree_hash_root()
        ts.append((time.perf_counter() - t0) * 1e3)
    from lighthouse_tpu.common import tracing
    cold = tracing.stage_split("cold_merkle")
    push = tracing.stage_split("leaf_push")
    return {
        "state_root_cold_ms": round(cold_ms, 1),
        "state_root_cold_push_ms": cold.get("push_ms"),
        "state_root_cold_compute_ms": cold.get("compute_ms"),
        "push_overlap_ms": cold.get("push_overlap_ms"),
        "push_chunks": cold.get("push_chunks"),
        # non-registry big fields (balances, participation, …) stream
        # through merkle_levels_device; totals for the cold build above
        "leaf_push_wait_ms": push.get("wait_ms"),
        "leaf_push_overlap_ms": push.get("overlap_ms"),
        "leaf_push_builds": push.get("builds"),
        "state_root_incremental_ms": round(min(ts), 2),
    }


def _device_resident_state_root_bench() -> dict:
    """Device-resident BeaconState roots (ISSUE 6 tentpole): ONE column
    push materializes HBM as the source of truth, then every warm root's
    H2D is bounded by the dirty fraction — the ~5 s full-state re-stage
    of the cold row above is eliminated from the warm path, not
    overlapped.  Reports the materialize-once split, a zero-dirty warm
    root (bytes pushed ≈ 0), and a 0.1% / 1% / 10% dirty-fraction sweep
    with bytes-pushed-per-root.  Residency is read through the DEVICE
    LEDGER snapshot (ISSUE 15) — per-subsystem attribution + HBM
    watermarks ride along for free."""
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.common.device_ledger import LEDGER
    from lighthouse_tpu.types.device_state import materialize_state
    from lighthouse_tpu.types.presets import MAINNET
    from lighthouse_tpu.types.factory import spec_types
    from lighthouse_tpu.types.chain_spec import ForkName
    from lighthouse_tpu.types.validators import ValidatorRegistry

    n = 1 << STATE_LOG2
    rng = np.random.default_rng(3)
    T = spec_types(MAINNET)
    state = T.state_cls(ForkName.CAPELLA)()
    reg = ValidatorRegistry(n)
    reg._n = n
    reg.init_columns(
        pubkey=rng.integers(0, 256, (n, 48), dtype=np.uint8),
        withdrawal_credentials=rng.integers(0, 256, (n, 32), dtype=np.uint8),
        effective_balance=np.full(n, 32 * 10**9, dtype=np.uint64))
    state.validators = reg
    state.balances = np.full(n, 32 * 10**9, dtype=np.uint64)
    state.previous_epoch_participation = np.zeros(n, dtype=np.uint8)
    state.current_epoch_participation = np.zeros(n, dtype=np.uint8)
    state.inactivity_scores = np.zeros(n, dtype=np.uint64)

    from lighthouse_tpu.ops.device_tree import (
        LEGACY_RESIDENCY_SUBSYSTEMS as _RESIDENCY_SUBS)
    _base = {s: dict(row) for s, row
             in LEDGER.snapshot()["subsystems"].items()}

    def _pushed_bytes() -> int:
        snap = LEDGER.snapshot()["subsystems"]
        return sum(snap[s]["h2d_bytes"] for s in _RESIDENCY_SUBS)

    materialize_state(state)  # the ONE full-width push of this lineage
    mat = tracing.stage_split("materialize")
    out = {
        "state_root_device_materialize_ms": mat.get("materialize_ms"),
        "state_root_device_materialize_bytes": mat.get("bytes_pushed"),
    }

    def timed_root() -> tuple:
        before = _pushed_bytes()
        t0 = time.perf_counter()
        state.tree_hash_root()
        ms = (time.perf_counter() - t0) * 1e3
        return ms, _pushed_bytes() - before

    # Zero-dirty warm root: nothing to scatter — the headline "bytes
    # pushed per warm root ≈ 0 after materialization" number.
    ms0, bytes0 = timed_root()
    out["state_root_device_warm_clean_ms"] = round(ms0, 2)
    out["state_root_device_warm_clean_bytes"] = int(bytes0)

    salt = 1
    for label, frac in (("0.1", 1000), ("1", 100), ("10", 10)):
        k = max(n // frac, 1)
        ts, pushed = [], []
        for _ in range(RUNS):
            idx = rng.choice(n, k, replace=False)
            state.validators.wcol("effective_balance")[idx] -= np.uint64(salt)
            state.balances[idx] = (
                np.asarray(state.balances)[idx] - np.uint64(salt))
            salt += 1
            ms, nb = timed_root()
            ts.append(ms)
            pushed.append(nb)
        out[f"state_root_device_warm_{label}pct_ms"] = round(min(ts), 2)
        out[f"state_root_device_push_bytes_{label}pct"] = int(min(pushed))
    # ONE consistent snapshot for the whole report (not one per cell).
    snap = LEDGER.snapshot()["subsystems"]

    def _delta(sub: str, key: str) -> int:
        return int(snap[sub][key] - _base[sub][key])

    out["state_root_device_ops"] = {
        k: sum(_delta(s, k) for s in _RESIDENCY_SUBS)
        for k in ("scatters", "rebuilds", "materializes")}
    # Per-subsystem attribution of this row's device traffic + the HBM
    # watermarks the materialized state holds (the ledger's new axis).
    out["state_root_device_ledger"] = {
        s: {"h2d_bytes": _delta(s, "h2d_bytes"),
            "d2h_bytes": _delta(s, "d2h_bytes"),
            "resident_bytes": snap[s]["resident_bytes"],
            "hbm_high_water_bytes": snap[s]["hbm_high_water_bytes"]}
        for s in _RESIDENCY_SUBS
        if any(_delta(s, k) for k in ("h2d_bytes", "d2h_bytes"))
        or snap[s]["resident_bytes"]}
    return out


# Shared Capella block fixture (block row + trace_overhead row): built
# once per process — the 62-slot setup chain costs far more than either
# measurement.
_BLOCK_FIXTURE: dict = {}


def _block_fixture() -> dict:
    """2^14-validator mainnet harness advanced to slot 62 plus a block
    at 63 packing ~120 aggregates (the BASELINE row 3 shape).  Caller
    must have the fake BLS backend installed (signing shape only)."""
    if not _BLOCK_FIXTURE:
        from lighthouse_tpu.testing.harness import StateHarness
        from lighthouse_tpu.types.presets import MAINNET

        h = StateHarness(n_validators=1 << 14, preset=MAINNET)
        # Empty blocks to slot 62 (epoch 1) — state roots skipped during
        # setup (nothing validates them here) — then a block at 63 packing
        # one aggregate per committee for the current-epoch slots whose
        # roots the head state can resolve: 30 slots × 4 committees = 120
        # attestations (≈ the 128-att BASELINE shape).
        for _ in range(62):
            sb = h.build_block(attestations=[], sync_participation=0.0,
                               compute_state_root=False)
            h.apply_block(sb, validate_state_root=False)
        atts = []
        for s in range(32, 62):
            atts.extend(h.attestations_for_slot(h.state, s))
        signed = h.build_block(slot=63, attestations=atts[:128],
                               sync_participation=0.0,
                               compute_state_root=False)
        _BLOCK_FIXTURE.update(
            h=h, signed=signed, pre=h.state,
            fork=h.fork_at(int(signed.message.slot)))
    return _BLOCK_FIXTURE


def _run_block_once(fx) -> tuple:
    """One slot-advance + block apply + state root over the fixture;
    returns (total_ms, slots_ms, roots_ms)."""
    from lighthouse_tpu.state_transition import SignatureStrategy
    from lighthouse_tpu.state_transition.per_block import process_block
    from lighthouse_tpu.state_transition.per_slot import process_slots

    h, signed = fx["h"], fx["signed"]
    state = fx["pre"].copy()
    t0 = time.perf_counter()
    state = process_slots(state, int(signed.message.slot), h.preset,
                          h.spec, h.T)
    slots_ms = (time.perf_counter() - t0) * 1e3
    process_block(state, signed, fx["fork"], h.preset, h.spec, h.T,
                  strategy=SignatureStrategy.NO_VERIFICATION)
    t1 = time.perf_counter()
    state.tree_hash_root()
    roots_ms = (time.perf_counter() - t1) * 1e3
    return (time.perf_counter() - t0) * 1e3, slots_ms, roots_ms


def _block_transition_bench() -> dict:
    """BASELINE row 3: Capella block with 128 attestations, per-phase
    (state-transition cost; crypto is covered by the sets benchmark)."""
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.crypto import bls

    prev_backend = next(
        k for k, v in bls._BACKENDS.items() if v is bls.get_backend())
    bls.set_backend("fake")
    try:
        fx = _block_fixture()
        signed = fx["signed"]
        ts, phases = [], {}
        for _ in range(RUNS):
            total, slots_ms, roots_ms = _run_block_once(fx)
            ts.append(total)
            if not phases or total <= min(ts):
                # Phase split through the tracing stage adapter — the
                # ONE read surface bench and the slot traces share
                # (ISSUE 9: no parallel reporting channels).
                phases = tracing.stage_split("block")
                phases["slot_advance_ms"] = round(slots_ms, 2)
                phases["state_roots_ms"] = round(roots_ms, 2)
        n_atts = len(signed.message.body.attestations)
        return {
            "block_transition_ms": round(min(ts), 1),
            "block_transition_attestations": n_atts,
            "block_transition_atts_per_s":
                round(n_atts / (min(ts) / 1e3), 1),
            # VERDICT item 7 groundwork: where the block milliseconds
            # live — ops apply vs committee resolution vs participation
            # updates vs roots (per_block.LAST_BLOCK_TIMINGS via the
            # tracing adapter).
            "block_phase_split": {k: round(v, 2)
                                  for k, v in sorted(phases.items())},
        }
    finally:
        bls.set_backend(prev_backend)


def _block_with_sigs_bench() -> dict:
    """ISSUE 14: the block row WITH signatures — the overlapped
    dispatch pipeline vs the trailing synchronous verify, on the shared
    2^14-validator / ~120-attestation Capella fixture.

    The device verify is MODELED by a sleeping backend at a fixed
    flagship rate (the sleep releases the GIL, so the overlap against
    the numpy/hashing transition is real); real-device numbers come
    from ``scripts/validate_block_sigs.py --device``.  Everything else —
    set building with batched pubkey materialization + shared signing
    roots, dedup, async dispatch before the participation/rewards
    phase, deferred applies, post-state-root hash, join — is the REAL
    import code path (``defer_sig_join`` shape).  Set
    ``BENCH_SIGS_TRACE_OUT=file.json`` to also write the Chrome slot
    trace of one overlapped run (the ISSUE 14 artifact)."""
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.state_transition import SignatureStrategy
    from lighthouse_tpu.state_transition.per_block import process_block
    from lighthouse_tpu.state_transition.per_slot import process_slots

    rate_holder = {"rate": BLOCK_SIGS_MODELED_RATE}

    class _ModeledBackend:
        """Sleeps exactly the modeled device time, then accepts."""
        name = "modeled"

        def verify_signature_sets(self, sets):
            time.sleep(len(sets) / rate_holder["rate"])
            return True

        def verify(self, signature, pubkeys, message):
            return True

        def aggregate_verify(self, signature, pubkeys, messages):
            return True

    prev_backend = next(
        k for k, v in bls._BACKENDS.items() if v is bls.get_backend())
    bls.register_backend("modeled", _ModeledBackend())
    bls.set_backend("fake")   # fixture building only
    prev_knob = os.environ.pop("LIGHTHOUSE_TPU_OVERLAP_BLOCK_SIGS", None)
    try:
        fx = _block_fixture()
        h, signed = fx["h"], fx["signed"]
        pre_adv = fx["pre"].copy()
        pre_adv = process_slots(pre_adv, int(signed.message.slot),
                                h.preset, h.spec, h.T)
        bls.set_backend("modeled")

        def run(overlap: bool, rate: float) -> float:
            os.environ["LIGHTHOUSE_TPU_OVERLAP_BLOCK_SIGS"] = \
                "1" if overlap else "0"
            rate_holder["rate"] = rate
            state = pre_adv.copy()
            t0 = time.perf_counter()
            acc = process_block(state, signed, fx["fork"], h.preset,
                                h.spec, h.T,
                                strategy=SignatureStrategy.VERIFY_BULK,
                                defer_sig_join=True)
            state.tree_hash_root()   # the import path's overlap window
            acc.finish()
            return (time.perf_counter() - t0) * 1e3

        run(True, BLOCK_SIGS_MODELED_RATE)  # warm (first-root effects)
        overlap_ts, sync_ts, mesh_ts = [], [], []
        sig_split, block_split, mesh_split = {}, {}, {}
        for _ in range(RUNS):
            t = run(True, BLOCK_SIGS_MODELED_RATE)
            if not overlap_ts or t <= min(overlap_ts):
                # Stage splits of the best run, via the ONE adapter
                # surface (ISSUE 9 rule).
                sig_split = tracing.stage_split("block_sigs")
                block_split = tracing.stage_split("block")
            overlap_ts.append(t)
            sync_ts.append(run(False, BLOCK_SIGS_MODELED_RATE))
            # The mesh-sharded projection: the K-bucketed sharded path
            # the batch dispatches through on a pod (8-chip model).
            tm = run(True, BLOCK_SIGS_MESH_RATE)
            if not mesh_ts or tm <= min(mesh_ts):
                mesh_split = tracing.stage_split("block_sigs")
            mesh_ts.append(tm)

        trace_out = os.environ.get("BENCH_SIGS_TRACE_OUT")
        if trace_out:
            TR = tracing.TRACER
            was = TR.enabled
            try:
                if not was:
                    TR.reset()
                TR.enable()
                slot = int(signed.message.slot)
                TR.set_slot(slot)
                with TR.span("block_import", cat="block_import",
                             slot=slot):
                    run(True, BLOCK_SIGS_MESH_RATE)
                chrome = TR.chrome_trace(slot)
                with open(trace_out, "w") as f:
                    json.dump(chrome, f)
            finally:
                if was:
                    TR.enable()
                else:
                    TR.disable()
                    TR.reset()

        dv = float(sig_split.get("device_verify_ms") or 0.0)
        jw = float(sig_split.get("join_wait_ms") or 0.0)
        mdv = float(mesh_split.get("device_verify_ms") or 0.0)
        mjw = float(mesh_split.get("join_wait_ms") or 0.0)
        return {
            "block_with_sigs_overlap_ms": round(min(overlap_ts), 1),
            "block_with_sigs_sync_ms": round(min(sync_ts), 1),
            "block_with_sigs_attestations":
                len(signed.message.body.attestations),
            "block_with_sigs_sets": sig_split.get("sets"),
            "block_with_sigs_deduped": sig_split.get("deduped"),
            "block_with_sigs_device_verify_ms": round(dv, 2),
            "block_with_sigs_join_wait_ms": round(jw, 2),
            "block_with_sigs_join_wait_frac":
                None if dv <= 0 else round(jw / dv, 4),
            "block_with_sigs_overlap_efficiency":
                sig_split.get("overlap_efficiency"),
            "block_with_sigs_mesh_overlap_ms": round(min(mesh_ts), 1),
            "block_with_sigs_mesh_device_verify_ms": round(mdv, 2),
            "block_with_sigs_mesh_join_wait_ms": round(mjw, 2),
            "block_with_sigs_mesh_join_wait_frac":
                None if mdv <= 0 else round(mjw / mdv, 4),
            "block_with_sigs_dispatched_before_apply": bool(
                "sig_dispatch_ms" in block_split
                and "deferred_apply_ms" in block_split),
            "block_with_sigs_modeled": True,
            "block_with_sigs_modeled_rate_sets_per_s":
                BLOCK_SIGS_MODELED_RATE,
            "block_with_sigs_mesh_rate_sets_per_s": BLOCK_SIGS_MESH_RATE,
            "block_with_sigs_phase_split": {
                k: round(v, 2) for k, v in sorted(block_split.items())
                if isinstance(v, (int, float))},
        }
    finally:
        if prev_knob is None:
            os.environ.pop("LIGHTHOUSE_TPU_OVERLAP_BLOCK_SIGS", None)
        else:
            os.environ["LIGHTHOUSE_TPU_OVERLAP_BLOCK_SIGS"] = prev_knob
        bls.set_backend(prev_backend)


def _trace_overhead_bench() -> dict:
    """ISSUE 9 acceptance gate: the block transition row with tracing
    OFF vs ON — an enabled tracer must cost <1% (spans + the stage
    adapter are the only additions on this path).  Min-of-several per
    mode, interleaved, per the noisy-box rule; one extra round when the
    first measurement misses the bound.  Unlosable: reports the
    measured percentage and a boolean, rc stays 0 either way."""
    from lighthouse_tpu.common.tracing import TRACER
    from lighthouse_tpu.crypto import bls

    prev_backend = next(
        k for k, v in bls._BACKENDS.items() if v is bls.get_backend())
    bls.set_backend("fake")
    was_enabled = TRACER.enabled
    try:
        fx = _block_fixture()
        _run_block_once(fx)  # warm (first root pays jit/cache effects)

        def measure(rounds: int) -> tuple:
            off, on = [], []
            for _ in range(rounds):
                TRACER.disable()
                off.append(_run_block_once(fx)[0])
                # Keep the configured ring: shrinking it here would
                # evict an enabled operator's already-assembled traces.
                TRACER.enable()
                on.append(_run_block_once(fx)[0])
            return min(off), min(on)

        spans_before = sum(s["spans"] for s in TRACER.slot_summaries())
        off_ms, on_ms = measure(4)
        pct = (on_ms - off_ms) / off_ms * 100.0
        if pct >= 1.0:  # noisy-box rule: re-measure before concluding
            off2, on2 = measure(4)
            off_ms, on_ms = min(off_ms, off2), min(on_ms, on2)
            pct = (on_ms - off_ms) / off_ms * 100.0
        # Delta, not ring total: an enabled-operator ring may already
        # hold thousands of spans from earlier slots.
        spans = sum(s["spans"] for s in TRACER.slot_summaries()) \
            - spans_before
        return {
            "trace_overhead_block_off_ms": round(off_ms, 2),
            "trace_overhead_block_on_ms": round(on_ms, 2),
            "trace_overhead_pct": round(pct, 3),
            "trace_overhead_within_bound": bool(pct < 1.0),
            "trace_overhead_spans_recorded": spans,
        }
    finally:
        # Only discard OUR slot traces when the operator didn't have
        # tracing on (an enabled-tracer run keeps its ring intact apart
        # from this row's own slots; the ring size is never changed).
        if was_enabled:
            TRACER.enable()
        else:
            TRACER.disable()
            TRACER.reset()
        bls.set_backend(prev_backend)


def _epoch_transition_bench() -> dict:
    """Single-pass epoch processing at registry scale (2^20 validators,
    random participation), with the per-stage decomposition from
    ``per_epoch.LAST_EPOCH_TIMINGS`` plus the stepwise-oracle time for the
    trajectory and a whole-epoch committee-shuffle (CommitteeCache build)
    row — the one-shot committee resolution the vectorized swap-or-not
    shuffle buys."""
    from lighthouse_tpu.state_transition import per_epoch as PE
    from lighthouse_tpu.state_transition.committees import CommitteeCache
    from lighthouse_tpu.types.chain_spec import ChainSpec, ForkName
    from lighthouse_tpu.types.factory import spec_types
    from lighthouse_tpu.types.presets import MAINNET
    from lighthouse_tpu.types.validators import ValidatorRegistry

    n = 1 << STATE_LOG2
    rng = np.random.default_rng(7)
    T = spec_types(MAINNET)
    spec = ChainSpec.mainnet().with_forks_at_genesis(ForkName.CAPELLA)
    state = T.state_cls(ForkName.CAPELLA)()
    reg = ValidatorRegistry(n)
    reg._n = n
    reg.init_columns(
        pubkey=rng.integers(0, 256, (n, 48), dtype=np.uint8),
        withdrawal_credentials=rng.integers(0, 256, (n, 32), dtype=np.uint8),
        effective_balance=np.full(n, 32 * 10 ** 9, dtype=np.uint64),
        activation_epoch=np.zeros(n, dtype=np.uint64))
    state.validators = reg
    state.balances = np.full(n, 32 * 10 ** 9, dtype=np.uint64)
    state.previous_epoch_participation = rng.integers(0, 8, n).astype(np.uint8)
    state.current_epoch_participation = rng.integers(0, 8, n).astype(np.uint8)
    state.inactivity_scores = np.zeros(n, dtype=np.uint64)
    state.slot = 8 * 32 + 31
    state.finalized_checkpoint = T.Checkpoint(epoch=6, root=b"\x01" * 32)
    state.previous_justified_checkpoint = T.Checkpoint(epoch=6,
                                                       root=b"\x01" * 32)
    state.current_justified_checkpoint = T.Checkpoint(epoch=7,
                                                      root=b"\x02" * 32)

    ts, steps = [], []
    for _ in range(RUNS):
        s2 = state.copy()
        t0 = time.perf_counter()
        PE.process_epoch_single_pass(s2, ForkName.CAPELLA, MAINNET, spec, T)
        ts.append((time.perf_counter() - t0) * 1e3)
        s3 = state.copy()
        t0 = time.perf_counter()
        PE.process_epoch_stepwise(s3, ForkName.CAPELLA, MAINNET, spec, T)
        steps.append((time.perf_counter() - t0) * 1e3)
    from lighthouse_tpu.common import tracing
    stages = tracing.stage_split("epoch")
    t0 = time.perf_counter()
    CommitteeCache(state, 8, MAINNET)
    shuffle_ms = (time.perf_counter() - t0) * 1e3
    return {
        "epoch_transition_ms": round(min(ts), 1),
        "epoch_transition_stepwise_ms": round(min(steps), 1),
        "epoch_validators": n,
        "epoch_context_ms": round(stages.get("context_ms", 0), 2),
        "epoch_justification_ms": round(stages.get("justification_ms", 0), 2),
        "epoch_inactivity_ms": round(stages.get("inactivity_ms", 0), 2),
        "epoch_rewards_ms": round(stages.get("rewards_ms", 0), 2),
        "epoch_registry_ms": round(stages.get("registry_ms", 0), 2),
        "epoch_slashings_ms": round(stages.get("slashings_ms", 0), 2),
        "epoch_effective_balance_ms":
            round(stages.get("effective_balance_ms", 0), 2),
        "epoch_shuffle_ms": round(shuffle_ms, 1),
    }


def _fork_choice_bench() -> dict:
    """Device fork choice (ISSUE 8): whole-slot score-delta application +
    find_head at mainnet-shaped widths — {2^14, 2^18, 2^21} validators ×
    {1k, 16k} unfinalized nodes.  Three engines over IDENTICAL state: the
    host ProtoArray (per-node python walk, the oracle), the columnar
    numpy engine (masked vector step per tree level), and the fused
    jitted device kernel (segment-sum + level-scheduled propagation in
    one XLA program).  Each timed round re-votes 1/32 of the registry
    (one slot's worth of latest-message churn) and runs
    compute_deltas → apply_score_changes → find_head.  Host rows never
    need a chip; the device sub-rows degrade to an error note on a dead
    backend (rc stays 0)."""
    from lighthouse_tpu.fork_choice import DeviceProtoArrayForkChoice
    from lighthouse_tpu.fork_choice.proto_array import ZERO_ROOT

    out: dict = {}
    heads_agree = True
    runs = 3

    def build_tree(n_nodes: int, rng,
                   shape: str = "bushy") -> DeviceProtoArrayForkChoice:
        """``bushy``: uniform random parents (healthy forking, depth
        ~2·ln n — the level sweep's home turf).  ``chain``: each block
        extends the last (long non-finality, depth = n — the adaptive
        dispatch's walk arm)."""
        dev = DeviceProtoArrayForkChoice(engine="numpy")
        roots = [b"\x00" * 4 + b"\xfc" * 28]
        dev.on_block(slot=0, root=roots[0], parent_root=b"\x00" * 32,
                     state_root=roots[0], justified_epoch=1,
                     justified_root=roots[0], finalized_epoch=1,
                     finalized_root=roots[0])
        for i in range(1, n_nodes):
            r = int(i).to_bytes(4, "little") + b"\xfc" * 28
            parent = roots[-1] if shape == "chain" \
                else roots[int(rng.integers(len(roots)))]
            dev.on_block(slot=i, root=r, parent_root=parent,
                         state_root=r, justified_epoch=1,
                         justified_root=roots[0], finalized_epoch=1,
                         finalized_root=roots[0])
            roots.append(r)
        return dev

    def round_trip(pa, anchor, balances, rng, nv, epoch):
        # one slot of latest-message churn: 1/32 of the registry re-votes
        k = max(nv // 32, 1)
        vals = rng.integers(0, nv, k)
        target = int(rng.integers(len(pa.indices)))
        root = int(target).to_bytes(4, "little") + b"\xfc" * 28
        if root not in pa.indices:
            root = anchor
        pa.process_attestation_batch([(vals, root, epoch)])
        t0 = time.perf_counter()
        deltas = pa.compute_deltas(balances)
        pa.apply_score_changes(deltas, (1, anchor), (1, anchor),
                               ZERO_ROOT, 0, 10_000_000)
        head = pa.find_head(anchor, 10_000_000)
        return (time.perf_counter() - t0) * 1e3, head

    shapes = [("bushy", 10, "1k"), ("bushy", 14, "16k"),
              ("chain", 10, "1k_chain"), ("chain", 14, "16k_chain")]
    for shape, n_log, n_label in shapes:
        n_nodes = 1 << n_log
        base = build_tree(n_nodes, np.random.default_rng(7), shape)
        anchor = b"\x00" * 4 + b"\xfc" * 28
        # seed votes: every validator has a latest message.  Chain rows
        # run one validator width — they exist to pin the topology axis
        # (the adaptive walk arm), not to re-sweep the validator axis.
        for v_log in ((18,) if shape == "chain" else (14, 18, 21)):
            nv = 1 << v_log
            tag = f"v2e{v_log}_n{n_label}"
            rng = np.random.default_rng(9)
            seed_vals = np.arange(nv)
            cols = DeviceProtoArrayForkChoice.from_host(base.to_host(),
                                                        engine="numpy")
            for chunk in np.array_split(seed_vals, 64):
                t = int(rng.integers(n_nodes))
                cols.process_attestation_batch(
                    [(chunk, int(t).to_bytes(4, "little") + b"\xfc" * 28,
                      1)])
            balances = np.full(nv, 32 * 10**9, np.uint64)
            host = cols.to_host()
            engines = [("columnar", cols), ("host", host)]
            try:
                from lighthouse_tpu.fork_choice.device_proto_array import (
                    warmup)
                if shape != "chain":
                    # chain depth exceeds the jit depth guard: the device
                    # engine serves those rounds from its host fallback,
                    # so there is no kernel shape to pre-lower
                    warmup(n_nodes, nv)
                dev = DeviceProtoArrayForkChoice.from_host(host,
                                                           engine="jit")
                engines.append(("device", dev))
            except Exception as e:
                out["fork_choice_device_error"] = \
                    f"{type(e).__name__}: {e}"
            heads = {}
            for name, pa in engines:
                erng = np.random.default_rng(11)
                ts = []
                for r in range(runs):
                    ms, head = round_trip(pa, anchor, balances, erng, nv,
                                          epoch=2 + r)
                    ts.append(ms)
                heads[name] = head
                out[f"fork_choice_{name}_ms_{tag}"] = round(min(ts), 2)
            if len(set(heads.values())) != 1:
                heads_agree = False
    out["fork_choice_heads_agree"] = heads_agree
    return out


def _with_pack_knob(value, fn):
    """Run ``fn`` with LIGHTHOUSE_TPU_DEVICE_PACK pinned (knobs read the
    environment at call time; bench rows own the process env, so plain
    set/pop like validate_transition.py)."""
    os.environ["LIGHTHOUSE_TPU_DEVICE_PACK"] = value
    try:
        return fn()
    finally:
        os.environ.pop("LIGHTHOUSE_TPU_DEVICE_PACK", None)


def _op_pool_bench() -> dict:
    """BASELINE row 5: max-cover packing over 100k (and 500k) pooled
    attestations — the host CELF oracle against the fixed-shape device
    greedy-pack, plus the HBM-roofline model of the pack rounds (the
    number a real TPU's pack dispatch is bounded by; on host-only boxes
    the device engine is the numpy rounds oracle, so the model carries
    the device claim the same way ``block_with_sigs`` models the
    signature mesh)."""
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.op_pool import bench_pack_attestations
    from lighthouse_tpu.op_pool.device_pack import modeled_pack_ms

    out = {}
    host_ms, host_packed = _with_pack_knob(
        "0", lambda: bench_pack_attestations(100_000))
    dev_ms, dev_packed = _with_pack_knob(
        "1", lambda: bench_pack_attestations(100_000))
    stats = tracing.stage_split("op_pool")
    modeled = modeled_pack_ms(stats.get("entries", 0),
                              stats.get("candidates", 0),
                              stats.get("rounds", 0))
    out["op_pool_pack_100k_ms"] = round(host_ms, 1)
    out["op_pool_pack_100k_device_path_ms"] = round(dev_ms, 1)
    out["op_pool_pack_100k_modeled_device_ms"] = round(modeled, 2)
    out["op_pool_pack_100k_modeled_speedup"] = round(
        host_ms / modeled, 1) if modeled > 0 else None
    out["op_pool_pack_100k_match"] = host_packed == dev_packed
    out["op_pool_packed"] = dev_packed
    out["op_pool_pack_engine"] = stats.get("engine")
    out["op_pool_pack_stage_split"] = {
        k: round(v, 2) if isinstance(v, float) else v
        for k, v in stats.items()}
    # 500k: host oracle measured live; the device side is the roofline
    # model on the linearly-scaled shape (the fixture is uniform per
    # aggregate) — re-running the numpy rounds oracle at 5x the shape
    # costs ~2 min of bench wall for no extra signal, and selection
    # parity is the differential suite's job, not this row's.
    host_ms5, _packed5 = _with_pack_knob(
        "0", lambda: bench_pack_attestations(500_000))
    modeled5 = modeled_pack_ms(stats.get("entries", 0) * 5,
                               stats.get("candidates", 0) * 5,
                               stats.get("rounds", 0))
    out["op_pool_pack_500k_ms"] = round(host_ms5, 1)
    out["op_pool_pack_500k_modeled_device_ms"] = round(modeled5, 2)
    out["op_pool_pack_500k_modeled_speedup"] = round(
        host_ms5 / modeled5, 1) if modeled5 > 0 else None
    return out


def _block_production_bench() -> dict:
    """End-to-end block production on a live MINIMAL chain: adopt the
    speculatively pre-advanced state → pack the pool → assemble + state
    root, with the adopt/pack/assemble phase split from the op_pool
    stage source.  The ``block_production_ms`` key is the SLO
    objective's bench-side twin (budget: slot/3)."""
    from lighthouse_tpu.beacon_chain import BeaconChain
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.store import HotColdDB
    from lighthouse_tpu.testing.harness import StateHarness
    from lighthouse_tpu.types.presets import MINIMAL
    from lighthouse_tpu.validator_client.beacon_node import (
        InProcessBeaconNode,
    )

    h = StateHarness(n_validators=64, preset=MINIMAL)
    hdr = h.state.latest_block_header.copy()
    hdr.state_root = h.state.tree_hash_root()
    chain = BeaconChain(
        store=HotColdDB.memory(h.preset, h.spec, h.T),
        genesis_state=h.state.copy(),
        genesis_block_root=hdr.tree_hash_root(),
        preset=h.preset, spec=h.spec, T=h.T)
    bn = InProcessBeaconNode(chain)
    # A few slots of real traffic so the pool has something to pack.
    for slot in range(1, 4):
        chain.per_slot_task(slot)
        signed = h.build_block(slot=slot, attestations=[])
        h.apply_block(signed)
        chain.process_block(signed, is_timely=True)
        from lighthouse_tpu.state_transition.per_slot import process_slots
        adv = process_slots(h.state.copy(), slot + 1, h.preset, h.spec,
                            h.T)
        chain.process_attestation_batch(h.attestations_for_slot(adv, slot))
    slot = 4
    chain.per_slot_task(slot)  # primes the speculative pre-advance
    from lighthouse_tpu.op_pool.device_pack import reset_stats
    reset_stats()  # a previous row's pack must not leak into the split
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        bn.produce_block(slot, b"\x00" * 96)
        ts.append((time.perf_counter() - t0) * 1e3)
    total = min(ts)
    split = tracing.stage_split("op_pool")
    adopt = split.get("adopt_ms", 0.0) or 0.0
    pack = sum(split.get(k, 0.0) or 0.0
               for k in ("csr_build_ms", "coverage_ms",
                         "select_rounds_ms"))
    return {
        "block_production_ms": round(total, 2),
        "block_production_adopted": bool(split.get("adopted")),
        "block_production_phases": {
            "adopt_ms": round(adopt, 3),
            "pack_ms": round(pack, 3),
            "assemble_ms": round(max(total - adopt - pack, 0.0), 3),
        },
    }


def _breaker_attribution(prefix: str, before=None):
    """Stage-attribution guard (ISSUE 7): record whether any resilience
    circuit breaker was open — or tripped — while a row's device-stage
    timings were taken.  A host-fallback window during the run would
    silently skew device-stage numbers; the flag makes a skewed row
    self-describing instead of quietly wrong."""
    from lighthouse_tpu.beacon_chain import verification_service as V

    state = (V.any_breaker_open(), V.total_breaker_trips())
    if before is None:
        return state
    return {
        f"{prefix}_breaker_open_during_run":
            bool(before[0] or state[0] or state[1] > before[1]),
        f"{prefix}_breaker_trips_total": state[1],
    }


def _stream_verify_bench() -> dict:
    """Streaming verification service drill — the robustness row: a
    2000 msg/s burst stream with 10% injected dispatch faults and one
    sustained outage window, through the service's adaptive micro-batch
    scheduler and resilience envelope (modeled fixed-cost dispatch —
    this row measures the BATCHING/RESILIENCE policy; crypto throughput
    is the bls rows' number).  `stream_zero_loss` is the headline: no
    valid message lost despite the outage (host fallback carried the
    stream, the breaker re-closed after recovery).  Pure host logic."""
    from lighthouse_tpu.common.device_ledger import LEDGER
    from lighthouse_tpu.testing.stream_drill import run_drill

    # Device-ledger attribution of the drill (ISSUE 15): dispatch
    # counts + verify wall through the envelope seam, read from the
    # ledger snapshot rather than any module-global residency dict.
    _base = {k: v for k, v in LEDGER.snapshot()["subsystems"]
             ["bls"].items()}
    out = run_drill(n_messages=256, rate_per_s=2000.0, burst_every=32,
                    burst_size=16, fail_rate=0.10, outage=(6, 14),
                    slo_ms=50.0, max_batch=32, backend="fake",
                    realtime=True, dispatch_model_ms=(2.0, 0.05), seed=0)
    env = out["envelope"]
    _bls = LEDGER.snapshot()["subsystems"]["bls"]
    return {
        "stream_ledger_device_dispatches":
            int(_bls["dispatches"] - _base["dispatches"]),
        "stream_ledger_dispatch_wall_total_ms":
            round(_bls["dispatch_wall_ms"] - _base["dispatch_wall_ms"], 2),
        "stream_ledger_h2d_bytes":
            int(_bls["h2d_bytes"] - _base["h2d_bytes"]),
        "stream_messages": out["messages"],
        "stream_zero_loss": out["zero_loss"],
        "stream_recovered": out["recovered"],
        "stream_slo_ms": out["slo_ms"],
        "stream_latency_p50_ms": out["latency_p50_ms"],
        "stream_latency_p99_ms": out["latency_p99_ms"],
        "stream_slo_violations": out["slo_violations"],
        "stream_batch_size_hist": out["batch_size_hist"],
        "stream_dispatches": out["dispatches"],
        "stream_shed": out["shed"],
        "stream_host_fallbacks": env["host_fallbacks"],
        "stream_faults_injected":
            out["injector"]["injected"].get("bls_dispatch", 0),
        "stream_breaker": env["breaker"],
        "stream_result_paths": out["result_paths"],
        "stream_wall_s": out["wall_s"],
    }


def _sustained_slo_bench() -> dict:
    """Sustained mainnet-cadence SLO drill (ISSUE 13): quick-size
    compressed-time run of testing/sustained_load — a block per slot +
    subnet attestation stream + committee aggregates through the real
    gossip → processor → streaming-verify → fork-choice → op-pool
    pipeline, with an injected device outage mid-run.  Reports the SLO
    scoreboard: per-objective attainment + p50/p99, shed/fallback
    counts, and the health-transition log (healthy → degraded →
    healthy, attributed to the outage).  Pure host logic on the fake
    backend."""
    from lighthouse_tpu.testing.sustained_load import run_sustained

    board = run_sustained(slots=12, slot_s=0.4, n_validators=64,
                          faults_outage_slots=(4, 6), seed=0)
    out = {
        "sustained_slots": board["config"]["slots"],
        "sustained_slot_s": board["config"]["slot_s"],
        "sustained_wall_s": board["wall_s"],
        "sustained_rate_atts_per_s": board["rate_atts_per_s"],
        "sustained_messages": board["messages"]["submitted"],
        "sustained_zero_loss": board["loss"]["zero_loss"],
        "sustained_shed": board["messages"]["shed"],
        "sustained_host_fallbacks": board["host_fallbacks"],
        "sustained_health_final": board["health"]["state"],
        "sustained_health_transitions": [
            f"{t['from']}->{t['to']}"
            + (f" ({','.join(t['reasons'])})" if t["reasons"] else "")
            for t in board["health"]["transitions"]],
        "sustained_outage_attributed":
            board["fault_attribution"]["attributed"],
        # Warm-slot device-transfer budget (ISSUE 15): the SLO-style
        # attainment row the device ledger exports through the drill.
        "sustained_device_budget_ok": board["device_budget"]["ok"],
        "sustained_device_budget_attainment":
            board["device_budget"]["attainment"],
    }
    for row in board["objectives"]:
        name = row["name"]
        out[f"sustained_attainment_{name}"] = \
            row["slow"].get("attainment")
        if row["kind"] == "latency":
            out[f"sustained_{name}_p50_ms"] = row["slow"].get("p50_ms")
            out[f"sustained_{name}_p99_ms"] = row["slow"].get("p99_ms")
        else:
            out[f"sustained_{name}_rate"] = row["slow"].get("rate")
    return out


def _proof_engine_bench() -> dict:
    """Device Merkle-branch extraction (ISSUE 17): batched gather of
    proof branches from a resident 2^21-leaf DeviceTree at 1/64/1024
    concurrent gindices — zero re-hashing, one device program per batch
    — vs the host-walk oracle (one full hashlib rebuild, the
    `merkle_proof.MerkleTree._levels` shape) and the cached-levels host
    branch-assembly rate.  A sample branch is verified against the
    device root before any number is believed."""
    import numpy as np

    from lighthouse_tpu.ops.device_tree import DeviceTree
    from lighthouse_tpu.ops.merkle_proof import verify_merkle_proof
    from lighthouse_tpu.ops.proof_engine import DeviceProofEngine
    from lighthouse_tpu.ops.sha256 import words_to_bytes

    log2 = 21
    n = 1 << log2
    rng = np.random.default_rng(7)
    leaves = rng.integers(0, 1 << 32, size=(n, 8),
                          dtype=np.uint64).astype(np.uint32)
    t0 = time.perf_counter()
    tree = DeviceTree.from_host_leaves(leaves)
    build_ms = (time.perf_counter() - t0) * 1e3
    eng = DeviceProofEngine(tree)
    root = words_to_bytes(tree.root_words())

    out: dict = {"proof_tree_log2_leaves": log2,
                 "proof_tree_build_ms": round(build_ms, 1)}
    for batch in (1, 64, 1024):
        # Deterministic leaf gindices spread across the width.
        gs = [n + (i * 2_097_143) % n for i in range(batch)]
        eng.branches(gs)  # warm the gather jit for this batch shape
        best = min(_time_one(lambda: eng.branches(gs))
                   for _ in range(5 if batch < 1024 else 3))
        out[f"proof_extract_batch_{batch}_per_s"] = round(batch / best, 1)
    # Correctness gate: one device branch must verify against the
    # device root (and it did NOT come from any hash on the way out).
    g = n + 12345
    branch = eng.branches([g])[g]
    leaf = leaves[12345].astype(">u4").tobytes()
    assert verify_merkle_proof(leaf, branch, log2, 12345, root), \
        "device branch failed verification against device root"
    # Host-walk oracle: the per-request shape the engine replaces — a
    # full levels rebuild (what MerkleTree.proof pays at this width) is
    # ~2^22 hashes, so walk a 2^14-leaf slice and scale (the walk is
    # linear in width by construction) — plus the cached-levels host
    # branch-assembly rate.
    import hashlib
    slice_log2 = 14
    lv = [leaves[i].astype(">u4").tobytes()
          for i in range(1 << slice_log2)]
    t0 = time.perf_counter()
    host_levels = [lv]
    while len(lv) > 1:
        lv = [hashlib.sha256(lv[i] + lv[i + 1]).digest()
              for i in range(0, len(lv), 2)]
        host_levels.append(lv)
    slice_ms = (time.perf_counter() - t0) * 1e3
    out["proof_extract_host_walk_ms"] = round(
        slice_ms * (n / (1 << slice_log2)), 1)

    def host_branch(i: int) -> list:
        return [host_levels[d][(i >> d) ^ 1] for d in range(slice_log2)]

    best = min(_time_one(lambda: [host_branch(i % (1 << slice_log2))
                                  for i in range(1024)])
               for _ in range(5))
    out["proof_extract_host_cached_per_s"] = round(1024 / best, 1)
    return out


def _lc_bootstrap_bench() -> dict:
    """Light-client bootstrap latency (ISSUE 17): the re-homed
    `LightClientServer.bootstrap` — header + current sync committee +
    the device-extracted `current_sync_committee_branch` — over a warm
    proof server, vs the host `state_field_proof` walk it replaced."""
    from lighthouse_tpu.beacon_chain import BeaconChain
    from lighthouse_tpu.light_client import (LightClientServer,
                                             state_field_proof)
    from lighthouse_tpu.store import HotColdDB
    from lighthouse_tpu.testing.harness import StateHarness
    from lighthouse_tpu.types.presets import MINIMAL

    h = StateHarness(n_validators=64, preset=MINIMAL)
    hdr = h.state.latest_block_header.copy()
    hdr.state_root = h.state.tree_hash_root()
    chain = BeaconChain(
        store=HotColdDB.memory(h.preset, h.spec, h.T),
        genesis_state=h.state.copy(),
        genesis_block_root=hdr.tree_hash_root(),
        preset=h.preset, spec=h.spec, T=h.T)
    srv = LightClientServer(chain)
    srv.bootstrap()  # warm: field tree materialize + gather jit
    best = min(_time_one(srv.bootstrap) for _ in range(20))
    state = chain.head.state
    host_best = min(_time_one(lambda: state_field_proof(
        state, "current_sync_committee")) for _ in range(20))
    return {
        "light_client_bootstrap_ms": round(best * 1e3, 3),
        "light_client_host_branch_ms": round(host_best * 1e3, 3),
        "light_client_proof_stats": chain.proof_server.stats(),
    }


def _time_one(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _stage_split_bench() -> dict:
    """VERDICT r4 #2: the measured per-stage decomposition of the fused
    pipeline (marshal/hash/prepare/Miller/fold/finalize) — at the r5
    C=2 bucket (comparable with the BENCH_SELF_r05 baselines: final_exp
    51.7 / HTC 44.29 / Miller 32.39 / fold 10.99 ms) AND the C=8 bucket
    the 1024-set row now dispatches as one program, where the fixed
    final-exp tail amortizes 4× further."""
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.crypto.profiling import profile_stages

    mark = _breaker_attribution("stage_split")
    # Both reads go through the tracing stage adapter (ISSUE 9: one
    # source for bench rows and slot traces).
    profile_stages(C=2)
    out = tracing.stage_split("bls_kernels")
    profile_stages(C=8)
    wide = tracing.stage_split("bls_kernels")
    out.update({k.replace("stage_", "stage_c8_"): v
                for k, v in wide.items() if k != "stage_shape"})
    out.update(_breaker_attribution("stage_split", mark))
    return out


def _slasher_bench() -> dict:
    """VERDICT r4 #9: slasher span-plane ingest at registry scale.
    history=512 bounds the planes at 2×1 GiB (the bench process already
    carries earlier rows' arrays; gc runs between rows)."""
    from lighthouse_tpu.slasher import bench_span_update

    return bench_span_update(n_validators=1 << 20, n_atts=1024,
                             history=512, per_att=256)


def _kzg_bench() -> dict:
    """Deneb data-availability workload: verify_blob_kzg_proof_batch over
    a block's worth of mainnet-width blobs through the device path
    (barycentric Fr kernel + 2-lanes-per-blob Miller batch + shared final
    exponentiation), stage timings from kzg.device.LAST_KZG_TIMINGS.

    Fixtures come from the INSECURE known-tau setup: commitments/proofs
    via one G1 scalar-mul each instead of a width-sized MSM — the
    VERIFIER's work (the thing measured) is identical to a ceremony
    setup's.
    """
    import random
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.kzg import device as D, kzg as K
    from lighthouse_tpu.kzg.fr import BLS_MODULUS
    from lighthouse_tpu.kzg.trusted_setup import verification_setup

    width = int(os.environ.get("BENCH_KZG_WIDTH", "4096"))
    n_blobs = int(os.environ.get("BENCH_KZG_BLOBS", "6"))  # MAX_BLOBS
    t0 = time.perf_counter()
    # Verifier-only setup: the known-tau commit/prove fast paths and the
    # verifier never read g1_lagrange, so skip the width-sized table.
    setup = verification_setup(width)
    rng = random.Random(0)
    blobs, cms, pfs = [], [], []
    for _ in range(n_blobs):
        blob = K.polynomial_to_blob(
            [rng.randrange(BLS_MODULUS) for _ in range(width)])
        cm = K.blob_to_kzg_commitment(blob, setup)
        blobs.append(blob)
        cms.append(cm)
        pfs.append(K.compute_blob_kzg_proof(blob, cm, setup))
    setup_s = time.perf_counter() - t0

    # Correctness gates (+ kernel warm-up): valid accepted, tampered
    # rejected, device agrees with the host RLC fold.
    t0 = time.perf_counter()
    if not K.verify_blob_kzg_proof_batch(blobs, cms, pfs, setup,
                                         use_device=True):
        raise RuntimeError("valid blob batch rejected")
    cold_ms = (time.perf_counter() - t0) * 1e3
    # Tamper: blob 0's proof replaced by its commitment — a valid G1
    # point that is the wrong proof for ANY batch size (incl. n_blobs=1).
    if K.verify_blob_kzg_proof_batch(blobs, cms,
                                     [cms[0]] + pfs[1:], setup,
                                     use_device=True):
        raise RuntimeError("tampered blob batch accepted")
    if not K.verify_blob_kzg_proof_batch(blobs, cms, pfs, setup,
                                         use_device=False):
        raise RuntimeError("host fallback rejected a valid batch")

    ts = []
    for _ in range(RUNS):
        t0 = time.perf_counter()
        if not K.verify_blob_kzg_proof_batch(blobs, cms, pfs, setup,
                                             use_device=True):
            raise RuntimeError("valid batch rejected in timing loop")
        ts.append((time.perf_counter() - t0) * 1e3)
    best = min(ts)
    stages = tracing.stage_split("kzg")
    return {
        "kzg_batch_verify_ms": round(best, 1),
        "kzg_batch_cold_ms": round(cold_ms, 1),
        "kzg_blobs": n_blobs,
        "kzg_field_elements_per_blob": width,
        "kzg_blobs_per_s": round(n_blobs / (best / 1e3), 1),
        "kzg_challenge_ms": stages.get("challenge_ms"),
        "kzg_eval_ms": stages.get("eval_ms"),
        "kzg_lane_prep_ms": stages.get("lane_prep_ms"),
        "kzg_pairing_ms": stages.get("pairing_ms"),
        "kzg_pairing_lanes": stages.get("lanes"),
        "kzg_setup_s": round(setup_s, 1),
    }


def _secure_channel_bench() -> dict:
    """Secure p2p overhead (VERDICT r5 item 8's 'measured, not assumed'
    requirement): noise-xx handshake latency + AEAD record throughput of
    the pure-python/numpy channel every wire byte now crosses."""
    import secrets
    import socket
    import threading

    from lighthouse_tpu.network.secure import chacha, noise, x25519

    sk = secrets.token_bytes(32)
    t0 = time.perf_counter()
    x25519.pubkey(sk)
    x_ms = (time.perf_counter() - t0) * 1e3

    hs = []
    for _ in range(5):
        a, b = socket.socketpair()
        out = {}
        t = threading.Thread(
            target=lambda: out.__setitem__("r", noise.respond(b, sk)))
        t.start()
        t0 = time.perf_counter()
        ch_i = noise.initiate(a, secrets.token_bytes(32))
        t.join()
        hs.append((time.perf_counter() - t0) * 1e3)
        a.close()
        b.close()
    ch_r = out["r"]

    frame = secrets.token_bytes(64 << 10)  # one gossip-block-ish record
    n = 32
    t0 = time.perf_counter()
    records = [ch_i.encrypt(frame) for _ in range(n)]
    enc_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for rec in records:
        ch_r.decrypt(rec[4:])
    dec_s = time.perf_counter() - t0
    mb = n * len(frame) / 1e6
    return {
        "secure_handshake_ms": round(min(hs), 2),
        "secure_x25519_ms": round(x_ms, 2),
        "secure_aead_encrypt_mb_s": round(mb / enc_s, 1),
        "secure_aead_decrypt_mb_s": round(mb / dec_s, 1),
        "secure_record_kb": len(frame) >> 10,
    }


def _restart_recovery_bench() -> dict:
    """Restart-recovery row (crash-safe store PR): cold
    ``BeaconChain.from_store`` against an on-disk SQLite datadir whose
    node "crashed" (no shutdown persist — only the atomic import batches
    and the finalization-time snapshots survive), at chain lengths
    {64, 512} slots.  Reports the cold-boot milliseconds (CRC verify +
    snapshot reconcile + journal replay + head load) and the replay
    count (how many imports the journal had to re-apply — bounded by the
    finalization persist cadence, NOT the chain length).  Pure host
    logic."""
    import tempfile

    from lighthouse_tpu.beacon_chain import BeaconChain
    from lighthouse_tpu.crypto import bls as B
    from lighthouse_tpu.store import HotColdDB, SqliteStore
    from lighthouse_tpu.testing.crash_drill import (
        build_chain_fixture, import_sequence, make_chain)

    out: dict = {}
    prev_backend = B.get_backend()
    B.set_backend("fake")
    try:
        with tempfile.TemporaryDirectory() as tmp:
            for slots in (64, 512):
                t0 = time.perf_counter()
                # +5: land the crash mid-epoch — an epoch-aligned length
                # ends exactly on a finalization persist (empty journal),
                # which would measure a replay-free boot only.
                fx = build_chain_fixture(slots=slots + 5)
                build_s = time.perf_counter() - t0
                path = os.path.join(tmp, f"bench-{slots}.sqlite")
                kv = SqliteStore(path)
                store = HotColdDB(kv, fx.preset, fx.spec, fx.T)
                chain = make_chain(store, fx)
                t0 = time.perf_counter()
                import_sequence(chain, fx)
                import_s = time.perf_counter() - t0
                head = chain.head.root
                kv.close()  # crash: no shutdown persist
                t0 = time.perf_counter()
                kv2 = SqliteStore(path)
                store2 = HotColdDB(kv2, fx.preset, fx.spec, fx.T)
                chain2 = BeaconChain.from_store(
                    store=store2, preset=fx.preset, spec=fx.spec, T=fx.T)
                cold_ms = (time.perf_counter() - t0) * 1e3
                ok = chain2.head.root == head
                report = chain2.last_recovery
                kv2.close()
                out.update({
                    f"restart_cold_from_store_ms_{slots}":
                        round(cold_ms, 1),
                    f"restart_replayed_blocks_{slots}":
                        len(report.replayed) if report else -1,
                    f"restart_head_matches_{slots}": ok,
                    f"restart_build_s_{slots}": round(build_s, 1),
                    f"restart_import_s_{slots}": round(import_s, 1),
                })
    finally:
        B.set_backend(getattr(prev_backend, "name", "python"))
    return out


def _epoch_replay_bench() -> dict:
    """Epoch-batched replay row (batched-replay PR): the serial
    ``BlockReplayer`` (per-block import — the catch-up oracle) vs the
    ``EpochReplayer`` window (known state roots + ONE boundary root)
    at window sizes {32, 64, 128} on a 64-validator MINIMAL chain.

    The HEADLINE 64-block known-root shape models the device-resident
    root engine at the measured flagship rate
    (``DEVICE_ROOT_MODELED_MS`` = BENCH r5 ``state_root_incremental_ms``
    — the sleep releases the GIL, same discipline as the block-sigs
    row): the serial path charges one device root program per slot via
    its ``state_root_fn``; the batched path looks known roots up for
    free and charges ONE boundary program.  The pure-host window table
    rides along (``epoch_replay_host`` — there the incremental tree
    cache bounds the differential to the dirty-chunk hash per block),
    as does the ``sigs`` shape: the window's signature sets in ONE
    dispatcher batch against the modeled sleeping BLS backend vs
    per-block synchronous verifies."""
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.crypto import bls as B
    from lighthouse_tpu.state_transition import EpochReplayer
    from lighthouse_tpu.state_transition.block_replayer import BlockReplayer
    from lighthouse_tpu.state_transition.per_block import SignatureStrategy
    from lighthouse_tpu.testing.harness import StateHarness
    from lighthouse_tpu.types.presets import MINIMAL

    class _ModeledBackend:
        """Sleeps the modeled device time per batch, then accepts —
        the sleep releases the GIL, so the window dispatch genuinely
        overlaps the boundary hash."""
        name = "modeled"

        def verify_signature_sets(self, sets):
            time.sleep(len(sets) / BLOCK_SIGS_MODELED_RATE)
            return True

        def verify(self, signature, pubkeys, message):
            return True

        def aggregate_verify(self, signature, pubkeys, messages):
            return True

    prev_backend = next(
        k for k, v in B._BACKENDS.items() if v is B.get_backend())
    B.register_backend("modeled", _ModeledBackend())
    B.set_backend("fake")
    out: dict = {}
    try:
        h = StateHarness(n_validators=64, preset=MINIMAL)
        genesis = h.state.copy()
        for _ in range(128):
            h.apply_block(h.build_block(),
                          strategy=SignatureStrategy.NO_VERIFICATION)

        def serial_s(blocks, root_fn=None) -> float:
            rep = BlockReplayer(genesis.copy(), h.preset, h.spec, h.T,
                                strategy=SignatureStrategy.NO_VERIFICATION,
                                state_root_fn=root_fn)
            t0 = time.perf_counter()
            rep.apply_blocks(blocks)
            return time.perf_counter() - t0

        def batched_s(blocks, verify: bool) -> float:
            rep = EpochReplayer(genesis.copy(), h.preset, h.spec, h.T,
                                verify_signatures=verify)
            t0 = time.perf_counter()
            rep.apply_window(blocks)
            return time.perf_counter() - t0

        def serial_sigs_s(blocks) -> float:
            rep = BlockReplayer(genesis.copy(), h.preset, h.spec, h.T,
                                strategy=SignatureStrategy.VERIFY_BULK)
            t0 = time.perf_counter()
            rep.apply_blocks(blocks)
            return time.perf_counter() - t0

        windows: dict = {}
        for n in (32, 64, 128):
            blocks = h.blocks[:n]
            ser = min(serial_s(blocks) for _ in range(2))
            bat = min(batched_s(blocks, False) for _ in range(2))
            windows[str(n)] = {
                "serial_blocks_per_s": round(n / ser, 1),
                "batched_blocks_per_s": round(n / bat, 1),
                "speedup": round(ser / bat, 2),
            }
            if n == 64:
                # Stage decomposition of the window, via the ONE
                # adapter surface (stage-source rule).
                out["epoch_replay_stage_split"] = {
                    k: v for k, v in
                    tracing.stage_split("replay").items()
                    if not isinstance(v, str)}
        out["epoch_replay_host"] = windows

        # HEADLINE: the 64-block known-root shape at the modeled
        # device-resident root rate.  The serial oracle's per-slot root
        # lands on the device engine (one program per slot, measured
        # latency); the batched window's known roots are free lookups
        # and ONE boundary program closes the window.
        blocks = h.blocks[:64]
        claims = {int(b.message.slot): bytes(b.message.state_root)
                  for b in blocks}

        def device_root_fn(slot):
            time.sleep(DEVICE_ROOT_MODELED_MS / 1e3)
            return claims.get(int(slot))

        ser = min(serial_s(blocks, device_root_fn) for _ in range(2))
        bat = min(batched_s(blocks, False)
                  for _ in range(2)) + DEVICE_ROOT_MODELED_MS / 1e3
        out.update({
            "epoch_replay_blocks_per_s": round(64 / bat, 1),
            "epoch_replay_serial_blocks_per_s": round(64 / ser, 1),
            "epoch_replay_speedup_64": round(ser / bat, 2),
            "epoch_replay_device_root_modeled_ms": DEVICE_ROOT_MODELED_MS,
        })

        # Signature-on shape: the 64-block window's sets through ONE
        # dispatcher batch (modeled sleeping device) vs per-block
        # synchronous verifies at the same modeled rate.
        B.set_backend("modeled")
        blocks = h.blocks[:64]
        sig_ser = min(serial_sigs_s(blocks) for _ in range(2))
        sig_bat = min(batched_s(blocks, True) for _ in range(2))
        out.update({
            "epoch_replay_sigs_serial_blocks_per_s":
                round(64 / sig_ser, 1),
            "epoch_replay_sigs_blocks_per_s": round(64 / sig_bat, 1),
            "epoch_replay_sigs_speedup": round(sig_ser / sig_bat, 2),
        })
    finally:
        B.set_backend(prev_backend)
    return out


# (name, fn, emitted-metric-name).  FAST rows first: the BLS row pays
# its per-process trace and compile before it can answer, so under an
# unknown timeout the cheap rows must already be on the tail; the
# combined line re-emits after every row so the LAST captured line is
# always a full record of everything measured so far.
_ROWS = [
    ("secure", _secure_channel_bench, "secure_channel"),
    ("stream", _stream_verify_bench, "stream_verify"),
    ("sustained", _sustained_slo_bench, "sustained_slo"),
    ("restart", _restart_recovery_bench, "restart_recovery"),
    ("replay", _epoch_replay_bench, "epoch_replay_blocks_per_s"),
    ("lc_bootstrap", _lc_bootstrap_bench, "light_client_bootstrap"),
    ("proof", _proof_engine_bench, "proof_extract_batch"),
    ("registry", _registry_htr_bench, "registry_htr_2e%d" % REG_LOG2),
    ("state_root", _incremental_state_root_bench,
     "state_root_2e%d" % STATE_LOG2),
    ("state_device", _device_resident_state_root_bench,
     "state_root_device_resident"),
    ("fork_choice", _fork_choice_bench, "fork_choice_apply"),
    ("op_pool", _op_pool_bench, "op_pool_pack_100k"),
    ("production", _block_production_bench, "block_production"),
    ("slasher", _slasher_bench, "slasher_span_update_1m"),
    ("block", _block_transition_bench, "block_transition_128att"),
    ("block_sigs", _block_with_sigs_bench, "block_with_sigs"),
    ("trace", _trace_overhead_bench, "trace_overhead"),
    ("epoch", _epoch_transition_bench,
     "epoch_transition_2e%d" % STATE_LOG2),
    ("stages", _stage_split_bench, "bls_stage_split"),
    ("kzg", _kzg_bench, "kzg_batch_verify"),
    ("bls", _bls_bench, "bls_batch_verify_%d_sets" % N_SETS),
]


# Previous combined snapshot (BENCH_LATEST.json), read ONCE at startup
# before the per-row rewrites clobber it — the regression report's
# baseline.
_PREV_BENCH: dict = {}


def _load_prev_bench() -> None:
    try:
        with open("BENCH_LATEST.json", "r") as fh:
            prev = json.load(fh)
        if isinstance(prev, dict):
            _PREV_BENCH.update(prev)
    except (OSError, ValueError):
        pass


def _regressions(merged: dict) -> dict:
    """Noise-aware regression report vs the previous BENCH_LATEST.json
    snapshot.  Rows already take min-of-several; this box's memory
    bandwidth is ±40% noisy between runs, so only >2x deltas are
    flagged — and the section is informational (rc stays 0; a flagged
    row means "re-measure before believing", not "fail the run")."""
    if not _PREV_BENCH:
        return {"compared": 0, "flagged": [],
                "note": "no previous BENCH_LATEST.json"}
    flagged = []
    compared = 0
    for key, new in merged.items():
        old = _PREV_BENCH.get(key)
        if isinstance(new, bool) or isinstance(old, bool) \
                or not isinstance(new, (int, float)) \
                or not isinstance(old, (int, float)):
            continue
        if key.endswith("_ms"):
            lower_better = True
        elif key.endswith("_per_s"):
            lower_better = False
        else:
            continue
        if old <= 0 or new <= 0:
            continue
        compared += 1
        worse_by = (new / old) if lower_better else (old / new)
        if worse_by > 2.0:
            flagged.append({"metric": key, "previous": old,
                            "current": new,
                            "worse_by": round(worse_by, 2)})
    flagged.sort(key=lambda r: -r["worse_by"])
    return {"compared": compared, "flagged": flagged}


def _parse_cli(argv: list) -> tuple:
    """Minimal CLI: ``--list`` prints the row names and exits;
    ``--only ROW[,ROW…]`` (or ``--only=ROW[,…]``) runs a subset.
    Unknown flags are refused — before this, ANY argv ran the full
    bench, so a typo'd flag silently cost a full run."""
    only = None
    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg == "--list":
            for name, _fn, metric in _ROWS:
                print(f"{name:14s} -> {metric}")
            raise SystemExit(0)
        if arg == "--only" or arg.startswith("--only="):
            if arg == "--only":
                if i + 1 >= len(argv):
                    print("bench: --only needs ROW[,ROW…] "
                          "(see --list)", file=sys.stderr)
                    raise SystemExit(2)
                spec = argv[i + 1]
                i += 2
            else:
                spec = arg.split("=", 1)[1]
                i += 1
            names = [r for r in spec.split(",") if r]
            if not names:
                # `--only=` / `--only ,,`: refusing beats silently
                # running ZERO rows and exiting 0 as if measured.
                print("bench: --only got an empty row list "
                      "(see --list)", file=sys.stderr)
                raise SystemExit(2)
            known = {name for name, _f, _m in _ROWS}
            bad = sorted(set(names) - known)
            if bad:
                print(f"bench: unknown row(s) {bad}; known: "
                      f"{sorted(known)}", file=sys.stderr)
                raise SystemExit(2)
            only = set(names)
            continue
        print(f"bench: unknown argument {arg!r} (use --list / "
              f"--only ROW[,ROW…])", file=sys.stderr)
        raise SystemExit(2)
    return (only,)


def main() -> int:
    (only,) = _parse_cli(sys.argv[1:])
    # Sweep temp snapshots stranded by previously killed runs (the
    # per-run temp below is pid-unique, so anything matching is stale).
    import glob
    for stale in glob.glob("*.json.tmp"):
        try:
            os.unlink(stale)
        except OSError:
            pass
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # Every row is named for the chip; a run without one measures
        # nothing it could report under those names.
        print(f"bench: no TPU (JAX found {dev.platform!r}); "
              f"run on the chip", file=sys.stderr)
        return 1
    _emit({"metric": "device", "platform": dev.platform,
           "kind": dev.device_kind, "count": len(jax.devices())})
    from lighthouse_tpu.common.compile_cache import enable
    enable()

    # Per-row hang watchdog: a device call can wedge with no
    # Python-level timeout possible; if a row exceeds its budget, dump
    # every stack and HARD-EXIT — the rows already printed are still
    # captured (the whole point of incremental emission).  Cold
    # compiles of the BLS programs are long, hence the generous default.
    row_timeout = float(os.environ.get("BENCH_ROW_TIMEOUT_S", "2700"))

    # Regression baseline: snapshot the PREVIOUS combined record before
    # the per-row rewrites below clobber BENCH_LATEST.json.
    _load_prev_bench()

    merged: dict = {}
    skipped: list = []
    failed: list = []
    # Pid-unique temp: concurrent runs cannot clobber each other's
    # snapshot mid-write, and the startup sweep can tell it's stale.
    tmp_path = f"BENCH_LATEST.{os.getpid()}.json.tmp"
    try:
        for name, fn, metric in _ROWS:
            if only is not None and name not in only:
                continue
            elapsed = time.monotonic() - _T_START
            if elapsed > BUDGET_S:
                skipped.append(name)
                _emit({"metric": metric, "skipped": "budget",
                       "elapsed_s": round(elapsed, 1)})
                continue
            t0 = time.monotonic()
            faulthandler.dump_traceback_later(row_timeout, exit=True,
                                              file=sys.stderr)
            try:
                row = fn()
            except Exception as e:  # the other rows still run; rc is 1
                traceback.print_exc(file=sys.stderr)
                _emit({"metric": metric,
                       "error": f"{type(e).__name__}: {e}"})
                merged[f"{name}_error"] = f"{type(e).__name__}: {e}"
                failed.append(name)
                continue
            finally:
                faulthandler.cancel_dump_traceback_later()
                import gc
                gc.collect()  # free each row's arrays before the next
            merged.update(row)
            _emit({"metric": metric,
                   "row_s": round(time.monotonic() - t0, 1), **row})
            combined = _combined(merged, skipped)
            _emit(combined)  # tail capture always ends on a full record
            # ATOMICITY: per-row snapshots land in a pid-unique temp;
            # the real BENCH_LATEST.json is replaced ONCE by the rename
            # at end of run — a killed run can no longer leave a
            # truncated/partial artifact that guts the baseline.
            try:
                with open(tmp_path, "w") as f:
                    json.dump(combined, f)
            except OSError:
                pass

        combined = _combined(merged, skipped)
        combined["failed"] = failed
        print(json.dumps(combined))
        if failed:
            return 1
        if only is not None:
            # A subset run would overwrite the full snapshot with a
            # slice — keep the regression baseline intact.
            print(json.dumps({"metric": "bench_latest",
                              "note": "subset run (--only): "
                                      "BENCH_LATEST.json left "
                                      "untouched"}))
            return 0
        try:
            with open(tmp_path, "w") as f:
                json.dump(combined, f)
            os.replace(tmp_path, "BENCH_LATEST.json")
        except OSError:
            pass
        return 0
    finally:
        # Whatever the exit path (subset return, watchdog, exception),
        # never strand the temp snapshot.
        try:
            os.unlink(tmp_path)
        except OSError:
            pass


def _combined(merged: dict, skipped: list) -> dict:
    bls_row = {}
    if "sets_per_s" in merged:
        bls_row = {
            "value": merged["sets_per_s"],
            "unit": "sets/s",
            "vs_baseline": round(
                merged["sets_per_s"] / (1e3 / BLST_EST_MS_PER_SET), 3),
        }
    out = {
        "metric": f"bls_batch_verify_{N_SETS}_sets",
        **bls_row,
        "baseline": f"blst single-core estimate {BLST_EST_MS_PER_SET} ms/set",
        **merged,
        "regressions": _regressions(merged),
        "skipped": skipped,
        "total_s": round(time.monotonic() - _T_START, 1),
    }
    if "sets_per_s" in merged:  # the gates inside _bls_bench actually ran
        out["correctness"] = (
            "valid batch accepted, tampered batch rejected; "
            "device hash-to-curve == host RFC-9380 oracle; "
            "registry root == host-spec root (tested suite)")
    return out


if __name__ == "__main__":
    sys.exit(main())
