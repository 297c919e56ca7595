"""Differential tests for the device-resident BeaconState (ISSUE 6).

The contract: once :func:`materialize_state` makes the device buffers the
source of truth, ``hash_tree_root`` is byte-identical to the host spec
path under ARBITRARY interleavings of scatter mutations / append / grow /
copy — and ``copy()`` is copy-on-write (mutating a clone never leaks into
the parent, in either direction).  A host twin state, mutated identically
and hashed through the PR-3-proven host incremental cache, is the oracle.

All of this is quick-tier: the dirty-propagation and rebuild programs are
merkle-shaped (XLA ``hash64`` scans at test widths — seconds, not the
minutes a pairing-scale program costs per process).
"""

import numpy as np
import pytest

from lighthouse_tpu.ops.device_tree import (reset_residency_stats,
                                            residency_snapshot)
from lighthouse_tpu.types.chain_spec import ForkName
from lighthouse_tpu.types.device_state import (DeviceColumn,
                                               materialize_state,
                                               store_column)
from lighthouse_tpu.types.factory import spec_types
from lighthouse_tpu.types.presets import MAINNET, MINIMAL
from lighthouse_tpu.types.validators import Validator, ValidatorRegistry

FAR = 2 ** 64 - 1


def _mk_state(n: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    T = spec_types(MAINNET)
    state = T.state_cls(ForkName.CAPELLA)()
    reg = ValidatorRegistry(n)
    reg._n = n
    reg.init_columns(
        pubkey=rng.integers(0, 256, (n, 48), dtype=np.uint8),
        withdrawal_credentials=rng.integers(0, 256, (n, 32), dtype=np.uint8),
        effective_balance=(rng.integers(0, 33, n) * 10 ** 9).astype(
            np.uint64),
        slashed=rng.random(n) < 0.1)
    state.validators = reg
    state.balances = rng.integers(0, 40 * 10 ** 9, n).astype(np.uint64)
    state.previous_epoch_participation = rng.integers(0, 8, n).astype(
        np.uint8)
    state.current_epoch_participation = rng.integers(0, 8, n).astype(np.uint8)
    state.inactivity_scores = rng.integers(0, 100, n).astype(np.uint64)
    return state


def _twins(n: int, seed: int = 7):
    """(host-oracle state, device-resident state), identical contents.
    On the CPU test backend the auto-materialization threshold never
    trips, so the twin stays on the host incremental path and the device
    twin is materialized explicitly."""
    host = _mk_state(n, seed)
    dev = _mk_state(n, seed)
    assert materialize_state(dev)
    return host, dev


def _rand_validator(rng) -> Validator:
    return Validator(
        pubkey=rng.integers(0, 256, 48, dtype=np.uint8).tobytes(),
        withdrawal_credentials=rng.integers(0, 256, 32,
                                            dtype=np.uint8).tobytes(),
        effective_balance=int(rng.integers(0, 33)) * 10 ** 9,
        slashed=bool(rng.random() < 0.5),
        activation_eligibility_epoch=int(rng.integers(0, 10)),
        activation_epoch=int(rng.integers(0, 10)),
        exit_epoch=FAR,
        withdrawable_epoch=FAR)


def test_materialized_root_matches_host_and_stays_warm():
    host, dev = _twins(70)
    assert dev.tree_hash_root() == host.tree_hash_root()

    # Warm scatter path: a handful of dirty records / balance cells.
    for s in (host, dev):
        s.validators.wcol("effective_balance")[5] = np.uint64(7)
        s.balances[3] = np.uint64(11)
        s.inactivity_scores[9] = np.uint64(2)
        s.current_epoch_participation[1] = np.uint8(3)
    assert dev.tree_hash_root() == host.tree_hash_root()

    # Clean repeat: nothing dirty, roots stable.
    assert dev.tree_hash_root() == host.tree_hash_root()


def test_randomized_mutation_interleavings():
    """Arbitrary op interleavings, root-compared after every round —
    including rounds where only ONE side took an extra root (cache
    cadences desynchronized on purpose)."""
    rng = np.random.default_rng(42)
    host, dev = _twins(60, seed=3)

    def op_balance_scatter(s):
        n = len(s.validators)
        idx = rng.integers(0, s.balances.shape[0], 5)
        s.balances[np.unique(idx)] = np.uint64(rng.integers(0, 1 << 40))

    def op_wcol(s):
        col = rng.choice(["effective_balance", "exit_epoch",
                          "withdrawable_epoch"])
        i = int(rng.integers(0, len(s.validators)))
        s.validators.wcol(col)[i] = np.uint64(rng.integers(0, 1 << 30))

    def op_slash(s):
        i = int(rng.integers(0, len(s.validators)))
        s.validators.wcol("slashed")[i] = True

    def op_set(s):
        i = int(rng.integers(0, len(s.validators)))
        s.validators.set(i, _rand_validator(np.random.default_rng(
            int(rng.integers(0, 1 << 30)))))

    def op_append(s):
        v = _rand_validator(np.random.default_rng(
            int(rng.integers(0, 1 << 30))))
        s.validators.append(v)
        s.balances = np.concatenate(
            [np.asarray(s.balances, dtype=np.uint64),
             np.array([32 * 10 ** 9], dtype=np.uint64)])

    def op_store_column_touched(s):
        n = s.balances.shape[0]
        bal = np.asarray(s.balances, dtype=np.uint64).copy()
        idx = np.unique(rng.integers(0, n, 7))
        bal[idx] = bal[idx] // np.uint64(2)
        store_column(s, "balances", bal, touched=idx)

    def op_store_column_full(s):
        n = s.inactivity_scores.shape[0]
        store_column(s, "inactivity_scores",
                     rng.integers(0, 50, n).astype(np.uint64))

    def op_participation(s):
        n = s.previous_epoch_participation.shape[0]
        i = int(rng.integers(0, n))
        s.previous_epoch_participation[i] |= np.uint8(1)

    ops = [op_balance_scatter, op_wcol, op_slash, op_set, op_append,
           op_store_column_touched, op_store_column_full, op_participation]

    for rnd in range(12):
        # rng state must advance identically for both twins: pre-draw the
        # op sequence, then re-seed a per-round generator for each twin.
        picks = rng.integers(0, len(ops), int(rng.integers(1, 6)))
        round_seed = int(rng.integers(0, 1 << 31))
        for s in (host, dev):
            rng = np.random.default_rng(round_seed)
            for p in picks:
                ops[p](s)
        if rnd % 3 == 1:
            dev.tree_hash_root()  # desync cache cadence on purpose
        if rnd % 4 == 2:
            host.tree_hash_root()
        rng = np.random.default_rng(round_seed ^ 0x5EED)
        assert dev.tree_hash_root() == host.tree_hash_root(), f"round {rnd}"
    assert type(dev).serialize(dev) == type(host).serialize(host)


def test_copy_on_write_isolation():
    host, dev = _twins(40, seed=11)
    r0 = dev.tree_hash_root()

    clone = dev.copy()
    assert clone.tree_hash_root() == r0

    # Mutating the clone must not leak into the parent...
    clone.balances[0] = np.uint64(1)
    clone.validators.wcol("effective_balance")[2] = np.uint64(3)
    r_clone = clone.tree_hash_root()
    assert r_clone != r0
    assert dev.tree_hash_root() == r0

    # ...nor the parent into the clone (either order of next mutation).
    dev.balances[7] = np.uint64(9)
    r_dev = dev.tree_hash_root()
    assert r_dev != r0
    assert clone.tree_hash_root() == r_clone

    # Chains of copies stay independent too.
    c2 = clone.copy()
    c2.inactivity_scores[1] = np.uint64(5)
    assert c2.tree_hash_root() != r_clone
    assert clone.tree_hash_root() == r_clone

    # And a host twin mutated identically agrees with every lineage.
    host.balances[7] = np.uint64(9)
    assert host.tree_hash_root() == r_dev


def test_adopted_device_column_roots_without_pull():
    """A jax-array store (the jitted epoch sweep's output) is ADOPTED:
    the device array becomes the column, the root re-reduces in HBM, and
    the host twin assigning the same values agrees."""
    import jax.numpy as jnp
    import jax

    host, dev = _twins(48, seed=5)
    host.tree_hash_root(), dev.tree_hash_root()

    n = host.balances.shape[0]
    new = np.random.default_rng(1).integers(
        0, 1 << 40, n).astype(np.uint64)
    with jax.enable_x64(True):
        dev_arr = jnp.asarray(new)
    store_column(dev, "balances", dev_arr)
    store_column(host, "balances", new.copy())
    assert isinstance(dev.__dict__["balances"], DeviceColumn)
    assert dev.tree_hash_root() == host.tree_hash_root()

    # Host mutation after an adopted era pulls once and stays exact.
    for s in (host, dev):
        s.balances[2] = np.uint64(123)
    assert dev.tree_hash_root() == host.tree_hash_root()


def test_adopted_then_host_write_before_any_root():
    """A tracked write landing after an adoption but BEFORE any root must
    not lose the adoption-era delta: the cache's baseline predates the
    adopted values, so only a full diff can recover them (regression —
    index tracking used to report just the new write's indices)."""
    import jax.numpy as jnp
    import jax

    host, dev = _twins(48, seed=5)
    host.tree_hash_root(), dev.tree_hash_root()
    n = host.balances.shape[0]
    new = np.random.default_rng(1).integers(0, 1 << 40, n).astype(np.uint64)
    with jax.enable_x64(True):
        dev_arr = jnp.asarray(new)
    store_column(dev, "balances", dev_arr)   # adopt; no root taken
    store_column(host, "balances", new.copy())
    for s in (host, dev):                     # scatter write, still no root
        s.balances[2] = np.uint64(123)
    assert dev.tree_hash_root() == host.tree_hash_root()

    # Same shape through the touched= seam of store_column.
    dev2_host, dev2 = _twins(48, seed=6)
    dev2_host.tree_hash_root(), dev2.tree_hash_root()
    with jax.enable_x64(True):
        arr2 = jnp.asarray(new)
    store_column(dev2, "balances", arr2)
    store_column(dev2_host, "balances", new.copy())
    bal = new.copy()
    bal[[1, 3]] = np.uint64(9)
    store_column(dev2, "balances", bal.copy(),
                 touched=np.array([1, 3]))
    store_column(dev2_host, "balances", bal.copy(),
                 touched=np.array([1, 3]))
    assert dev2.tree_hash_root() == dev2_host.tree_hash_root()


def test_warm_root_pushes_only_dirty_bytes():
    """The acceptance criterion in miniature: after materialization a
    clean root pushes ZERO bytes, and a k-record-dirty root pushes bytes
    proportional to k — never the full state."""
    _, dev = _twins(64, seed=9)
    dev.tree_hash_root()

    reset_residency_stats()
    dev.tree_hash_root()
    clean = residency_snapshot()
    assert clean["bytes_pushed"] == 0
    assert clean["rebuilds"] == 0 and clean["materializes"] == 0

    full_push = 64 * 121  # raw registry bytes, the re-stage this replaces
    dev.validators.wcol("effective_balance")[3] = np.uint64(1)
    reset_residency_stats()
    dev.tree_hash_root()
    dirty = residency_snapshot()
    assert 0 < dirty["bytes_pushed"] < full_push
    assert dirty["scatters"] >= 1


def test_registry_growth_across_pow2_boundary():
    host, dev = _twins(62, seed=13)
    host.tree_hash_root(), dev.tree_hash_root()
    rng = np.random.default_rng(17)
    for k in range(6):  # 62 → 68 crosses the 64-leaf width boundary
        v = _rand_validator(np.random.default_rng(k))
        for s in (host, dev):
            s.validators.append(v)
            s.balances = np.concatenate(
                [np.asarray(s.balances, dtype=np.uint64),
                 np.array([k], dtype=np.uint64)])
        if k % 2:
            assert dev.tree_hash_root() == host.tree_hash_root(), k
    assert dev.tree_hash_root() == host.tree_hash_root()


def test_env_knob_disables_device_residency(monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_DEVICE_STATE", "0")
    s = _mk_state(32)
    assert materialize_state(s) is False
    r = s.tree_hash_root()

    # Flipping the knob off mid-life on an ALREADY materialized state
    # falls back to the host path without corrupting the root.
    monkeypatch.delenv("LIGHTHOUSE_TPU_DEVICE_STATE")
    s2 = _mk_state(32)
    assert materialize_state(s2)
    s2.tree_hash_root()
    s2.balances[1] = np.uint64(4)
    monkeypatch.setenv("LIGHTHOUSE_TPU_DEVICE_STATE", "0")
    s.balances[1] = np.uint64(4)
    assert s2.tree_hash_root() == s.tree_hash_root()

    # And flipping BACK ON after host-path roots consumed the dirty marks
    # must not serve a stale device tree: registry writes made during the
    # off era re-materialize instead of being lost.
    for t in (s, s2):
        t.validators.wcol("effective_balance")[5] = np.uint64(77)
    s2.tree_hash_root()  # host path (knob off): consumes s2's marks
    monkeypatch.delenv("LIGHTHOUSE_TPU_DEVICE_STATE")
    assert s2.tree_hash_root() == s.tree_hash_root()


def test_knob_off_after_host_then_device_era(monkeypatch):
    """Host roots BEFORE materialization leave host tree levels behind;
    device-era registry writes bypass them, so flipping the knob off must
    rebuild the host tree instead of patching the stale one (regression)."""
    s = _mk_state(32, seed=4)
    oracle = _mk_state(32, seed=4)
    s.tree_hash_root()           # host cold: host levels populated
    assert materialize_state(s)
    s.tree_hash_root()           # device era begins
    for t in (s, oracle):
        t.validators.wcol("effective_balance")[5] = np.uint64(77)
        t.balances[3] = np.uint64(5)
    s.tree_hash_root()           # device scatter; host levels now stale
    monkeypatch.setenv("LIGHTHOUSE_TPU_DEVICE_STATE", "0")
    assert s.tree_hash_root() == oracle.tree_hash_root()


def test_untracked_write_paths_raise_or_track():
    _, dev = _twins(16, seed=21)
    dev.tree_hash_root()
    col = dev.balances
    assert isinstance(col, DeviceColumn)
    # Basic-slice reads are read-only views: a bypass write raises
    # instead of silently desynchronizing the device tree.
    view = col[2:5]
    with pytest.raises(ValueError):
        view[0] = 1
    # ...while tracked writes through the column handle keep working.
    col[2:5] = np.uint64(8)
    host = _mk_state(16, seed=21)
    host.balances[2:5] = np.uint64(8)
    assert dev.tree_hash_root() == host.tree_hash_root()


def test_epoch_processing_differential_on_materialized_state():
    """The per-epoch store_column seams (single-pass sweep) land on a
    device-resident state bit-identically to the host path."""
    from lighthouse_tpu.state_transition import per_epoch as PE
    from lighthouse_tpu.testing.random_states import random_epoch_state
    from lighthouse_tpu.types.chain_spec import ChainSpec

    T = spec_types(MINIMAL)
    spec = ChainSpec()
    for seed in (1, 2, 3):
        rng = np.random.default_rng(seed)
        host = random_epoch_state(rng, 48, T, MINIMAL, ForkName.CAPELLA)
        rng = np.random.default_rng(seed)
        dev = random_epoch_state(rng, 48, T, MINIMAL, ForkName.CAPELLA)
        assert materialize_state(dev)
        dev.tree_hash_root()
        PE.process_epoch(host, ForkName.CAPELLA, MINIMAL, spec, T)
        PE.process_epoch(dev, ForkName.CAPELLA, MINIMAL, spec, T)
        assert type(dev).serialize(dev) == type(host).serialize(host), seed
        assert dev.tree_hash_root() == host.tree_hash_root(), seed


def test_block_chain_differential_on_materialized_state():
    """A harness chain applied on a device-resident lineage (fork-choice
    style copies every block) matches the host chain byte-for-byte —
    the batched-attestation and sync-aggregate scatter seams included."""
    from lighthouse_tpu.crypto import bls as B
    from lighthouse_tpu.testing import StateHarness

    B.set_backend("fake")
    try:
        h_host = StateHarness(n_validators=64, preset=MINIMAL)
        h_dev = StateHarness(n_validators=64, preset=MINIMAL)
        assert materialize_state(h_dev.state)
        h_dev.state.tree_hash_root()
        for h in (h_host, h_dev):
            h.extend_chain(8)
            h.make_deposit(70)
            h.extend_chain(2)
        assert type(h_dev.state).serialize(h_dev.state) == \
            type(h_host.state).serialize(h_host.state)
        assert h_dev.state.tree_hash_root() == h_host.state.tree_hash_root()
    finally:
        B.set_backend("python")
