"""Differential driver: device-resident BeaconState vs the host oracle.

Builds twin states of ``--validators N``, materializes one
(:func:`~lighthouse_tpu.types.device_state.materialize_state` — HBM
becomes the source of truth), applies ``--mutations M`` randomized rounds
of scatter mutations / appends / copies to BOTH, and asserts the
device-resident ``hash_tree_root`` is byte-identical to the host
incremental root after every round — printing per-round warm-root
timings and the bytes-pushed-per-root residency accounting.  Exit 1 on
the first mismatch (the ``validate_transition.py`` idiom, one layer
down).

``--warmup`` pre-compiles the dirty-propagation programs (leaf scatter →
level propagation, the registry-mirror scatter, and the full-level
rebuild bodies) at the widths the chosen ``--validators`` implies, so a
fresh node's — or the test suite's — first warm root is a persistent
compile-cache hit instead of a cold XLA build.

Compile-cache note (mirrors ``tests/conftest.py``): cache entries do NOT
transfer between processes with different XLA flags.  To warm the same
``.jax_cache`` the test suite reads, run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/validate_state_residency.py --warmup ...

(this script enables the one compile-cache directory itself, like
conftest: ``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache``).  With ``--device`` the attached
backend is kept instead (real-TPU residency, Pallas hash kernels).

Usage:
    python scripts/validate_state_residency.py --validators 256 --mutations 32
    python scripts/validate_state_residency.py --validators 4096 --warmup
    python scripts/validate_state_residency.py --device --validators 65536
"""

import sys; sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))  # noqa: E402

import argparse
import os
import time

import numpy as np


def _configure_jax(device: bool) -> None:
    import jax
    if not device:
        jax.config.update("jax_platforms", "cpu")
    from lighthouse_tpu.common.compile_cache import enable
    enable()


def _mk_state(n: int, seed: int):
    from lighthouse_tpu.types.chain_spec import ForkName
    from lighthouse_tpu.types.factory import spec_types
    from lighthouse_tpu.types.presets import MAINNET
    from lighthouse_tpu.types.validators import ValidatorRegistry

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
        slashed=rng.random(n) < 0.05)
    state.validators = reg
    state.balances = rng.integers(0, 40 * 10 ** 9, n).astype(np.uint64)
    state.previous_epoch_participation = rng.integers(0, 8, n).astype(
        np.uint8)
    state.current_epoch_participation = rng.integers(0, 8, n).astype(np.uint8)
    state.inactivity_scores = rng.integers(0, 100, n).astype(np.uint64)
    return state


def _mutate_round(rng: np.random.Generator, state, k: int) -> None:
    """One randomized mutation round: k scatter writes across the hot
    columns, plus occasional set/append (grow) and row rewrites."""
    from lighthouse_tpu.types.device_state import store_column
    from lighthouse_tpu.types.validators import Validator

    n = state.balances.shape[0]
    idx = np.unique(rng.integers(0, n, max(k, 1)))
    state.balances[idx] = rng.integers(0, 1 << 40, idx.size).astype(
        np.uint64)
    reg = state.validators
    ridx = np.unique(rng.integers(0, len(reg), max(k // 2, 1)))
    state.validators.wcol("effective_balance")[ridx] = (
        rng.integers(0, 33, ridx.size) * 10 ** 9).astype(np.uint64)
    i = int(rng.integers(0, n))
    state.inactivity_scores[i] = np.uint64(rng.integers(0, 1000))
    state.current_epoch_participation[i] |= np.uint8(2)
    if rng.random() < 0.3:  # exact-touched store (the transition-pass seam)
        bal = np.asarray(state.balances, dtype=np.uint64).copy()
        t = np.unique(rng.integers(0, n, 3))
        bal[t] = bal[t] // np.uint64(2)
        store_column(state, "balances", bal, touched=t)
    if rng.random() < 0.2:  # append + grow
        vseed = int(rng.integers(0, 1 << 30))
        vr = np.random.default_rng(vseed)
        reg.append(Validator(
            pubkey=vr.integers(0, 256, 48, dtype=np.uint8).tobytes(),
            withdrawal_credentials=vr.integers(
                0, 256, 32, dtype=np.uint8).tobytes(),
            effective_balance=32 * 10 ** 9, slashed=False,
            activation_eligibility_epoch=0, activation_epoch=0,
            exit_epoch=2 ** 64 - 1, withdrawable_epoch=2 ** 64 - 1))
        state.balances = np.concatenate(
            [np.asarray(state.balances, dtype=np.uint64),
             np.array([32 * 10 ** 9], dtype=np.uint64)])


# The residency subsystems (device-ledger attribution; ISSUE 15 —
# residency is read through the LEDGER snapshot, not
# ops.device_tree.residency_snapshot()).  One shared definition with
# the legacy view.


def _subs():
    from lighthouse_tpu.ops.device_tree import (
        LEGACY_RESIDENCY_SUBSYSTEMS)
    return LEGACY_RESIDENCY_SUBSYSTEMS


def _ledger_snapshot():
    from lighthouse_tpu.common.device_ledger import LEDGER
    return LEDGER.snapshot()["subsystems"]


def _pushed(snap) -> int:
    return sum(snap[s]["h2d_bytes"] for s in _subs())


def validate(n: int, mutations: int, seed: int, copy_every: int) -> int:
    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.types.device_state import materialize_state

    host = _mk_state(n, seed)
    dev = _mk_state(n, seed)
    base = _ledger_snapshot()
    t0 = time.perf_counter()
    if not materialize_state(dev):
        print("materialize_state declined (LIGHTHOUSE_TPU_DEVICE_STATE=0?)")
        return 1
    mat = tracing.stage_split("materialize")
    print(f"materialize: {mat.get('materialize_ms')} ms, "
          f"{mat.get('bytes_pushed')} bytes pushed "
          f"(one-time)", flush=True)
    host.tree_hash_root()

    failures = 0
    for m in range(mutations):
        round_seed = seed * 100003 + m
        k = int(np.random.default_rng(round_seed).integers(1, 64))
        for s in (host, dev):
            _mutate_round(np.random.default_rng(round_seed), s, k)
        if copy_every and m % copy_every == copy_every - 1:
            # COW: continue on clones; the originals must keep their root.
            r_host, r_dev = host.tree_hash_root(), dev.tree_hash_root()
            host2, dev2 = host.copy(), dev.copy()
            _mutate_round(np.random.default_rng(round_seed ^ 1), host2, 4)
            _mutate_round(np.random.default_rng(round_seed ^ 1), dev2, 4)
            if (host.tree_hash_root(), dev.tree_hash_root()) != \
                    (r_host, r_dev):
                print(f"round {m}: COW LEAK into parent")
                failures += 1
            host, dev = host2, dev2
        before = _pushed(_ledger_snapshot())
        t0 = time.perf_counter()
        r_dev = dev.tree_hash_root()
        dev_ms = (time.perf_counter() - t0) * 1e3
        pushed = _pushed(_ledger_snapshot()) - before
        t0 = time.perf_counter()
        r_host = host.tree_hash_root()
        host_ms = (time.perf_counter() - t0) * 1e3
        status = "OK" if r_dev == r_host else "MISMATCH"
        print(f"round {m}: {status}  device {dev_ms:.1f} ms "
              f"({pushed} B pushed) vs host {host_ms:.1f} ms", flush=True)
        if r_dev != r_host:
            failures += 1
            break
    snap = _ledger_snapshot()

    def tot(key: str) -> int:
        return sum(snap[s][key] - base[s][key] for s in _subs())

    print(f"totals: {tot('h2d_bytes')} B pushed, "
          f"{tot('d2h_bytes')} B pulled, {tot('scatters')} scatters, "
          f"{tot('rebuilds')} rebuilds, "
          f"{tot('materializes')} materializes")
    print("per-subsystem ledger:")
    for s in _subs():
        row = snap[s]
        print(f"  {s:16s} h2d={row['h2d_bytes'] - base[s]['h2d_bytes']} B "
              f"d2h={row['d2h_bytes'] - base[s]['d2h_bytes']} B "
              f"resident={row['resident_bytes']} B "
              f"high_water={row['hbm_high_water_bytes']} B")
    return failures


def warmup(n: int) -> None:
    """Pre-compile the dirty-propagation / rebuild programs for an
    ``n``-validator state into the persistent compile cache: the generic
    leaf-scatter tree program at the packed-column widths, and the
    registry mirror's fused scatter + rebuild at the registry width —
    driven through a real materialized state so the traced shapes match
    what ``hash_tree_root`` dispatches (a shape warmed any other way can
    still cold-compile under a differently-configured process; see the
    compile-cache note in the module docstring)."""
    from lighthouse_tpu.ops.device_tree import warmup_scatter
    from lighthouse_tpu.ops.merkle import _next_pow2
    from lighthouse_tpu.types.device_state import materialize_state

    t0 = time.perf_counter()
    w = _next_pow2(max(n, 1))
    programs = warmup_scatter(max(w // 4, 8))  # u64-packed column width
    state = _mk_state(n, seed=0)
    materialize_state(state)
    state.tree_hash_root()
    for k in (1, 8, 64):
        idx = np.arange(min(k, n), dtype=np.int64)
        state.validators.wcol("effective_balance")[idx] = np.uint64(7 + k)
        state.balances[idx] = np.uint64(9 + k)
        state.tree_hash_root()
        programs += 2
    print(f"warmup: ~{programs} programs driven in "
          f"{time.perf_counter() - t0:.1f} s (persistent cache: .jax_cache)")


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--validators", type=int, default=256)
    ap.add_argument("--mutations", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--copy-every", type=int, default=5,
                    help="interleave a copy-on-write fork every K rounds "
                         "(0 disables)")
    ap.add_argument("--device", action="store_true",
                    help="keep the attached backend (real-TPU residency) "
                         "instead of pinning jax to CPU")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile the dirty-propagation programs for "
                         "this width into .jax_cache, then exit")
    args = ap.parse_args()
    _configure_jax(args.device)
    if args.warmup:
        warmup(args.validators)
        return
    failures = validate(args.validators, args.mutations, args.seed,
                        args.copy_every)
    print("RESULT:", "PASS" if failures == 0 else f"{failures} FAILURES")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
