#!/usr/bin/env python3
"""The per-slot hot path, once, on the chip.

``python chip_smoke.py`` drives the package's main path through its
normal entry points at mainnet size in ONE process on a TPU, checks every
result against an oracle, and prints one JSON line per phase.

Phases (one chip — the default):

a. ``device``      JAX must see a TPU.  Anything else exits 1 and prints
                   no result: there is no CPU run and no retry.
b. ``state_root``  a MAINNET Capella ``BeaconState`` with 2^20 validators
                   made from ``--seed``, materialized device-resident
                   (``types.device_state.materialize_state``); the full
                   root, then 3 rounds that each change 1 % of balances
                   and of participation and take a warm root.  Every root
                   must equal a hashlib SSZ fold of the same values kept
                   here, and the state subsystems' device dispatches must
                   advance.
c. ``bls``         first the node's warmup (``common.compile_cache.warmup``)
                   of the buckets c and d dispatch, then the ``tpu``
                   backend with every verify on the device
                   (``LIGHTHOUSE_TPU_HOST_FASTPATH_MAX=0``): 1024 sets x
                   16 keys over 64 messages, valid and with one key swap,
                   then one ``fast_aggregate_verify``-shaped batch (512
                   shared keys, 256 messages) valid and with one
                   signature swap.  Verdicts must equal the host
                   backend's.
d. ``block_import`` real-signed ``testing.harness.StateHarness`` blocks
                   with attestations and a sync aggregate through
                   ``BeaconChain.process_block`` on the ``tpu`` backend;
                   each head's state root must equal the block's claimed
                   root, and a block whose signature was swapped must be
                   refused.
e. ``fork_choice_pack`` one vote round through the fork-choice engine and
                   one attestation pack through the greedy-pack engine
                   that ``auto`` picks on a TPU (``jit``); head, weights
                   and pack must equal the host and numpy engines.

After c-e the ``bls`` device-ledger dispatch count must have risen, no
verify may have been served by the host fallback, and no breaker may
have tripped.

``--chips 4`` runs only the mesh path, on a 4-device mesh:

``bls_sharded``  ``parallel.bls_shard.sharded_verify_signature_sets`` at
                 1024 x 16 on the 4-device mesh, valid and tampered, against
                 the host backend's verdicts — the verdicts the one-chip
                 run shows the 1-device pipeline returns (a 1-device
                 compile of the sharded program would only add 4-chip
                 time);
``mesh_slot``    ``parallel.mesh_slot.run_slot_model`` on 4 devices vs 1:
                 bit-identical digests, transfer budget ok, and per-shard
                 ledger rows on every device.

Each phase line carries ``wall_s`` and the host seconds JAX spent in that
phase tracing (``trace_s``), lowering (``lower_s``) and compiling
(``compile_s``) programs, named with the device they ran on.

Compile cache: ``JAX_COMPILATION_CACHE_DIR`` when it is set, else the
fixed ``<repo>/.jax_cache`` (``lighthouse_tpu.common.compile_cache``).  A
warm cache skips backend compiles only; every process traces and lowers.

The LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
and is printed only when every phase passed; any mismatch or exception
exits non-zero.  One process holds the chip: nothing here starts a child
process.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import threading
import time

import numpy as np

N_VALIDATORS_LOG2 = 20
STATE_ROUNDS = 3
CHANGE_FRACTION = 100          # 1 % of the registry per round
N_SETS, KEYS_PER_SET, N_MSGS = 1024, 16, 64
SYNC_KEYS, SYNC_MSGS = 512, 256
BLOCKS = 3
# The (sets, keys) buckets phases c and d dispatch — the flagship's
# 16-key sets, single-key sets (shared-key collapse, proposer, randao),
# the MINIMAL harness's 4-key committees and its 32-key sync aggregate —
# compiled up front through the node's warmup API, as a node does at
# boot: a cold compile inside the resilience envelope's device deadline
# would be served by the host fallback instead.
WARM_BUCKETS = ((N_SETS, KEYS_PER_SET), (SYNC_MSGS, 1), (8, 4), (1, 32))


def _parse(argv):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated state, key and pool "
                         "(default 0)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-e on one chip (default); 4: only "
                         "the sharded BLS verify and the mesh slot on a "
                         "4-device mesh")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# Phase accounting: wall time + JAX's own trace / lower / compile seconds
# ---------------------------------------------------------------------------

_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}


class CompileClock:
    """Collects JAX's trace/lower/compile durations as time intervals.
    Nested programs (a jit inside a jit) report overlapping intervals, so
    a phase's figure is the length of their UNION, not their sum."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans = {k: [] for k in _EVENTS.values()}

    def listen(self, event: str, duration: float, **_kw) -> None:
        key = _EVENTS.get(event)
        if key is not None:
            end = time.monotonic()
            with self._lock:
                self._spans[key].append((end - duration, end))

    def since(self, t0: float) -> dict:
        out = {}
        with self._lock:
            for key, spans in self._spans.items():
                total, reach = 0.0, t0
                for a, b in sorted(spans):
                    a = max(a, reach)
                    if b > a:
                        total += b - a
                        reach = b
                out[key] = round(total, 3)
        return out


class Phase:
    """``with Phase(...) as p:`` — times one phase, collects its fields
    into ``p.out`` and prints its JSON line; an exception propagates
    after the line records it."""

    def __init__(self, name: str, clock: CompileClock, device: dict):
        self.name, self.clock, self.device = name, clock, device
        self.out: dict = {}

    def __enter__(self):
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        row = {"phase": self.name, "device": self.device,
               "wall_s": round(time.monotonic() - self.t0, 3),
               **self.clock.since(self.t0), **self.out}
        if exc is not None:
            row["ok"] = False
            row["error"] = f"{exc_type.__name__}: {exc}"
        print(json.dumps(row, default=str), flush=True)
        return False


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# b. state root: a hashlib SSZ fold of the state's values
# ---------------------------------------------------------------------------

ZERO_HASHES = [b"\x00" * 32]
for _ in range(64):
    ZERO_HASHES.append(hashlib.sha256(ZERO_HASHES[-1] * 2).digest())


def hash_rows(rows: np.ndarray) -> np.ndarray:
    """sha256 of every 64-byte row of ``rows`` → (m, 32) uint8."""
    b = np.ascontiguousarray(rows, np.uint8).tobytes()
    sha = hashlib.sha256
    out = b"".join(sha(b[i:i + 64]).digest() for i in range(0, len(b), 64))
    return np.frombuffer(out, np.uint8).reshape(-1, 32)


def merkle_root(chunks: np.ndarray, depth: int) -> bytes:
    """SSZ merkleization of (k, 32) chunks into a depth-``depth`` tree."""
    level = np.ascontiguousarray(chunks, np.uint8).reshape(-1, 32)
    if level.shape[0] == 0:
        return ZERO_HASHES[depth]
    for d in range(depth):
        if level.shape[0] == 1 and d > 0:
            root = level[0].tobytes()
            for e in range(d, depth):
                root = hashlib.sha256(root + ZERO_HASHES[e]).digest()
            return root
        if level.shape[0] % 2:
            level = np.concatenate(
                [level, np.frombuffer(ZERO_HASHES[d], np.uint8)[None]])
        level = hash_rows(level.reshape(-1, 64))
    return level[0].tobytes()


def mix_in_length(root: bytes, n: int) -> bytes:
    return hashlib.sha256(root + n.to_bytes(32, "little")).digest()


def pack_bytes(raw: bytes) -> np.ndarray:
    pad = (-len(raw)) % 32
    return np.frombuffer(raw + b"\x00" * pad, np.uint8).reshape(-1, 32)


def u64_chunks(a: np.ndarray) -> np.ndarray:
    return pack_bytes(np.asarray(a, "<u8").tobytes())


def leaf32(a: np.ndarray, dtype: str) -> np.ndarray:
    """Per-row little-endian scalar padded to a 32-byte leaf."""
    raw = np.asarray(a).astype(dtype).view(np.uint8).reshape(len(a), -1)
    out = np.zeros((len(a), 32), np.uint8)
    out[:, :raw.shape[1]] = raw
    return out


def registry_root(cols: dict, limit_depth: int = 40) -> bytes:
    """hash_tree_root(List[Validator, 2^40]) of the registry columns."""
    n = len(cols["effective_balance"])
    pk = np.zeros((n, 64), np.uint8)
    pk[:, :48] = cols["pubkey"]
    pk_root = hash_rows(pk)
    l1 = np.empty((n, 4, 64), np.uint8)
    l1[:, 0, :32], l1[:, 0, 32:] = pk_root, cols["withdrawal_credentials"]
    l1[:, 1, :32] = leaf32(cols["effective_balance"], "<u8")
    l1[:, 1, 32:] = leaf32(cols["slashed"], "u1")
    epochs = ("activation_eligibility_epoch", "activation_epoch",
              "exit_epoch", "withdrawable_epoch")
    for j, f in enumerate(epochs):
        l1[:, 2 + j // 2, 32 * (j % 2):32 * (j % 2) + 32] = \
            leaf32(cols[f], "<u8")
    l2 = hash_rows(l1.reshape(-1, 64)).reshape(n, 2, 64)
    roots = hash_rows(hash_rows(l2.reshape(-1, 64)).reshape(n, 64))
    return mix_in_length(merkle_root(roots, limit_depth), n)


class StateOracle:
    """The state root from values kept HERE: hashlib folds for the
    registry-sized lists and the big vectors; the package's host SSZ for
    the small containers only."""

    BIG = ("validators", "balances", "previous_epoch_participation",
           "current_epoch_participation", "inactivity_scores",
           "randao_mixes", "block_roots", "state_roots", "slashings")

    def __init__(self, state, cols, balances, prev_part, cur_part,
                 inactivity, preset):
        self.n = len(balances)
        self.preset = preset
        fields = type(state).FIELDS
        self.order = list(fields)
        self.small = {f: t.hash_tree_root(getattr(state, f))
                      for f, t in fields.items() if f not in self.BIG}
        self.registry = registry_root(cols)
        self.balances, self.prev, self.cur = balances, prev_part, cur_part
        self.inactivity = inactivity

    def root(self) -> bytes:
        n, p = self.n, self.preset
        # List[uint64, 2^40] → 2^38 chunks; List[uint8, 2^40] → 2^35.
        roots = dict(self.small)
        roots["validators"] = self.registry
        roots["balances"] = mix_in_length(
            merkle_root(u64_chunks(self.balances), 38), n)
        roots["inactivity_scores"] = mix_in_length(
            merkle_root(u64_chunks(self.inactivity), 38), n)
        roots["previous_epoch_participation"] = mix_in_length(
            merkle_root(pack_bytes(self.prev.tobytes()), 35), n)
        roots["current_epoch_participation"] = mix_in_length(
            merkle_root(pack_bytes(self.cur.tobytes()), 35), n)
        hist = (p.SLOTS_PER_HISTORICAL_ROOT - 1).bit_length()
        zero_roots = np.zeros((p.SLOTS_PER_HISTORICAL_ROOT, 32), np.uint8)
        roots["block_roots"] = roots["state_roots"] = \
            merkle_root(zero_roots, hist)
        roots["randao_mixes"] = merkle_root(
            np.zeros((p.EPOCHS_PER_HISTORICAL_VECTOR, 32), np.uint8),
            (p.EPOCHS_PER_HISTORICAL_VECTOR - 1).bit_length())
        roots["slashings"] = merkle_root(
            u64_chunks(np.zeros(p.EPOCHS_PER_SLASHINGS_VECTOR, np.uint64)),
            (p.EPOCHS_PER_SLASHINGS_VECTOR // 4 - 1).bit_length())
        leaves = np.frombuffer(b"".join(roots[f] for f in self.order),
                               np.uint8).reshape(-1, 32)
        return merkle_root(leaves, (len(self.order) - 1).bit_length())


def make_state(n: int, rng):
    """A MAINNET Capella state over ``n`` seeded validators (the
    bench.py construction) plus the host copies the oracle hashes."""
    from lighthouse_tpu.types.chain_spec import ForkName
    from lighthouse_tpu.types.factory import spec_types
    from lighthouse_tpu.types.presets import MAINNET
    from lighthouse_tpu.types.validators import ValidatorRegistry

    T = spec_types(MAINNET)
    state = T.state_cls(ForkName.CAPELLA)()
    cols = {
        "pubkey": rng.integers(0, 256, (n, 48), dtype=np.uint8),
        "withdrawal_credentials": rng.integers(0, 256, (n, 32),
                                               dtype=np.uint8),
        "effective_balance": np.full(n, 32 * 10**9, np.uint64),
        "slashed": np.zeros(n, bool),
        "activation_eligibility_epoch": np.zeros(n, np.uint64),
        "activation_epoch": np.zeros(n, np.uint64),
        "exit_epoch": np.full(n, 2**64 - 1, np.uint64),
        "withdrawable_epoch": np.full(n, 2**64 - 1, np.uint64),
    }
    reg = ValidatorRegistry(n)
    reg._n = n
    reg.init_columns(**cols)
    state.validators = reg
    balances = (32 * 10**9 + rng.integers(0, 10**9, n)).astype(np.uint64)
    prev = rng.integers(0, 8, n).astype(np.uint8)
    cur = rng.integers(0, 8, n).astype(np.uint8)
    inactivity = np.zeros(n, np.uint64)
    state.balances = balances.copy()
    state.previous_epoch_participation = prev.copy()
    state.current_epoch_participation = cur.copy()
    state.inactivity_scores = inactivity.copy()
    return state, cols, balances, prev, cur, inactivity, MAINNET


STATE_SUBSYSTEMS = ("device_tree", "registry_mirror", "packed_cache")
STATE_OPS = ("dispatches", "materializes", "rebuilds", "scatters")


def state_dispatches() -> int:
    """Device programs the state subsystems ran, as the ledger counts
    them (the residency protocol records its materialize / rebuild /
    scatter programs as ops of their own)."""
    from lighthouse_tpu.common.device_ledger import LEDGER
    subs = LEDGER.snapshot()["subsystems"]
    return sum(int(subs[s][k]) for s in STATE_SUBSYSTEMS for k in STATE_OPS)


def phase_state_root(p: Phase, seed: int,
                     log2_n: int = N_VALIDATORS_LOG2,
                     rounds: int = STATE_ROUNDS) -> None:
    from lighthouse_tpu.types.device_state import materialize_state

    rng = np.random.default_rng(seed)
    n = 1 << log2_n
    t0 = time.monotonic()
    state, cols, bal, prev, cur, inact, preset = make_state(n, rng)
    oracle = StateOracle(state, cols, bal, prev, cur, inact, preset)
    p.out.update(validators=n, build_s=round(time.monotonic() - t0, 3))

    d0 = state_dispatches()
    t0 = time.monotonic()
    check(materialize_state(state), "materialize_state refused")
    root = state.tree_hash_root()
    p.out["materialize_root_s"] = round(time.monotonic() - t0, 3)
    t0 = time.monotonic()
    want = oracle.root()
    p.out["oracle_s"] = round(time.monotonic() - t0, 3)
    check(root == want, f"cold root {root.hex()} != oracle {want.hex()}")
    d1 = state_dispatches()
    check(d1 > d0, "no state-subsystem dispatch for the cold root")

    warm = []
    k = max(n // CHANGE_FRACTION, 1)
    for r in range(rounds):
        idx = rng.choice(n, k, replace=False)
        bal[idx] = bal[idx] + rng.integers(1, 10**6, k).astype(np.uint64)
        state.balances[idx] = bal[idx]
        pidx = rng.choice(n, k, replace=False)
        cur[pidx] = rng.integers(0, 8, k).astype(np.uint8)
        state.current_epoch_participation[pidx] = cur[pidx]
        before = state_dispatches()
        t0 = time.monotonic()
        root = state.tree_hash_root()
        warm.append(round(time.monotonic() - t0, 4))
        want = oracle.root()
        check(root == want, f"warm root {r} {root.hex()} != oracle")
        check(state_dispatches() > before,
              f"warm root {r} dispatched nothing on the device")
    p.out.update(warm_root_s=warm, changed_per_round=k,
                 state_dispatches=state_dispatches() - d0,
                 oracle="hashlib SSZ fold == device root, cold + "
                        f"{rounds} warm", ok=True)


# ---------------------------------------------------------------------------
# c. batched BLS verify
# ---------------------------------------------------------------------------

def flagship_sets(n_sets: int = N_SETS, kps: int = KEYS_PER_SET,
                  n_msgs: int = N_MSGS, seed: int = 0):
    """``n_sets`` aggregate sets of ``kps`` distinct keys over ``n_msgs``
    messages (the bench.py flagship shape), keys from ``seed``."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.fields import R

    rng = np.random.default_rng(seed)
    base = int(rng.integers(1 << 20, 1 << 40))
    sk = [base + 7 * i for i in range(n_sets * kps)]
    pks = [bls.SecretKey(v).public_key() for v in sk]
    msgs = [b"att-data-%03d" % i for i in range(n_msgs)]
    sets = []
    for i in range(n_sets):
        m = msgs[i % n_msgs]
        agg = bls.SecretKey(sum(sk[i * kps:(i + 1) * kps]) % R).sign(m)
        sets.append(bls.SignatureSet(agg, pks[i * kps:(i + 1) * kps], m))
    return sets, sk, pks


def key_swapped(sets, i: int, j: int):
    """Set i keeps its signature but claims set j's keys."""
    from lighthouse_tpu.crypto import bls
    bad = list(sets)
    bad[i] = bls.SignatureSet(sets[i].signature, sets[j].signing_keys,
                              sets[i].message)
    return bad


def sig_swapped(sets, i: int, j: int):
    """Set i carries set j's (valid, other-message) signature."""
    from lighthouse_tpu.crypto import bls
    bad = list(sets)
    bad[i] = bls.SignatureSet(sets[j].signature, sets[i].signing_keys,
                              sets[i].message)
    return bad


def bls_dispatches() -> int:
    from lighthouse_tpu.common.device_ledger import LEDGER
    return int(LEDGER.snapshot()["subsystems"]["bls"]["dispatches"])


def timed(fn, *a):
    t0 = time.monotonic()
    out = fn(*a)
    return out, round(time.monotonic() - t0, 3)


def phase_bls(p: Phase, seed: int, n_sets: int = N_SETS,
              kps: int = KEYS_PER_SET, sync_keys: int = SYNC_KEYS,
              sync_msgs: int = SYNC_MSGS) -> None:
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.fields import R

    from lighthouse_tpu.common.compile_cache import warmup

    bls.set_backend("tpu")
    tpu, host = bls.get_backend(), bls._BACKENDS["python"]
    t0 = time.monotonic()
    warm = warmup(WARM_BUCKETS)
    p.out["warmup"] = {"s": round(time.monotonic() - t0, 3),
                       "compiled": warm.get("compiled")}
    t0 = time.monotonic()
    sets, sk, pks = flagship_sets(n_sets, kps, seed=seed)
    bad = key_swapped(sets, 17 % n_sets, 18 % n_sets)
    p.out["setup_s"] = round(time.monotonic() - t0, 3)
    d0 = bls_dispatches()
    verdicts = {}
    for name, batch in (("valid", sets), ("key_swap", bad)):
        dev, cold_s = timed(tpu.verify_signature_sets, batch)
        again, warm_s = timed(tpu.verify_signature_sets, batch)
        ref, host_s = timed(host.verify_signature_sets, batch)
        check(dev == again == ref == (name == "valid"),
              f"{name}: device {dev}/{again} host {ref}")
        verdicts[name] = {"device": dev, "host": ref, "first_s": cold_s,
                          "warm_s": warm_s, "host_s": host_s}
    p.out["flagship"] = {"sets": n_sets, "keys_per_set": kps,
                         "messages": N_MSGS, **verdicts}

    keys = pks[:sync_keys]
    agg_sk = bls.SecretKey(sum(sk[:sync_keys]) % R)
    msgs = [b"sync-comm-%03d" % i for i in range(sync_msgs)]
    fsets = [bls.SignatureSet(agg_sk.sign(m), list(keys), m) for m in msgs]
    fbad = sig_swapped(fsets, 3, 4)
    shared = {}
    for name, batch in (("valid", fsets), ("sig_swap", fbad)):
        dev, cold_s = timed(tpu.verify_signature_sets, batch)
        again, warm_s = timed(tpu.verify_signature_sets, batch)
        ref, host_s = timed(host.verify_signature_sets, batch)
        check(dev == again == ref == (name == "valid"),
              f"shared {name}: device {dev}/{again} host {ref}")
        shared[name] = {"device": dev, "host": ref, "first_s": cold_s,
                        "warm_s": warm_s, "host_s": host_s}
    p.out["fast_aggregate"] = {"keys": sync_keys, "messages": sync_msgs,
                               **shared}
    d1 = bls_dispatches()
    check(d1 > d0, "no bls device dispatch")
    p.out.update(bls_dispatches=d1 - d0,
                 oracle="verdicts == python/native host backend", ok=True)


# ---------------------------------------------------------------------------
# d. block import through BeaconChain
# ---------------------------------------------------------------------------

def phase_block_import(p: Phase, seed: int, n_validators: int = 64,
                       blocks: int = BLOCKS) -> None:
    from lighthouse_tpu.beacon_chain import BeaconChain
    from lighthouse_tpu.crypto import bls as B
    from lighthouse_tpu.state_transition.per_block import SignatureStrategy
    from lighthouse_tpu.store import HotColdDB
    from lighthouse_tpu.testing.harness import StateHarness
    from lighthouse_tpu.types.presets import MINIMAL

    B.set_backend("python")            # the harness signs for real
    t0 = time.monotonic()
    h = StateHarness(n_validators=n_validators, preset=MINIMAL)
    hdr = h.state.latest_block_header.copy()
    hdr.state_root = h.state.tree_hash_root()
    db = HotColdDB.memory(h.preset, h.spec, h.T)
    chain = BeaconChain(store=db, genesis_state=h.state.copy(),
                        genesis_block_root=hdr.tree_hash_root(),
                        preset=h.preset, spec=h.spec, T=h.T)
    built = []
    for _ in range(blocks + 1):
        sb = h.build_block()
        h.apply_block(sb, strategy=SignatureStrategy.NO_VERIFICATION)
        built.append(sb)
    body = built[0].message.body
    check(len(body.attestations) > 0 or blocks > 1,
          "harness built no attestations")
    check(any(len(b.message.body.attestations) > 0 for b in built),
          "no block carries attestations")
    check(all(any(b.message.body.sync_aggregate.sync_committee_bits)
              for b in built), "a block lacks a sync aggregate")
    p.out["build_s"] = round(time.monotonic() - t0, 3)

    B.set_backend("tpu")
    d0 = bls_dispatches()
    imports = []
    for sb in built[:blocks]:
        slot = int(sb.message.slot)
        chain.per_slot_task(slot)
        root, s = timed(chain.process_block, sb)
        head_state_root = chain.head.state.tree_hash_root()
        check(chain.head.root == root, f"slot {slot}: head is not the block")
        check(head_state_root == bytes(sb.message.state_root),
              f"slot {slot}: head state root != block's claimed root")
        imports.append({"slot": slot, "import_s": s,
                        "attestations": len(sb.message.body.attestations)})
    bad = built[blocks].copy()
    bad.signature = built[0].signature     # valid point, other message
    chain.per_slot_task(int(bad.message.slot))
    try:
        chain.process_block(bad)
        refused = None
    except Exception as e:  # the chain's typed rejection
        refused = type(e).__name__
    check(refused is not None, "tampered block imported")
    check(bls_dispatches() > d0, "block import dispatched no bls batch")
    p.out.update(imports=imports, tampered_refused=refused,
                 bls_dispatches=bls_dispatches() - d0,
                 oracle="head state root == claimed root; tampered "
                        "refused", ok=True)


# ---------------------------------------------------------------------------
# e. fork choice + op-pool pack on their TPU engines
# ---------------------------------------------------------------------------

def _root(i: int) -> bytes:
    return int(i).to_bytes(4, "little") + b"\xab" * 28


def fork_choice_round(rng, n_nodes: int, n_validators: int) -> dict:
    from lighthouse_tpu.fork_choice import (
        DeviceProtoArrayForkChoice, EXEC_OPTIMISTIC, ProtoArrayForkChoice)
    from lighthouse_tpu.fork_choice.proto_array import ZERO_ROOT

    parents = [int(rng.integers(0, i)) for i in range(1, n_nodes)]
    votes = rng.integers(0, n_nodes, n_validators)
    balances = rng.choice(np.array([16, 31, 32], np.uint64) * 10**9,
                          n_validators).astype(np.uint64)
    engines = {"host": ProtoArrayForkChoice(),
               "numpy": DeviceProtoArrayForkChoice(engine="numpy"),
               "auto": DeviceProtoArrayForkChoice()}
    out = {}
    for name, pa in engines.items():
        pa.on_block(slot=0, root=_root(0), parent_root=ZERO_ROOT,
                    state_root=_root(0), justified_epoch=1,
                    justified_root=_root(0), finalized_epoch=1,
                    finalized_root=_root(0), execution_status=EXEC_OPTIMISTIC)
        for i, par in enumerate(parents, start=1):
            pa.on_block(slot=i, root=_root(i), parent_root=_root(par),
                        state_root=_root(i), justified_epoch=1,
                        justified_root=_root(0), finalized_epoch=1,
                        finalized_root=_root(0),
                        execution_status=EXEC_OPTIMISTIC)
        pa.process_attestation_batch(
            [(np.flatnonzero(votes == j), _root(j), 2)
             for j in range(n_nodes)])
        deltas = pa.compute_deltas(balances)
        pa.apply_score_changes(deltas, (1, _root(0)), (1, _root(0)),
                               ZERO_ROOT, 0, n_nodes)
        out[name] = (pa.find_head(_root(0), n_nodes),
                     [int(nd.weight) for nd in pa.nodes])
    check(engines["auto"].engine == "jit",
          f"auto fork-choice engine is {engines['auto'].engine}")
    check(out["auto"] == out["numpy"] == out["host"],
          "fork-choice engines disagree on head or weights")
    return {"nodes": n_nodes, "validators": n_validators,
            "head": out["host"][0][:4].hex(), "engine": "jit"}


def pack_round(rng, n_cands: int, n_validators: int, limit: int) -> dict:
    from lighthouse_tpu.op_pool import device_pack as DP
    from lighthouse_tpu.op_pool.max_cover import greedy_pack

    sizes = rng.integers(1, 65, n_cands)
    segs = [rng.choice(n_validators, int(s), replace=False) for s in sizes]
    offsets = np.zeros(n_cands + 1, np.int64)
    np.cumsum(sizes, out=offsets[1:])
    flat_e = np.concatenate(segs).astype(np.int64)
    bal = rng.choice(np.array([31, 32], np.int64) * 10**9, n_validators)
    flat_w = bal[flat_e]
    host, _, _ = greedy_pack(flat_e, flat_w, offsets, n_validators, limit)
    num = DP.greedy_pack_device(flat_e, flat_w, offsets, n_validators,
                                limit, engine="numpy")
    auto = DP.greedy_pack_device(flat_e, flat_w, offsets, n_validators,
                                 limit)
    check(DP.LAST_PACK_STATS.get("engine") == "jit",
          f"auto pack engine is {DP.LAST_PACK_STATS.get('engine')}")
    check(list(auto) == list(num) == list(host), "pack engines disagree")
    return {"candidates": n_cands, "validators": n_validators,
            "limit": limit, "selected": len(host), "engine": "jit"}


def phase_fork_choice_pack(p: Phase, seed: int, n_nodes: int = 48,
                           n_validators: int = 1 << 14,
                           n_cands: int = 1024, limit: int = 128) -> None:
    rng = np.random.default_rng(seed)
    p.out["fork_choice"] = fork_choice_round(rng, n_nodes, n_validators)
    p.out["pack"] = pack_round(rng, n_cands, n_validators, limit)
    p.out.update(oracle="jit == numpy == host engines", ok=True)


# ---------------------------------------------------------------------------
# --chips 4: the sharded paths
# ---------------------------------------------------------------------------

def phase_bls_sharded(p: Phase, seed: int, devices,
                      n_sets: int = N_SETS, kps: int = KEYS_PER_SET) -> None:
    from lighthouse_tpu.common.device_ledger import LEDGER
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.parallel.bls_shard import (
        sharded_verify_signature_sets)
    from lighthouse_tpu.parallel.mesh import make_mesh

    host = bls._BACKENDS["python"]
    sets, _sk, _pks = flagship_sets(n_sets, kps, seed=seed)
    bad = key_swapped(sets, 17 % n_sets, 18 % n_sets)
    mesh = make_mesh(devices)
    rows = {}
    for name, batch in (("valid", sets), ("key_swap", bad)):
        dev, first_s = timed(sharded_verify_signature_sets, batch, mesh)
        again, warm_s = timed(sharded_verify_signature_sets, batch, mesh)
        ref, host_s = timed(host.verify_signature_sets, batch)
        check(dev == again == ref == (name == "valid"),
              f"sharded {name}: mesh {dev}/{again} host {ref}")
        rows[name] = {"mesh": dev, "host": ref, "first_s": first_s,
                      "warm_s": warm_s, "host_s": host_s}
    shards = LEDGER.shard_totals().get("bls", {})
    fed = sorted(i for i, r in shards.items() if r.get("h2d_bytes"))
    check(len(fed) == len(devices),
          f"bls set planes landed on shards {fed} of {len(devices)}")
    p.out.update(sets=n_sets, keys_per_set=kps, devices=len(devices),
                 runs=rows, shard_rows=shards,
                 oracle=f"{len(devices)}-device verdicts == host verdicts",
                 ok=True)


def phase_mesh_slot(p: Phase, n: int) -> None:
    from lighthouse_tpu.parallel import mesh_slot as MS

    with MS.forced_devices(n):
        mesh_out = MS.run_slot_model()
    with MS.forced_devices(1):
        ref = MS.run_slot_model()
    check(mesh_out["devices"] == n, f"mesh ran on {mesh_out['devices']}")
    check(mesh_out["digest"] == ref["digest"],
          "mesh slot digest != 1-device digest")
    check(mesh_out["budget"]["ok"] and ref["budget"]["ok"],
          "warm-slot transfer budget breached")
    shards = mesh_out["shards"]
    for sub, rows in shards.items():
        live = {str(i) for i, r in rows.items()
                if any(int(v) for v in r.values())}
        check(len(live) in (0, n),
              f"{sub}: shard rows on {sorted(live)} of {n} devices")
    check(any(len(r) == n for r in shards.values()),
          "no subsystem reported rows on every device")
    p.out.update(devices=n, digest=mesh_out["digest"][:16],
                 rows_1dev=ref["rows"], rows_mesh=mesh_out["rows"],
                 shards=shards, oracle="digest == 1-device, budget ok",
                 ok=True)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def ledger_guards(trips0: int) -> dict:
    from lighthouse_tpu.beacon_chain import verification_service as VS
    from lighthouse_tpu.common.metrics import REGISTRY

    # The streamed-verification envelope ("bls") and the global envelope
    # around block and op-pool verifies ("bls_global").
    out = {name: REGISTRY.counter(name).value
           for env in ("bls", "bls_global")
           for name in (f"{env}_host_fallbacks_total",
                        f"{env}_device_faults_total")}
    trips = VS.total_breaker_trips() - trips0
    for env in ("bls", "bls_global"):
        n = out[f"{env}_host_fallbacks_total"]
        check(n == 0, f"{n} verifies served by the host ({env})")
    check(trips == 0, f"{trips} breaker trips")
    return {**out, "breaker_trips": trips}


def main(argv=None) -> int:
    args = _parse(argv)
    # Every verify is a device verify: no small-batch host fast path.
    os.environ["LIGHTHOUSE_TPU_HOST_FASTPATH_MAX"] = "0"
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU — JAX found {device}", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1

    from lighthouse_tpu.common.compile_cache import enable
    from lighthouse_tpu.beacon_chain import verification_service as VS

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.listen)
    cache = enable()
    print(json.dumps({"phase": "device", "device": device,
                      "compile_cache": cache, "ok": True}), flush=True)
    trips0 = VS.total_breaker_trips()
    if args.chips == 1:
        with Phase("state_root", clock, device) as p:
            phase_state_root(p, args.seed)
        with Phase("bls", clock, device) as p:
            phase_bls(p, args.seed)
        with Phase("block_import", clock, device) as p:
            phase_block_import(p, args.seed)
        with Phase("fork_choice_pack", clock, device) as p:
            phase_fork_choice_pack(p, args.seed)
    else:
        with Phase("bls_sharded", clock, device) as p:
            phase_bls_sharded(p, args.seed, devs[:args.chips])
        with Phase("mesh_slot", clock, device) as p:
            phase_mesh_slot(p, args.chips)
    with Phase("guards", clock, device) as p:
        p.out.update(ledger_guards(trips0), ok=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
