"""The plain reference of a Capella ``BeaconState`` root (MAINNET preset).

Every root here is folded with ``hashlib`` from values the benchmark made
itself (``harness/state_data.py``): the SSZ rules of the consensus specs
written out for the one container that the state-root cells hash, and
incremental Merkle trees for the registry-sized lists.  The hashing
helpers and the registry fold are copied from the bring-up smoke run's
oracle; the small containers, which that oracle took from the system's
own SSZ, are written out here so that the reference imports nothing of
the system under test.
"""

from __future__ import annotations

import hashlib

import numpy as np

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


def depth_of(limit_chunks: int) -> int:
    return max(int(limit_chunks) - 1, 0).bit_length()


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


class MerkleTree:
    """All levels of one padded tree over (n, 32) chunks, re-hashed along
    the paths of changed chunks only; the root folds the padded top with
    zero hashes up to ``depth`` and mixes in ``length`` when given."""

    def __init__(self, chunks: np.ndarray, depth: int):
        n = chunks.shape[0]
        width = 1 << max(n - 1, 0).bit_length()
        level = np.zeros((width, 32), np.uint8)
        level[:n] = chunks
        self.levels = [level]
        while self.levels[-1].shape[0] > 1:
            above = hash_rows(self.levels[-1].reshape(-1, 64))
            self.levels.append(above.copy())
        self.depth = depth
        self._dirty: list = []

    def set(self, idx: np.ndarray, rows: np.ndarray) -> None:
        self.levels[0][idx] = rows
        self._dirty.append(np.asarray(idx, np.int64))

    def root(self, length: int | None = None) -> bytes:
        if self._dirty:
            idx = np.unique(np.concatenate(self._dirty))
            self._dirty = []
            for lvl in range(1, len(self.levels)):
                idx = np.unique(idx >> 1)
                below = self.levels[lvl - 1]
                pairs = np.concatenate([below[2 * idx], below[2 * idx + 1]],
                                       axis=1)
                self.levels[lvl][idx] = hash_rows(pairs)
        root = self.levels[-1][0].tobytes()
        for d in range(len(self.levels) - 1, self.depth):
            root = hashlib.sha256(root + ZERO_HASHES[d]).digest()
        return root if length is None else mix_in_length(root, length)


# ---------------------------------------------------------------------------
# The small containers, written out from the consensus specs (Capella)
# ---------------------------------------------------------------------------

def _chunk(b: bytes) -> bytes:
    return b + b"\x00" * (32 - len(b))


def _u64(v: int) -> bytes:
    return _chunk(int(v).to_bytes(8, "little"))


def _bytes_root(b: bytes) -> bytes:
    """Root of a ByteVector[N]: its packed chunks, merkleized."""
    if len(b) <= 32:
        return _chunk(b)
    chunks = pack_bytes(b)
    return merkle_root(chunks, depth_of(chunks.shape[0]))


def _container(roots: list) -> bytes:
    return merkle_root(np.frombuffer(b"".join(roots), np.uint8)
                       .reshape(-1, 32), depth_of(len(roots)))


def fork_root(f: dict) -> bytes:
    return _container([_chunk(f["previous_version"]),
                       _chunk(f["current_version"]), _u64(f["epoch"])])


def header_root(h: dict) -> bytes:
    return _container([_u64(h["slot"]), _u64(h["proposer_index"]),
                       h["parent_root"], h["state_root"], h["body_root"]])


def eth1_data_root(e: dict) -> bytes:
    return _container([e["deposit_root"], _u64(e["deposit_count"]),
                       e["block_hash"]])


def checkpoint_root(c: dict) -> bytes:
    return _container([_u64(c["epoch"]), c["root"]])


def sync_committee_root(s: dict) -> bytes:
    keys = np.frombuffer(b"".join(_bytes_root(k) for k in s["pubkeys"]),
                         np.uint8).reshape(-1, 32)
    return _container([merkle_root(keys, depth_of(len(s["pubkeys"]))),
                       _bytes_root(s["aggregate_pubkey"])])


def payload_header_root(p: dict) -> bytes:
    extra = p["extra_data"]
    extra_root = mix_in_length(
        merkle_root(pack_bytes(extra), 0) if extra else ZERO_HASHES[0],
        len(extra))
    return _container([
        p["parent_hash"], _chunk(p["fee_recipient"]), p["state_root"],
        p["receipts_root"], _bytes_root(p["logs_bloom"]), p["prev_randao"],
        _u64(p["block_number"]), _u64(p["gas_limit"]), _u64(p["gas_used"]),
        _u64(p["timestamp"]), extra_root,
        int(p["base_fee_per_gas"]).to_bytes(32, "little"), p["block_hash"],
        p["transactions_root"], p["withdrawals_root"]])


def bitvector4_root(bits: list) -> bytes:
    return _chunk(bytes([sum(int(b) << i for i, b in enumerate(bits))]))


def empty_list_root(limit_chunks: int) -> bytes:
    return mix_in_length(ZERO_HASHES[depth_of(limit_chunks)], 0)


CAPELLA_FIELDS = (
    "genesis_time", "genesis_validators_root", "slot", "fork",
    "latest_block_header", "block_roots", "state_roots", "historical_roots",
    "eth1_data", "eth1_data_votes", "eth1_deposit_index", "validators",
    "balances", "randao_mixes", "slashings", "previous_epoch_participation",
    "current_epoch_participation", "justification_bits",
    "previous_justified_checkpoint", "current_justified_checkpoint",
    "finalized_checkpoint", "inactivity_scores", "current_sync_committee",
    "next_sync_committee", "latest_execution_payload_header",
    "next_withdrawal_index", "next_withdrawal_validator_index",
    "historical_summaries")

# MAINNET preset list limits, in chunks.
HISTORICAL_ROOTS_LIMIT = 1 << 24
ETH1_VOTES_LIMIT = 64 * 32
REGISTRY_LIMIT_DEPTH = 40          # List[Validator, 2^40]
U64_LIST_DEPTH = 38                # List[uint64, 2^40] → 2^38 chunks
U8_LIST_DEPTH = 35                 # List[uint8, 2^40] → 2^35 chunks


def small_roots(small: dict) -> dict:
    """Roots of the state's small fields from their plain values."""
    return {
        "genesis_time": _u64(small["genesis_time"]),
        "genesis_validators_root": small["genesis_validators_root"],
        "slot": _u64(small["slot"]),
        "fork": fork_root(small["fork"]),
        "latest_block_header": header_root(small["latest_block_header"]),
        "historical_roots": empty_list_root(HISTORICAL_ROOTS_LIMIT),
        "eth1_data": eth1_data_root(small["eth1_data"]),
        # List[Eth1Data, 2048] of composite elements: one chunk each.
        "eth1_data_votes": empty_list_root(ETH1_VOTES_LIMIT),
        "eth1_deposit_index": _u64(small["eth1_deposit_index"]),
        "justification_bits": bitvector4_root(small["justification_bits"]),
        "previous_justified_checkpoint":
            checkpoint_root(small["previous_justified_checkpoint"]),
        "current_justified_checkpoint":
            checkpoint_root(small["current_justified_checkpoint"]),
        "finalized_checkpoint": checkpoint_root(small["finalized_checkpoint"]),
        "current_sync_committee":
            sync_committee_root(small["current_sync_committee"]),
        "next_sync_committee":
            sync_committee_root(small["next_sync_committee"]),
        "latest_execution_payload_header":
            payload_header_root(small["latest_execution_payload_header"]),
        "next_withdrawal_index": _u64(small["next_withdrawal_index"]),
        "next_withdrawal_validator_index":
            _u64(small["next_withdrawal_validator_index"]),
        "historical_summaries": empty_list_root(HISTORICAL_ROOTS_LIMIT),
    }


class StateReference:
    """The state root from the benchmark's own copy of every value:
    incremental hashlib trees for the big lists and vectors, the written
    out SSZ of the small containers, and the registry fold once."""

    def __init__(self, data: dict):
        n = len(data["balances"])
        self.n = n
        self.small = dict(data["small"])
        self.registry = registry_root(data["registry"], REGISTRY_LIMIT_DEPTH)
        self.balances = data["balances"].copy()
        self.cur = data["current_epoch_participation"].copy()
        self.trees = {
            "balances": MerkleTree(u64_chunks(self.balances), U64_LIST_DEPTH),
            "inactivity_scores": MerkleTree(
                u64_chunks(data["inactivity_scores"]), U64_LIST_DEPTH),
            "previous_epoch_participation": MerkleTree(
                pack_bytes(data["previous_epoch_participation"].tobytes()),
                U8_LIST_DEPTH),
            "current_epoch_participation": MerkleTree(
                pack_bytes(self.cur.tobytes()), U8_LIST_DEPTH),
            "block_roots": MerkleTree(data["block_roots"],
                                      depth_of(len(data["block_roots"]))),
            "state_roots": MerkleTree(data["state_roots"],
                                      depth_of(len(data["state_roots"]))),
            "randao_mixes": MerkleTree(data["randao_mixes"],
                                       depth_of(len(data["randao_mixes"]))),
            "slashings": MerkleTree(
                u64_chunks(data["slashings"]),
                depth_of(len(data["slashings"]) // 4)),
        }

    def apply(self, w: dict) -> None:
        """One slot's writes (``harness/state_data.slot_writes``)."""
        idx, vals = w["participation"]
        self.cur[idx] = vals
        chunks = np.unique(idx // 32)
        self.trees["current_epoch_participation"].set(
            chunks, self.cur.reshape(-1, 32)[chunks])
        bidx, bvals = w["balances"]
        self.balances[bidx] = bvals
        chunks = np.unique(bidx // 4)
        self.trees["balances"].set(
            chunks, u64_chunks(self.balances.reshape(-1, 4)[chunks]
                               .reshape(-1)).reshape(-1, 32))
        for field in ("block_roots", "state_roots", "randao_mixes"):
            i, root = w[field]
            self.trees[field].set(np.array([i]),
                                  np.frombuffer(root, np.uint8)[None])
        self.small["slot"] = w["slot"]
        self.small["latest_block_header"] = w["latest_block_header"]

    def root(self) -> bytes:
        roots = small_roots(self.small)
        roots["validators"] = self.registry
        n = self.n
        for f in ("balances", "inactivity_scores",
                  "previous_epoch_participation",
                  "current_epoch_participation"):
            roots[f] = self.trees[f].root(length=n)
        for f in ("block_roots", "state_roots", "randao_mixes", "slashings"):
            roots[f] = self.trees[f].root()
        leaves = np.frombuffer(b"".join(roots[f] for f in CAPELLA_FIELDS),
                               np.uint8).reshape(-1, 32)
        return merkle_root(leaves, depth_of(len(CAPELLA_FIELDS)))
