"""The plain reference of a Capella block's signed messages and of a
state's root from its values (MAINNET preset).

Written out from the consensus specs in ``hashlib`` SSZ over the
helpers of ``ssz_state.py``; nothing here imports the system under test.
Values are plain: ints, bytes, lists of dicts and numpy arrays, as the
benchmark made them or read them back.

- Signing roots: ``AttestationData``, the block (its header over the
  body's root), the sync aggregate's block root and the randao epoch,
  each with ``compute_domain`` over the state's fork and genesis
  validators root.
- Block bodies: every Capella ``BeaconBlockBody`` field, attestations,
  sync aggregate, execution payload and withdrawals written out; the
  operation lists a range-sync cell's blocks leave empty are rooted as
  empty lists of their limits.
- States: the root of a Capella ``BeaconState`` from its values, with
  the eth1 votes and historical summaries lists that a chain grows.
"""

from __future__ import annotations

import numpy as np

from .ssz_state import (
    CAPELLA_FIELDS,
    HISTORICAL_ROOTS_LIMIT,
    REGISTRY_LIMIT_DEPTH,
    U8_LIST_DEPTH,
    U64_LIST_DEPTH,
    ZERO_HASHES,
    _bytes_root,
    _chunk,
    _container,
    _u64,
    checkpoint_root,
    depth_of,
    eth1_data_root,
    header_root,
    merkle_root,
    mix_in_length,
    pack_bytes,
    registry_root,
    small_roots,
    u64_chunks,
)

DOMAIN_BEACON_PROPOSER = bytes.fromhex("00000000")
DOMAIN_BEACON_ATTESTER = bytes.fromhex("01000000")
DOMAIN_RANDAO = bytes.fromhex("02000000")
DOMAIN_SYNC_COMMITTEE = bytes.fromhex("07000000")

# MAINNET preset limits.
MAX_VALIDATORS_PER_COMMITTEE = 2048
MAX_PROPOSER_SLASHINGS = 16
MAX_ATTESTER_SLASHINGS = 2
MAX_ATTESTATIONS = 128
MAX_DEPOSITS = 16
MAX_VOLUNTARY_EXITS = 16
MAX_BLS_TO_EXECUTION_CHANGES = 16
MAX_WITHDRAWALS_PER_PAYLOAD = 16
MAX_TRANSACTIONS_PER_PAYLOAD = 1 << 20
MAX_BYTES_PER_TRANSACTION = 1 << 30
ETH1_VOTES_LIMIT = 64 * 32


# -- domains and signing roots ----------------------------------------------

def compute_domain(domain_type: bytes, version: bytes,
                   genesis_validators_root: bytes) -> bytes:
    fork_data_root = _container([_chunk(version), genesis_validators_root])
    return domain_type + fork_data_root[:28]


def domain_at(fork: dict, genesis_validators_root: bytes,
              domain_type: bytes, epoch: int) -> bytes:
    """``get_domain``: the fork's previous version before its epoch."""
    version = (fork["previous_version"] if epoch < fork["epoch"]
               else fork["current_version"])
    return compute_domain(domain_type, version, genesis_validators_root)


def signing_root(object_root: bytes, domain: bytes) -> bytes:
    return _container([object_root, domain])


def attestation_data_root(d: dict) -> bytes:
    return _container([_u64(d["slot"]), _u64(d["index"]),
                       d["beacon_block_root"], checkpoint_root(d["source"]),
                       checkpoint_root(d["target"])])


def epoch_root(epoch: int) -> bytes:
    return _u64(epoch)


# -- lists, bits --------------------------------------------------------------

def list_root(roots: list, limit: int) -> bytes:
    """List[composite, limit]: one chunk per element root."""
    if not roots:
        return mix_in_length(ZERO_HASHES[depth_of(limit)], 0)
    chunks = np.frombuffer(b"".join(roots), np.uint8).reshape(-1, 32)
    return mix_in_length(merkle_root(chunks, depth_of(limit)), len(roots))


def bitlist_root(bits: np.ndarray, limit: int) -> bytes:
    packed = np.packbits(np.asarray(bits, bool), bitorder="little").tobytes()
    chunks = pack_bytes(packed)
    depth = depth_of((limit + 255) // 256)
    root = (merkle_root(chunks, depth) if chunks.shape[0]
            else ZERO_HASHES[depth])
    return mix_in_length(root, len(bits))


def bitvector_root(bits: np.ndarray) -> bytes:
    packed = np.packbits(np.asarray(bits, bool), bitorder="little").tobytes()
    chunks = pack_bytes(packed)
    return merkle_root(chunks, depth_of(chunks.shape[0]))


def byte_list_root(b: bytes, limit: int) -> bytes:
    depth = depth_of((limit + 31) // 32)
    root = merkle_root(pack_bytes(b), depth) if b else ZERO_HASHES[depth]
    return mix_in_length(root, len(b))


# -- the block ----------------------------------------------------------------

def attestation_root(a: dict) -> bytes:
    return _container([
        bitlist_root(a["aggregation_bits"], MAX_VALIDATORS_PER_COMMITTEE),
        attestation_data_root(a["data"]), _bytes_root(a["signature"])])


def withdrawal_root(w: dict) -> bytes:
    return _container([_u64(w["index"]), _u64(w["validator_index"]),
                       _chunk(w["address"]), _u64(w["amount"])])


def payload_root(p: dict) -> bytes:
    """Capella ``ExecutionPayload``."""
    txs = [byte_list_root(t, MAX_BYTES_PER_TRANSACTION)
           for t in p["transactions"]]
    return _container([
        p["parent_hash"], _chunk(p["fee_recipient"]), p["state_root"],
        p["receipts_root"], _bytes_root(p["logs_bloom"]), p["prev_randao"],
        _u64(p["block_number"]), _u64(p["gas_limit"]), _u64(p["gas_used"]),
        _u64(p["timestamp"]), byte_list_root(p["extra_data"], 32),
        int(p["base_fee_per_gas"]).to_bytes(32, "little"), p["block_hash"],
        list_root(txs, MAX_TRANSACTIONS_PER_PAYLOAD),
        list_root([withdrawal_root(w) for w in p["withdrawals"]],
                  MAX_WITHDRAWALS_PER_PAYLOAD)])


def body_root(b: dict) -> bytes:
    """Capella ``BeaconBlockBody`` with no slashings, deposits, exits or
    BLS changes."""
    sync = _container([bitvector_root(b["sync_committee_bits"]),
                       _bytes_root(b["sync_committee_signature"])])
    return _container([
        _bytes_root(b["randao_reveal"]), eth1_data_root(b["eth1_data"]),
        b["graffiti"],
        list_root([], MAX_PROPOSER_SLASHINGS),
        list_root([], MAX_ATTESTER_SLASHINGS),
        list_root([attestation_root(a) for a in b["attestations"]],
                  MAX_ATTESTATIONS),
        list_root([], MAX_DEPOSITS),
        list_root([], MAX_VOLUNTARY_EXITS),
        sync, payload_root(b["execution_payload"]),
        list_root([], MAX_BLS_TO_EXECUTION_CHANGES)])


def block_root(blk: dict) -> bytes:
    """``hash_tree_root(BeaconBlock)``: its header over the body root."""
    return header_root({"slot": blk["slot"],
                        "proposer_index": blk["proposer_index"],
                        "parent_root": blk["parent_root"],
                        "state_root": blk["state_root"],
                        "body_root": body_root(blk["body"])})


# -- the state ----------------------------------------------------------------

def _vector_root(rows: np.ndarray) -> bytes:
    return merkle_root(rows, depth_of(len(rows)))


def state_root(v: dict) -> bytes:
    """Root of a Capella ``BeaconState`` from its values: ``registry``
    columns, the balance, participation and inactivity lists, the root
    vectors, ``slashings`` and the ``small`` fields (as
    ``ssz_state.small_roots`` takes them, plus ``eth1_data_votes`` and
    ``historical_summaries`` lists)."""
    n = len(v["balances"])
    small = v["small"]
    roots = small_roots(small)
    roots["eth1_data_votes"] = list_root(
        [eth1_data_root(e) for e in small["eth1_data_votes"]],
        ETH1_VOTES_LIMIT)
    roots["historical_summaries"] = list_root(
        [_container([h["block_summary_root"], h["state_summary_root"]])
         for h in small["historical_summaries"]], HISTORICAL_ROOTS_LIMIT)
    roots["validators"] = registry_root(v["registry"], REGISTRY_LIMIT_DEPTH)
    for f in ("balances", "inactivity_scores"):
        roots[f] = mix_in_length(
            merkle_root(u64_chunks(v[f]), U64_LIST_DEPTH), n)
    for f in ("previous_epoch_participation", "current_epoch_participation"):
        roots[f] = mix_in_length(merkle_root(
            pack_bytes(np.asarray(v[f], np.uint8).tobytes()), U8_LIST_DEPTH),
            n)
    for f in ("block_roots", "state_roots", "randao_mixes"):
        roots[f] = _vector_root(np.asarray(v[f], np.uint8))
    roots["slashings"] = merkle_root(u64_chunks(v["slashings"]),
                                     depth_of(len(v["slashings"]) // 4))
    leaves = np.frombuffer(b"".join(roots[f] for f in CAPELLA_FIELDS),
                           np.uint8).reshape(-1, 32)
    return merkle_root(leaves, depth_of(len(CAPELLA_FIELDS)))

