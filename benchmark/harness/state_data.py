"""The state-root cells' data, made from ``--seed``: a Capella state's
values at a stated registry size, and the writes of each slot.

Both the system under test and the plain reference are fed from here, so
the two see the same values and nothing either of them computed.  Slots'
writes are made in slot order from ``(seed, slot)`` and the balances
they have already written.
"""

from __future__ import annotations

import numpy as np

FAR_FUTURE = 2**64 - 1
GWEI_32 = 32 * 10**9


def _rng(seed: int, *tag: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, int(seed) >> 63, *tag])


def _b(rng, n: int) -> bytes:
    return rng.integers(0, 256, n, dtype=np.uint8).tobytes()


def make_state_data(seed: int, n: int, p: dict) -> dict:
    """Every value of the state before the first slot.  ``p`` holds the
    preset's vector lengths and the deployment's epoch and fork."""
    if n % 32:
        raise ValueError("the registry size must be a multiple of 32")
    rng = _rng(seed, 0)
    registry = {
        "pubkey": rng.integers(0, 256, (n, 48), dtype=np.uint8),
        "withdrawal_credentials": rng.integers(0, 256, (n, 32),
                                               dtype=np.uint8),
        "effective_balance": np.full(n, GWEI_32, np.uint64),
        "slashed": np.zeros(n, bool),
        "activation_eligibility_epoch": np.zeros(n, np.uint64),
        "activation_epoch": np.zeros(n, np.uint64),
        "exit_epoch": np.full(n, FAR_FUTURE, np.uint64),
        "withdrawable_epoch": np.full(n, FAR_FUTURE, np.uint64),
    }
    epoch = int(p["start_epoch"])
    sync = [_b(rng, 48) for _ in range(p["sync_committee_size"])]
    nxt = [_b(rng, 48) for _ in range(p["sync_committee_size"])]
    small = {
        "genesis_time": 1606824023,
        "genesis_validators_root": _b(rng, 32),
        "slot": epoch * p["slots_per_epoch"],
        "fork": {"previous_version": bytes.fromhex(p["previous_version"]),
                 "current_version": bytes.fromhex(p["current_version"]),
                 "epoch": int(p["fork_epoch"])},
        "latest_block_header": {"slot": 0, "proposer_index": 0,
                                "parent_root": _b(rng, 32),
                                "state_root": b"\x00" * 32,
                                "body_root": _b(rng, 32)},
        "eth1_data": {"deposit_root": _b(rng, 32), "deposit_count": n,
                      "block_hash": _b(rng, 32)},
        "eth1_deposit_index": n,
        "justification_bits": [True, True, True, True],
        "previous_justified_checkpoint": {"epoch": epoch - 2,
                                          "root": _b(rng, 32)},
        "current_justified_checkpoint": {"epoch": epoch - 1,
                                         "root": _b(rng, 32)},
        "finalized_checkpoint": {"epoch": epoch - 2, "root": _b(rng, 32)},
        "current_sync_committee": {"pubkeys": sync,
                                   "aggregate_pubkey": _b(rng, 48)},
        "next_sync_committee": {"pubkeys": nxt,
                                "aggregate_pubkey": _b(rng, 48)},
        "latest_execution_payload_header": {
            "parent_hash": _b(rng, 32), "fee_recipient": _b(rng, 20),
            "state_root": _b(rng, 32), "receipts_root": _b(rng, 32),
            "logs_bloom": _b(rng, 256), "prev_randao": _b(rng, 32),
            "block_number": 18_000_000, "gas_limit": 30_000_000,
            "gas_used": 14_000_000, "timestamp": 1_700_000_000,
            "extra_data": _b(rng, 16), "base_fee_per_gas": 12 * 10**9,
            "block_hash": _b(rng, 32), "transactions_root": _b(rng, 32),
            "withdrawals_root": _b(rng, 32)},
        "next_withdrawal_index": 40_000_000,
        "next_withdrawal_validator_index": int(rng.integers(0, n)),
    }
    # The sync committee's members sit in distinct balance chunks, and
    # proposers are drawn outside them, so every slot dirties the same
    # number of balance chunks.
    chunks = rng.choice(n // 4, p["sync_committee_size"], replace=False)
    members = (chunks * 4 + rng.integers(0, 4, chunks.size)).astype(np.int64)
    return {
        "registry": registry,
        "balances": (GWEI_32 + rng.integers(0, 10**9, n)).astype(np.uint64),
        "previous_epoch_participation":
            rng.choice(np.array([0, 1, 3, 7], np.uint8), n,
                       p=[0.02, 0.01, 0.05, 0.92]),
        "current_epoch_participation":
            rng.choice(np.array([0, 1, 3, 7], np.uint8), n,
                       p=[0.02, 0.01, 0.05, 0.92]),
        "inactivity_scores": np.zeros(n, np.uint64),
        "slashings": np.zeros(p["epochs_per_slashings_vector"], np.uint64),
        "block_roots": rng.integers(
            0, 256, (p["slots_per_historical_root"], 32), dtype=np.uint8),
        "state_roots": rng.integers(
            0, 256, (p["slots_per_historical_root"], 32), dtype=np.uint8),
        "randao_mixes": rng.integers(
            0, 256, (p["epochs_per_historical_vector"], 32), dtype=np.uint8),
        "small": small,
        "sync_members": np.sort(members),
    }


class SlotWrites:
    """The writes of slot ``s`` (0 = the first slot after set-up starts):
    the current-epoch participation flags of the slot's attesters (one
    slot's share of a seeded per-epoch permutation of the registry), the
    balances of the sync committee and the proposer, the previous slot's
    block and state roots, the epoch's randao mix, and the slot's block
    header."""

    def __init__(self, seed: int, data: dict, p: dict):
        self.seed = seed
        self.p = p
        self.n = len(data["balances"])
        self.balances = data["balances"].copy()
        self.members = data["sync_members"]
        member_chunk = np.zeros(self.n // 4, bool)
        member_chunk[self.members // 4] = True
        self.proposer_pool = np.flatnonzero(
            ~np.repeat(member_chunk, 4)).astype(np.int64)
        self.first_slot = int(data["small"]["slot"])
        self._perm = (None, None)

    def _permutation(self, epoch: int) -> np.ndarray:
        if self._perm[0] != epoch:
            self._perm = (epoch, _rng(self.seed, 1, epoch).permutation(
                self.n).astype(np.int64))
        return self._perm[1]

    def __call__(self, s: int) -> dict:
        p = self.p
        spe = p["slots_per_epoch"]
        slot = self.first_slot + s
        epoch, j = divmod(slot, spe)
        per_slot = self.n // spe
        idx = self._permutation(epoch)[j * per_slot:(j + 1) * per_slot]
        rng = _rng(self.seed, 2, slot)
        flags = rng.choice(np.array([1, 3, 7], np.uint8), idx.size,
                           p=[0.03, 0.07, 0.90])
        proposer = int(self.proposer_pool[rng.integers(
            0, self.proposer_pool.size)])
        bidx = np.concatenate([self.members, [proposer]])
        rewards = rng.integers(10_000, 30_000, bidx.size).astype(np.uint64)
        self.balances[bidx] += rewards
        hist = p["slots_per_historical_root"]
        return {
            "slot": slot,
            "participation": (idx, flags),
            "balances": (bidx, self.balances[bidx].copy()),
            "block_roots": ((slot - 1) % hist, _b(rng, 32)),
            "state_roots": ((slot - 1) % hist, _b(rng, 32)),
            "randao_mixes": (epoch % p["epochs_per_historical_vector"],
                             _b(rng, 32)),
            "latest_block_header": {
                "slot": slot, "proposer_index": proposer,
                "parent_root": _b(rng, 32), "state_root": b"\x00" * 32,
                "body_root": _b(rng, 32)},
        }
