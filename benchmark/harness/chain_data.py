"""The range-sync cell's data, made from ``--seed``: a Capella anchor
state of real keys and the full mainnet blocks that extend it.

The anchor's values come from ``state_data.make_state_data`` with two
changes: the registry's public keys are the seed's keys
(``ref/keys.py``: ``sk_i = base + i * step``), and both sync committees
are members' keys.  Blocks are built on a state of their own that the
program's committee, proposer and withdrawal functions and its
``NO_VERIFICATION`` transition carry forward one block at a time (the
serial per-block path, not the batched window the cell times); that
transition also gives each block its ``state_root``.  Every signed
message's signing root comes from the plain reference
(``ref/ssz_chain.py``), and each signature is ``(sum of sk_i) * H(m)``,
one G2 multiplication per set on the host's native library.

Each block carries, as a mainnet block at 2^20 validators does:

- 128 attestations: the aggregates of the previous slot's committees and
  a second aggregate of each committee two slots back, every member's
  bit set with probability ``participation``; each ``AttestationData``
  is therefore included in two consecutive blocks;
- a sync aggregate of the current committee at the same participation;
- the randao reveal and the proposer's signature;
- the Capella payload's withdrawals from the sweep (no transactions: the
  consensus layer only roots them).

Next to each block the maker keeps the plain values the reference reads:
the block's fields and, per signature set, its kind, signers, message
and signature.  Those plain values are all a batch is: the cell's
process rebuilds the program's blocks from them (``BlockBuilder``), so
the maker can run in a process of its own (``harness/chain_maker.py``).
"""

from __future__ import annotations

import concurrent.futures as cf
import hashlib
import multiprocessing as mp
import os

import numpy as np

from ref import curve as RC
from ref import fields as RF
from ref import keys as K
from ref import ssz_chain as S

from .state_data import _b, _rng, make_state_data


def compress_g1_rows(points) -> np.ndarray:
    """(n, 48) uint8 compressed encodings of affine G1 points."""
    out = bytearray(48 * len(points))
    for i, p in enumerate(points):
        out[48 * i:48 * i + 48] = RC.g1_compress(p)
    return np.frombuffer(bytes(out), np.uint8).reshape(-1, 48)


def aggregate_pubkey(pks, indices):
    acc = None
    for i in indices:
        acc = RC.g1_add(acc, pks[int(i)])
    return acc


def public_keys(base: int, step: int, n: int) -> list:
    """``ref/keys.public_keys(base, step, 0, n)``, its chunks of 2^17
    keys made by up to 8 processes at once."""
    chunk = 1 << 17
    firsts = list(range(0, n, chunk))
    if len(firsts) == 1:
        return K.public_keys(base, step, 0, n)
    counts = [min(chunk, n - f) for f in firsts]
    ctx = mp.get_context("spawn")
    with cf.ProcessPoolExecutor(min(8, len(firsts), os.cpu_count() or 1),
                                mp_context=ctx) as ex:
        parts = ex.map(K.public_keys, [base] * len(firsts),
                       [step] * len(firsts), firsts, counts)
        return [pt for part in parts for pt in part]


def anchor_data(seed: int, n: int, p: dict, pks) -> dict:
    """``make_state_data`` with the seed's public keys in the registry
    and members' keys in both sync committees."""
    data = make_state_data(seed, n, p)
    rows = compress_g1_rows(pks)
    data["registry"]["pubkey"] = rows
    size = p["sync_committee_size"]
    nxt = np.sort(_rng(seed, 7).choice(n, size, replace=False))
    for field, members in (("current_sync_committee", data["sync_members"]),
                           ("next_sync_committee", nxt)):
        data["small"][field] = {
            "pubkeys": [rows[i].tobytes() for i in members],
            "aggregate_pubkey": RC.g1_compress(
                aggregate_pubkey(pks, members))}
    return data


def range_sync_batches(start_slot: int, last_slot: int,
                       slots_per_epoch: int) -> list:
    """``(first_slot, count)`` of the epoch-aligned batches a syncing
    chain forms from ``start_slot`` to ``last_slot``, as
    ``network/range_sync.SyncingChain`` forms them."""
    from lighthouse_tpu.network.range_sync import ChainType, SyncingChain
    sc = SyncingChain(b"\x00" * 32, last_slot, start_slot, slots_per_epoch,
                      ChainType.FINALIZED)
    return [(b.start_slot, b.count) for b in sc.batches]


def anchor_state(data: dict, preset_name: str):
    """The program's state holding ``data``'s values, its justification
    bits in the program's own Bitvector form (a numpy bool array, which
    the epoch transition slices)."""
    from .state_slots import _program_state
    state, T = _program_state(data, preset_name)
    state.justification_bits = np.array(
        data["small"]["justification_bits"], bool)
    return state, T


def batch_plan(anchor_slot: int, slots_per_epoch: int, count: int) -> list:
    """The first ``count`` range-sync batches after ``anchor_slot``."""
    return range_sync_batches(anchor_slot + 1,
                              anchor_slot + slots_per_epoch * 2 * (count + 1),
                              slots_per_epoch)[:count]


def _signer(task):
    """``(sum of secret keys) * H(m)``, compressed; the native G2 ladder
    and hash-to-curve release the interpreter while they run."""
    from lighthouse_tpu.crypto import bls
    h, sk = task
    return RC.g2_compress(bls._g2_mul_fast(h, sk))


class BlockBuilder:
    """The program's signed blocks from their plain values, and a bad
    peer's copy of a batch, on the chain that ``state`` (the anchor)
    starts."""

    def __init__(self, state, T, preset, spec, base: int, step: int):
        self.T, self.preset, self.spec = T, preset, spec
        self.base, self.step = base, step
        f = state.fork
        self.fork = {"previous_version": bytes(f.previous_version),
                     "current_version": bytes(f.current_version),
                     "epoch": int(f.epoch)}
        self.gvr = bytes(state.genesis_validators_root)

    def sk_sum(self, indices: np.ndarray) -> int:
        idx = np.asarray(indices, np.int64)
        return (len(idx) * self.base + self.step * int(idx.sum())) % RF.R

    def _domain(self, domain_type: bytes, epoch: int) -> bytes:
        """``get_domain`` (no fork is crossed: the fork is fixed)."""
        return S.domain_at(self.fork, self.gvr, domain_type, epoch)

    def _fork_name(self, slot: int):
        return self.spec.fork_name_at_epoch(slot // self.preset.SLOTS_PER_EPOCH)

    def blocks(self, meta: list) -> list:
        """The signed program blocks of a batch's plain values."""
        return [self._program_block(m["block"], next(
            s["signature"] for s in m["sets"] if s["kind"] == "proposal"))
            for m in meta]

    def tampered(self, meta: list) -> list:
        """The program blocks of ``meta`` with the signatures of the first
        two aggregates of its last block swapped, re-signed by its
        proposer: the two aggregates are invalid and every other set is
        sound."""
        from lighthouse_tpu.crypto.hash_to_curve import hash_to_g2
        plain = dict(meta[-1]["block"])
        body = dict(plain["body"])
        atts = [dict(a) for a in body["attestations"]]
        atts[0]["signature"], atts[1]["signature"] = \
            atts[1]["signature"], atts[0]["signature"]
        body["attestations"] = atts
        plain["body"] = body
        epoch = plain["slot"] // self.preset.SLOTS_PER_EPOCH
        msg = S.signing_root(S.block_root(plain), self._domain(
            S.DOMAIN_BEACON_PROPOSER, epoch))
        sig = _signer((hash_to_g2(msg),
                       self.sk_sum([plain["proposer_index"]])))
        return self.blocks(meta[:-1]) + [self._program_block(plain, sig)]

    def _program_block(self, v: dict, signature: bytes):
        T = self.T
        fork = self._fork_name(v["slot"])
        b = v["body"]

        def cp(c):
            return T.Checkpoint(epoch=c["epoch"], root=c["root"])

        atts = [T.Attestation(
            aggregation_bits=a["aggregation_bits"].copy(),
            data=T.AttestationData(
                slot=a["data"]["slot"], index=a["data"]["index"],
                beacon_block_root=a["data"]["beacon_block_root"],
                source=cp(a["data"]["source"]),
                target=cp(a["data"]["target"])),
            signature=a["signature"]) for a in b["attestations"]]
        p = b["execution_payload"]
        payload = T.payload_cls(fork)(
            **{k: v2 for k, v2 in p.items() if k != "withdrawals"},
            withdrawals=[T.Withdrawal(**w) for w in p["withdrawals"]])
        body = T.body_cls(fork)(
            randao_reveal=b["randao_reveal"],
            eth1_data=T.Eth1Data(**b["eth1_data"]), graffiti=b["graffiti"],
            proposer_slashings=[], attester_slashings=[], attestations=atts,
            deposits=[], voluntary_exits=[],
            sync_aggregate=T.SyncAggregate(
                sync_committee_bits=b["sync_committee_bits"].copy(),
                sync_committee_signature=b["sync_committee_signature"]),
            execution_payload=payload, bls_to_execution_changes=[])
        block = T.block_cls(fork)(
            slot=v["slot"], proposer_index=v["proposer_index"],
            parent_root=v["parent_root"], state_root=v["state_root"],
            body=body)
        return T.signed_block_cls(fork)(message=block, signature=signature)


class ChainMaker(BlockBuilder):
    """Builds signed blocks slot after slot on ``state`` (mutated)."""

    def __init__(self, seed: int, state, T, preset, spec, base: int,
                 step: int, participation: float):
        super().__init__(state, T, preset, spec, base, step)
        self.seed, self.state = seed, state
        self.participation = participation
        self.att_data: dict = {}     # (slot, index) → plain AttestationData
        self.h_memo: dict = {}       # message → H(m), for its two inclusions
        self.sync_memo = (None, None)
        self.pool = cf.ThreadPoolExecutor(min(16, os.cpu_count() or 1))

    def close(self) -> None:
        self.pool.shutdown(wait=True)

    # -- keys and signatures ------------------------------------------------

    def _h(self, message: bytes):
        from lighthouse_tpu.crypto.hash_to_curve import hash_to_g2
        h = self.h_memo.get(message)
        if h is None:
            h = self.h_memo[message] = hash_to_g2(message)
        return h

    def sign_all(self, jobs: list) -> list:
        """Signatures of ``(message, secret key)`` pairs, in order."""
        hs = list(self.pool.map(self._h, [m for m, _ in jobs]))
        return list(self.pool.map(_signer, [(h, sk) for h, (_m, sk)
                                            in zip(hs, jobs)]))

    # -- plain values of the state -------------------------------------------

    def _data(self, slot: int, index: int) -> dict:
        """The ``AttestationData`` of committee ``index`` at ``slot``, as
        the state advanced past ``slot`` votes it (``StateHarness``)."""
        key = (slot, index)
        d = self.att_data.get(key)
        if d is not None:
            return d
        from lighthouse_tpu.state_transition.helpers import (
            current_epoch, get_block_root_at_slot)
        st, pr = self.state, self.preset
        spe = pr.SLOTS_PER_EPOCH
        epoch = slot // spe
        head = bytes(get_block_root_at_slot(st, slot, pr))
        start = epoch * spe
        target = (bytes(get_block_root_at_slot(st, start, pr))
                  if start < int(st.slot) else head)
        src = (st.current_justified_checkpoint
               if epoch == current_epoch(st, pr)
               else st.previous_justified_checkpoint)
        d = self.att_data[key] = {
            "slot": slot, "index": index, "beacon_block_root": head,
            "source": {"epoch": int(src.epoch), "root": bytes(src.root)},
            "target": {"epoch": epoch, "root": target}}
        return d

    def _sync_members(self) -> np.ndarray:
        keys = tuple(bytes(k) for k in
                     self.state.current_sync_committee.pubkeys)
        if self.sync_memo[0] != keys:
            reg = self.state.validators
            self.sync_memo = (keys, np.array(
                [reg.pubkey_index(k) for k in keys], np.int64))
        return self.sync_memo[1]

    # -- one block ------------------------------------------------------------

    def _bits(self, rng, n: int) -> np.ndarray:
        bits = rng.random(n) < self.participation
        if not bits.any():
            bits[int(rng.integers(0, n))] = True
        return bits

    def block(self, slot: int) -> dict:
        """The plain values of the block at ``slot``: its fields and its
        signature sets (``BlockBuilder.blocks`` signs it for the
        program)."""
        from lighthouse_tpu.state_transition import SignatureStrategy
        from lighthouse_tpu.state_transition.committees import (
            get_beacon_committee, get_beacon_proposer_index,
            get_committee_count_per_slot)
        from lighthouse_tpu.state_transition.helpers import (
            get_block_root_at_slot, get_randao_mix)
        from lighthouse_tpu.state_transition.per_block import (
            get_expected_withdrawals, process_block)
        from lighthouse_tpu.state_transition.per_slot import process_slots

        T, pr, spec = self.T, self.preset, self.spec
        spe = pr.SLOTS_PER_EPOCH
        st = self.state = process_slots(self.state, slot, pr, spec, T)
        epoch = slot // spe
        rng = _rng(self.seed, 11, slot)
        proposer = int(get_beacon_proposer_index(st, pr))

        sets, jobs, atts = [], [], []
        for t in (slot - 1, slot - 2):
            e = t // spe
            domain = self._domain(S.DOMAIN_BEACON_ATTESTER, e)
            for c in range(get_committee_count_per_slot(st, e, pr)):
                committee = get_beacon_committee(st, t, c, pr)
                bits = self._bits(rng, len(committee))
                idx = committee[bits]
                data = self._data(t, c)
                msg = S.signing_root(S.attestation_data_root(data), domain)
                atts.append({"aggregation_bits": bits, "data": data})
                sets.append({"kind": "attestation", "signers": idx,
                             "message": msg})
                jobs.append((msg, self.sk_sum(idx)))
        members = self._sync_members()
        sbits = self._bits(rng, len(members))
        prev_root = bytes(get_block_root_at_slot(st, slot - 1, pr))
        msg = S.signing_root(prev_root, self._domain(
            S.DOMAIN_SYNC_COMMITTEE, (slot - 1) // spe))
        sets.append({"kind": "sync", "signers": members[sbits],
                     "message": msg})
        jobs.append((msg, self.sk_sum(members[sbits])))
        msg = S.signing_root(S.epoch_root(epoch),
                             self._domain(S.DOMAIN_RANDAO, epoch))
        sets.append({"kind": "randao", "signers": np.array([proposer]),
                     "message": msg})
        jobs.append((msg, self.sk_sum([proposer])))
        sigs = self.sign_all(jobs)
        for s, sig in zip(sets, sigs):
            s["signature"] = sig
        for a, sig in zip(atts, sigs):
            a["signature"] = sig

        hdr = st.latest_execution_payload_header
        parent_hash = bytes(hdr.block_hash)
        payload = {
            "parent_hash": parent_hash, "fee_recipient": _b(rng, 20),
            "state_root": _b(rng, 32), "receipts_root": _b(rng, 32),
            "logs_bloom": _b(rng, 256),
            "prev_randao": bytes(get_randao_mix(st, epoch, pr)),
            "block_number": int(hdr.block_number) + 1,
            "gas_limit": 30_000_000,
            "gas_used": int(rng.integers(10_000_000, 30_000_000)),
            "timestamp": int(st.genesis_time) + slot * spec.seconds_per_slot,
            "extra_data": _b(rng, 16),
            "base_fee_per_gas": int(rng.integers(5, 50)) * 10**9,
            "block_hash": hashlib.sha256(
                parent_hash + slot.to_bytes(8, "little")).digest(),
            "transactions": [],
            "withdrawals": [
                {"index": int(w[0]), "validator_index": int(w[1]),
                 "address": bytes(w[2]), "amount": int(w[3])}
                for w in get_expected_withdrawals(st, pr)]}
        e1 = st.eth1_data
        body = {
            "randao_reveal": sets[-1]["signature"],
            "eth1_data": {"deposit_root": bytes(e1.deposit_root),
                          "deposit_count": int(e1.deposit_count),
                          "block_hash": bytes(e1.block_hash)},
            "graffiti": _b(rng, 32), "attestations": atts,
            "sync_committee_bits": sbits,
            "sync_committee_signature": sets[-2]["signature"],
            "execution_payload": payload}
        plain = {"slot": slot, "proposer_index": proposer,
                 "parent_root": bytes(st.latest_block_header.tree_hash_root()),
                 "state_root": b"\x00" * 32, "body": body}
        signed = self._program_block(plain, b"\x00" * 96)
        process_block(st, signed, self._fork_name(slot), pr, spec, T,
                      strategy=SignatureStrategy.NO_VERIFICATION)
        plain["state_root"] = bytes(st.tree_hash_root())
        msg = S.signing_root(S.block_root(plain), self._domain(
            S.DOMAIN_BEACON_PROPOSER, epoch))
        sig = self.sign_all([(msg, self.sk_sum([proposer]))])[0]
        sets.append({"kind": "proposal", "signers": np.array([proposer]),
                     "message": msg, "signature": sig})
        self._forget(slot)
        return {"block": plain, "sets": sets}

    def _forget(self, slot: int) -> None:
        """Drop memos no later block reads (its attestations reach two
        slots back)."""
        for key in [k for k in self.att_data if k[0] < slot - 1]:
            del self.att_data[key]
        if len(self.h_memo) > 4 * 1024:
            self.h_memo.clear()

    def batch(self, first_slot: int, count: int) -> list:
        """The plain values of the blocks of slots ``first_slot .. +
        count - 1`` (no missed slots)."""
        return [self.block(s) for s in range(first_slot, first_slot + count)]


def state_values(state) -> dict:
    """The plain values of a program ``BeaconState`` (Capella) that
    ``ref/ssz_chain.state_root`` roots."""
    reg = state.validators
    cols = {c: np.array(reg.col(c)) for c in reg._COLUMNS}

    def cp(c):
        return {"epoch": int(c.epoch), "root": bytes(c.root)}

    def sc(c):
        return {"pubkeys": [bytes(k) for k in c.pubkeys],
                "aggregate_pubkey": bytes(c.aggregate_pubkey)}

    h = state.latest_block_header
    e = state.eth1_data
    p = state.latest_execution_payload_header
    payload = {f: getattr(p, f) for f in (
        "parent_hash", "fee_recipient", "state_root", "receipts_root",
        "logs_bloom", "prev_randao", "block_number", "gas_limit",
        "gas_used", "timestamp", "extra_data", "base_fee_per_gas",
        "block_hash", "transactions_root", "withdrawals_root")}
    payload = {k: (bytes(v) if isinstance(v, (bytes, bytearray, memoryview))
                   else v) for k, v in payload.items()}
    f = state.fork
    small = {
        "genesis_time": int(state.genesis_time),
        "genesis_validators_root": bytes(state.genesis_validators_root),
        "slot": int(state.slot),
        "fork": {"previous_version": bytes(f.previous_version),
                 "current_version": bytes(f.current_version),
                 "epoch": int(f.epoch)},
        "latest_block_header": {
            "slot": int(h.slot), "proposer_index": int(h.proposer_index),
            "parent_root": bytes(h.parent_root),
            "state_root": bytes(h.state_root),
            "body_root": bytes(h.body_root)},
        "eth1_data": {"deposit_root": bytes(e.deposit_root),
                      "deposit_count": int(e.deposit_count),
                      "block_hash": bytes(e.block_hash)},
        "eth1_data_votes": [
            {"deposit_root": bytes(v.deposit_root),
             "deposit_count": int(v.deposit_count),
             "block_hash": bytes(v.block_hash)}
            for v in state.eth1_data_votes],
        "eth1_deposit_index": int(state.eth1_deposit_index),
        "justification_bits": [bool(x) for x in state.justification_bits],
        "previous_justified_checkpoint":
            cp(state.previous_justified_checkpoint),
        "current_justified_checkpoint":
            cp(state.current_justified_checkpoint),
        "finalized_checkpoint": cp(state.finalized_checkpoint),
        "current_sync_committee": sc(state.current_sync_committee),
        "next_sync_committee": sc(state.next_sync_committee),
        "latest_execution_payload_header": payload,
        "next_withdrawal_index": int(state.next_withdrawal_index),
        "next_withdrawal_validator_index":
            int(state.next_withdrawal_validator_index),
        "historical_summaries": [
            {"block_summary_root": bytes(s.block_summary_root),
             "state_summary_root": bytes(s.state_summary_root)}
            for s in state.historical_summaries],
    }
    return {
        "registry": cols,
        "balances": np.array(state.balances, np.uint64),
        "previous_epoch_participation":
            np.array(state.previous_epoch_participation, np.uint8),
        "current_epoch_participation":
            np.array(state.current_epoch_participation, np.uint8),
        "inactivity_scores": np.array(state.inactivity_scores, np.uint64),
        "slashings": np.array(state.slashings, np.uint64),
        "block_roots": np.array(state.block_roots, np.uint8),
        "state_roots": np.array(state.state_roots, np.uint8),
        "randao_mixes": np.array(state.randao_mixes, np.uint8),
        "small": small,
    }
