"""Range-sync import of full blocks through ``process_chain_segment``,
against the benchmark's plain reference, at a small registry.

The blocks are the range-sync cell's (``benchmark/harness/chain_data.py``):
a Capella anchor of real keys, every committee of every slot aggregated
into two consecutive blocks at 95 % participation, a sync aggregate, the
randao reveal and the proposal, MAINNET preset.  The segment's signature
batch goes through the block-signature dispatcher and its envelope with
the host BLS backend standing in for the device.

Also here: a large window's sets kept on the device in chunks, with the
block-signature envelope's deadline given once per dispatch, the native G2
decompression against the python oracle, and the copy-on-write
state snapshots the segment commit takes per block.
"""

import os
import random
import sys
import threading

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lighthouse_tpu.crypto import bls as B  # noqa: E402
from lighthouse_tpu.crypto import curve as C  # noqa: E402
from lighthouse_tpu.crypto import fields as F  # noqa: E402
from lighthouse_tpu.crypto import native  # noqa: E402
from lighthouse_tpu.state_transition import sig_dispatch as SD  # noqa: E402

N = 2048          # the smallest registry with 512 sync members apart
SEED = 2626000007


@pytest.fixture(scope="module")
def segment():
    """A chain anchored at the cell's anchor state and the first range-sync
    batch after it (63 blocks over two epochs), with its tampered copy."""
    from harness.chain_data import (ChainMaker, anchor_data, anchor_state,
                                    range_sync_batches)
    from ref import keys as K

    from lighthouse_tpu.beacon_chain import BeaconChain
    from lighthouse_tpu.store import HotColdDB
    from lighthouse_tpu.types import presets
    from lighthouse_tpu.types.chain_spec import ChainSpec

    prev = next(k for k, v in B._BACKENDS.items() if v is B.get_backend())
    B.set_backend("python")
    p = {"slots_per_historical_root": 8192,
         "epochs_per_historical_vector": 65536,
         "epochs_per_slashings_vector": 8192, "sync_committee_size": 512,
         "start_epoch": 262144, "fork_epoch": 194048,
         "previous_version": "02000000", "current_version": "03000000",
         "slots_per_epoch": 32}
    base, step = K.key_schedule(SEED)
    pks = K.public_keys(base, step, 0, N)
    data = anchor_data(SEED, N, p, pks)
    anchor, T = anchor_state(data, "MAINNET")
    maker_state, _ = anchor_state(data, "MAINNET")
    preset, spec = presets.MAINNET, ChainSpec.mainnet()
    hdr = anchor.latest_block_header.copy()
    hdr.state_root = anchor.tree_hash_root()
    chain = BeaconChain(store=HotColdDB.memory(preset, spec, T),
                        genesis_state=anchor,
                        genesis_block_root=hdr.tree_hash_root(),
                        preset=preset, spec=spec, T=T)
    for i, pt in enumerate(pks):
        chain.pubkey_cache._by_index[i] = B.PublicKey(pt)
    maker = ChainMaker(SEED, maker_state, T, preset, spec, base, step, 0.95)
    slot0 = int(anchor.slot)
    first, count = range_sync_batches(slot0 + 1, slot0 + 64, 32)[0]
    meta = maker.batch(first, count)
    blocks, tampered = maker.blocks(meta), maker.tampered(meta)
    maker.close()
    yield {"chain": chain, "blocks": blocks, "meta": meta,
           "tampered": tampered, "pks": pks}
    B.set_backend(prev)


@pytest.fixture()
def device_stand_in(monkeypatch):
    """The default block-signature dispatcher with the host backend in
    the device's place, behind its own envelope; records every batch."""
    seen = []

    def device(sets):
        seen.append(list(sets))
        return B._BACKENDS["python"].verify_signature_sets(sets)

    monkeypatch.setattr(SD, "_DEFAULT", SD.BlockSigDispatcher(
        device_fn=device,
        host_fn=B._BACKENDS["python"].verify_signature_sets))
    return seen


def test_full_block_segment_matches_reference(segment, device_stand_in,
                                              monkeypatch):
    """The tampered copy is refused with nothing committed; the sound
    segment commits every block on the device path, its sets go out in
    chunks of two 32-set pipeline sub-batches while the blocks apply,
    carry the reference's messages and aggregate keys and verify under
    the plain pairing, and the head's root is the last claim and the
    reference's root of the head's values."""
    from harness.chain_data import aggregate_pubkey, state_values
    from ref import curve as RC
    from ref import keys as K
    from ref.ssz_chain import state_root

    from lighthouse_tpu.beacon_chain.errors import InvalidSignatures
    from lighthouse_tpu.sync import Outcome, process_chain_segment

    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "32")
    chain, blocks, meta = segment["chain"], segment["blocks"], segment["meta"]
    head = chain.head.root
    bad = process_chain_segment(chain, segment["tampered"])
    assert bad.outcome is Outcome.FATAL and bad.imported == 0
    assert isinstance(bad.error, InvalidSignatures)
    assert chain.head.root == head

    device_stand_in.clear()
    res = process_chain_segment(chain, blocks)
    assert res.outcome is Outcome.OK and res.batched
    assert res.imported == len(blocks) == 63
    assert SD.LAST_SIG_DISPATCH["path"] == "device"
    total = sum(len(m["sets"]) for m in meta)
    assert [len(c) for c in device_stand_in] == \
        [64] * (total // 64) + [total % 64]
    sets = [x for c in device_stand_in for x in c]
    assert len(sets) == total

    by_sig = {RC.g2_compress(s.signature.point): s for s in sets}
    rng = random.Random(SEED)
    kinds = {}
    for m in meta:
        for s in m["sets"]:
            kinds.setdefault(s["kind"], []).append(s)
    sample = [rng.choice(kinds[k]) for k in
              ("attestation", "attestation", "sync", "proposal", "randao")]
    for s in sample:
        prog = by_sig[s["signature"]]
        agg = aggregate_pubkey(segment["pks"], s["signers"])
        assert bytes(prog.message) == s["message"], s["kind"]
        keys = None
        for k in prog.signing_keys:
            keys = RC.g1_add(keys, k.point)
        assert keys == agg, s["kind"]
        assert K.verify(agg, s["message"], RC.g2_decompress(s["signature"]))

    st = chain.head.state
    claim = meta[-1]["block"]["state_root"]
    assert bytes(st.tree_hash_root()) == claim
    assert state_root(state_values(st)) == claim


def _sets(n: int, keys: int):
    sk = B.SecretKey(12345)
    msg = b"\x07" * 32
    sig = sk.sign(msg)
    pk = sk.public_key()
    return [B.SignatureSet(sig, [pk] * keys, msg) for _ in range(n)]


@pytest.fixture()
def envelope(monkeypatch):
    """A fresh global BLS envelope with a 200 ms dispatch deadline."""
    from lighthouse_tpu.beacon_chain import verification_service as VS
    monkeypatch.setenv("LIGHTHOUSE_TPU_VERIFY_DEADLINE_MS", "200")
    VS.uninstall_global_envelope()
    yield VS.global_bls_envelope()
    VS.uninstall_global_envelope()


def test_large_batch_deadline_scales_with_its_dispatches(envelope,
                                                         monkeypatch):
    """A sound 512-set batch whose device time (30 ms a 64-set dispatch,
    240 ms) is past one dispatch's 200 ms deadline stays on the device;
    a device that hangs is abandoned and the host serves the batch."""
    from lighthouse_tpu.beacon_chain import verification_service as VS
    from lighthouse_tpu.crypto import tpu_backend as TB
    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "64")
    sets = _sets(448, 1) + _sets(64, 2)
    assert TB.dispatch_count(sets) == 8

    def slow_device(batch):
        threading.Event().wait(0.03 * TB.dispatch_count(batch))
        return True

    ok, path = VS.block_sig_dispatch(slow_device, sets)
    assert (ok, path) == (True, "device")
    assert envelope.stats["deadline_faults"] == 0

    release = threading.Event()
    hosted = []

    def hung_device(batch):
        release.wait(30)
        return True

    real_host = B._BACKENDS["python"].verify_signature_sets
    try:
        B._BACKENDS["python"].verify_signature_sets = \
            lambda batch: hosted.append(len(batch)) or False
        ok, path = VS.block_sig_dispatch(hung_device, sets)
    finally:
        del B._BACKENDS["python"].verify_signature_sets
        release.set()
    assert (ok, path) == (False, "host") and hosted == [len(sets)]
    assert B._BACKENDS["python"].verify_signature_sets == real_host
    assert envelope.stats["deadline_faults"] >= 1


def test_large_window_stays_on_device_in_chunks(envelope, monkeypatch):
    """A sound window whose sets take far longer to verify (20 ms a set)
    than one dispatch's 200 ms deadline stays on the device: its sets go
    out in chunks that each fit.  A device that hangs is abandoned and
    the host serves every chunk."""
    from lighthouse_tpu.state_transition import EpochReplayer
    from lighthouse_tpu.testing.harness import StateHarness
    from lighthouse_tpu.types.presets import MINIMAL

    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "1")
    prev = next(k for k, v in B._BACKENDS.items() if v is B.get_backend())
    B.set_backend("python")
    release = threading.Event()
    try:
        h = StateHarness(n_validators=16, preset=MINIMAL)
        genesis = h.state.copy()
        h.extend_chain(8)

        def replay(device):
            seen, hosted = [], []
            disp = SD.BlockSigDispatcher(
                device_fn=lambda batch: seen.append(len(batch)) or device(
                    batch),
                host_fn=lambda batch: hosted.append(len(batch)) or True,
                envelope=envelope)
            submit = disp.submit
            batches = []
            disp.submit = lambda sets, slot=None: batches.append(
                submit(sets, slot=slot)) or batches[-1]
            EpochReplayer(genesis.copy(), h.preset, h.spec, h.T,
                          verify_signatures=True,
                          sig_dispatcher=disp).apply_window(h.blocks)
            return seen, hosted, [b.stats["path"] for b in batches]

        def slow(batch):
            threading.Event().wait(0.02 * len(batch))
            return True

        seen, hosted, paths = replay(slow)
        assert 0.02 * sum(seen) > 0.2
        assert max(seen) == 2 and not hosted
        assert set(paths) == {"device"}
        assert envelope.stats["deadline_faults"] == 0

        _seen, hosted, paths = replay(lambda batch: release.wait(30))
        assert sum(hosted) == sum(seen) and set(paths) == {"host"}
        assert envelope.stats["deadline_faults"] >= 1
    finally:
        release.set()
        B.set_backend(prev)


@pytest.mark.skipif(not native.available(), reason="no native library")
def test_native_signature_decompression_matches_python():
    """Valid encodings (both signs) decompress to the python oracle's
    points; an x off the curve and an on-curve point outside G2 are
    refused with the oracle's reasons."""
    rng = random.Random(5)
    pts = [B._g2_mul_fast(C.G2_GEN, rng.randrange(1, F.R)) for _ in range(6)]
    for p in pts + [C.g2_neg(p) for p in pts[:2]]:
        enc = C.g2_compress(p)
        assert B._g2_point_checked.__wrapped__(enc) == C.g2_decompress(enc)
    assert B._g2_point_checked.__wrapped__(C.g2_compress(None)) is None
    reasons = set()
    while len(reasons) < 2:
        x = (rng.randrange(F.P), rng.randrange(F.P))
        enc = bytearray(x[1].to_bytes(48, "big") + x[0].to_bytes(48, "big"))
        enc[0] |= 0x80
        with pytest.raises(B.BlsError) as e:
            B._g2_point_checked.__wrapped__(bytes(enc))
        reasons.add(str(e.value))
    assert reasons == {"x not on curve", "signature not in the G2 subgroup"}


def test_state_copy_shares_columns_until_written():
    """Registry columns and the hash cache's stored columns are shared
    copy-on-write: writes through either lineage stay in it, and the
    committee shufflings ride the copy."""
    from lighthouse_tpu.state_transition.committees import (
        get_committee_cache)
    from lighthouse_tpu.testing.harness import StateHarness
    from lighthouse_tpu.types.presets import MINIMAL

    h = StateHarness(n_validators=64, preset=MINIMAL)
    a = h.state
    a.tree_hash_root()
    cache = get_committee_cache(a, 0, h.preset)
    b = a.copy()
    assert b.validators._effective_balance is a.validators._effective_balance
    assert b._committee_caches[0] is cache
    b.validators.wcol("effective_balance")[3] = 7
    assert int(a.validators.col("effective_balance")[3]) != 7
    c = a.copy()
    c.validators.set(1, c.validators[2])
    c.validators.append(c.validators[0])
    assert len(a.validators) == 64 and len(c.validators) == 65
    assert a.validators[1] != a.validators[2]
    assert a.tree_hash_root() != b.tree_hash_root()
    assert np.array_equal(a.copy().validators.col("pubkey"),
                          a.validators.col("pubkey"))


@pytest.mark.parametrize("bad", [(0,), (5,), (2, 4), (1, 2, 3, 4, 5)])
def test_refused_window_names_its_first_bad_block(bad):
    """The halving bisect of a refused window names the first block whose
    sets fail, wherever the bad blocks sit."""
    from lighthouse_tpu.state_transition import (EpochReplayer,
                                                 WindowSignaturesInvalid)
    from lighthouse_tpu.testing.harness import StateHarness
    from lighthouse_tpu.types.presets import MINIMAL

    prev = next(k for k, v in B._BACKENDS.items() if v is B.get_backend())
    B.set_backend("python")
    try:
        h = StateHarness(n_validators=16, preset=MINIMAL)
        genesis = h.state.copy()
        h.extend_chain(6)
        blocks = [b.copy() for b in h.blocks]
        for i in bad:   # a valid point over the wrong message
            blocks[i].signature = h.blocks[(i + 1) % 6].signature
        rep = EpochReplayer(genesis, h.preset, h.spec, h.T,
                            verify_signatures=True)
        with pytest.raises(WindowSignaturesInvalid) as ei:
            rep.apply_window(blocks)
    finally:
        B.set_backend(prev)
    assert ei.value.slot == int(blocks[bad[0]].message.slot)

