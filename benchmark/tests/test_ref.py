"""The plain references against published answers and against the
program at small sizes."""

import math

import numpy as np
import pytest

from harness.common import CompileClock, percentile
from ref import curve as C
from ref import keys as K
from ref.hash_to_curve import hash_to_g2

RFC_DST = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"
# RFC 9380 J.10.1, msg = "abc": ((x_c0, x_c1), (y_c0, y_c1)), affine.
RFC_ABC = (
    ("02c2d18e033b960562aae3cab37a27ce00d80ccd5ba4b7fe0e7a210245129dbe"
     "c7780ccc7954725f4168aff2787776e6",
     "139cddbccdc5e91b9623efd38c49f81a6f83f175e80b06fc374de9eb4b41dfe4"
     "ca3a230ed250fbe3a2acf73a41177fd8"),
    ("1787327b68159716a37440985269cf584bcb1e621d3a7202be6ea05c4cfe244a"
     "eb197642555a0645fb87bf7466b2ba48",
     "00aa65dae3c8d732d10ecd2c50f8a1baf3001578f71c694e03866e9f3d49ac1e"
     "1ce70dd94a733534f106d4cec0eddd16"),
)
# The eth2 interop key 0: secret key and its compressed public key.
INTEROP_SK = 0x25295F0D1D592A90B333E26E85149708208E9F8E8BC18F6C77BD62F8AD7A6866
INTEROP_PK = ("a99a76ed7796f7be22d5b7e85deeb7c5677e88e511e0b337618f8c4eb6134"
              "9b4bf2d153f649f7b53359fe8b94a38e44c")


def test_hash_to_g2_matches_rfc9380():
    (x0, x1), (y0, y1) = hash_to_g2(b"abc", RFC_DST)
    assert (format(x0, "096x"), format(x1, "096x")) == RFC_ABC[0]
    assert (format(y0, "096x"), format(y1, "096x")) == RFC_ABC[1]


def test_interop_public_key():
    pk = C.g1_mul(C.G1_GEN, INTEROP_SK)
    assert C.g1_compress(pk).hex() == INTEROP_PK


def test_progressions_equal_scalar_multiples_and_verify():
    base, step = K.key_schedule(2**35 + 3)
    pks = K.public_keys(base, step, 10, 40)
    msg = b"m" * 32
    sigs = K.signatures(base, step, 10, 40, msg)
    for i in (0, 1, 17, 39):
        sk = K.secret_key(base, step, 10 + i)
        assert pks[i] == C.g1_mul(C.G1_GEN, sk)
        assert sigs[i] == C.g2_mul(hash_to_g2(msg), sk)
    assert K.verify(pks[3], msg, sigs[3])
    assert not K.verify(pks[3], msg, sigs[4])
    assert not K.verify(pks[3], b"n" * 32, sigs[3])


def test_key_schedule_takes_large_seeds():
    assert K.key_schedule(2**33 + 5) != K.key_schedule(2**33 + 6)


def test_state_reference_equals_the_program_at_small_size():
    """The written-out SSZ of the Capella state against the program's
    root, on a small registry, before and after slots of writes."""
    from harness.state_data import SlotWrites, make_state_data
    from harness.state_slots import _program_state
    from ref.ssz_state import StateReference

    p = {"slots_per_historical_root": 8192,
         "epochs_per_historical_vector": 65536,
         "epochs_per_slashings_vector": 8192, "sync_committee_size": 512,
         "start_epoch": 300000, "fork_epoch": 194048,
         "previous_version": "02000000", "current_version": "03000000",
         "slots_per_epoch": 32}
    data = make_state_data(11, 4096, p)
    state, T = _program_state(data, "MAINNET")
    ref = StateReference(data)
    assert ref.root() == state.tree_hash_root()
    writes, mine = SlotWrites(11, data, p), SlotWrites(11, data, p)
    for s in range(3):
        w = writes(s)
        ref.apply(mine(s))
        idx, flags = w["participation"]
        state.current_epoch_participation[idx] = flags
        bidx, bvals = w["balances"]
        state.balances[bidx] = bvals
        for f in ("block_roots", "state_roots", "randao_mixes"):
            i, root = w[f]
            getattr(state, f)[i] = np.frombuffer(root, np.uint8)
        state.slot = w["slot"]
        state.latest_block_header = T.BeaconBlockHeader(
            **w["latest_block_header"])
        assert ref.root() == state.tree_hash_root()


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert percentile(vals, 95) == 95
    assert percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_compile_clock_takes_the_union_of_nested_spans():
    clock = CompileClock()
    clock._spans["trace_s"] = [(1.0, 3.0), (2.0, 4.0), (10.0, 11.0)]
    clock._spans["compile_s"] = [(5.0, 6.0)]
    got = clock.between(0.0)
    assert math.isclose(got["trace_s"], 4.0)
    assert clock.between(2.5, 10.5)["trace_s"] == pytest.approx(2.0)
    assert clock.count("compile_s", 0.0, 5.5) == 0
    assert clock.count("compile_s", 0.0, 6.0) == 1
