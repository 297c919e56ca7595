"""Host BLS12-381 reference: fields, curves, pairing, hash-to-curve, sigs.

Mirrors the reference's crypto test strategy (unit tests per layer plus the
semantics of ``verify_signature_sets`` — ``/root/reference/crypto/bls``);
spec-vector conformance is a later round (no vectors in this offline env),
so correctness rests on algebraic invariants: group laws, pairing
bilinearity, isogeny structure, sign/verify roundtrips, tamper rejection.
"""

import random

import pytest

from lighthouse_tpu.crypto import fields as F
from lighthouse_tpu.crypto import curve as C
from lighthouse_tpu.crypto.pairing import pairing, multi_pairing
from lighthouse_tpu.crypto import hash_to_curve as H
from lighthouse_tpu.crypto import bls

rng = random.Random(0xBEEF)


# --- fields ----------------------------------------------------------------

def test_fq2_inv_mul_roundtrip():
    for _ in range(10):
        a = (rng.randrange(1, F.P), rng.randrange(F.P))
        assert F.fq2_mul(a, F.fq2_inv(a)) == F.FQ2_ONE


def test_fq12_inv_frobenius():
    a = ((tuple((rng.randrange(F.P), rng.randrange(F.P)) for _ in range(3))),
         (tuple((rng.randrange(F.P), rng.randrange(F.P)) for _ in range(3))))
    assert F.fq12_mul(a, F.fq12_inv(a)) == F.FQ12_ONE
    # frob composed P times == frob of next order
    f1 = F.fq12_frobenius(a, 1)
    f2 = F.fq12_frobenius(f1, 1)
    assert f2 == F.fq12_frobenius(a, 2)


def test_fq2_sqrt():
    for _ in range(10):
        a = (rng.randrange(F.P), rng.randrange(F.P))
        sq = F.fq2_sqr(a)
        r = F.fq2_sqrt(sq)
        assert r is not None and F.fq2_sqr(r) == sq


# --- curve groups ----------------------------------------------------------

def test_generators_have_order_r():
    assert C.g1_mul_full(C.G1_GEN, F.R) is None
    assert C.g2_mul_full(C.G2_GEN, F.R) is None


def test_group_law_matches_scalar_ring():
    a, b = rng.randrange(F.R), rng.randrange(F.R)
    assert C.g1_add(C.g1_mul(C.G1_GEN, a), C.g1_mul(C.G1_GEN, b)) == \
        C.g1_mul(C.G1_GEN, (a + b) % F.R)
    assert C.g2_add(C.g2_mul(C.G2_GEN, a), C.g2_mul(C.G2_GEN, b)) == \
        C.g2_mul(C.G2_GEN, (a + b) % F.R)


def test_serialization_roundtrip():
    for k in (1, 2, 0xDEADBEEF):
        p = C.g1_mul(C.G1_GEN, k)
        assert C.g1_decompress(C.g1_compress(p)) == p
        q = C.g2_mul(C.G2_GEN, k)
        assert C.g2_decompress(C.g2_compress(q)) == q
    assert C.g1_decompress(C.g1_compress(None)) is None
    assert C.g2_decompress(C.g2_compress(None)) is None


def test_deserialize_rejects_garbage():
    with pytest.raises(ValueError):
        C.g1_decompress(b"\x00" * 48)     # compression bit unset
    with pytest.raises(ValueError):
        C.g1_decompress(b"\xff" * 48)     # x >= p
    with pytest.raises(ValueError):
        C.g2_decompress(b"\x80" + b"\x00" * 95)  # x=0 not on curve? (x^3+b QR check)


# --- pairing ---------------------------------------------------------------

def test_pairing_bilinearity():
    a, b = 0xABCD, 0x1234
    e1 = pairing(C.g1_mul(C.G1_GEN, a), C.g2_mul(C.G2_GEN, b))
    e2 = pairing(C.g1_mul(C.G1_GEN, b), C.g2_mul(C.G2_GEN, a))
    e3 = F.fq12_pow(pairing(C.G1_GEN, C.G2_GEN), a * b % F.R)
    assert e1 == e2 == e3


def test_pairing_nondegenerate():
    assert pairing(C.G1_GEN, C.G2_GEN) != F.FQ12_ONE


def test_multi_pairing_product_identity():
    assert multi_pairing([(C.G1_GEN, C.G2_GEN),
                          (C.g1_neg(C.G1_GEN), C.G2_GEN)]) == F.FQ12_ONE


# --- hash to curve ---------------------------------------------------------

def test_expand_message_xmd_structure():
    # independently recompute the XMD construction with hashlib
    import hashlib
    msg, dst, n = b"abc", b"MY-DST", 48
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(b"\x00" * 64 + msg + n.to_bytes(2, "big") + b"\x00" + dst_prime).digest()
    b1 = hashlib.sha256(b0 + b"\x01" + dst_prime).digest()
    b2 = hashlib.sha256(bytes(x ^ y for x, y in zip(b0, b1)) + b"\x02" + dst_prime).digest()
    assert H.expand_message_xmd(msg, dst, n) == (b1 + b2)[:48]


def test_iso_map_lands_on_curve_and_is_homomorphic():
    def twist_point(seed):
        r = random.Random(seed)
        while True:
            x = (r.randrange(F.P), r.randrange(F.P))
            y = F.fq2_sqrt(H._gx_twist(x))
            if y is not None:
                return (x, y)
    p1, p2 = twist_point(1), twist_point(2)
    q1, q2 = H.iso_map(p1), H.iso_map(p2)
    assert C.g2_on_curve(q1) and C.g2_on_curve(q2)
    s = C._affine_add(C.FQ2, p1, p2)  # chord add: curve-b-independent
    assert H.iso_map(s) == C.g2_add(q1, q2)


def test_h_eff_is_multiple_of_true_cofactor():
    assert H.H_EFF_G2 % H.H2_TWIST_COFACTOR == 0
    assert H.H_EFF_G2 % F.R != 0


def test_hash_to_g2_in_subgroup_and_deterministic():
    h = H.hash_to_g2(b"test message")
    assert C.g2_subgroup_check(h)
    assert h == H.hash_to_g2(b"test message")
    assert h != H.hash_to_g2(b"test messagf")


# --- signatures ------------------------------------------------------------

def _keypair(seed: int):
    sk = bls.SecretKey(seed % F.R or 1)
    return sk, sk.public_key()


def test_sign_verify_roundtrip():
    sk, pk = _keypair(12345)
    sig = sk.sign(b"attestation data")
    assert sig.verify(pk, b"attestation data")
    assert not sig.verify(pk, b"attestation datb")
    _, pk2 = _keypair(999)
    assert not sig.verify(pk2, b"attestation data")


def test_serialized_roundtrip_verify():
    sk, pk = _keypair(777)
    msg = b"round trip"
    sig = bls.Signature.deserialize(sk.sign(msg).serialize())
    pk2 = bls.PublicKey.deserialize(pk.serialize())
    assert sig.verify(pk2, msg)


def test_fast_aggregate_verify():
    msg = b"sync committee root"
    keys = [_keypair(s) for s in (11, 22, 33)]
    agg = bls.aggregate_signatures([sk.sign(msg) for sk, _ in keys])
    assert agg.fast_aggregate_verify([pk for _, pk in keys], msg)
    assert not agg.fast_aggregate_verify([pk for _, pk in keys[:2]], msg)
    assert not agg.fast_aggregate_verify([], msg)


def test_aggregate_verify_distinct_messages():
    pairs = [(_keypair(s), b"msg%d" % s) for s in (5, 6)]
    agg = bls.aggregate_signatures([sk.sign(m) for (sk, _), m in pairs])
    assert agg.aggregate_verify([pk for (_, pk), _ in pairs],
                                [m for _, m in pairs])
    assert not agg.aggregate_verify([pk for (_, pk), _ in pairs],
                                    [b"msg5", b"wrong"])


def test_infinity_pubkey_invalid():
    with pytest.raises(bls.BlsError):
        bls.PublicKey.deserialize(bytes([0xC0]) + b"\x00" * 47)


def test_infinity_signature_deserializes_but_fails_verify():
    sig = bls.Signature.deserialize(bls.INFINITY_SIGNATURE)
    assert sig.point is None
    _, pk = _keypair(42)
    assert not sig.verify(pk, b"x")


def test_verify_signature_sets_semantics():
    msgs = [b"a", b"b", b"c"]
    sets = []
    for i, m in enumerate(msgs):
        sk, pk = _keypair(1000 + i)
        sets.append(bls.SignatureSet(sk.sign(m), [pk], m))
    assert bls.verify_signature_sets(sets)
    # empty list => False  (impls/blst.rs:41-43)
    assert not bls.verify_signature_sets([])
    # one bad signature poisons the batch
    bad = sets[:2] + [bls.SignatureSet(sets[0].signature,
                                       sets[2].signing_keys, b"c")]
    assert not bls.verify_signature_sets(bad)
    # empty signing keys => False  (impls/blst.rs:86-89)
    assert not bls.verify_signature_sets(
        [bls.SignatureSet(sets[0].signature, [], b"a")])
    # infinity signature => False
    inf = bls.Signature.deserialize(bls.INFINITY_SIGNATURE)
    assert not bls.verify_signature_sets(
        [bls.SignatureSet(inf, sets[0].signing_keys, b"a")])


def test_verify_signature_sets_multi_signer():
    msg = b"aggregate attestation"
    keys = [_keypair(s) for s in (201, 202, 203)]
    agg = bls.aggregate_signatures([sk.sign(msg) for sk, _ in keys])
    s = bls.SignatureSet(agg, [pk for _, pk in keys], msg)
    assert bls.verify_signature_sets([s])


def test_fake_backend():
    bls.set_backend("fake")
    try:
        sk, pk = _keypair(7)
        sig = sk.sign(b"m")
        assert sig.verify(pk, b"anything")  # fake: always true for valid shapes
        inf = bls.Signature.deserialize(bls.INFINITY_SIGNATURE)
        assert not inf.verify(pk, b"m")
        assert not bls.verify_signature_sets([])
    finally:
        bls.set_backend("python")


def test_pubkey_table_lru_eviction():
    """Generational LRU halving (ADVICE r4): hot keys touched every batch
    stay resident; junk from earlier batches ages out; columns survive
    compaction bit-exact."""
    import numpy as np
    from lighthouse_tpu.crypto import tpu_backend as TB

    tbl = TB._DevicePubkeyTable(initial=8, max_keys=16)
    hot = [bls.SecretKey(1000 + i).public_key().point for i in range(4)]
    junk = [bls.SecretKey(5000 + i).public_key().point for i in range(24)]
    ji = 0
    for _ in range(6):
        for p in hot:
            tbl.index_of(p)
        for p in junk[ji:ji + 4]:   # bounded junk per batch (64-set queues)
            tbl.index_of(p)
        ji += 4
        tbl.maybe_reset()
    assert tbl._n <= 16
    for p in hot:
        i = tbl._index.get(p)
        assert i is not None, "hot key evicted by junk stream"
        assert (tbl._host[:, i] ==
                np.frombuffer(TB._g1_aff_col(p), np.uint32)).all()
    # Evicted keys re-insert cleanly.
    j = tbl.index_of(junk[0])
    assert (tbl._host[:, j] ==
            np.frombuffer(TB._g1_aff_col(junk[0]), np.uint32)).all()


def test_pubkey_table_indices_of_matches_index_of():
    """The batched lookup gives what per-key lookups give — repeats,
    keys new to the table in first-seen order, a capacity doubling in
    between — and marks every key used in the current generation."""
    import numpy as np
    from lighthouse_tpu.crypto import tpu_backend as TB

    pts = [bls.SecretKey(300 + i).public_key().point for i in range(12)]
    keys = pts[:5] + pts[2:4] + pts[5:] + [pts[0]]
    one = TB._DevicePubkeyTable(initial=8)
    for p in pts[:3]:
        one.index_of(p)
    many = TB._DevicePubkeyTable(initial=8)
    for p in pts[:3]:
        many.index_of(p)
    many.maybe_reset()
    one.maybe_reset()
    want = [one.index_of(p) for p in keys]
    got = many.indices_of(keys)
    assert got.dtype == np.int32 and got.tolist() == want
    assert (many._host[:, :many._n] == one._host[:, :one._n]).all()
    assert all(many._last_used[p] == many._gen for p in keys)


def test_pubkey_table_add_all_matches_index_of():
    """The bulk fill gives the columns, indices and capacity growth that
    one ``index_of`` per key gives, skips keys already resident, and
    re-uploads the device copy."""
    import numpy as np
    from lighthouse_tpu.crypto import tpu_backend as TB

    pts = [bls.SecretKey(700 + i).public_key().point for i in range(20)]
    one = TB._DevicePubkeyTable(initial=8)
    for p in pts[:3] + pts:
        one.index_of(p)
    bulk = TB._DevicePubkeyTable(initial=8)
    bulk.index_of(pts[0])
    bulk.device()
    bulk.add_all(pts[:3] + pts + pts[5:7])
    assert bulk._n == one._n == 21 and bulk._host.shape == one._host.shape
    assert (bulk._host == one._host).all()
    assert all(bulk._index[p] == one._index[p] for p in pts)
    assert bulk._device is None
    np.testing.assert_array_equal(np.asarray(bulk.device()), one._host)


@pytest.mark.slow
def test_aggregate_verify_many_distinct_messages():
    """VERDICT r4 weak #9 shape: a deposit-block-style aggregate_verify
    with HUNDREDS of distinct (pubkey, message) pairs in one relation —
    exercises the N-single-key-set funnel end to end (native multi-
    pairing batches all N+1 Miller loops under one final exp)."""
    import time

    n = 256
    sks = [bls.SecretKey(0x9000 + i) for i in range(n)]
    pks = [k.public_key() for k in sks]
    msgs = [b"deposit-%04d" % i for i in range(n)]
    agg = bls.aggregate_signatures(
        [sk.sign(m) for sk, m in zip(sks, msgs)])
    t0 = time.perf_counter()
    assert agg.aggregate_verify(pks, msgs)
    dt = time.perf_counter() - t0
    # tampered: swap two messages
    swapped = list(msgs)
    swapped[3], swapped[7] = swapped[7], swapped[3]
    assert not agg.aggregate_verify(pks, swapped)
    # sanity bound: the native path should stay well under a second per
    # hundred pairs even on this 1-core host
    assert dt < 30, f"aggregate_verify({n}) took {dt:.1f}s"
