"""BLS signatures over BLS12-381 (min-pubkey-size: pk in G1, sig in G2).

The host-facing API of the framework's crypto layer, mirroring the
reference's backend-generic wrapper (``/root/reference/crypto/bls/src/``):

- ``SecretKey`` / ``PublicKey`` / ``Signature`` / ``AggregateSignature`` with
  compressed ZCash encodings (48/96 bytes).
- the consensus-critical validity rules: an all-zero (infinity) public key is
  INVALID (``generic_public_key.rs:14-15``); deserialization subgroup-checks
  points; the canonical infinity signature is representable and fails
  verification against any pubkey set.
- ``SignatureSet`` + ``verify_signature_sets`` — random-linear-combination
  batch verification with one multi-pairing, replicating
  ``impls/blst.rs:36-119``: per-set nonzero 64-bit random scalar, signature
  subgroup checks, per-set pubkey aggregation, empty-set/empty-keys => False.

Backends (the ``bls::impls::*`` seam):

- ``python``  — this module's pure-Python pairing (ground truth).
- ``fake``    — always-true verification for logic tests, like the
  reference's ``fake_crypto`` (``impls/fake_crypto.rs:29,105``).
- ``tpu``     — device-batched verification (lighthouse_tpu.ops), registered
  when the pairing kernels land.
"""

from __future__ import annotations

import secrets
from dataclasses import dataclass
from functools import lru_cache
from typing import Iterable, List, Optional, Sequence

from . import curve as C
from . import fields as F
from .hash_to_curve import DST_G2, hash_to_g2
from .pairing import multi_pairing_is_one

PUBLIC_KEY_BYTES_LEN = 48
SIGNATURE_BYTES_LEN = 96
SECRET_KEY_BYTES_LEN = 32
INFINITY_SIGNATURE = bytes([0xC0]) + b"\x00" * 95
RAND_BITS = 64


class BlsError(ValueError):
    pass


@lru_cache(maxsize=1 << 16)
def _g1_point_checked(data: bytes):
    """Decompress + subgroup-check a G1 pubkey encoding, memoized by bytes —
    the decompressed-pubkey cache role of ``validator_pubkey_cache.rs``
    pushed down to the codec (pure function of the encoding)."""
    try:
        point = C.g1_decompress(data)
    except ValueError as e:
        # Curve-codec rejections ("x not on curve", bad flags, x >= p)
        # are key-material failures: surface them as BlsError so callers
        # classifying signature-material errors (block import's
        # InvalidSignatures boundary) see one type.
        raise BlsError(str(e)) from e
    if point is None:
        raise BlsError("infinity public key is invalid")
    if not C.g1_subgroup_check(point):
        raise BlsError("public key not in the G1 subgroup")
    return point


def _g2_mul_fast(point, scalar: int):
    """[scalar]P via the native 256-bit ladder when built (signing and
    RLC hot path, ~7× python); falls back to the curve oracle."""
    from . import native

    if point is not None and 0 <= scalar < (1 << 256) and native.ready():
        return native.g2_mul(point, scalar)
    return C.g2_mul(point, scalar)


@lru_cache(maxsize=1 << 16)
def _g2_point_checked(data: bytes):
    try:
        point = C.g2_decompress(data)
    except ValueError as e:
        raise BlsError(str(e)) from e
    if point is not None and not C.g2_subgroup_check(point):
        raise BlsError("signature not in the G2 subgroup")
    return point


@dataclass(frozen=True)
class SecretKey:
    scalar: int

    @classmethod
    def random(cls) -> "SecretKey":
        # Rejection sampling: reducing mod R would bias ~9.5% of the range.
        while True:
            k = secrets.randbits(255)
            if 0 < k < F.R:
                return cls(k)

    @classmethod
    def deserialize(cls, data: bytes) -> "SecretKey":
        if len(data) != SECRET_KEY_BYTES_LEN:
            raise BlsError(f"secret key must be {SECRET_KEY_BYTES_LEN} bytes")
        k = int.from_bytes(data, "big")
        if k == 0 or k >= F.R:
            raise BlsError("secret key scalar out of range")
        return cls(k)

    def serialize(self) -> bytes:
        return self.scalar.to_bytes(32, "big")

    def public_key(self) -> "PublicKey":
        return PublicKey(C.g1_mul(C.G1_GEN, self.scalar))

    def sign(self, message: bytes) -> "Signature":
        return Signature(_g2_mul_fast(hash_to_g2(message), self.scalar))


@dataclass(frozen=True)
class PublicKey:
    point: tuple  # affine G1, never None (infinity pubkeys are invalid)

    def __post_init__(self):
        if self.point is None:
            raise BlsError("infinity public key is invalid")

    @classmethod
    def deserialize(cls, data: bytes) -> "PublicKey":
        if len(data) != PUBLIC_KEY_BYTES_LEN:
            raise BlsError(f"public key must be {PUBLIC_KEY_BYTES_LEN} bytes")
        return cls(_g1_point_checked(bytes(data)))

    def serialize(self) -> bytes:
        return C.g1_compress(self.point)


def aggregate_points(points):
    """G1 sum of pre-validated pubkey POINTS.

    Large sums route through the native jacobian accumulator when built
    (~5 µs/point vs ~500 µs python affine adds) — the sync-committee
    512-key aggregate drops from ~260 ms to ~3 ms."""
    from . import native
    if len(points) >= 16 and native.ready():
        return native.g1_aggregate(list(points))
    acc = None
    for p in points:
        acc = C.g1_add(acc, p)
    return acc


def aggregate_public_keys(keys: Sequence[PublicKey]):
    """G1 sum of pubkey points (keys pre-validated at deserialization)."""
    return aggregate_points([k.point for k in keys])


@dataclass(frozen=True)
class Signature:
    point: Optional[tuple]  # affine G2; None = infinity signature

    @classmethod
    def deserialize(cls, data: bytes) -> "Signature":
        if len(data) != SIGNATURE_BYTES_LEN:
            raise BlsError(f"signature must be {SIGNATURE_BYTES_LEN} bytes")
        return cls(_g2_point_checked(bytes(data)))

    def serialize(self) -> bytes:
        return C.g2_compress(self.point)

    def verify(self, pubkey: PublicKey, message: bytes) -> bool:
        return get_backend().verify(self, [pubkey], message)

    def fast_aggregate_verify(self, pubkeys: Sequence[PublicKey],
                              message: bytes) -> bool:
        """Aggregate-signature verify: one message, many signers."""
        if not pubkeys:
            return False
        return get_backend().verify(self, list(pubkeys), message)

    def aggregate_verify(self, pubkeys: Sequence[PublicKey],
                         messages: Sequence[bytes]) -> bool:
        """Distinct message per signer: e(g1, sig) == prod_i e(pk_i, H(m_i))."""
        if not pubkeys or len(pubkeys) != len(messages):
            return False
        return get_backend().aggregate_verify(self, list(pubkeys),
                                              list(messages))


def aggregate_signatures(sigs: Iterable[Signature]) -> Signature:
    """G2 sum; empty input yields the infinity signature (like the
    reference's ``AggregateSignature::infinity``)."""
    acc = None
    for s in sigs:
        if s.point is not None:
            acc = C.g2_add(acc, s.point)
    return Signature(acc)


@dataclass(frozen=True)
class SignatureSet:
    """{aggregate signature, signing keys, one message} —
    ``generic_signature_set.rs:62-73``."""
    signature: Optional[Signature]
    signing_keys: List[PublicKey]
    message: bytes


def signature_set_key(s: SignatureSet) -> tuple:
    """Exact-identity key of a set: (message, signature point, signing
    key points).  Two sets with equal keys verify identically under any
    backend."""
    return (bytes(s.message),
            None if s.signature is None else s.signature.point,
            tuple(k.point for k in s.signing_keys))


def dedup_signature_sets(sets: Sequence[SignatureSet]
                         ) -> tuple[List[SignatureSet], int]:
    """Drop exact-duplicate sets (same message, keys AND signature)
    before a batch dispatch; returns ``(unique_sets, dropped)``.

    Verdict-identical by construction: the RLC batch verifies iff every
    DISTINCT set verifies (duplicates contribute redundant random-linear
    terms), and the empty/invalid-set pre-checks see at least one copy
    of each distinct set.  A block's batch hits this when the proposer
    packs the same committee aggregate twice (allowed by spec) or an
    attester-slashing attestation repeats an included attestation."""
    seen: set = set()
    out: List[SignatureSet] = []
    for s in sets:
        key = signature_set_key(s)
        if key in seen:
            continue
        seen.add(key)
        out.append(s)
    return out, len(sets) - len(out)


# ---------------------------------------------------------------------------
# Backend seam
# ---------------------------------------------------------------------------

class PythonBackend:
    """Pure-Python pairing backend (ground truth, slow)."""

    name = "python"

    def verify(self, signature: Signature, pubkeys: Sequence[PublicKey],
               message: bytes) -> bool:
        if signature.point is None or not pubkeys:
            return False
        agg_pk = aggregate_public_keys(pubkeys)
        if agg_pk is None:
            return False
        h = hash_to_g2(message)
        # e(-g1, sig) * e(agg_pk, H(m)) == 1
        return multi_pairing_is_one([
            (C.g1_neg(C.G1_GEN), signature.point),
            (agg_pk, h),
        ])

    def aggregate_verify(self, signature: Signature,
                         pubkeys: Sequence[PublicKey],
                         messages: Sequence[bytes]) -> bool:
        if signature.point is None:
            return False
        pairs = [(pk.point, hash_to_g2(m)) for pk, m in zip(pubkeys, messages)]
        pairs.append((C.g1_neg(C.G1_GEN), signature.point))
        return multi_pairing_is_one(pairs)

    def verify_signature_sets(self, sets: Sequence[SignatureSet]) -> bool:
        """Random-linear-combination batch verify (``impls/blst.rs:36-119``).

        With per-set random nonzero 64-bit c_i:
            e(-g1, sum_i c_i * sig_i) * prod_i e(c_i * pk_agg_i, H(m_i)) == 1
        """
        if not sets:
            return False
        pairs = []
        sig_acc = None  # G2 accumulator of c_i * sig_i
        for s in sets:
            if s.signature is None or s.signature.point is None:
                return False  # empty signature => failure
            if not s.signing_keys:
                return False  # no signing keys => invalid
            c = 0
            while c == 0:
                c = secrets.randbits(RAND_BITS)
            agg_pk = aggregate_public_keys(s.signing_keys)
            if agg_pk is None:
                return False
            sig_acc = C.g2_add(sig_acc, _g2_mul_fast(s.signature.point, c))
            pairs.append((C.g1_mul(agg_pk, c), hash_to_g2(s.message)))
        if sig_acc is None:
            return False
        pairs.append((C.g1_neg(C.G1_GEN), sig_acc))
        return multi_pairing_is_one(pairs)


class FakeBackend:
    """Always-true verification for logic tests (``impls/fake_crypto.rs``).

    Deserialization validity rules still apply — only the pairing is skipped.
    """

    name = "fake"

    def verify(self, signature, pubkeys, message) -> bool:
        return signature.point is not None and bool(pubkeys)

    def aggregate_verify(self, signature, pubkeys, messages) -> bool:
        return signature.point is not None and bool(pubkeys)

    def verify_signature_sets(self, sets) -> bool:
        if not sets:
            return False
        return all(
            s.signature is not None and s.signature.point is not None
            and s.signing_keys
            for s in sets)


_BACKENDS = {"python": PythonBackend(), "fake": FakeBackend()}
_active = _BACKENDS["python"]


def register_backend(name: str, backend) -> None:
    _BACKENDS[name] = backend


def set_backend(name: str) -> None:
    global _active
    if name == "tpu" and name not in _BACKENDS:
        from . import tpu_backend  # noqa: F401  (registers itself)
    _active = _BACKENDS[name]


def get_backend():
    return _active


# Optional dispatch wrapper — the resilience seam.  When set (by
# `beacon_chain.verification_service.install_global_envelope`), every
# module-level `verify_signature_sets` call routes through
# `wrapper(active_backend, sets)`, which adds deadline/retry/circuit-
# breaker/host-fallback around the device dispatch.  Backends invoked
# DIRECTLY (`get_backend().verify_signature_sets`) bypass it — that is
# how the wrapper itself calls the device without recursing.
_dispatch_wrapper = None


def set_dispatch_wrapper(wrapper) -> None:
    """Install (or clear, with None) the global dispatch wrapper."""
    global _dispatch_wrapper
    _dispatch_wrapper = wrapper


def verify_signature_sets(sets: Sequence[SignatureSet]) -> bool:
    if _dispatch_wrapper is not None:
        return _dispatch_wrapper(_active, sets)
    return _active.verify_signature_sets(sets)
