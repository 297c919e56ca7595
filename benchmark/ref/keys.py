"""Seeded BLS keys and signatures in bulk, by incremental point addition.

Key i of a run is ``sk_i = base + i * step (mod r)``, so

    pk_{i+1}  = pk_i  + step * G1
    sig_{i+1} = sig_i + step * H(m)      (within one message m)

and a run's keys and signatures cost one Jacobian mixed addition each,
plus one batched inversion per list to bring them back to affine
coordinates.  Everything here is the benchmark's own arithmetic over the
copied field and curve modules; nothing comes from the system under test.
"""

from __future__ import annotations

import random

from . import curve as C
from . import fields as F
from .hash_to_curve import hash_to_g2


def key_schedule(seed: int) -> tuple[int, int]:
    """``(base, step)`` of the run's secret keys, from ``seed``."""
    rng = random.Random(seed)
    return rng.randrange(1, F.R), rng.randrange(1, 1 << 64)


def secret_key(base: int, step: int, i: int) -> int:
    return (base + i * step) % F.R


def _madd(f, p, q):
    """Jacobian ``p`` + affine ``q`` (y^2 = x^3 + b, madd-2007-bl)."""
    x1, y1, z1 = p
    z1z1 = f.sqr(z1)
    u2 = f.mul(q[0], z1z1)
    s2 = f.mul(f.mul(q[1], z1), z1z1)
    h = f.sub(u2, x1)
    if h == f.zero:            # p == ±q: never met by a seeded progression
        return C._jac_add(f, p, C._jac_from_affine(f, q))
    hh = f.sqr(h)
    i4 = f.add(f.add(hh, hh), f.add(hh, hh))
    j = f.mul(h, i4)
    r = f.sub(s2, y1)
    r = f.add(r, r)
    v = f.mul(x1, i4)
    x3 = f.sub(f.sub(f.sqr(r), j), f.add(v, v))
    yj = f.mul(y1, j)
    y3 = f.sub(f.mul(r, f.sub(v, x3)), f.add(yj, yj))
    z3 = f.sub(f.sub(f.sqr(f.add(z1, h)), z1z1), hh)
    return (x3, y3, z3)


def _to_affine_batch(f, jac: list) -> list:
    """Affine coordinates of Jacobian points with one field inversion
    (Montgomery's trick)."""
    n = len(jac)
    prefix = [f.one] * (n + 1)
    for i, (_x, _y, z) in enumerate(jac):
        prefix[i + 1] = f.mul(prefix[i], z)
    inv = f.inv(prefix[n])
    out = [None] * n
    for i in range(n - 1, -1, -1):
        x, y, z = jac[i]
        zi = f.mul(inv, prefix[i])
        inv = f.mul(inv, z)
        zi2 = f.sqr(zi)
        out[i] = (f.mul(x, zi2), f.mul(y, f.mul(zi2, zi)))
    return out


def progression(f, start, step, n: int) -> list:
    """``[start + i * step for i in range(n)]`` as affine points."""
    jac = [C._jac_from_affine(f, start)]
    for _ in range(n - 1):
        jac.append(_madd(f, jac[-1], step))
    return _to_affine_batch(f, jac)


def public_keys(base: int, step: int, first: int, n: int) -> list:
    """Affine G1 public keys of keys ``first .. first + n - 1``."""
    start = C.g1_mul(C.G1_GEN, secret_key(base, step, first))
    return progression(C.FQ, start, C.g1_mul(C.G1_GEN, step), n)


def signatures(base: int, step: int, first: int, n: int,
               message: bytes) -> list:
    """Affine G2 signatures of keys ``first .. first + n - 1`` on
    ``message``."""
    h = hash_to_g2(message)
    start = C.g2_mul(h, secret_key(base, step, first))
    return progression(C.FQ2, start, C.g2_mul(h, step), n)


def verify(pk, message: bytes, sig) -> bool:
    """The plain single-signature BLS verify:
    e(pk, H(m)) == e(G1, sig), as e(-G1, sig) * e(pk, H(m)) == 1."""
    from .pairing import multi_pairing_is_one
    if sig is None or pk is None:
        return False
    if not (C.g2_on_curve(sig) and C.g2_subgroup_check(sig)):
        return False
    return multi_pairing_is_one([(C.g1_neg(C.G1_GEN), sig),
                                 (pk, hash_to_g2(message))])
