"""Pallas TPU kernels for batched BLS12-381 pairing — the production path.

The XLA limb modules (:mod:`.limb_field`/:mod:`.limb_pairing`) are
semantically exact but dispatch-bound: a Miller loop lowers to ~400k tiny
kernel launches.  Here the whole loop body lives inside single Pallas programs —
the same move as the Merkle sub-tree kernel (:mod:`..ops.merkle_kernel`) —
so one launch runs the full 63-iteration Miller loop for a lane-batch of
pairs with every intermediate in VMEM/registers.

Data layout: **limb planes**.  An Fq element batch is a ``(26, M)`` uint32
array — 16-bit limbs down the sublanes, M independent elements across the
vector lanes.  Tower elements are python tuples of planes (Fq2 = 2, Fq6 =
3×Fq2, Fq12 = 2×Fq6), and every tower multiply concatenates its base
products along the lane axis so the kernel issues ONE wide ``mont_mul``
per level — Karatsuba all the way down (3/6/18 ⇒ 54 base products per
Fq12 multiply instead of schoolbook 144).

Mosaic rejects captured array constants, so every field/Frobenius constant
is packed into one ``(rows, 1)`` uint32 input (:data:`CONSTS_PLANES`) and
the static exponent bit strings ride along as SMEM inputs; kernels call
:func:`_bind_consts` first, and the in-kernel helpers read the bound
slices.  Semantics are bit-identical to the XLA path (Montgomery residues
< 2N, full-width reduction, HHT cubed final exponentiation), so the host
oracle (:mod:`.pairing`) validates both.

Kernels:

- :func:`miller_kernel_call` — batched Miller loops (63-iter fori_loop
  in-kernel, conditional add-step lane-selected per the static bit string).
- :func:`product_kernel_call` — masked lane product folded (lane-roll
  butterfly) down to 128 residue-class products; the host multiplies those
  and runs ONE shared :func:`..pairing.final_exponentiation_cubed`.
- :func:`prepare_kernel_call` — per-set G1 pubkey aggregation (K-major
  lane blocks, sequential-K fori accumulate), 64-bit RLC double-and-add
  ladders for the aggregates and for −c_i·G, and batched Fermat-ladder
  affine conversion; the signature side of the RLC rides the pairing
  bilinearity (∏ e(c_i·pk_i, H_i)·∏ e(−c_i·G, σ_i) == 1), so no G2
  ladder exists at all.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fields as F
from . import limb_field as LF
from . import limb_pairing as XP

LIMBS = 26
M16 = np.uint32(0xFFFF)
U32 = jnp.uint32

X_BITS_MILLER = XP.X_BITS_MILLER     # 63 bits, MSB-first (implicit top 1)
X_BITS_FULL = XP.X_BITS_FULL         # 64 bits
P_MINUS_2_BITS = XP.P_MINUS_2_BITS   # 379 bits


# -- packed constants --------------------------------------------------------

def _build_consts() -> tuple[np.ndarray, dict]:
    """Stack every plane constant into one (rows, 1) u32 array + slice map.

    Each block is padded to 32 rows so every in-kernel slice starts on a
    sublane-tile boundary — Mosaic gives offset layouts to unaligned
    slices, and a later lane-concat of mixed-offset pieces fails to lower.
    """
    blocks: list[np.ndarray] = []
    index: dict[str, tuple[int, int]] = {}

    def put(name: str, limbs: np.ndarray):
        start = sum(b.shape[0] for b in blocks)
        arr = np.asarray(limbs, np.uint32).reshape(-1, 1)
        pad = (-arr.shape[0]) % 32
        if pad:
            arr = np.concatenate([arr, np.zeros((pad, 1), np.uint32)])
        blocks.append(arr)
        index[name] = (start, start + len(np.asarray(limbs).reshape(-1)))

    put("N", LF.N_LIMBS)
    put("NPRIME", LF._NPRIME_LIMBS)
    put("N2", LF.N2_LIMBS)
    put("ONE", LF.ONE_MONT)
    for k in (2, 4, 8, 16):
        put(f"K{k}", LF.int_to_limbs(k * F.P))
    for k in range(8):
        put(f"ZP{k}", LF.int_to_limbs(k * F.P))
    for n in (1, 2, 3):
        gam = np.asarray(XP._GAMMA[n])  # (2, 3, 2, 26)
        for i in range(2):
            for j in range(3):
                for u in range(2):
                    put(f"FROB{n}_{i}{j}{u}", gam[i, j, u])
    from . import curve as C
    ng = C.g1_neg(C.G1_GEN)
    put("NEGG_X", LF.to_mont(ng[0]))
    put("NEGG_Y", LF.to_mont(ng[1]))

    # Hash-to-curve constants (SSWU + isogeny + psi cofactor clearing).
    from . import hash_to_curve as H2C

    def put2(name: str, v):
        put(name + "0", LF.to_mont(v[0] % F.P))
        put(name + "1", LF.to_mont(v[1] % F.P))

    put2("H2C_A", H2C.A_TWIST)
    put2("H2C_B", H2C.B_TWIST)
    put2("H2C_Z", H2C.Z_SSWU)
    neg_b_over_a = F.fq2_mul(F.fq2_neg(H2C.B_TWIST), F.fq2_inv(H2C.A_TWIST))
    put2("H2C_NEGBA", neg_b_over_a)
    x1_exc = F.fq2_mul(H2C.B_TWIST,
                       F.fq2_inv(F.fq2_mul(H2C.Z_SSWU, H2C.A_TWIST)))
    put2("H2C_X1EXC", x1_exc)
    for k in range(4):
        put2(f"H2C_E8I{k}", H2C.E8_INV_POWS[k])
        put2(f"H2C_T{k}", H2C.T_KS[k])
    for tag, coeffs in (("XN", H2C._ISO3_X_NUM), ("XD", H2C._ISO3_X_DEN),
                        ("YN", H2C._ISO3_Y_NUM), ("YD", H2C._ISO3_Y_DEN)):
        for i, cf in enumerate(coeffs):
            put2(f"H2C_{tag}{i}", cf)
    put2("H2C_PSI_CX", H2C._PSI_CX)
    put2("H2C_PSI_CY", H2C._PSI_CY)
    put("RAW_ONE", LF.int_to_limbs(1))  # mont→canonical via mont_mul
    put("POW2", [1 << k for k in range(LIMBS)])  # carry-word bit weights
    return np.concatenate(blocks, axis=0), index


CONSTS_PLANES, _CONST_INDEX = _build_consts()

# (52, 1352) f32 selection matrix for the MXU band product (transposed so
# the in-kernel contraction is SEL @ feat → (52, M) — see k_band_mxu).
BAND_SEL_T = LF.band_sel_matrix(2 * LIMBS).T.copy()

# Bound during kernel tracing: name → plane value; plus bit-string refs.
_KC: dict = {}


def _bind_consts(cref, xbits_ref=None, pbits_ref=None,
                 band_ref=None, mm=()) -> None:
    c = cref[:]
    for name, (a, b) in _CONST_INDEX.items():
        _KC[name] = c[a:b]
    for n in (1, 2, 3):
        _KC[f"FROBT{n}"] = tuple(
            tuple((_KC[f"FROB{n}_{i}{j}0"], _KC[f"FROB{n}_{i}{j}1"])
                  for j in range(3)) for i in range(2))
    _KC["xbits"] = xbits_ref
    _KC["pbits"] = pbits_ref
    # MXU band-selection matrix; None (eager/legacy drives) falls back to
    # the VPU pad-and-add band inside k_mont_mul.
    _KC["band"] = band_ref
    # Default OFF: only the hash-to-curve kernel trace flips this (its
    # pltpu.repeat materialization is Mosaic-only); re-binding here keeps
    # the process-global flag from leaking into later eager/CPU drives.
    _KC["in_mosaic"] = False
    # The (a, b, out) product scratch of :func:`_mm_scratch`, or None
    # (eager drives): :func:`_mont_many` loops over it when bound.
    _KC["mm"] = tuple(mm) or None


def _const_specs():
    return [pl.BlockSpec(memory_space=pltpu.VMEM),   # consts
            pl.BlockSpec(memory_space=pltpu.SMEM),   # x bits
            pl.BlockSpec(memory_space=pltpu.SMEM),   # p−2 bits
            pl.BlockSpec(memory_space=pltpu.VMEM)]   # band-sel matrix


# Most products one :func:`_mont_many` call batches (an Fq12 multiply's
# 18 Fq2 Karatsuba triples).
MM_MAX = 54


def _mm_scratch(lanes: int):
    """VMEM scratch for the looped products of a kernel whose planes are
    at most ``lanes`` wide: operand a, operand b, product."""
    return [pltpu.VMEM((MM_MAX, LIMBS, lanes), jnp.uint32)] * 3


def _const_args():
    return (jnp.asarray(CONSTS_PLANES),
            jnp.asarray(X_BITS_FULL.reshape(-1, 1).astype(np.int32)),
            jnp.asarray(P_MINUS_2_BITS.reshape(-1, 1).astype(np.int32)),
            jnp.asarray(BAND_SEL_T))


# ---------------------------------------------------------------------------
# In-kernel base-field ops on (26, M) planes
# ---------------------------------------------------------------------------


# Carry resolution.  A ripple over the 26 limb rows traces ~4 ops per
# row, and the field add/sub/neg/muls ops sit under every tower multiply:
# a Python-unrolled ripple made one Fq12 multiply ~53k traced ops and the
# final-exponentiation kernels minutes of host tracing per process.  The
# carry chain is instead resolved as one word addition: once every limb
# carries at most 1, limb k either generates a carry (g), propagates an
# incoming one (p: limb == 0xFFFF) or kills it, and adding the packed words
# G + (G|P) runs exactly that chain through the integer adder (bit k of
# (G + (G|P) + cin) ^ G ^ (G|P) is the carry INTO limb k).  The results are
# the same integers, limb for limb, as the ripple.


def _pack_bits(bits):
    """(26, M) 0/1 rows → (1, M) word, row k at bit k.  The reduction runs
    in int32 (Mosaic reduces no unsigned types); the sum is < 2^26."""
    w = (bits * _KC["POW2"]).astype(jnp.int32)
    return jnp.sum(w, axis=0, keepdims=True).astype(U32)


def _carry_word(g, p, cin=None):
    """Carries of a 26-limb chain: bit k = carry into limb k (bit 0 is
    ``cin``), bit 26 = carry out.  ``g`` and ``p`` are exclusive 0/1
    rows."""
    gw = _pack_bits(g)
    bw = gw | _pack_bits(p)
    s = gw + bw if cin is None else gw + bw + cin
    return s ^ gw ^ bw


def _carry_rows(word):
    """(1, M) carry word → (26, M) 0/1 carry-in rows."""
    return ((word & _KC["POW2"]) != 0).astype(U32)


def _shift_up(x):
    """Row k ← row k−1, row 0 ← 0 (move each column's overflow one limb
    up)."""
    return jnp.concatenate([jnp.zeros_like(x[0:1]), x[:-1]], axis=0)


def _resolve01(s, cin=None):
    """(26, M) columns < 2^17 (so each carries at most 1) → 16-bit limbs
    of (Σ s_k 2^16k + cin) mod 2^416, and the (1, M) carry word."""
    w = _carry_word(s >> np.uint32(16), (s == M16).astype(U32), cin)
    return (s + _carry_rows(w)) & M16, w


def k_carry(t, ncols: int = LIMBS):
    """Normalize 26 uint32 columns (< 2^23) to 16-bit limbs, mod 2^416
    (the top column's overflow is dropped, as the ripple drops it)."""
    if ncols != LIMBS:
        raise ValueError("k_carry normalizes 26-limb planes")
    s = (t & M16) + _shift_up(t >> np.uint32(16))    # < 2^16 + 2^7
    return _resolve01(s)[0]


def _cond_sub_raw(x, k_plane):
    """x − k if x ≥ k else x, for normalized 26-limb x and k."""
    d = x - k_plane                                    # u32, wraps
    w = _carry_word((x < k_plane).astype(U32), (x == k_plane).astype(U32))
    norm = (d - _carry_rows(w)) & M16
    no_borrow = (w >> np.uint32(LIMBS)) == 0
    return jnp.where(no_borrow, norm, x)


def k_add(a, b):
    """a + b < 2N (cond-subtracted), matching :func:`..limb_field.add`."""
    return _cond_sub_raw(_resolve01(a + b)[0], _KC["N2"])


def k_sub(a, b):
    """a − b + 2N (< 2^416 for a, b < 2N) as a + 2N + ~b + 1, then
    cond-subtracted below 2N."""
    t = a + _KC["N2"] + (M16 - b)                      # < 3 · 2^16
    s = (t & M16) + _shift_up(t >> np.uint32(16))      # < 2^16 + 3
    one = jnp.ones_like(a[0:1])
    return _cond_sub_raw(_resolve01(s, one)[0], _KC["N2"])


def k_neg(a):
    """2N − a as 2N + ~a + 1 (a < 2N; the 2^416 wrap is dropped)."""
    one = jnp.ones_like(a[0:1])
    return _resolve01(_KC["N2"] + (M16 - a), one)[0]


def k_muls(a, s: int):
    """a · s for small 0 ≤ s ≤ 16, reduced below 2N (value < 32N < 2^416)."""
    if not 0 <= s <= 16:
        raise ValueError("small-scalar multiply supports 0..16")
    x = k_carry(a * np.uint32(s), LIMBS)
    for k in (16, 8, 4, 2):
        x = _cond_sub_raw(x, _KC[f"K{k}"])
    return x


def k_band(a, b, ncols: int):
    """Schoolbook column sums of a·b over planes, pad-and-add form.
    Columns < 52·2^16 < 2^23."""
    t = jnp.zeros((ncols, a.shape[1]), U32)
    for i in range(LIMBS):
        p = a[i:i + 1] * b
        lo = p & M16
        hi = p >> np.uint32(16)
        wl = min(LIMBS, ncols - i)
        if wl > 0:
            t = t + jnp.pad(lo[:wl], ((i, ncols - i - wl), (0, 0)))
        wh = min(LIMBS, ncols - i - 1)
        if wh > 0:
            t = t + jnp.pad(hi[:wh], ((i + 1, ncols - i - 1 - wh), (0, 0)))
    return t


def k_band_mxu(a, b, ncols: int):
    """MXU band product on planes: the column accumulation of
    :func:`k_band` as ONE (ncols, 1352) × (1352, M) f32 matmul against
    the bound selection matrix (:data:`BAND_SEL_T`).  Exact: partial
    terms < 2^16, column sums ≤ 52 terms < 2^22 — inside f32's
    integer-exact range; bit-identical to :func:`k_band` (asserted in
    tests/test_bls_shard.py and scripts/validate_bls_shard.py)."""
    # [:ncols] both LOADS the bound Ref (dot_general rejects raw Refs)
    # and drops the rows a narrow band never needs (ncols=26 halves the
    # m-band matmul).  Prefix slices keep the sublane offset at 0.
    sel = _KC["band"][:ncols]
    los, his = [], []
    for i in range(LIMBS):
        p = a[i:i + 1] * b                  # row i of the outer product
        los.append(p & M16)
        his.append(p >> np.uint32(16))
    # Mosaic converts no unsigned type to or from f32: go through int32
    # (exact: every value here is < 2^22).
    feat = jnp.concatenate(los + his, axis=0).astype(jnp.int32)
    t = jax.lax.dot_general(
        sel, feat.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)  # (ncols, M)
    return t.astype(jnp.int32).astype(U32)


def _k_band_any(a, b, ncols: int):
    """Band-product dispatch: MXU matmul when enabled AND a selection
    matrix rode in with the consts; VPU pad-and-add otherwise."""
    if LF.use_mxu() and _KC.get("band") is not None:
        return k_band_mxu(a, b, ncols)
    return k_band(a, b, ncols)


def k_mont_mul(a, b):
    """Batched Montgomery product on planes — same algorithm and bounds as
    :func:`..limb_field.mont_mul` (full-width reduction).

    The high 26 rows are collected into a FRESH concat: slicing rows
    [26:52] out of a 52-row array would give the value a sublane-offset
    layout, which poisons every later lane-concat it reaches (Mosaic
    can't mix offset layouts in one concatenate)."""
    t = _k_band_any(a, b, 2 * LIMBS)
    t_low = k_carry(t[:LIMBS], LIMBS)
    m = k_carry(_k_band_any(t_low, _KC["NPRIME"], LIMBS), LIMBS)
    u = _k_band_any(m, _KC["N"], 2 * LIMBS)
    s = t + u                                          # < 2^24
    s = (s & M16) + _shift_up(s >> np.uint32(16))      # < 2^16 + 2^8
    # T + mN ≡ 0 (mod R), so the low 26 columns sum to 0 or exactly R:
    # the carry into limb 26 is 1 iff any of them is non-zero.
    cin = (_pack_bits((s[:LIMBS] != 0).astype(U32)) != 0).astype(U32)
    hi = jnp.concatenate([s[k:k + 1] for k in range(LIMBS, 2 * LIMBS)],
                         axis=0)
    # (T + mN)/R < 2N < 2^382 ⇒ the final carry-out is always zero.
    return _resolve01(hi, cin)[0]


def k_is_zero(a):
    """(1, M) bool: a ≡ 0 mod N for lazy values < 8N."""
    acc = None
    for k in range(8):
        eq = jnp.all(a == _KC[f"ZP{k}"], axis=0, keepdims=True)
        acc = eq if acc is None else (acc | eq)
    return acc


def k_fq_inv(a):
    """Fermat ladder a^(p−2); inv(0) = 0.  (26, M) planes."""
    one = jnp.broadcast_to(_KC["ONE"], a.shape)
    pbits = _KC["pbits"]

    def body(i, acc):
        acc = k_mont_mul(acc, acc)
        take = pbits[i, 0] == 1
        return jnp.where(take, k_mont_mul(acc, a), acc)

    return jax.lax.fori_loop(0, P_MINUS_2_BITS.shape[0], body, one)


# ---------------------------------------------------------------------------
# Tower on plane tuples: Fq2 = (c0, c1); Fq6 = 3×Fq2; Fq12 = 2×Fq6
# ---------------------------------------------------------------------------


def _mont_many(pairs):
    """Montgomery products of a list of (a, b) plane pairs → list of planes.

    Inside a kernel that bound a product scratch (``_bind_consts(...,
    mm)``) the products run as a ``fori_loop`` over ONE lane-block-wide
    :func:`k_mont_mul`: Mosaic's compile time grows with the straight-line
    vector code, and a single mont_mul over the lane-concat of an Fq12
    multiply's 54 products compiled for ~40 s.  Elsewhere (eager drives)
    the pairs concatenate along lanes into one wide mont_mul.  Each lane's
    arithmetic is the same either way."""
    n, m = len(pairs), pairs[0][0].shape[1]
    mm = _KC.get("mm")
    if mm is not None and 1 < n <= MM_MAX and m <= mm[0].shape[2]:
        a_ref, b_ref, o_ref = mm
        for i, (a, b) in enumerate(pairs):
            a_ref[i, :, :m] = jnp.broadcast_to(a, (LIMBS, m))
            b_ref[i, :, :m] = jnp.broadcast_to(b, (LIMBS, m))

        def body(i, carry):
            o_ref[i, :, :m] = k_mont_mul(a_ref[i, :, :m], b_ref[i, :, :m])
            return carry

        jax.lax.fori_loop(0, n, body, 0)
        return [o_ref[i, :, :m] for i in range(n)]
    a = jnp.concatenate([p[0] for p in pairs], axis=1)
    b = jnp.concatenate([p[1] for p in pairs], axis=1)
    out = k_mont_mul(a, b)
    m = pairs[0][0].shape[1]
    return [out[:, i * m:(i + 1) * m] for i in range(len(pairs))]


def fq2_add(a, b):
    return (k_add(a[0], b[0]), k_add(a[1], b[1]))


def fq2_sub(a, b):
    return (k_sub(a[0], b[0]), k_sub(a[1], b[1]))


def fq2_neg(a):
    return (k_neg(a[0]), k_neg(a[1]))


def fq2_conj(a):
    return (a[0], k_neg(a[1]))


def fq2_muls(a, s: int):
    return (k_muls(a[0], s), k_muls(a[1], s))


def fq2_mul_by_xi(a):
    """ξ = 1 + u:  (a0 − a1) + (a0 + a1)u."""
    return (k_sub(a[0], a[1]), k_add(a[0], a[1]))


def _fq2_mul_parts(a, b):
    """Karatsuba part list: [a0b0, a1b1, (a0+a1)(b0+b1)]."""
    return [(a[0], b[0]), (a[1], b[1]),
            (k_add(a[0], a[1]), k_add(b[0], b[1]))]


def _fq2_from_parts(p):
    m0, m1, m2 = p
    return (k_sub(m0, m1), k_sub(m2, k_add(m0, m1)))


def fq2_mul(a, b):
    return _fq2_from_parts(_mont_many(_fq2_mul_parts(a, b)))


def fq2_mul_many(pairs):
    """Batch several independent Fq2 products into one mont_mul."""
    parts = []
    for a, b in pairs:
        parts.extend(_fq2_mul_parts(a, b))
    flat = _mont_many(parts)
    return [_fq2_from_parts(flat[3 * i:3 * i + 3]) for i in range(len(pairs))]


def _fq6_mul_pairs(a, b):
    a0, a1, a2 = a
    b0, b1, b2 = b
    return [(a0, b0), (a1, b1), (a2, b2),
            (fq2_add(a0, a1), fq2_add(b0, b1)),
            (fq2_add(a1, a2), fq2_add(b1, b2)),
            (fq2_add(a0, a2), fq2_add(b0, b2))]


def _fq6_from_parts(v):
    v0, v1, v2, v01, v12, v02 = v
    c0 = fq2_add(v0, fq2_mul_by_xi(fq2_sub(v12, fq2_add(v1, v2))))
    c1 = fq2_add(fq2_sub(v01, fq2_add(v0, v1)), fq2_mul_by_xi(v2))
    c2 = fq2_add(fq2_sub(v02, fq2_add(v0, v2)), v1)
    return (c0, c1, c2)


def fq6_mul(a, b):
    return _fq6_from_parts(fq2_mul_many(_fq6_mul_pairs(a, b)))


def fq6_add(a, b):
    return tuple(fq2_add(x, y) for x, y in zip(a, b))


def fq6_sub(a, b):
    return tuple(fq2_sub(x, y) for x, y in zip(a, b))


def fq6_neg(a):
    return tuple(fq2_neg(x) for x in a)


def fq6_mul_by_v(a):
    return (fq2_mul_by_xi(a[2]), a[0], a[1])


def fq12_mul(a, b):
    """Karatsuba-2 over Fq6: 3 Fq6 products in one wide mont_mul."""
    a0, a1 = a
    b0, b1 = b
    pairs = (_fq6_mul_pairs(a0, b0) + _fq6_mul_pairs(a1, b1)
             + _fq6_mul_pairs(fq6_add(a0, a1), fq6_add(b0, b1)))
    flat = fq2_mul_many(pairs)
    v00 = _fq6_from_parts(flat[0:6])
    v11 = _fq6_from_parts(flat[6:12])
    vxx = _fq6_from_parts(flat[12:18])
    c0 = fq6_add(v00, fq6_mul_by_v(v11))
    c1 = fq6_sub(vxx, fq6_add(v00, v11))
    return (c0, c1)


def fq12_sqr(a):
    return fq12_mul(a, a)


def fq12_conj(a):
    return (a[0], fq6_neg(a[1]))


def fq12_select(take, a, b):
    return tuple(tuple((jnp.where(take, x, y), jnp.where(take, u, v))
                       for (x, u), (y, v) in zip(ca, cb))
                 for ca, cb in zip(a, b))


def fq12_one_like(m: int):
    one = jnp.broadcast_to(_KC["ONE"], (LIMBS, m))
    zero = jnp.zeros((LIMBS, m), U32)
    return (((one, zero), (zero, zero), (zero, zero)),
            ((zero, zero), (zero, zero), (zero, zero)))


def fq12_frobenius(a, n: int):
    tab = _KC[f"FROBT{n}"]
    pairs = []
    for i in range(2):
        for j in range(3):
            c = a[i][j]
            if n % 2:
                c = fq2_conj(c)
            g = tab[i][j]
            gb = (jnp.broadcast_to(g[0], c[0].shape),
                  jnp.broadcast_to(g[1], c[1].shape))
            pairs.append((c, gb))
    muls = fq2_mul_many(pairs)
    return ((muls[0], muls[1], muls[2]), (muls[3], muls[4], muls[5]))


def fq2_inv(a):
    n = k_add(k_mont_mul(a[0], a[0]), k_mont_mul(a[1], a[1]))
    ni = k_fq_inv(n)
    return (k_mont_mul(a[0], ni), k_mont_mul(k_neg(a[1]), ni))


def fq6_inv(a):
    a0, a1, a2 = a
    p = fq2_mul_many([(a0, a0), (a1, a2), (a2, a2), (a1, a1),
                      (a0, a1), (a0, a2)])
    a00, a12, a22, a11, a01, a02 = p
    c0 = fq2_sub(a00, fq2_mul_by_xi(a12))
    c1 = fq2_sub(fq2_mul_by_xi(a22), a01)
    c2 = fq2_sub(a11, a02)
    q = fq2_mul_many([(a0, c0), (a2, c1), (a1, c2)])
    nrm = fq2_add(q[0], fq2_mul_by_xi(fq2_add(q[1], q[2])))
    ni = fq2_inv(nrm)
    inv = fq2_mul_many([(c0, ni), (c1, ni), (c2, ni)])
    return (inv[0], inv[1], inv[2])


def fq12_inv(a):
    a0, a1 = a
    s0 = fq6_mul(a0, a0)
    s1 = fq6_mul(a1, a1)
    nrm = fq6_sub(s0, fq6_mul_by_v(s1))
    ni = fq6_inv(nrm)
    return (fq6_mul(a0, ni), fq6_mul(fq6_neg(a1), ni))


def fq12_is_one(a):
    one = fq12_one_like(a[0][0][0].shape[1])
    acc = None
    for i in range(2):
        for j in range(3):
            for u in range(2):
                z = k_is_zero(k_sub(a[i][j][u], one[i][j][u]))
                acc = z if acc is None else (acc & z)
    return acc


# -- plane packing: 32-row blocks ↔ tuples ----------------------------------
#
# Ref I/O uses one 32-row block per Fq plane (26 limb rows + 6 zero rows):
# slicing a ref at a non-multiple-of-8 row gives the value a sublane-offset
# layout, and Mosaic cannot lane-concat mixed-offset pieces (same reason the
# constant blocks are 32-row padded).

BLOCK_ROWS = 32


def pack_planes(planes):
    """List of (26, M) planes → (32·k, M) block layout."""
    m = planes[0].shape[1]
    z = jnp.zeros((BLOCK_ROWS - LIMBS, m), U32)
    out = []
    for p in planes:
        out.append(p)
        out.append(z)
    return jnp.concatenate(out, axis=0)


def unpack_planes(x, k: int):
    return [x[i * BLOCK_ROWS:i * BLOCK_ROWS + LIMBS] for i in range(k)]


def pack_fq12(a):
    return pack_planes([a[i][j][u] for i in range(2) for j in range(3)
                        for u in range(2)])


def unpack_fq12(x):
    c = unpack_planes(x, 12)
    return (((c[0], c[1]), (c[2], c[3]), (c[4], c[5])),
            ((c[6], c[7]), (c[8], c[9]), (c[10], c[11])))


def unpack_fq2s(x, k: int):
    c = unpack_planes(x, 2 * k)
    return [(c[2 * i], c[2 * i + 1]) for i in range(k)]


# ---------------------------------------------------------------------------
# Generic projective point ops (RCB complete addition), G1/G2
# ---------------------------------------------------------------------------


class _G1ops:
    coord_planes = LIMBS
    mul_many = staticmethod(_mont_many)
    add = staticmethod(k_add)
    sub = staticmethod(k_sub)

    @staticmethod
    def b3(t):
        return k_muls(t, 12)

    muls = staticmethod(k_muls)

    @staticmethod
    def zero_is(z):
        return k_is_zero(z)

    @staticmethod
    def one_like(m):
        return jnp.broadcast_to(_KC["ONE"], (LIMBS, m))

    @staticmethod
    def zero_like(m):
        return jnp.zeros((LIMBS, m), U32)


class _G2ops:
    coord_planes = 2 * LIMBS
    mul_many = staticmethod(fq2_mul_many)
    add = staticmethod(fq2_add)
    sub = staticmethod(fq2_sub)

    @staticmethod
    def b3(t):
        return fq2_muls(fq2_mul_by_xi(t), 12)

    muls = staticmethod(fq2_muls)

    @staticmethod
    def zero_is(z):
        return k_is_zero(z[0]) & k_is_zero(z[1])

    @staticmethod
    def one_like(m):
        return (jnp.broadcast_to(_KC["ONE"], (LIMBS, m)),
                jnp.zeros((LIMBS, m), U32))

    @staticmethod
    def zero_like(m):
        return (jnp.zeros((LIMBS, m), U32), jnp.zeros((LIMBS, m), U32))


def point_add(ops, p, q):
    """Complete addition (same formulas/order as limb_curve.point_add)."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    r1 = ops.mul_many([
        (X1, X2), (Y1, Y2), (Z1, Z2),
        (ops.add(X1, Y1), ops.add(X2, Y2)),
        (ops.add(Y1, Z1), ops.add(Y2, Z2)),
        (ops.add(X1, Z1), ops.add(X2, Z2))])
    t0, t1, t2, pxy, pyz, pxz = r1
    s3 = ops.sub(pxy, ops.add(t0, t1))
    s4 = ops.sub(pyz, ops.add(t1, t2))
    s5 = ops.sub(pxz, ops.add(t0, t2))
    b3t2 = ops.b3(t2)
    um = ops.sub(t1, b3t2)
    up = ops.add(t1, b3t2)
    r2 = ops.mul_many([
        (s3, um), (s4, s5), (up, um), (t0, s5), (s4, up), (t0, s3)])
    a_s3um, a_s4s5, a_upum, a_t0s5, a_s4up, a_t0s3 = r2
    X3 = ops.sub(a_s3um, ops.b3(a_s4s5))
    Y3 = ops.add(a_upum, ops.muls(ops.b3(a_t0s5), 3))
    Z3 = ops.add(a_s4up, ops.muls(a_t0s3, 3))
    return (X3, Y3, Z3)


def point_select(ops, take, p, q):
    def sel(a, b):
        if isinstance(a, tuple):
            return tuple(jnp.where(take, x, y) for x, y in zip(a, b))
        return jnp.where(take, a, b)
    return tuple(sel(a, b) for a, b in zip(p, q))


def point_identity(ops, m: int):
    return (ops.zero_like(m), ops.one_like(m), ops.zero_like(m))


def scalar_mul(ops, p, lo, hi, nbits: int = 64):
    """Per-lane double-and-add; lo/hi are (1, M) uint32 scalar words."""
    m = (p[0][0] if isinstance(p[0], tuple) else p[0]).shape[1]
    acc = point_identity(ops, m)

    def body(i, carry):
        acc, base = carry
        word = jnp.where(i < 32, lo, hi)
        bit = (word >> (i.astype(U32) % np.uint32(32))) & np.uint32(1)
        added = point_add(ops, acc, base)
        acc = point_select(ops, bit == 1, added, acc)
        base = point_add(ops, base, base)
        return (acc, base)

    acc, _ = jax.lax.fori_loop(0, nbits, body, (acc, p))
    return acc


# ---------------------------------------------------------------------------
# Miller loop kernel
# ---------------------------------------------------------------------------


LANE_BLOCK = 128  # Mosaic lane-concat pieces must be 128-aligned

# The Miller/prepare/hash kernels' wide-concat mont_mul temporaries brush
# against Mosaic's default 16 MB scoped-VMEM budget (v5e VMEM is far
# larger); raise the per-kernel limit rather than contorting the code.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _line_fq12(A, B, C, m):
    zero = (jnp.zeros((LIMBS, m), U32), jnp.zeros((LIMBS, m), U32))
    return ((A, B, zero), (zero, C, zero))


def _fq2_mul_fq(a, s):
    o = _mont_many([(a[0], s), (a[1], s)])
    return (o[0], o[1])


def _miller_dbl_step(f, T, xP, yP):
    """Doubling half of a Miller iteration: f ← f²·l_{T,T}(P), T ← 2T.
    Line: A = 3X³−2Y²Z, B = −3X²Z·xP, C = 2YZ²·yP."""
    m = xP.shape[1]
    X, Y, Z = T
    XX, YY, ZZ = fq2_mul_many([(X, X), (Y, Y), (Z, Z)])
    X3, Y2Z, X2Z, YZ2 = fq2_mul_many([(X, XX), (YY, Z), (XX, Z), (Y, ZZ)])
    A = fq2_sub(fq2_muls(X3, 3), fq2_muls(Y2Z, 2))
    B = fq2_neg(_fq2_mul_fq(fq2_muls(X2Z, 3), xP))
    C = _fq2_mul_fq(fq2_muls(YZ2, 2), yP)
    l_dbl = _line_fq12(A, B, C, m)
    T2 = point_add(_G2ops, T, T)
    return fq12_mul(fq12_sqr(f), l_dbl), T2


def _miller_add_step(f, T2, Qx, Qy, Q, xP, yP):
    """Addition half: f ← f·l_{T,Q}(P), T ← T + Q (chord through T2, Q)."""
    m = xP.shape[1]
    X, Y, Z = T2
    r = fq2_mul_many([(Qy, Z), (Qx, Z)])
    Nn = fq2_sub(r[0], Y)
    Dd = fq2_sub(r[1], X)
    r2 = fq2_mul_many([(Nn, Qx), (Qy, Dd)])
    A = fq2_sub(r2[0], r2[1])
    B = fq2_neg(_fq2_mul_fq(Nn, xP))
    C = _fq2_mul_fq(Dd, yP)
    l_add = _line_fq12(A, B, C, m)
    T3 = point_add(_G2ops, T2, Q)
    return fq12_mul(f, l_add), T3


def _miller_kernel(cref, xbits_ref, pbits_ref, band_ref, g1_ref, g2_ref,
                   out_ref, *mm):
    """One 63-iteration fori; the add-step runs under ``lax.cond`` on the
    static bit, so the 58 zero bits of |x| (Hamming weight 6) skip the
    add-step's ~38% of the loop's products instead of computing and
    discarding it.  (A fully segment-unrolled variant blew the 16 MB
    scoped-VMEM budget — straight-line segments keep too many
    simultaneously-live buffers; the cond body stays loop-scoped.)"""
    _bind_consts(cref, xbits_ref, pbits_ref, band_ref, mm)
    xP, yP = unpack_planes(g1_ref[:], 2)
    Qx, Qy = unpack_fq2s(g2_ref[:], 2)
    m = xP.shape[1]
    Q = (Qx, Qy, _G2ops.one_like(m))
    f0 = fq12_one_like(m)
    xbits = _KC["xbits"]

    def body(i, carry):
        f, T = carry
        f, T = _miller_dbl_step(f, T, xP, yP)
        bit = xbits[i + 1, 0]  # skip the implicit leading 1
        return jax.lax.cond(
            bit == 1,
            lambda f, T: _miller_add_step(f, T, Qx, Qy, Q, xP, yP),
            lambda f, T: (f, T),
            f, T)

    f, _ = jax.lax.fori_loop(0, X_BITS_MILLER.shape[0], body, (f0, Q))
    out_ref[:] = pack_fq12(fq12_conj(f))  # x < 0


@jax.jit
def miller_kernel_call(g1_planes, g2_planes):
    """g1 (64, M) affine blocks, g2 (128, M) → f (384, M) Fq12 blocks.

    M must be a multiple of 128; the grid runs one 128-lane block per cell
    (bounds both VMEM and per-launch latency)."""
    m = g1_planes.shape[1]
    if m % LANE_BLOCK:
        raise ValueError("pad miller lanes to a multiple of 128")
    g = m // LANE_BLOCK
    return pl.pallas_call(
        _miller_kernel,
        grid=(g,),
        in_specs=_const_specs() + [
            pl.BlockSpec((2 * BLOCK_ROWS, LANE_BLOCK), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((4 * BLOCK_ROWS, LANE_BLOCK), lambda i: (0, i),
                         memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((12 * BLOCK_ROWS, LANE_BLOCK), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((12 * BLOCK_ROWS, m), jnp.uint32),
        scratch_shapes=_mm_scratch(LANE_BLOCK),
        compiler_params=_COMPILER_PARAMS,
    )(*_const_args(), g1_planes, g2_planes)


def _const_block_specs():
    """Const specs for gridded kernels: every cell sees the full blocks."""
    cs = CONSTS_PLANES.shape[0]
    return [pl.BlockSpec((cs, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(BAND_SEL_T.shape, lambda i: (0, 0),
                         memory_space=pltpu.VMEM)]


# ---------------------------------------------------------------------------
# Lane-product kernel (butterfly to 128 class products)
# ---------------------------------------------------------------------------


def _product_kernel(cref, xbits_ref, pbits_ref, band_ref, f_ref, mask_ref,
                    out_ref, *mm, lanes: int):
    _bind_consts(cref, xbits_ref, pbits_ref, band_ref, mm)
    f = unpack_fq12(f_ref[:])
    mask = mask_ref[:]
    f = fq12_select(mask != 0, f, fq12_one_like(lanes))
    w = lanes // 2
    while w >= LANE_BLOCK:
        # roll by w lanes via aligned concat; multiply-accumulate.
        def roll(x):
            return jnp.concatenate([x[:, w:], x[:, :w]], axis=1)

        g = tuple(tuple((roll(c0), roll(c1)) for (c0, c1) in c6) for c6 in f)
        f = fq12_mul(f, g)
        w //= 2
    out_ref[:] = pack_fq12(f)


FQ12_ROWS = 12 * BLOCK_ROWS


@jax.jit
def product_kernel_call(f_planes, mask):
    """Masked lane product, reduced to 128 residue-class products.

    f (384, M) blocks, mask (1, M) int32, M a power of two ≥ 128.  Returns
    (384, M) blocks where lane j holds the product of lanes ≡ j (mod 128);
    the host multiplies the first 128 lanes' values for the total.
    """
    m = f_planes.shape[1]
    if m < LANE_BLOCK or m & (m - 1):
        raise ValueError("lane count must be a power of two ≥ 128")
    return pl.pallas_call(
        partial(_product_kernel, lanes=m),
        in_specs=_const_specs() + [pl.BlockSpec(memory_space=pltpu.VMEM),
                                   pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((12 * BLOCK_ROWS, m), jnp.uint32),
        compiler_params=_COMPILER_PARAMS,
    )(*_const_args(), f_planes, mask)


def _product_chunk_kernel(cref, xbits_ref, pbits_ref, band_ref, f_ref,
                          mask_ref, out_ref, *mm):
    """One 256-lane chunk → 128 residue-class products (lane j and j+128
    hold the same value after the fold; only [0:128] is written)."""
    _bind_consts(cref, xbits_ref, pbits_ref, band_ref, mm)
    f = unpack_fq12(f_ref[:])
    mask = mask_ref[:]
    f = fq12_select(mask != 0, f, fq12_one_like(2 * LANE_BLOCK))
    g = _fq12_roll(f, LANE_BLOCK)
    f = fq12_mul(f, g)
    half = tuple(tuple((c0[:, :LANE_BLOCK], c1[:, :LANE_BLOCK])
                       for (c0, c1) in c6) for c6 in f)
    out_ref[:] = pack_fq12(half)


@jax.jit
def product_chunks_kernel_call(f_planes, mask):
    """Per-chunk masked lane fold: (384, C·256) Miller outputs →
    (384, C·128) residue-class products, one grid cell per chunk.  The
    concatenated output feeds :func:`finalize_kernel_call` directly."""
    m = f_planes.shape[1]
    if m % (2 * LANE_BLOCK):
        raise ValueError("lane count must be C · 256")
    C = m // (2 * LANE_BLOCK)
    return pl.pallas_call(
        _product_chunk_kernel,
        grid=(C,),
        in_specs=_const_specs() + [
            pl.BlockSpec((12 * BLOCK_ROWS, 2 * LANE_BLOCK), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2 * LANE_BLOCK), lambda c: (0, c),
                         memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((12 * BLOCK_ROWS, LANE_BLOCK), lambda c: (0, c),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((12 * BLOCK_ROWS, C * LANE_BLOCK),
                                       jnp.uint32),
        scratch_shapes=_mm_scratch(2 * LANE_BLOCK),
        compiler_params=_COMPILER_PARAMS,
    )(*_const_args(), f_planes, mask)


def _miller_fold_kernel(cref, xbits_ref, pbits_ref, band_ref, g1_ref,
                        g2_ref, mask_ref, out_ref, *mm):
    """FUSED Miller scan + masked per-chunk lane fold: one 256-lane cell
    runs the 63-iteration Miller loop AND the 256→128 residue-class
    product in the same program, so the σ/RLC product fold stops being a
    separate dispatch (VERDICT r5 item 2).  The fold reuses the Miller
    loop's VMEM-resident f — no (384, 256) HBM round-trip between the
    two stages."""
    _bind_consts(cref, xbits_ref, pbits_ref, band_ref, mm)
    xP, yP = unpack_planes(g1_ref[:], 2)
    Qx, Qy = unpack_fq2s(g2_ref[:], 2)
    m = xP.shape[1]                     # 2 · LANE_BLOCK lanes per cell
    Q = (Qx, Qy, _G2ops.one_like(m))
    f0 = fq12_one_like(m)
    xbits = _KC["xbits"]

    def body(i, carry):
        f, T = carry
        f, T = _miller_dbl_step(f, T, xP, yP)
        bit = xbits[i + 1, 0]           # skip the implicit leading 1
        return jax.lax.cond(
            bit == 1,
            lambda f, T: _miller_add_step(f, T, Qx, Qy, Q, xP, yP),
            lambda f, T: (f, T),
            f, T)

    f, _ = jax.lax.fori_loop(0, X_BITS_MILLER.shape[0], body, (f0, Q))
    f = fq12_conj(f)                    # x < 0
    f = fq12_select(mask_ref[:] != 0, f, fq12_one_like(m))
    f = fq12_mul(f, _fq12_roll(f, LANE_BLOCK))
    half = tuple(tuple((c0[:, :LANE_BLOCK], c1[:, :LANE_BLOCK])
                       for (c0, c1) in c6) for c6 in f)
    out_ref[:] = pack_fq12(half)


@jax.jit
def miller_fold_kernel_call(g1_planes, g2_planes, mask):
    """g1 (64, C·256) affine blocks, g2 (128, C·256), mask (1, C·256)
    int32 → (384, C·128) folded residue-class products — the fused twin
    of :func:`miller_kernel_call` + :func:`product_chunks_kernel_call`.
    The output feeds :func:`finalize_kernel_call` directly."""
    m = g1_planes.shape[1]
    if m % (2 * LANE_BLOCK):
        raise ValueError("pad fused miller lanes to a multiple of 256")
    C = m // (2 * LANE_BLOCK)
    return pl.pallas_call(
        _miller_fold_kernel,
        grid=(C,),
        in_specs=_const_specs() + [
            pl.BlockSpec((2 * BLOCK_ROWS, 2 * LANE_BLOCK), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((4 * BLOCK_ROWS, 2 * LANE_BLOCK), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2 * LANE_BLOCK), lambda c: (0, c),
                         memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((12 * BLOCK_ROWS, LANE_BLOCK), lambda c: (0, c),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((12 * BLOCK_ROWS, C * LANE_BLOCK),
                                       jnp.uint32),
        scratch_shapes=_mm_scratch(2 * LANE_BLOCK),
        compiler_params=_COMPILER_PARAMS,
    )(*_const_args(), g1_planes, g2_planes, mask)


# ---------------------------------------------------------------------------
# Sigma kernel: per-chunk RLC-scaled signature aggregation (G2)
# ---------------------------------------------------------------------------
#
# The signature side of the batch equation collapses to ONE pairing lane:
#     ∏ e(−c_i·G, σ_i) = e(−G, Σ c_i·σ_i)
# so instead of one Miller lane per set, each 128-set chunk runs a 64-bit
# G2 double-and-add ladder (the same RLC scalars as the pk side) and a
# lane butterfly to fold the chunk's scaled signatures into one point;
# the XLA glue (:func:`sigma_add`, :func:`sigma_column`) combines the
# per-chunk partials and hands the single aggregate to a dedicated Miller
# cell paired with the constant −G.


def _sigma_kernel(cref, xbits_ref, pbits_ref, band_ref, sig_ref, mask_ref,
                  lo_ref, hi_ref, out_ref, *mm):
    _bind_consts(cref, xbits_ref, pbits_ref, band_ref, mm)
    S = PREP_S
    cols = unpack_fq2s(sig_ref[:], 2)  # [x, y] as Fq2 planes
    live = mask_ref[:] != 0
    pt = point_select(_G2ops, live,
                      (cols[0], cols[1], _G2ops.one_like(S)),
                      point_identity(_G2ops, S))
    scaled = scalar_mul(_G2ops, pt, lo_ref[:], hi_ref[:])
    # Butterfly fold: after log2(S) roll-multiplies every lane holds the
    # full chunk sum.
    w = S // 2
    while w >= 1:
        rolled = tuple(
            (_roll_lanes(c0, w), _roll_lanes(c1, w)) for (c0, c1) in scaled)
        scaled = point_add(_G2ops, scaled, rolled)
        w //= 2
    out_ref[:] = pack_planes([scaled[0][0], scaled[0][1],
                              scaled[1][0], scaled[1][1],
                              scaled[2][0], scaled[2][1]])


@jax.jit
def sigma_kernel_call(sig_cols, mask, lo, hi):
    """sig (128, C·128) affine G2 signature columns, mask/lo/hi (1, C·128)
    → (192, C·128) projective per-chunk Σ c_s·σ_s (every lane of a chunk's
    block holds that chunk's full sum)."""
    m = sig_cols.shape[1]
    if m % PREP_S:
        raise ValueError("sigma lanes must be C · 128")
    C = m // PREP_S
    return pl.pallas_call(
        _sigma_kernel,
        grid=(C,),
        in_specs=_const_specs() + [
            pl.BlockSpec((4 * BLOCK_ROWS, PREP_S), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, PREP_S), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, PREP_S), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, PREP_S), lambda c: (0, c),
                         memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((6 * BLOCK_ROWS, PREP_S), lambda c: (0, c),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((6 * BLOCK_ROWS, C * PREP_S),
                                       jnp.uint32),
        scratch_shapes=_mm_scratch(PREP_S),
        compiler_params=_COMPILER_PARAMS,
    )(*_const_args(), sig_cols, mask, lo, hi)


def sigma_point(partial):
    """(192, 128) sigma-cell output → its chunk sum as one projective
    limb point (3, 2, 26) (lane 0; every lane holds the same sum)."""
    comps = partial.reshape(6, BLOCK_ROWS, PREP_S)[:, :LIMBS, 0]  # (6, 26)
    return comps.reshape(3, 2, LIMBS)


def sigma_add(acc, partial):
    """Accumulate the next chunk's sum into ``acc`` (XLA glue)."""
    from . import limb_curve as LC
    return LC.point_add(LC.G2_OPS, acc, sigma_point(partial))


def sigma_column(acc):
    """Σ over chunks → ONE miller-ready affine G2 column (128,) and its
    identity flag (XLA glue, once per verify call)."""
    is_ident = XP.T.fq2_is_zero(acc[2])
    aff = XP.g2_proj_to_affine(acc[None])[0]                  # (2, 2, 26)
    col = jnp.zeros((4 * BLOCK_ROWS,), jnp.uint32)
    col = col.at[0:LIMBS].set(aff[0, 0])
    col = col.at[BLOCK_ROWS:BLOCK_ROWS + LIMBS].set(aff[0, 1])
    col = col.at[2 * BLOCK_ROWS:2 * BLOCK_ROWS + LIMBS].set(aff[1, 0])
    col = col.at[3 * BLOCK_ROWS:3 * BLOCK_ROWS + LIMBS].set(aff[1, 1])
    return col, is_ident


# ---------------------------------------------------------------------------
# Finalize kernel: full lane fold + in-kernel final exponentiation
# ---------------------------------------------------------------------------


def k_fq12_pow_x_abs(f):
    """f^|x| (BLS parameter ladder), 64 static bits from SMEM."""
    m = f[0][0][0].shape[1]
    one = fq12_one_like(m)
    xbits = _KC["xbits"]

    def body(i, acc):
        acc = fq12_sqr(acc)
        take = xbits[i, 0] == 1
        return fq12_select(take, fq12_mul(acc, f), acc)

    return jax.lax.fori_loop(0, X_BITS_FULL.shape[0], body, one)


def k_pow_u(f):
    """f^u for the (negative) BLS parameter — cyclotomic f only."""
    return fq12_conj(k_fq12_pow_x_abs(f))


def k_final_exp_easy(f):
    """Easy part f^((q⁶−1)(q²+1)) — needs the true Fq12 inverse."""
    m = fq12_mul(fq12_conj(f), fq12_inv(f))
    return fq12_mul(fq12_frobenius(m, 2), m)


def k_final_exp_hard(m):
    """HHT hard part ×3: m^(3·(p⁴−p²+1)/r) for cyclotomic m."""
    m1 = fq12_mul(k_pow_u(m), fq12_conj(m))              # m^(u−1)
    k2 = fq12_mul(k_pow_u(m1), fq12_conj(m1))            # ^(u−1)
    k3 = fq12_mul(k_pow_u(k2), fq12_frobenius(k2, 1))    # ^(u+p)
    k4 = fq12_mul(fq12_mul(k_pow_u(k_pow_u(k3)), fq12_frobenius(k3, 2)),
                  fq12_conj(k3))                         # ^(u²+p²−1)
    return fq12_mul(k4, fq12_mul(fq12_sqr(m), m))


def k_final_exp_cubed(f):
    """f^(3·(q¹²−1)/r) — same HHT decomposition as the host oracle
    (:func:`..pairing.final_exponentiation_cubed`) and the XLA twin
    (:func:`..limb_pairing.final_exponentiation_cubed`)."""
    return k_final_exp_hard(k_final_exp_easy(f))


def _roll_lanes(x, w: int):
    """Rotate lanes left by w.  Aligned concat when both pieces are
    128-multiples; ``pltpu.roll`` for sub-128 shifts."""
    m = x.shape[1]
    if w % LANE_BLOCK == 0 and (m - w) % LANE_BLOCK == 0:
        return jnp.concatenate([x[:, w:], x[:, :w]], axis=1)
    return pltpu.roll(x, m - w, 1)


def _fq12_roll(f, w: int):
    return tuple(tuple((_roll_lanes(c0, w), _roll_lanes(c1, w))
                       for (c0, c1) in c6) for c6 in f)


# -- the final exponentiation as a step program ------------------------------
#
# Straight-line, :func:`k_final_exp_cubed` holds ~30 Fq12 multiplies, and
# Mosaic's compile time grows with straight-line code (one Fq12 multiply
# compiled for ~50 s).  The kernels below run the SAME multiply sequence
# as a fori_loop over the step table of :mod:`.limb_pairing` (its XLA
# final exponentiation scans the same table): each step is dst ←
# T(reg[a])·U(reg[b]) (or a copy of T(reg[a])) with T ∈ {id, conj} and
# U ∈ {id, conj, frob1, frob2}, so ONE Fq12 multiply is compiled.  The
# registers live in a VMEM scratch.

from .limb_pairing import (  # noqa: E402  (the shared step table)
    FINAL_EXP_PROGRAM, _N_REGS, _OP_COPY, _OP_MUL, _R_A, _R_F, _R_I,
    _R_K, _R_M, _R_ONE, _R_X, _T_CONJ, _T_FROB1, _T_FROB2, _T_ID)


def _fq12_frobenius_dyn(a, n):
    """:func:`fq12_frobenius` for a traced ``n`` ∈ {1, 2}."""
    odd = n == 1
    pairs = []
    for i in range(2):
        for j in range(3):
            c = a[i][j]
            c = (c[0], jnp.where(odd, k_neg(c[1]), c[1]))
            g1, g2 = _KC["FROBT1"][i][j], _KC["FROBT2"][i][j]
            pairs.append((c, (jnp.broadcast_to(jnp.where(odd, g1[0], g2[0]),
                                               c[0].shape),
                              jnp.broadcast_to(jnp.where(odd, g1[1], g2[1]),
                                               c[1].shape))))
    muls = fq2_mul_many(pairs)
    return ((muls[0], muls[1], muls[2]), (muls[3], muls[4], muls[5]))


def _fq12_where(take, a, b):
    return tuple(tuple((jnp.where(take, x, y), jnp.where(take, u, v))
                       for (x, u), (y, v) in zip(ca, cb))
                 for ca, cb in zip(a, b))


def _fq12_step(prog, j, get, put, regs):
    """Step ``j`` of a step table over a register file reached through
    ``get(regs, i)`` / ``put(regs, i, value) → regs``."""
    op, dst, ra, ta, rb, tb = (prog[6 * j + k] for k in range(6))
    va = get(regs, ra)
    va = _fq12_where(ta == _T_CONJ, fq12_conj(va), va)
    vb = get(regs, rb)
    vb = _fq12_where(
        tb >= _T_FROB1, _fq12_frobenius_dyn(vb, tb - _T_FROB1 + 1),
        _fq12_where(tb == _T_CONJ, fq12_conj(vb), vb))
    res = _fq12_where(op == _OP_COPY, va, fq12_mul(va, vb))
    return put(regs, dst, res)


def _run_fq12_program(prog, n_steps: int, get, put, regs):
    return jax.lax.fori_loop(
        0, n_steps, lambda j, r: _fq12_step(prog, j, get, put, r), regs)


def _finalize_easy_kernel(cref, xbits_ref, pbits_ref, band_ref, f_ref,
                          out_ref, *mm):
    """(384, 128) residue-class products (dead lanes already 1) → the
    full lane fold F and its Fq12 inverse (rows [0:384] F, [384:768]
    F⁻¹), the inputs of the step program.  The fold's 7 roll-multiplies
    run as one fori_loop (rotate left by 64, 32, …, 1 lanes)."""
    _bind_consts(cref, xbits_ref, pbits_ref, band_ref, mm)
    f = unpack_fq12(f_ref[:])
    m = f[0][0][0].shape[1]

    def fold(i, f):
        w = jnp.right_shift(jnp.int32(m // 2), i)
        g = tuple(tuple((pltpu.roll(c0, m - w, 1), pltpu.roll(c1, m - w, 1))
                        for (c0, c1) in c6) for c6 in f)
        return fq12_mul(f, g)

    f = jax.lax.fori_loop(0, m.bit_length() - 1, fold, f)
    out_ref[:] = jnp.concatenate([pack_fq12(f), pack_fq12(fq12_inv(f))],
                                 axis=0)


def _finalize_hard_kernel(cref, xbits_ref, pbits_ref, band_ref, prog_ref,
                          e_ref, out_ref, regs_ref, *mm):
    """F, F⁻¹ → the final-exponentiation step program (easy part, then
    HHT hard part ×3) → ``∏ == 1`` int32 flag."""
    _bind_consts(cref, xbits_ref, pbits_ref, band_ref, mm)
    e = e_ref[:]
    regs_ref[_R_F] = e[:FQ12_ROWS]
    regs_ref[_R_I] = e[FQ12_ROWS:]
    regs_ref[_R_ONE] = pack_fq12(fq12_one_like(e.shape[1]))

    def get(regs, i):
        return unpack_fq12(regs_ref[i])

    def put(regs, i, v):
        regs_ref[i] = pack_fq12(v)
        return regs

    _run_fq12_program(prog_ref, FINAL_EXP_PROGRAM.shape[0] // 6, get, put,
                      0)
    g = unpack_fq12(regs_ref[_R_X])
    ok = fq12_is_one(g).astype(jnp.int32)  # (1, 128); all lanes equal
    out_ref[0, 0] = ok[0, 0]


def blocks_to_limb_fq12(f_planes):
    """(384, M) kernel block layout → (M, 2, 3, 2, 26) XLA-twin limb
    layout (the :mod:`..limb_pairing` convention)."""
    m = f_planes.shape[1]
    comps = f_planes.reshape(12, BLOCK_ROWS, m)[:, :LIMBS, :]  # (12, 26, M)
    comps = jnp.transpose(comps, (2, 0, 1))                    # (M, 12, 26)
    return comps.reshape(m, 2, 3, 2, LIMBS)


@jax.jit
def finalize_xla_tail(f_planes):
    """(384, 128) → verdict via the scanned XLA twin
    (:mod:`..limb_pairing`) — the Mosaic-free fallback finalize tail."""
    f = blocks_to_limb_fq12(f_planes)               # (128, 2, 3, 2, 26)
    prod = XP._product_reduce(f)
    ok = XP.fq12_is_one(XP.final_exponentiation_cubed(prod))
    return ok.astype(jnp.int32).reshape(1, 1)


def _finalize_call_body(f_planes):
    """Fold an entire batch's (384, M) lane products (M a power of two,
    ≥ 128) into one Fq12, run the shared final exponentiation on-device,
    and return a (1, 1) int32 ``is_one`` flag — the only bytes the host
    ever pulls back for a verify call.

    Widths above 128 are halved with the gridded 256→128 Pallas product
    cells (bounded VMEM per cell); the 128→1 fold + Fq12 inverse and the
    final-exponentiation step program run as two Pallas programs.
    :func:`finalize_xla_tail` is the scanned-XLA fallback."""
    m = f_planes.shape[1]
    if m < LANE_BLOCK or m & (m - 1):
        raise ValueError("lane count must be a power of two ≥ 128")
    while f_planes.shape[1] > LANE_BLOCK:
        ones = jnp.ones((1, f_planes.shape[1]), jnp.int32)
        f_planes = product_chunks_kernel_call(f_planes, ones)
    easy = pl.pallas_call(
        _finalize_easy_kernel,
        in_specs=_const_specs() + [pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((2 * FQ12_ROWS, LANE_BLOCK),
                                       jnp.uint32),
        scratch_shapes=_mm_scratch(LANE_BLOCK),
        compiler_params=_COMPILER_PARAMS,
    )(*_const_args(), f_planes)
    return pl.pallas_call(
        _finalize_hard_kernel,
        in_specs=_const_specs() + [pl.BlockSpec(memory_space=pltpu.SMEM),
                                   pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((_N_REGS, FQ12_ROWS, LANE_BLOCK),
                                   jnp.uint32)] + _mm_scratch(LANE_BLOCK),
        compiler_params=_COMPILER_PARAMS,
    )(*_const_args(), jnp.asarray(FINAL_EXP_PROGRAM), easy)


finalize_kernel_call = jax.jit(_finalize_call_body)
# Donated twin for the dispatcher's hot path: the (384, M) product
# concat is batch-local and never re-read, so its buffer (up to MBs at
# wide M) is recycled in place.  Callers that reuse their input
# (profiling loops, tests) keep the undonated entry above.
finalize_kernel_call_donated = jax.jit(_finalize_call_body,
                                       donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Prepare kernel: G1 aggregation + RLC ladders + affine conversion
# ---------------------------------------------------------------------------

PREP_S = 128  # sets per prepare launch (lane-block aligned)


def _prepare_kernel(cref, xbits_ref, pbits_ref, band_ref, pk_ref, kmask_ref,
                    lo_ref, hi_ref, g1_out_ref, flags_ref, *mm, K: int):
    _bind_consts(cref, xbits_ref, pbits_ref, band_ref, mm)
    S = PREP_S
    acc = point_identity(_G1ops, S)

    def body(k, acc):
        off = k * S
        x, y = unpack_planes(pk_ref[:, pl.ds(off, S)], 2)
        live = kmask_ref[:, pl.ds(off, S)] != 0
        blk = point_select(_G1ops, live, (x, y, _G1ops.one_like(S)),
                           point_identity(_G1ops, S))
        return point_add(_G1ops, acc, blk)

    acc = jax.lax.fori_loop(0, K, body, acc)
    # Live sets with identity aggregates are invalid (blst/PythonBackend
    # rule); reported per-lane and folded into the batch verdict.
    flags_ref[:] = (k_is_zero(acc[2])).astype(jnp.int32)
    scaled = scalar_mul(_G1ops, acc, lo_ref[:], hi_ref[:])
    zi = k_fq_inv(scaled[2])
    xa = k_mont_mul(scaled[0], zi)
    ya = k_mont_mul(scaled[1], zi)
    g1_out_ref[:] = pack_planes([xa, ya])


@partial(jax.jit, static_argnames=("K",))
def prepare_kernel_call(pk_planes, kmask, lo, hi, *, K: int):
    """pk (64, C·K·128) K-major blocks of AFFINE G1 pubkeys per chunk
    (chunk c's key k of set s at column c·K·128 + k·128 + s); kmask
    (1, C·K·128) int32; lo/hi (1, C·128) uint32 RLC scalar words.  The
    grid runs one cell per 128-set chunk.

    Returns (g1_aff (64, C·128) blocks, ident_flags (1, C·128) int32):
    lane s of chunk c holds the affine c_i·aggpk_i, to be paired with
    H(m_i).  The signature side of the RLC lives in ONE extra Miller
    lane built by :func:`sigma_kernel_call` + :func:`sigma_add` /
    :func:`sigma_column`:
    ∏ e(c_i·aggpk_i, H_i) · e(−G, Σ c_i·σ_i) == 1.
    """
    S = PREP_S
    if pk_planes.shape[1] % (K * S):
        raise ValueError("pk lanes must be C · K · 128")
    C = pk_planes.shape[1] // (K * S)
    return pl.pallas_call(
        partial(_prepare_kernel, K=K),
        grid=(C,),
        in_specs=_const_specs() + [
            pl.BlockSpec((2 * BLOCK_ROWS, K * S), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, K * S), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S), lambda c: (0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S), lambda c: (0, c), memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((2 * BLOCK_ROWS, S), lambda c: (0, c),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, S), lambda c: (0, c),
                                memory_space=pltpu.VMEM)),
        out_shape=(jax.ShapeDtypeStruct((2 * BLOCK_ROWS, S * C),
                                        jnp.uint32),
                   jax.ShapeDtypeStruct((1, S * C), jnp.int32)),
        scratch_shapes=_mm_scratch(PREP_S),
        compiler_params=_COMPILER_PARAMS,
    )(*_const_args(), pk_planes, kmask, lo, hi)
