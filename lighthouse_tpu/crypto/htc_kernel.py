"""Pallas TPU kernel for batched hash-to-curve onto G2 (RFC 9380 SSWU).

The message-hashing half of BLS verification — the H(m) of e(pk, H(m)) —
which the reference gets from blst's assembly ``hash_to_g2``
(``/root/reference/crypto/bls/src/impls/blst.rs:14``).  The host computes
``expand_message_xmd`` (SHA-256, microseconds) and ships the two Fq2 field
elements per message; everything algebraic runs on-device, batched over
lanes:

    u → simplified SWU onto E' (branchless 8-candidate sqrt, one 758-bit
    Fq2 ladder) → 3-isogeny to E (projective, no inversions) → u0+u1 point
    add → Budroni–Pintore psi cofactor clearing (two |x|-ladders) → affine.

Each grid cell handles 128 messages as 256 SSWU lanes (u0 block | u1
block interleaved per cell); output columns feed the Miller kernel's G2
input directly.  Constants live in :data:`..pairing_kernel.CONSTS_PLANES`;
the sqrt-ladder exponent bits ride in SMEM like the x/p−2 bit strings.
Host oracles: :func:`..hash_to_curve.map_to_curve_sswu` / ``iso_map`` /
``clear_cofactor`` (asserted equal in tests).
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import limb_field as LF
from . import hash_to_curve as H2C
from .pairing_kernel import (
    _KC, _bind_consts, _const_args, LIMBS, U32, BLOCK_ROWS, LANE_BLOCK,
    X_BITS_FULL, k_mont_mul, k_is_zero, k_sub, k_neg, _cond_sub_raw,
    fq2_add, fq2_sub, fq2_neg, fq2_conj, fq2_mul, fq2_mul_many, fq2_inv,
    point_add, point_select, point_identity, _G2ops,
    pack_planes, unpack_planes, CONSTS_PLANES, _COMPILER_PARAMS,
    _mm_scratch,
)

# LSB-first bits of (p²+7)/16 — the sqrt-ladder exponent (758 bits).
E16_BITS_LSB = np.array(
    [(H2C.E16_EXP >> i) & 1 for i in range(H2C.E16_EXP.bit_length())],
    dtype=np.int32)


def _htc_const_args():
    return _const_args() + (
        jnp.asarray(E16_BITS_LSB.reshape(-1, 1)),)


def _htc_const_specs():
    return [pl.BlockSpec(memory_space=pltpu.VMEM),   # consts
            pl.BlockSpec(memory_space=pltpu.SMEM),   # x bits
            pl.BlockSpec(memory_space=pltpu.SMEM),   # p−2 bits
            pl.BlockSpec(memory_space=pltpu.VMEM),   # band-sel matrix
            pl.BlockSpec(memory_space=pltpu.SMEM)]   # e16 bits


def _mat(c, m: int):
    """Materialize a (26, 1) constant plane to (26, m) REAL lanes.

    ``jnp.broadcast_to`` keeps a lane-broadcast layout inside Mosaic, and
    a later lane-concatenate of broadcast pieces crashes its vector
    layout pass (``vector_extract_rule: limits[i] <= dim(i)``) — observed
    on every H2C constant.  ``pltpu.roll``-era Mosaic provides
    ``pltpu.repeat`` as the explicit materialization; eager/CPU test
    drives (no Mosaic) use the plain broadcast."""
    if _KC.get("in_mosaic"):
        return pltpu.repeat(c, m, 1)
    return jnp.broadcast_to(c, (LIMBS, m))


def _kc2(name: str, m: int):
    """Fq2 constant materialized to (2 × (26, m))."""
    return (_mat(_KC[name + "0"], m), _mat(_KC[name + "1"], m))


def _fq2_zero(m: int):
    return (jnp.zeros((LIMBS, m), U32), jnp.zeros((LIMBS, m), U32))


def _fq2_one(m: int):
    return (_mat(_KC["ONE"], m), jnp.zeros((LIMBS, m), U32))


def _fq2_sel(take, a, b):
    return (jnp.where(take, a[0], b[0]), jnp.where(take, a[1], b[1]))


def k_fq2_eq(a, b):
    """(1, m) bool — equality mod N for lazy (< 2N) inputs."""
    return k_is_zero(k_sub(a[0], b[0])) & k_is_zero(k_sub(a[1], b[1]))


def k_fq2_is_zero(a):
    return k_is_zero(a[0]) & k_is_zero(a[1])


def k_canonical(a):
    """Montgomery-domain lazy plane → canonical (< N) value limbs."""
    v = k_mont_mul(a, jnp.broadcast_to(_KC["RAW_ONE"], a.shape))
    return _cond_sub_raw(v, _KC["N"])


def k_sgn0_fq2(a):
    """RFC 9380 sgn0 over Fq2 — (1, m) int32 ∈ {0, 1}."""
    c0 = k_canonical(a[0])
    c1 = k_canonical(a[1])
    s0 = (c0[0:1] & np.uint32(1)).astype(jnp.int32)
    z0 = jnp.all(c0 == 0, axis=0, keepdims=True).astype(jnp.int32)
    s1 = (c1[0:1] & np.uint32(1)).astype(jnp.int32)
    return s0 | (z0 & s1)


def k_fq2_pow_e16(a):
    """a^((p²+7)/16), LSB-first ladder: base-square and conditional
    multiply share ONE wide mont_mul per bit."""
    m = a[0].shape[1]
    res = _fq2_one(m)
    ebits = _KC["e16"]

    def body(i, carry):
        res, base = carry
        prods = fq2_mul_many([(base, base), (res, base)])
        take = jnp.full((1, m), ebits[i, 0] == 1)
        return (_fq2_sel(take, prods[1], res), prods[0])

    res, _ = jax.lax.fori_loop(0, E16_BITS_LSB.shape[0], body, (res, a))
    return res


def k_sswu_map(t):
    """Simplified SWU onto E' — branchless twin of
    :func:`..hash_to_curve.map_to_curve_sswu` (same outputs, asserted in
    tests).  t: Fq2 planes (2 × (26, m)) → affine (x, y) on E'."""
    m = t[0].shape[1]
    Zc = _kc2("H2C_Z", m)
    tt = fq2_mul(t, t)
    tv1 = fq2_mul(Zc, tt)                           # Z t²
    tv2 = fq2_add(fq2_mul(tv1, tv1), tv1)           # Z²t⁴ + Zt²
    d_zero = k_fq2_is_zero(tv2)
    x1 = fq2_mul(_kc2("H2C_NEGBA", m), fq2_add(_fq2_one(m), fq2_inv(tv2)))
    x1 = _fq2_sel(d_zero, _kc2("H2C_X1EXC", m), x1)
    A = _kc2("H2C_A", m)
    B = _kc2("H2C_B", m)
    gx1 = fq2_add(fq2_mul(fq2_mul(x1, x1), x1), fq2_add(fq2_mul(A, x1), B))
    c = k_fq2_pow_e16(gx1)
    # 8-candidate sqrt (see ..hash_to_curve.sqrt_or_z_times), one
    # candidate per loop step: c·E8⁻ᵏ against gx1 for k < 4 (→ y1), then
    # c·Tₖ against Z·gx1 (→ s); the last square root found wins, as in
    # the straight-line order.
    zgx1 = fq2_mul(Zc, gx1)
    consts = [_KC[f"H2C_E8I{k}{u}"] for k in range(4) for u in (0, 1)] \
        + [_KC[f"H2C_T{k}{u}"] for k in range(4) for u in (0, 1)]

    def candidate(j, carry):
        y1, s, is_qr = carry
        const = []
        for u in (0, 1):
            pick = consts[u]
            for k in range(1, 8):
                pick = jnp.where(j == k, consts[2 * k + u], pick)
            const.append(_mat(pick, m))
        cand = fq2_mul(c, (const[0], const[1]))
        first = j < 4
        target = _fq2_sel(jnp.full((1, m), first), gx1, zgx1)
        ok = k_fq2_eq(fq2_mul(cand, cand), target)
        y1 = _fq2_sel(ok & first, cand, y1)
        s = _fq2_sel(ok & ~first, cand, s)
        return y1, s, is_qr | (ok & first).astype(jnp.int32)

    # is_qr rides the loop as int32: Mosaic cannot carry an i1 vector.
    y1, s, is_qr = jax.lax.fori_loop(
        0, 8, candidate,
        (_fq2_zero(m), _fq2_zero(m), jnp.zeros((1, m), jnp.int32)))
    is_qr = is_qr != 0
    x2 = fq2_mul(tv1, x1)
    y2 = fq2_mul(fq2_mul(tv1, t), s)
    x = _fq2_sel(is_qr, x1, x2)
    y = _fq2_sel(is_qr, y1, y2)
    flip = k_sgn0_fq2(t) != k_sgn0_fq2(y)
    y = _fq2_sel(flip, fq2_neg(y), y)
    return x, y


def k_iso_map_proj(x, y):
    """3-isogeny E' → E as projective output (no inversions): x = XN/XD,
    y·YN/YD → (XN·YD, y·YN·XD, XD·YD).  Twin of
    :func:`..hash_to_curve.iso_map`."""
    m = x[0].shape[1]
    x_2 = fq2_mul(x, x)
    x_3 = fq2_mul(x_2, x)

    def poly(tag, degree, monic):
        acc = _kc2(f"H2C_{tag}0", m)
        pows = (None, x, x_2, x_3)
        terms = []
        for i in range(1, degree + 1):
            if monic and i == degree:
                continue
            terms.append(fq2_mul(_kc2(f"H2C_{tag}{i}", m), pows[i]))
        for tm in terms:
            acc = fq2_add(acc, tm)
        if monic:
            acc = fq2_add(acc, pows[degree])
        return acc

    xn = poly("XN", 3, monic=False)
    xd = poly("XD", 2, monic=True)
    yn = fq2_mul(y, poly("YN", 3, monic=False))
    yd = poly("YD", 3, monic=True)
    return (fq2_mul(xn, yd), fq2_mul(yn, xd), fq2_mul(xd, yd))


def k_g2_neg(p):
    return (p[0], fq2_neg(p[1]), p[2])


def k_g2_identity(m: int):
    """Materialized projective G2 identity (0 : 1 : 0)."""
    return (_fq2_zero(m), _fq2_one(m), _fq2_zero(m))


def k_g2_mul_x_abs(p):
    """[|x|]·P, MSB-first double-and-add over the 64 static x bits."""
    m = p[0][0].shape[1]
    acc = k_g2_identity(m)
    xbits = _KC["xbits"]

    def body(i, acc):
        acc = point_add(_G2ops, acc, acc)
        added = point_add(_G2ops, acc, p)
        take = jnp.full((1, m), xbits[i, 0] == 1)
        return point_select(_G2ops, take, added, acc)

    return jax.lax.fori_loop(0, X_BITS_FULL.shape[0], body, acc)


def k_psi(p):
    """Untwist-Frobenius-twist endomorphism, projective (twin of
    :func:`..hash_to_curve.psi`)."""
    m = p[0][0].shape[1]
    conj = tuple(fq2_conj(c) for c in p)
    return (fq2_mul(_kc2("H2C_PSI_CX", m), conj[0]),
            fq2_mul(_kc2("H2C_PSI_CY", m), conj[1]),
            conj[2])


def k_clear_cofactor(p):
    """Budroni–Pintore: h_eff·P = ([x²]P − [x]P − P) + ψ([x]P − P) +
    ψ²([2]P) — twin of :func:`..hash_to_curve.clear_cofactor`.  The two
    [x]-multiplies run as one two-step loop (one compiled ladder)."""
    def times_x(_i, carry):
        q, _prev = carry
        return k_g2_neg(k_g2_mul_x_abs(q)), q   # [x]q (x < 0)

    t2, t1 = jax.lax.fori_loop(0, 2, times_x, (p, p))  # [x²]P, [x]P
    acc = point_add(_G2ops, t2, k_g2_neg(t1))
    acc = point_add(_G2ops, acc, k_g2_neg(p))
    acc = point_add(_G2ops, acc, k_psi(point_add(_G2ops, t1, k_g2_neg(p))))
    return point_add(_G2ops, acc, k_psi(k_psi(point_add(_G2ops, p, p))))


def _hash_g2_kernel(cref, xbits_ref, pbits_ref, band_ref, e16_ref, u_ref,
                    out_ref, *mm):
    _bind_consts(cref, xbits_ref, pbits_ref, band_ref, mm)
    _KC["e16"] = e16_ref
    # in_mosaic is a trace-time flag: scope it to this trace so an eager /
    # interpret drive of the k_* helpers afterwards doesn't inherit it
    # (pltpu.repeat outside Mosaic would crash — ADVICE r4).
    _KC["in_mosaic"] = True
    try:
        M = LANE_BLOCK
        planes = unpack_planes(u_ref[:], 2)
        t = (planes[0], planes[1])              # (26, 2M): [u0 | u1] blocks
        x, y = k_sswu_map(t)
        q = k_iso_map_proj(x, y)
        # Combine u0 + u1: roll lane halves together (aligned 128-concat).
        rolled = tuple((jnp.concatenate([c0[:, M:], c0[:, :M]], axis=1),
                        jnp.concatenate([c1[:, M:], c1[:, :M]], axis=1))
                       for (c0, c1) in q)
        p = point_add(_G2ops, q, rolled)
        p = tuple((c0[:, :M], c1[:, :M]) for (c0, c1) in p)
        p = k_clear_cofactor(p)
        zi = fq2_inv(p[2])
        xa = fq2_mul(p[0], zi)
        ya = fq2_mul(p[1], zi)
        out_ref[:] = pack_planes([xa[0], xa[1], ya[0], ya[1]])
    finally:
        _KC["in_mosaic"] = False


@jax.jit
def hash_g2_kernel_call(u_planes):
    """u (64, 2M) interleaved per 128-message cell (cell g's lanes
    [g·256, g·256+128) hold u0, [g·256+128, g·256+256) hold u1) →
    (128, M) affine G2 columns, Miller-kernel G2 layout."""
    m2 = u_planes.shape[1]
    if m2 % (2 * LANE_BLOCK):
        raise ValueError("pad hash lanes to 2 · 128 per cell")
    g = m2 // (2 * LANE_BLOCK)
    return pl.pallas_call(
        _hash_g2_kernel,
        grid=(g,),
        in_specs=_htc_const_specs() + [
            pl.BlockSpec((2 * BLOCK_ROWS, 2 * LANE_BLOCK), lambda i: (0, i),
                         memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((4 * BLOCK_ROWS, LANE_BLOCK), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((4 * BLOCK_ROWS, g * LANE_BLOCK),
                                       jnp.uint32),
        scratch_shapes=_mm_scratch(2 * LANE_BLOCK),
        compiler_params=_COMPILER_PARAMS,
    )(*_htc_const_args(), u_planes)


# -- host marshalling ---------------------------------------------------------


from functools import lru_cache


@lru_cache(maxsize=1 << 14)
def _u_cols(msg: bytes) -> bytes:
    """Montgomery u-value columns for one message (2 × 64 rows), memoised
    — repeated messages across verify calls (same attestation data on
    many subnets) skip the expand+to_mont work."""
    u0, u1 = H2C.hash_to_field_fq2(msg, 2)
    out = np.zeros((2, 2 * BLOCK_ROWS), np.uint32)
    for j, u in enumerate((u0, u1)):
        out[j, 0:26] = LF.to_mont(u[0])
        out[j, 32:58] = LF.to_mont(u[1])
    return out.tobytes()


def u_planes_for_messages(messages, n_cells: int) -> np.ndarray:
    """expand_message_xmd each message (host SHA-256) and pack the Fq2
    u-values into the kernel's interleaved Montgomery column layout.
    ``messages``: list of (cell, slot, bytes); cells beyond the list pad
    with zero (still well-defined SSWU inputs, masked downstream)."""
    out = np.zeros((2 * BLOCK_ROWS, n_cells * 2 * LANE_BLOCK), np.uint32)
    for cell, slot, msg in messages:
        cols = np.frombuffer(_u_cols(bytes(msg)), np.uint32).reshape(2, -1)
        base = cell * 2 * LANE_BLOCK
        out[:, base + slot] = cols[0]
        out[:, base + LANE_BLOCK + slot] = cols[1]
    return out
