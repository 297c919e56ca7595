// Native BLS12-381 multi-pairing — the host fast path of the runtime.
//
// Role: the latency tier of BLS verification (single gossip-block proposer
// checks, small batches) where the TPU's fixed dispatch latency dominates,
// and the fast host oracle for tests.  The batch path stays on the TPU
// (lighthouse_tpu/crypto/pairing_kernel.py); this is the native analogue of
// the reference's blst host calls (/root/reference/crypto/bls/src/impls/
// blst.rs:36-119) — portable C++ (uint64 Montgomery + __int128), no asm.
//
// The math mirrors the repo's RFC-anchored python oracle
// (lighthouse_tpu/crypto/{fields,pairing}.py) and the device kernel's
// formulation (limb_pairing.py):
//   - tower Fq2 = Fq[u]/(u²+1), Fq6 = Fq2[v]/(v³-ξ), ξ = 1+u,
//     Fq12 = Fq6[w]/(w²-v)
//   - Miller loop over |x| = 0xd201000000010000 (MSB-first, leading bit
//     implicit), lines as (A + B·v + C·v·w) with the w³·(2YZ²) scaling
//     killed by the final exponentiation; f conjugated at the end (x<0)
//   - final exponentiation CUBED via the Hayashida–Hayasaka–Teruya ladder
//     3·(p⁴−p²+1)/r = (u−1)²·(u+p)·(u²+p²−1) + 3  — identical for the
//     only consumer, the == 1 check (GT has prime order r ≠ 3)
//
// Contract: callers pass AFFINE, ON-CURVE, non-infinity points in standard
// (non-Montgomery) little-endian 6×u64 limbs; subgroup/validity checks
// happen at deserialization on the python side.  Constants come from the
// generated header (scripts/gen_native_consts.py).

#include <cstdint>
#include <cstring>

#include "bls381_consts.h"

typedef unsigned __int128 u128;

// --------------------------------------------------------------------------
// Fp: 6×u64 little-endian, Montgomery form (R = 2^384)
// --------------------------------------------------------------------------

struct Fp { uint64_t l[6]; };

static inline void fp_zero(Fp &a) { std::memset(a.l, 0, sizeof a.l); }

static inline bool fp_is_zero(const Fp &a) {
    uint64_t v = 0;
    for (int i = 0; i < 6; i++) v |= a.l[i];
    return v == 0;
}

static inline bool fp_eq(const Fp &a, const Fp &b) {
    uint64_t v = 0;
    for (int i = 0; i < 6; i++) v |= a.l[i] ^ b.l[i];
    return v == 0;
}

// a += b with carry out
static inline uint64_t add6(uint64_t *a, const uint64_t *b) {
    u128 c = 0;
    for (int i = 0; i < 6; i++) {
        c += (u128)a[i] + b[i];
        a[i] = (uint64_t)c;
        c >>= 64;
    }
    return (uint64_t)c;
}

// a -= b with borrow out
static inline uint64_t sub6(uint64_t *a, const uint64_t *b) {
    u128 br = 0;
    for (int i = 0; i < 6; i++) {
        u128 d = (u128)a[i] - b[i] - br;
        a[i] = (uint64_t)d;
        br = (d >> 64) ? 1 : 0;
    }
    return (uint64_t)br;
}

static inline bool geq6(const uint64_t *a, const uint64_t *b) {
    for (int i = 5; i >= 0; i--) {
        if (a[i] != b[i]) return a[i] > b[i];
    }
    return true;
}

static inline void fp_add(Fp &r, const Fp &a, const Fp &b) {
    r = a;
    uint64_t c = add6(r.l, b.l);
    if (c || geq6(r.l, FP_P)) sub6(r.l, FP_P);
}

static inline void fp_sub(Fp &r, const Fp &a, const Fp &b) {
    r = a;
    if (sub6(r.l, b.l)) add6(r.l, FP_P);
}

static inline void fp_neg(Fp &r, const Fp &a) {
    // Alias-safe (callers write fp_neg(y, y)): build P − a in a
    // temporary — overwriting r first would corrupt an aliased input.
    if (fp_is_zero(a)) { r = a; return; }
    Fp t;
    for (int i = 0; i < 6; i++) t.l[i] = FP_P[i];
    sub6(t.l, a.l);
    r = t;
}

static inline void fp_dbl(Fp &r, const Fp &a) { fp_add(r, a, a); }

// CIOS Montgomery multiplication.
static void fp_mul(Fp &r, const Fp &a, const Fp &b) {
    uint64_t t[8] = {0};
    for (int i = 0; i < 6; i++) {
        u128 c = 0;
        uint64_t ai = a.l[i];
        for (int j = 0; j < 6; j++) {
            c += (u128)t[j] + (u128)ai * b.l[j];
            t[j] = (uint64_t)c;
            c >>= 64;
        }
        c += t[6];
        t[6] = (uint64_t)c;
        t[7] = (uint64_t)(c >> 64);

        uint64_t m = t[0] * FP_INV;
        c = (u128)t[0] + (u128)m * FP_P[0];
        c >>= 64;
        for (int j = 1; j < 6; j++) {
            c += (u128)t[j] + (u128)m * FP_P[j];
            t[j - 1] = (uint64_t)c;
            c >>= 64;
        }
        c += t[6];
        t[5] = (uint64_t)c;
        t[6] = t[7] + (uint64_t)(c >> 64);
        t[7] = 0;
    }
    for (int i = 0; i < 6; i++) r.l[i] = t[i];
    if (t[6] || geq6(r.l, FP_P)) sub6(r.l, FP_P);
}

static inline void fp_sqr(Fp &r, const Fp &a) { fp_mul(r, a, a); }

static void fp_from_limbs(Fp &r, const uint64_t *in) {  // standard -> Mont
    Fp t, r2;
    std::memcpy(t.l, in, 48);
    std::memcpy(r2.l, FP_R2, 48);
    fp_mul(r, t, r2);
}

static const Fp *fp_one() { return (const Fp *)FP_ONE_MONT; }

// --------------------------------------------------------------------------
// Fq2 = Fq[u]/(u²+1)
// --------------------------------------------------------------------------

struct Fp2 { Fp c0, c1; };

static inline void fp2_add(Fp2 &r, const Fp2 &a, const Fp2 &b) {
    fp_add(r.c0, a.c0, b.c0); fp_add(r.c1, a.c1, b.c1);
}
static inline void fp2_sub(Fp2 &r, const Fp2 &a, const Fp2 &b) {
    fp_sub(r.c0, a.c0, b.c0); fp_sub(r.c1, a.c1, b.c1);
}
static inline void fp2_neg(Fp2 &r, const Fp2 &a) {
    fp_neg(r.c0, a.c0); fp_neg(r.c1, a.c1);
}
static inline void fp2_conj(Fp2 &r, const Fp2 &a) {
    r.c0 = a.c0; fp_neg(r.c1, a.c1);
}
static inline void fp2_dbl(Fp2 &r, const Fp2 &a) { fp2_add(r, a, a); }

static void fp2_mul(Fp2 &r, const Fp2 &a, const Fp2 &b) {
    // Karatsuba: (a0b0 - a1b1) + ((a0+a1)(b0+b1) - a0b0 - a1b1) u
    Fp t0, t1, s0, s1, m;
    fp_mul(t0, a.c0, b.c0);
    fp_mul(t1, a.c1, b.c1);
    fp_add(s0, a.c0, a.c1);
    fp_add(s1, b.c0, b.c1);
    fp_mul(m, s0, s1);
    fp_sub(r.c0, t0, t1);
    fp_sub(m, m, t0);
    fp_sub(r.c1, m, t1);
}

static void fp2_sqr(Fp2 &r, const Fp2 &a) {
    // (a0+a1)(a0-a1) + (2 a0 a1) u
    Fp s, d, m;
    fp_add(s, a.c0, a.c1);
    fp_sub(d, a.c0, a.c1);
    fp_mul(m, a.c0, a.c1);
    fp_mul(r.c0, s, d);
    fp_dbl(r.c1, m);
}

static void fp2_mul_fp(Fp2 &r, const Fp2 &a, const Fp &s) {
    fp_mul(r.c0, a.c0, s); fp_mul(r.c1, a.c1, s);
}

// ξ·a with ξ = 1 + u:  (a0 - a1) + (a0 + a1) u
static inline void fp2_mul_xi(Fp2 &r, const Fp2 &a) {
    Fp t0, t1;
    fp_sub(t0, a.c0, a.c1);
    fp_add(t1, a.c0, a.c1);
    r.c0 = t0; r.c1 = t1;
}

static void fp_inv(Fp &r, const Fp &a);  // fwd

static void fp2_inv(Fp2 &r, const Fp2 &a) {
    // (a0 - a1 u) / (a0² + a1²)
    Fp d, t0, t1;
    fp_sqr(t0, a.c0);
    fp_sqr(t1, a.c1);
    fp_add(d, t0, t1);
    fp_inv(d, d);
    fp_mul(r.c0, a.c0, d);
    fp_mul(t0, a.c1, d);
    fp_neg(r.c1, t0);
}

static inline bool fp2_is_zero(const Fp2 &a) {
    return fp_is_zero(a.c0) && fp_is_zero(a.c1);
}
static inline bool fp2_eq(const Fp2 &a, const Fp2 &b) {
    return fp_eq(a.c0, b.c0) && fp_eq(a.c1, b.c1);
}

// Fermat inversion a^(p-2); MSB-first scan of p-2.  Used O(1) per call.
static void fp_inv(Fp &r, const Fp &a) {
    uint64_t e[6];
    std::memcpy(e, FP_P, 48);
    e[0] -= 2;  // p is odd, no borrow
    Fp acc = *fp_one();
    bool started = false;
    for (int i = 5; i >= 0; i--) {
        for (int b = 63; b >= 0; b--) {
            if (started) fp_sqr(acc, acc);
            if ((e[i] >> b) & 1) {
                if (started) fp_mul(acc, acc, a);
                else { acc = a; started = true; }
            }
        }
    }
    r = acc;
}

// --------------------------------------------------------------------------
// Fq6 = Fq2[v]/(v³ - ξ)
// --------------------------------------------------------------------------

struct Fp6 { Fp2 c0, c1, c2; };

static inline void fp6_add(Fp6 &r, const Fp6 &a, const Fp6 &b) {
    fp2_add(r.c0, a.c0, b.c0); fp2_add(r.c1, a.c1, b.c1);
    fp2_add(r.c2, a.c2, b.c2);
}
static inline void fp6_sub(Fp6 &r, const Fp6 &a, const Fp6 &b) {
    fp2_sub(r.c0, a.c0, b.c0); fp2_sub(r.c1, a.c1, b.c1);
    fp2_sub(r.c2, a.c2, b.c2);
}
static inline void fp6_neg(Fp6 &r, const Fp6 &a) {
    fp2_neg(r.c0, a.c0); fp2_neg(r.c1, a.c1); fp2_neg(r.c2, a.c2);
}

static void fp6_mul(Fp6 &r, const Fp6 &a, const Fp6 &b) {
    // Toom/Karatsuba (6 Fq2 muls):
    // c0 = a0b0 + ξ((a1+a2)(b1+b2) - a1b1 - a2b2)
    // c1 = (a0+a1)(b0+b1) - a0b0 - a1b1 + ξ a2b2
    // c2 = (a0+a2)(b0+b2) - a0b0 - a2b2 + a1b1
    Fp2 v0, v1, v2, t0, t1, t2, x;
    fp2_mul(v0, a.c0, b.c0);
    fp2_mul(v1, a.c1, b.c1);
    fp2_mul(v2, a.c2, b.c2);

    fp2_add(t0, a.c1, a.c2);
    fp2_add(t1, b.c1, b.c2);
    fp2_mul(t2, t0, t1);
    fp2_sub(t2, t2, v1);
    fp2_sub(t2, t2, v2);
    fp2_mul_xi(x, t2);
    Fp2 c0; fp2_add(c0, v0, x);

    fp2_add(t0, a.c0, a.c1);
    fp2_add(t1, b.c0, b.c1);
    fp2_mul(t2, t0, t1);
    fp2_sub(t2, t2, v0);
    fp2_sub(t2, t2, v1);
    fp2_mul_xi(x, v2);
    Fp2 c1; fp2_add(c1, t2, x);

    fp2_add(t0, a.c0, a.c2);
    fp2_add(t1, b.c0, b.c2);
    fp2_mul(t2, t0, t1);
    fp2_sub(t2, t2, v0);
    fp2_sub(t2, t2, v2);
    Fp2 c2; fp2_add(c2, t2, v1);

    r.c0 = c0; r.c1 = c1; r.c2 = c2;
}

static void fp6_sqr(Fp6 &r, const Fp6 &a) { fp6_mul(r, a, a); }

// v·a = (ξ a2, a0, a1)
static void fp6_mul_by_v(Fp6 &r, const Fp6 &a) {
    Fp2 t;
    fp2_mul_xi(t, a.c2);
    Fp2 a0 = a.c0, a1 = a.c1;
    r.c0 = t; r.c1 = a0; r.c2 = a1;
}

static void fp6_inv(Fp6 &r, const Fp6 &a) {
    // Standard: A = a0² - ξ a1 a2, B = ξ a2² - a0 a1, C = a1² - a0 a2,
    // F = a0 A + ξ(a2 B + a1 C);  r = (A, B, C)/F.
    Fp2 A, B, C, t, x, F2;
    fp2_sqr(t, a.c0);
    fp2_mul(x, a.c1, a.c2);
    fp2_mul_xi(x, x);
    fp2_sub(A, t, x);

    fp2_sqr(t, a.c2);
    fp2_mul_xi(t, t);
    fp2_mul(x, a.c0, a.c1);
    fp2_sub(B, t, x);

    fp2_sqr(t, a.c1);
    fp2_mul(x, a.c0, a.c2);
    fp2_sub(C, t, x);

    fp2_mul(t, a.c2, B);
    fp2_mul(x, a.c1, C);
    fp2_add(t, t, x);
    fp2_mul_xi(t, t);
    fp2_mul(x, a.c0, A);
    fp2_add(F2, x, t);

    fp2_inv(F2, F2);
    fp2_mul(r.c0, A, F2);
    fp2_mul(r.c1, B, F2);
    fp2_mul(r.c2, C, F2);
}

// --------------------------------------------------------------------------
// Fq12 = Fq6[w]/(w² - v)
// --------------------------------------------------------------------------

struct Fp12 { Fp6 c0, c1; };

static void fp12_one(Fp12 &r) {
    std::memset(&r, 0, sizeof r);
    r.c0.c0.c0 = *fp_one();
}

static void fp12_mul(Fp12 &r, const Fp12 &a, const Fp12 &b) {
    // Karatsuba: (a0b0 + v a1b1) + ((a0+a1)(b0+b1) - a0b0 - a1b1) w
    Fp6 t0, t1, s0, s1, m, x;
    fp6_mul(t0, a.c0, b.c0);
    fp6_mul(t1, a.c1, b.c1);
    fp6_add(s0, a.c0, a.c1);
    fp6_add(s1, b.c0, b.c1);
    fp6_mul(m, s0, s1);
    fp6_sub(m, m, t0);
    fp6_sub(m, m, t1);
    fp6_mul_by_v(x, t1);
    fp6_add(r.c0, t0, x);
    r.c1 = m;
}

static void fp12_sqr(Fp12 &r, const Fp12 &a) {
    // Complex squaring over Fq6[w], w² = v:
    //   c0' = (c0 + c1)(c0 + v·c1) − c0c1 − v·c0c1,  c1' = 2 c0c1
    Fp6 t0, t1, m, x;
    fp6_add(t0, a.c0, a.c1);
    fp6_mul_by_v(x, a.c1);
    fp6_add(t1, a.c0, x);
    fp6_mul(m, a.c0, a.c1);
    fp6_mul(t0, t0, t1);
    fp6_sub(t0, t0, m);
    fp6_mul_by_v(x, m);
    fp6_sub(r.c0, t0, x);
    fp6_add(r.c1, m, m);
}

// Granger–Scott cyclotomic squaring, for elements of the cyclotomic
// subgroup (post-easy-part only).  With a = (g0 + g1 v + g2 v²) +
// (h0 + h1 v + h2 v²) w and w² = v, the Fq4 subalgebra pairs are
// (g0, h1), (h0, g2), (g1, h2); per pair an Fq4 squaring
//   A = x² + ξ y²,  B = 2xy
// then the cyclotomic recombination (validated against the python
// fields oracle on cyclotomic elements in tests):
//   g0' = 3(g0² + ξh1²) − 2g0     h1' = 3·(2 g0 h1) + 2h1
//   g1' = 3(h0² + ξg2²) − 2g1     h2' = 3·(2 g2 h0) + 2h2
//   g2' = 3(g1² + ξh2²) − 2g2     h0' = 3·ξ·(2 g1 h2) + 2h0
static void fp12_cyclo_sqr(Fp12 &r, const Fp12 &a) {
    const Fp2 &g0 = a.c0.c0, &g1 = a.c0.c1, &g2 = a.c0.c2;
    const Fp2 &h0 = a.c1.c0, &h1 = a.c1.c1, &h2 = a.c1.c2;
    Fp2 t0, t1, s;

    Fp2 A0, B0;                             // pair (g0, h1)
    fp2_sqr(t0, g0);
    fp2_sqr(t1, h1);
    fp2_mul_xi(s, t1);
    fp2_add(A0, t0, s);                     // g0² + ξh1²
    fp2_add(s, g0, h1);
    fp2_sqr(s, s);
    fp2_sub(s, s, t0);
    fp2_sub(B0, s, t1);                     // 2 g0 h1

    Fp2 A1, B1;                             // pair (h0, g2)
    fp2_sqr(t0, h0);
    fp2_sqr(t1, g2);
    fp2_mul_xi(s, t1);
    fp2_add(A1, t0, s);                     // h0² + ξg2²
    fp2_add(s, h0, g2);
    fp2_sqr(s, s);
    fp2_sub(s, s, t0);
    fp2_sub(B1, s, t1);                     // 2 g2 h0

    Fp2 A2, B2;                             // pair (g1, h2)
    fp2_sqr(t0, g1);
    fp2_sqr(t1, h2);
    fp2_mul_xi(s, t1);
    fp2_add(A2, t0, s);                     // g1² + ξh2²
    fp2_add(s, g1, h2);
    fp2_sqr(s, s);
    fp2_sub(s, s, t0);
    fp2_sub(B2, s, t1);                     // 2 g1 h2

    Fp12 o;
    fp2_sub(t0, A0, g0); fp2_dbl(t0, t0); fp2_add(o.c0.c0, t0, A0);
    fp2_sub(t0, A1, g1); fp2_dbl(t0, t0); fp2_add(o.c0.c1, t0, A1);
    fp2_sub(t0, A2, g2); fp2_dbl(t0, t0); fp2_add(o.c0.c2, t0, A2);
    fp2_mul_xi(t1, B2);
    fp2_add(t0, t1, h0); fp2_dbl(t0, t0); fp2_add(o.c1.c0, t0, t1);
    fp2_add(t0, B0, h1); fp2_dbl(t0, t0); fp2_add(o.c1.c1, t0, B0);
    fp2_add(t0, B1, h2); fp2_dbl(t0, t0); fp2_add(o.c1.c2, t0, B1);
    r = o;
}

static inline void fp12_conj(Fp12 &r, const Fp12 &a) {
    r.c0 = a.c0; fp6_neg(r.c1, a.c1);
}

static void fp12_inv(Fp12 &r, const Fp12 &a) {
    // (c0 - c1 w) / (c0² - v c1²)
    Fp6 t0, t1, d;
    fp6_sqr(t0, a.c0);
    fp6_sqr(t1, a.c1);
    fp6_mul_by_v(t1, t1);
    fp6_sub(d, t0, t1);
    fp6_inv(d, d);
    fp6_mul(r.c0, a.c0, d);
    fp6_mul(t0, a.c1, d);
    fp6_neg(r.c1, t0);
}

static bool fp12_is_one(const Fp12 &a) {
    if (!fp_eq(a.c0.c0.c0, *fp_one())) return false;
    const Fp *z = &a.c0.c0.c1;
    // remaining 11 Fp coefficients must be zero
    for (int i = 1; i < 12; i++) {
        if (!fp_is_zero(((const Fp *)&a)[i])) return false;
    }
    (void)z;
    return true;
}

// Frobenius^n (n = 1..3): fq6 coeff i -> conj^n(a_i)·XI_3[n]^i;
// fq12 w-part additionally ·XI_6[n].
static void fp2_frob(Fp2 &r, const Fp2 &a, int n) {
    if (n & 1) fp2_conj(r, a); else r = a;
}

static void fp6_frob(Fp6 &r, const Fp6 &a, int n) {
    Fp2 t;
    fp2_frob(r.c0, a.c0, n);
    fp2_frob(t, a.c1, n);
    fp2_mul(r.c1, t, *(const Fp2 *)FROB_XI_3[n]);
    fp2_frob(t, a.c2, n);
    fp2_mul(r.c2, t, *(const Fp2 *)FROB_XI_3_SQ[n]);
}

static void fp12_frob(Fp12 &r, const Fp12 &a, int n) {
    fp6_frob(r.c0, a.c0, n);
    Fp6 t;
    fp6_frob(t, a.c1, n);
    const Fp2 *g = (const Fp2 *)FROB_XI_6[n];
    fp2_mul(r.c1.c0, t.c0, *g);
    fp2_mul(r.c1.c1, t.c1, *g);
    fp2_mul(r.c1.c2, t.c2, *g);
}

// --------------------------------------------------------------------------
// Miller loop: G2 homogeneous projective, lines as (A + B·v + C·v·w)
// --------------------------------------------------------------------------

struct G1Aff { Fp x, y; };
struct G2Aff { Fp2 x, y; };
struct G2Proj { Fp2 X, Y, Z; };

static const uint64_t X_ABS = 0xd201000000010000ULL;  // |BLS x|; x < 0

// fq6 × sparse (A + B·v): (a0A + ξa2B) + (a0B + a1A)v + (a1B + a2A)v²,
// Karatsuba on the first two coefficients — 5 fq2 muls.
static void fp6_mul_by_ab(Fp6 &r, const Fp6 &a, const Fp2 &A, const Fp2 &B) {
    Fp2 m1, m2, m3, m4, m5, s, t;
    fp2_mul(m1, a.c0, A);
    fp2_mul(m2, a.c1, B);
    fp2_add(s, a.c0, a.c1);
    fp2_add(t, A, B);
    fp2_mul(m3, s, t);
    fp2_sub(m3, m3, m1);
    fp2_sub(m3, m3, m2);                    // a0B + a1A
    fp2_mul(m4, a.c2, A);
    fp2_mul(m5, a.c2, B);
    fp2_mul_xi(t, m5);
    fp2_add(r.c0, m1, t);
    r.c1 = m3;
    fp2_add(r.c2, m2, m4);
}

// fq6 × (C·v): ξa2C + a0C·v + a1C·v² — 3 fq2 muls.
static void fp6_mul_by_cv(Fp6 &r, const Fp6 &a, const Fp2 &C) {
    Fp2 t0, t1, t2;
    fp2_mul(t0, a.c0, C);
    fp2_mul(t1, a.c1, C);
    fp2_mul(t2, a.c2, C);
    fp2_mul_xi(r.c0, t2);
    r.c1 = t0;
    r.c2 = t1;
}

// Sparse mul by a line (A + B·v + C·v·w) — 13 fq2 muls vs 18 generic.
static void fp12_mul_by_line(Fp12 &f, const Fp2 &A, const Fp2 &B,
                             const Fp2 &C) {
    // l = l0 + l1·w with l0 = (A, B, 0), l1 = (0, C, 0):
    // f' = (f0·l0 + v·(f1·l1)) + ((f0+f1)·(l0+l1) − f0·l0 − f1·l1)·w
    // and l0 + l1 = (A, B+C, 0).
    Fp6 t0, t1, s0, m, x;
    Fp2 bc;
    fp6_mul_by_ab(t0, f.c0, A, B);
    fp6_mul_by_cv(t1, f.c1, C);
    fp6_add(s0, f.c0, f.c1);
    fp2_add(bc, B, C);
    fp6_mul_by_ab(m, s0, A, bc);
    fp6_sub(m, m, t0);
    fp6_sub(m, m, t1);
    fp6_mul_by_v(x, t1);
    fp6_add(f.c0, t0, x);
    f.c1 = m;
}

// Doubling step: line l_{T,T}(P)·w³·(2YZ²) and T ← 2T.
//   A = 3X³ − 2Y²Z, B = −3X²Z·xP, C = 2YZ²·yP
static void dbl_step(G2Proj &T, const G1Aff &P, Fp2 &A, Fp2 &B, Fp2 &C) {
    Fp2 XX, YY, ZZ, X3, Y2Z, X2Z, YZ2, t;
    fp2_sqr(XX, T.X);
    fp2_sqr(YY, T.Y);
    fp2_sqr(ZZ, T.Z);
    fp2_mul(X3, XX, T.X);          // X³
    fp2_mul(Y2Z, YY, T.Z);         // Y²Z
    fp2_mul(X2Z, XX, T.Z);         // X²Z
    fp2_mul(YZ2, T.Y, ZZ);         // YZ²

    // A = 3X³ − 2Y²Z
    fp2_dbl(t, X3); fp2_add(t, t, X3);
    Fp2 u; fp2_dbl(u, Y2Z);
    fp2_sub(A, t, u);
    // B = −3X²Z·xP
    fp2_dbl(t, X2Z); fp2_add(t, t, X2Z);
    fp2_mul_fp(t, t, P.x);
    fp2_neg(B, t);
    // C = 2YZ²·yP
    fp2_dbl(t, YZ2);
    fp2_mul_fp(C, t, P.y);

    // T ← 2T (homogeneous projective doubling, a = 0):
    //   W = 3X², S = YZ, Bb = XYS, H = W² − 8Bb,
    //   X' = 2HS, Y' = W(4Bb − H) − 8Y²S², Z' = 8S³
    Fp2 W, S, Bb, H, t2;
    fp2_dbl(W, XX); fp2_add(W, W, XX);
    fp2_mul(S, T.Y, T.Z);
    fp2_mul(t, T.X, T.Y);
    fp2_mul(Bb, t, S);
    fp2_sqr(H, W);
    fp2_dbl(t, Bb); fp2_dbl(t, t); fp2_dbl(t, t);   // 8Bb
    fp2_sub(H, H, t);
    fp2_mul(t, H, S);
    fp2_dbl(T.X, t);                                 // X' = 2HS
    fp2_dbl(t, Bb); fp2_dbl(t, t);                   // 4Bb
    fp2_sub(t, t, H);
    fp2_mul(t, W, t);                                // W(4Bb − H)
    fp2_mul(t2, YY, S);
    fp2_mul(t2, t2, S);                              // Y²S²
    fp2_dbl(t2, t2); fp2_dbl(t2, t2); fp2_dbl(t2, t2);  // 8Y²S²
    fp2_sub(T.Y, t, t2);
    fp2_sqr(t, S);
    fp2_mul(t, t, S);                                // S³
    fp2_dbl(t, t); fp2_dbl(t, t); fp2_dbl(T.Z, t);   // Z' = 8S³
}

// Addition step: chord l_{T,Q}(P)·w³·D and T ← T + Q (Q affine).
//   N = y₂Z − Y, D = x₂Z − X; A = N·x₂ − y₂·D, B = −N·xP, C = D·yP
static void add_step(G2Proj &T, const G2Aff &Q, const G1Aff &P,
                     Fp2 &A, Fp2 &B, Fp2 &C) {
    Fp2 N, D, t, u;
    fp2_mul(t, Q.y, T.Z);
    fp2_sub(N, t, T.Y);
    fp2_mul(t, Q.x, T.Z);
    fp2_sub(D, t, T.X);

    fp2_mul(t, N, Q.x);
    fp2_mul(u, Q.y, D);
    fp2_sub(A, t, u);
    fp2_mul_fp(t, N, P.x);
    fp2_neg(B, t);
    fp2_mul_fp(C, D, P.y);

    // T ← T + Q (mixed homogeneous projective add; T ≠ ±Q inside the
    // Miller loop for prime-order inputs):
    //   U = N, V = D, VV = V², VVV = V³, R = VV·X,
    //   Aa = U²Z − VVV − 2R, X' = V·Aa, Y' = U(R − Aa) − VVV·Y, Z' = VVV·Z
    Fp2 VV, VVV, Rr, Aa, t2;
    fp2_sqr(VV, D);
    fp2_mul(VVV, VV, D);
    fp2_mul(Rr, VV, T.X);
    fp2_sqr(t, N);
    fp2_mul(t, t, T.Z);
    fp2_sub(t, t, VVV);
    fp2_dbl(t2, Rr);
    fp2_sub(Aa, t, t2);
    fp2_mul(T.X, D, Aa);
    fp2_sub(t, Rr, Aa);
    fp2_mul(t, N, t);
    fp2_mul(t2, VVV, T.Y);
    fp2_sub(T.Y, t, t2);
    fp2_mul(T.Z, VVV, T.Z);
}

// f ← f_{|x|,Q}(P) accumulated INTO f (callers chain pairs), conjugation
// applied by the caller once at the end.
static void miller_loop_acc(Fp12 &f, const G1Aff &P, const G2Aff &Q) {
    G2Proj T;
    T.X = Q.x; T.Y = Q.y;
    std::memset(&T.Z, 0, sizeof T.Z);
    T.Z.c0 = *fp_one();
    Fp12 g;
    fp12_one(g);
    Fp2 A, B, C;
    // MSB-first over |x| with the leading 1 implicit.
    for (int i = 62; i >= 0; i--) {
        fp12_sqr(g, g);
        dbl_step(T, P, A, B, C);
        fp12_mul_by_line(g, A, B, C);
        if ((X_ABS >> i) & 1) {
            add_step(T, Q, P, A, B, C);
            fp12_mul_by_line(g, A, B, C);
        }
    }
    fp12_mul(f, f, g);
}

// --------------------------------------------------------------------------
// Final exponentiation (cubed): HHT x-ladder — mirrors
// pairing.final_exponentiation_cubed / limb_pairing.
// --------------------------------------------------------------------------

static void pow_x_abs(Fp12 &r, const Fp12 &g) {
    // g^|x|, square-and-multiply MSB-first (|x| = 0xd201000000010000).
    // Inputs are cyclotomic (post-easy-part), so the squarings use the
    // Granger–Scott formulas (~3× cheaper than generic).
    Fp12 acc = g;
    for (int i = 62; i >= 0; i--) {
        fp12_cyclo_sqr(acc, acc);
        if ((X_ABS >> i) & 1) fp12_mul(acc, acc, g);
    }
    r = acc;
}

static void pow_u(Fp12 &r, const Fp12 &g) {  // g^u, u = -|x|; cyclotomic g
    Fp12 t;
    pow_x_abs(t, g);
    fp12_conj(r, t);
}

static void final_exp_cubed(Fp12 &r, const Fp12 &f) {
    Fp12 f1, m, m1, k2, k3, k4, t, u;
    // easy part: f^(q^6-1) then ^(q^2+1)
    fp12_conj(t, f);
    fp12_inv(u, f);
    fp12_mul(f1, t, u);
    fp12_frob(t, f1, 2);
    fp12_mul(m, t, f1);
    // hard part ladder
    pow_u(t, m); fp12_conj(u, m); fp12_mul(m1, t, u);
    pow_u(t, m1); fp12_conj(u, m1); fp12_mul(k2, t, u);
    pow_u(t, k2); fp12_frob(u, k2, 1); fp12_mul(k3, t, u);
    pow_u(t, k3); pow_u(t, t);
    fp12_frob(u, k3, 2); fp12_mul(t, t, u);
    fp12_conj(u, k3); fp12_mul(k4, t, u);
    fp12_sqr(t, m); fp12_mul(t, t, m);
    fp12_mul(r, k4, t);
}

// --------------------------------------------------------------------------
// G2 jacobian arithmetic over Fp2 (x = X/Z², y = Y/Z³; infinity Z = 0) —
// the curve half of hash-to-curve and scalar multiplication.
// --------------------------------------------------------------------------

struct G2Jac { Fp2 X, Y, Z; };

static inline bool fp2j_is_inf(const G2Jac &p) { return fp2_is_zero(p.Z); }

static void g2j_dbl(G2Jac &r, const G2Jac &p) {
    if (fp2j_is_inf(p)) { r = p; return; }
    Fp2 A, B, Cc, D, E, F2, X3, Y3, Z3, t;
    fp2_sqr(A, p.X);
    fp2_sqr(B, p.Y);
    fp2_sqr(Cc, B);
    fp2_add(t, p.X, B);
    fp2_sqr(t, t);
    fp2_sub(t, t, A);
    fp2_sub(t, t, Cc);
    fp2_dbl(D, t);                  // 2((X+Y²)² − X² − Y⁴)
    fp2_dbl(E, A); fp2_add(E, E, A);  // 3X²
    fp2_sqr(F2, E);
    fp2_sub(X3, F2, D);
    fp2_sub(X3, X3, D);
    fp2_sub(t, D, X3);
    fp2_mul(Y3, E, t);
    fp2_dbl(t, Cc); fp2_dbl(t, t); fp2_dbl(t, t);  // 8Y⁴
    fp2_sub(Y3, Y3, t);
    fp2_mul(t, p.Y, p.Z);
    fp2_dbl(Z3, t);
    r.X = X3; r.Y = Y3; r.Z = Z3;
}

static void g2j_add(G2Jac &r, const G2Jac &p, const G2Jac &q) {
    if (fp2j_is_inf(p)) { r = q; return; }
    if (fp2j_is_inf(q)) { r = p; return; }
    Fp2 Z1Z1, Z2Z2, U1, U2, S1, S2, H, Rr, t;
    fp2_sqr(Z1Z1, p.Z);
    fp2_sqr(Z2Z2, q.Z);
    fp2_mul(U1, p.X, Z2Z2);
    fp2_mul(U2, q.X, Z1Z1);
    fp2_mul(t, p.Y, q.Z);
    fp2_mul(S1, t, Z2Z2);
    fp2_mul(t, q.Y, p.Z);
    fp2_mul(S2, t, Z1Z1);
    fp2_sub(H, U2, U1);
    fp2_sub(Rr, S2, S1);
    if (fp2_is_zero(H)) {
        if (fp2_is_zero(Rr)) { g2j_dbl(r, p); return; }
        std::memset(&r, 0, sizeof r);      // P + (−P) = O
        r.X.c0 = *fp_one(); r.Y.c0 = *fp_one();
        fp_zero(r.Z.c0); fp_zero(r.Z.c1);
        return;
    }
    Fp2 HH, HHH, V, X3, Y3, Z3;
    fp2_sqr(HH, H);
    fp2_mul(HHH, HH, H);
    fp2_mul(V, U1, HH);
    fp2_sqr(X3, Rr);
    fp2_sub(X3, X3, HHH);
    fp2_sub(X3, X3, V);
    fp2_sub(X3, X3, V);
    fp2_sub(t, V, X3);
    fp2_mul(Y3, Rr, t);
    fp2_mul(t, S1, HHH);
    fp2_sub(Y3, Y3, t);
    fp2_mul(t, p.Z, q.Z);
    fp2_mul(Z3, t, H);
    r.X = X3; r.Y = Y3; r.Z = Z3;
}

static inline void g2j_neg(G2Jac &r, const G2Jac &p) {
    r.X = p.X; fp2_neg(r.Y, p.Y); r.Z = p.Z;
}

static void g2j_from_affine(G2Jac &r, const Fp2 &x, const Fp2 &y) {
    r.X = x; r.Y = y;
    r.Z.c0 = *fp_one(); fp_zero(r.Z.c1);
}

static bool g2j_to_affine(Fp2 &x, Fp2 &y, const G2Jac &p) {
    if (fp2j_is_inf(p)) return false;
    Fp2 zi, zi2, zi3;
    fp2_inv(zi, p.Z);
    fp2_sqr(zi2, zi);
    fp2_mul(zi3, zi2, zi);
    fp2_mul(x, p.X, zi2);
    fp2_mul(y, p.Y, zi3);
    return true;
}

// [|x|]P for the BLS parameter (64-bit MSB ladder).
static void g2j_mul_xabs(G2Jac &r, const G2Jac &p) {
    G2Jac acc = p;
    for (int i = 62; i >= 0; i--) {
        g2j_dbl(acc, acc);
        if ((X_ABS >> i) & 1) g2j_add(acc, acc, p);
    }
    r = acc;
}

// Generic scalar mul, scalar as 4 LE u64 limbs (256-bit ladder).
static void g2j_mul_scalar(G2Jac &r, const G2Jac &p, const uint64_t *s) {
    G2Jac acc;
    std::memset(&acc, 0, sizeof acc);
    acc.X.c0 = *fp_one(); acc.Y.c0 = *fp_one();
    bool started = false;
    for (int i = 3; i >= 0; i--) {
        for (int b = 63; b >= 0; b--) {
            if (started) g2j_dbl(acc, acc);
            if ((s[i] >> b) & 1) {
                if (started) g2j_add(acc, acc, p);
                else { acc = p; started = true; }
            }
        }
    }
    if (!started) { std::memset(&acc.Z, 0, sizeof acc.Z); }
    r = acc;
}

// --------------------------------------------------------------------------
// Hash-to-curve (curve half): SSWU → 3-isogeny → Budroni–Pintore cofactor
// — mirrors the RFC-anchored host oracle (lighthouse_tpu/crypto/
// hash_to_curve.py), constants from the generated header.
// --------------------------------------------------------------------------

static inline const Fp2 *c2(const uint64_t arr[2][6]) {
    return (const Fp2 *)arr;
}

// ω-candidate square root: (is_qr, root) with root² = α or Z·α
// (the branchless 8-candidate scheme; host oracle `sqrt_or_z_times`).
static bool fp2_sqrt_or_z(Fp2 &root, const Fp2 &alpha) {
    // c = α^((p²+7)/16) via the 761-bit header exponent.
    Fp2 c;
    c.c0 = *fp_one(); fp_zero(c.c1);
    bool started = false;
    for (int i = 11; i >= 0; i--) {
        for (int b = 63; b >= 0; b--) {
            if (started) fp2_sqr(c, c);
            if ((H2C_E16_EXP[i] >> b) & 1) {
                if (started) fp2_mul(c, c, alpha);
                else { c = alpha; started = true; }
            }
        }
    }
    Fp2 cand, sq;
    for (int k = 0; k < 4; k++) {
        fp2_mul(cand, c, *c2(H2C_E8_INV_POWS[k]));
        fp2_sqr(sq, cand);
        if (fp2_eq(sq, alpha)) { root = cand; return true; }
    }
    Fp2 za;
    fp2_mul(za, *c2(H2C_Z_SSWU), alpha);
    for (int k = 0; k < 4; k++) {
        fp2_mul(cand, c, *c2(H2C_T_KS[k]));
        fp2_sqr(sq, cand);
        if (fp2_eq(sq, za)) { root = cand; return false; }
    }
    // unreachable for α ≠ 0 (some 8th root of unity matches)
    root = c;
    return false;
}

static int fp2_sgn0(const Fp2 &a) {
    // RFC 9380 sgn0 for Fq2: sign of c0, or of c1 when c0 == 0 —
    // computed on the STANDARD (non-Montgomery) representative.
    Fp one_std, s0, s1;
    std::memset(&one_std, 0, sizeof one_std);
    one_std.l[0] = 1;
    fp_mul(s0, a.c0, one_std);
    fp_mul(s1, a.c1, one_std);
    int sign_0 = (int)(s0.l[0] & 1);
    bool zero_0 = fp_is_zero(s0);
    int sign_1 = (int)(s1.l[0] & 1);
    return sign_0 | (zero_0 ? sign_1 : 0);
}

// g(x) = x³ + A'x + B' on the SSWU twist curve.
static void gx_twist(Fp2 &r, const Fp2 &x) {
    Fp2 t;
    fp2_sqr(t, x);
    fp2_mul(t, t, x);
    Fp2 ax;
    fp2_mul(ax, *c2(H2C_A_TWIST), x);
    fp2_add(t, t, ax);
    fp2_add(r, t, *c2(H2C_B_TWIST));
}

// Simplified SWU onto E' (non-constant-time; hashes public messages).
static void sswu_map(Fp2 &x, Fp2 &y, const Fp2 &t) {
    Fp2 tv1, tv2, x1, gx1;
    fp2_sqr(tv1, t);
    fp2_mul(tv1, *c2(H2C_Z_SSWU), tv1);        // Z t²
    fp2_sqr(tv2, tv1);
    fp2_add(tv2, tv2, tv1);                     // Z²t⁴ + Zt²
    if (fp2_is_zero(tv2)) {
        Fp2 za;
        fp2_mul(za, *c2(H2C_Z_SSWU), *c2(H2C_A_TWIST));
        fp2_inv(za, za);
        fp2_mul(x1, *c2(H2C_B_TWIST), za);      // B / (Z·A)
    } else {
        Fp2 inv, nb, ia;
        fp2_inv(ia, *c2(H2C_A_TWIST));
        fp2_neg(nb, *c2(H2C_B_TWIST));
        fp2_mul(nb, nb, ia);                    // −B/A
        fp2_inv(inv, tv2);
        Fp2 onep;
        onep.c0 = *fp_one(); fp_zero(onep.c1);
        fp2_add(inv, inv, onep);                // 1 + 1/tv2
        fp2_mul(x1, nb, inv);
    }
    gx_twist(gx1, x1);
    Fp2 root;
    if (fp2_sqrt_or_z(root, gx1)) {
        x = x1; y = root;
    } else {
        // x2 = Zt²·x1 and g(x2) = (Zt²)³·g(x1) = Z²t⁶ · (Z·g(x1));
        // sqrt_or_z returned root² = Z·g(x1), so y2 = Z·t³·root — three
        // Fq2 muls instead of a second 761-bit exponentiation (this
        // branch runs for ~half of hash-derived inputs).
        fp2_mul(x, tv1, x1);
        Fp2 t3;
        fp2_sqr(t3, t);
        fp2_mul(t3, t3, t);                 // t³
        fp2_mul(y, t3, root);
        fp2_mul(y, *c2(H2C_Z_SSWU), y);     // Z·t³·root
    }
    if (fp2_sgn0(t) != fp2_sgn0(y)) fp2_neg(y, y);
}

static void poly_eval(Fp2 &r, const uint64_t coeffs[][2][6], int n,
                      const Fp2 &x) {
    std::memset(&r, 0, sizeof r);
    for (int i = n - 1; i >= 0; i--) {
        Fp2 t;
        fp2_mul(t, r, x);
        fp2_add(r, t, *c2(coeffs[i]));
    }
}

// 3-isogeny E' -> E; returns false for infinity (vanishing denominator).
static bool iso_map(Fp2 &xo, Fp2 &yo, const Fp2 &x, const Fp2 &y) {
    Fp2 xn, xd, yn, yd;
    poly_eval(xn, H2C_ISO_X_NUM, 4, x);
    poly_eval(xd, H2C_ISO_X_DEN, 3, x);
    poly_eval(yn, H2C_ISO_Y_NUM, 4, x);
    poly_eval(yd, H2C_ISO_Y_DEN, 4, x);
    if (fp2_is_zero(xd) || fp2_is_zero(yd)) return false;
    Fp2 inv;
    fp2_inv(inv, xd);
    fp2_mul(xo, xn, inv);
    fp2_inv(inv, yd);
    fp2_mul(yo, yn, inv);
    fp2_mul(yo, yo, y);
    return true;
}

// ψ(x, y) = (cx·conj(x), cy·conj(y)) on jacobian coords: conj applies
// coordinate-wise and the multipliers adjust (Z conj as well).
static void g2j_psi(G2Jac &r, const G2Jac &p) {
    Fp2 x, y;
    if (!g2j_to_affine(x, y, p)) { r = p; return; }
    Fp2 cx, cy;
    fp2_conj(x, x);
    fp2_conj(y, y);
    fp2_mul(cx, *c2(H2C_PSI_CX), x);
    fp2_mul(cy, *c2(H2C_PSI_CY), y);
    g2j_from_affine(r, cx, cy);
}

// Budroni–Pintore: h_eff·P = ([x²]P − [x]P − P) + ψ([x]P − P) + ψ²([2]P)
static void clear_cofactor(G2Jac &r, const G2Jac &p) {
    G2Jac t1, t2, acc, tmp, np, nt1;
    g2j_mul_xabs(t1, p);
    g2j_neg(t1, t1);               // [x]P (x < 0)
    g2j_mul_xabs(t2, t1);
    g2j_neg(t2, t2);               // [x²]P
    g2j_neg(nt1, t1);
    g2j_neg(np, p);
    g2j_add(acc, t2, nt1);
    g2j_add(acc, acc, np);
    g2j_add(tmp, t1, np);
    g2j_psi(tmp, tmp);
    g2j_add(acc, acc, tmp);
    g2j_add(tmp, p, p);
    g2j_psi(tmp, tmp);
    g2j_psi(tmp, tmp);
    g2j_add(r, acc, tmp);
}

// --------------------------------------------------------------------------
// G1 aggregation (jacobian): pubkey sums for fast_aggregate_verify and
// the shared-keygroup dedup in the tpu backend's batch marshalling.
// --------------------------------------------------------------------------

struct G1Jac { Fp X, Y, Z; };  // x = X/Z², y = Y/Z³; infinity: Z = 0

static void g1j_dbl(G1Jac &r, const G1Jac &p) {
    if (fp_is_zero(p.Z)) { r = p; return; }
    Fp A, B, Cc, D, t;
    fp_sqr(A, p.X);                 // X²
    fp_sqr(B, p.Y);                 // Y²
    fp_sqr(Cc, B);                  // Y⁴
    fp_add(t, p.X, B);
    fp_sqr(t, t);
    fp_sub(t, t, A);
    fp_sub(t, t, Cc);
    fp_dbl(D, t);                   // D = 2((X+Y²)² − X² − Y⁴)
    Fp E;
    fp_dbl(E, A); fp_add(E, E, A);  // 3X²
    Fp F2;
    fp_sqr(F2, E);
    Fp X3;
    fp_sub(X3, F2, D);
    fp_sub(X3, X3, D);              // E² − 2D
    Fp Y3;
    fp_sub(t, D, X3);
    fp_mul(Y3, E, t);
    Fp c8;
    fp_dbl(c8, Cc); fp_dbl(c8, c8); fp_dbl(c8, c8);  // 8Y⁴
    fp_sub(Y3, Y3, c8);
    Fp Z3;
    fp_mul(t, p.Y, p.Z);
    fp_dbl(Z3, t);
    r.X = X3; r.Y = Y3; r.Z = Z3;
}

// Mixed add: q affine (never infinity — callers filter).
static void g1j_add_aff(G1Jac &r, const G1Jac &p, const Fp &qx,
                        const Fp &qy) {
    if (fp_is_zero(p.Z)) {
        r.X = qx; r.Y = qy; r.Z = *fp_one();
        return;
    }
    Fp Z2, U2, S2, H, Rr, t;
    fp_sqr(Z2, p.Z);
    fp_mul(U2, qx, Z2);
    fp_mul(t, qy, Z2);
    fp_mul(S2, t, p.Z);
    fp_sub(H, U2, p.X);
    fp_sub(Rr, S2, p.Y);
    if (fp_is_zero(H)) {
        if (fp_is_zero(Rr)) { g1j_dbl(r, p); return; }
        r.X = *fp_one(); r.Y = *fp_one();  // P + (−P) = O (Z = 0)
        fp_zero(r.Z);
        return;
    }
    Fp HH, HHH, V;
    fp_sqr(HH, H);
    fp_mul(HHH, HH, H);
    fp_mul(V, p.X, HH);
    Fp X3;
    fp_sqr(X3, Rr);
    fp_sub(X3, X3, HHH);
    fp_sub(X3, X3, V);
    fp_sub(X3, X3, V);
    Fp Y3;
    fp_sub(t, V, X3);
    fp_mul(Y3, Rr, t);
    fp_mul(t, p.Y, HHH);
    fp_sub(Y3, Y3, t);
    Fp Z3;
    fp_mul(Z3, p.Z, H);
    r.X = X3; r.Y = Y3; r.Z = Z3;
}

// --------------------------------------------------------------------------
// C API
// --------------------------------------------------------------------------

extern "C" {

// n pairs; g1: n×12 u64 (x,y | 6 LE limbs each, standard form);
// g2: n×24 u64 (x.c0, x.c1, y.c0, y.c1).  Returns 1 iff
// prod_i e(P_i, Q_i) == 1.  Points must be affine, on-curve,
// non-infinity (validated python-side).
int bls381_multi_pairing_is_one(const uint64_t *g1, const uint64_t *g2,
                                uint64_t n) {
    Fp12 f;
    fp12_one(f);
    for (uint64_t i = 0; i < n; i++) {
        G1Aff P;
        fp_from_limbs(P.x, g1 + i * 12);
        fp_from_limbs(P.y, g1 + i * 12 + 6);
        G2Aff Q;
        fp_from_limbs(Q.x.c0, g2 + i * 24);
        fp_from_limbs(Q.x.c1, g2 + i * 24 + 6);
        fp_from_limbs(Q.y.c0, g2 + i * 24 + 12);
        fp_from_limbs(Q.y.c1, g2 + i * 24 + 18);
        miller_loop_acc(f, P, Q);
    }
    Fp12 fc, out;
    fp12_conj(fc, f);           // x < 0
    final_exp_cubed(out, fc);
    return fp12_is_one(out) ? 1 : 0;
}

// Raw product of Miller loops + cubed final exp, for oracle cross-checks:
// writes the 12 Fq coefficients (standard form, 6 LE limbs each, the
// (c0|c1)(a0,a1,a2)(fp0,fp1) nesting) to out[144].
void bls381_multi_pairing_gt(const uint64_t *g1, const uint64_t *g2,
                             uint64_t n, uint64_t *out) {
    Fp12 f;
    fp12_one(f);
    for (uint64_t i = 0; i < n; i++) {
        G1Aff P;
        fp_from_limbs(P.x, g1 + i * 12);
        fp_from_limbs(P.y, g1 + i * 12 + 6);
        G2Aff Q;
        fp_from_limbs(Q.x.c0, g2 + i * 24);
        fp_from_limbs(Q.x.c1, g2 + i * 24 + 6);
        fp_from_limbs(Q.y.c0, g2 + i * 24 + 12);
        fp_from_limbs(Q.y.c1, g2 + i * 24 + 18);
        miller_loop_acc(f, P, Q);
    }
    Fp12 fc, res;
    fp12_conj(fc, f);
    final_exp_cubed(res, fc);
    // Montgomery -> standard: multiply by 1 (mont_mul with literal 1).
    Fp one_std;
    std::memset(&one_std, 0, sizeof one_std);
    one_std.l[0] = 1;
    const Fp *coeffs = (const Fp *)&res;
    for (int i = 0; i < 12; i++) {
        Fp s;
        fp_mul(s, coeffs[i], one_std);
        std::memcpy(out + i * 6, s.l, 48);
    }
}

// Hash-to-curve curve half: u = (u0.c0, u0.c1, u1.c0, u1.c1) as 4×6 LE
// limbs (standard form); writes the affine G2 point (x.c0, x.c1, y.c0,
// y.c1) to out[24].  Returns 1 (the output is never infinity for
// hash-derived u with overwhelming probability; 0 on the pathological
// infinity case).
int bls381_hash_to_g2_u(const uint64_t *u, uint64_t *out) {
    Fp2 u0, u1;
    fp_from_limbs(u0.c0, u);
    fp_from_limbs(u0.c1, u + 6);
    fp_from_limbs(u1.c0, u + 12);
    fp_from_limbs(u1.c1, u + 18);

    G2Jac q0, q1, acc;
    std::memset(&q0, 0, sizeof q0);
    std::memset(&q1, 0, sizeof q1);
    Fp2 x, y, xi, yi;
    sswu_map(x, y, u0);
    if (iso_map(xi, yi, x, y)) g2j_from_affine(q0, xi, yi);
    sswu_map(x, y, u1);
    if (iso_map(xi, yi, x, y)) g2j_from_affine(q1, xi, yi);
    g2j_add(acc, q0, q1);
    clear_cofactor(acc, acc);

    Fp2 xa, ya;
    if (!g2j_to_affine(xa, ya, acc)) return 0;
    Fp one_std, t;
    std::memset(&one_std, 0, sizeof one_std);
    one_std.l[0] = 1;
    fp_mul(t, xa.c0, one_std); std::memcpy(out, t.l, 48);
    fp_mul(t, xa.c1, one_std); std::memcpy(out + 6, t.l, 48);
    fp_mul(t, ya.c0, one_std); std::memcpy(out + 12, t.l, 48);
    fp_mul(t, ya.c1, one_std); std::memcpy(out + 18, t.l, 48);
    return 1;
}

// [s]P for affine G2 P (24 u64) and 256-bit scalar s (4 LE u64); writes
// the affine product.  Returns 0 if the result is infinity.
int bls381_g2_mul(const uint64_t *p, const uint64_t *scalar,
                  uint64_t *out) {
    Fp2 x, y;
    fp_from_limbs(x.c0, p);
    fp_from_limbs(x.c1, p + 6);
    fp_from_limbs(y.c0, p + 12);
    fp_from_limbs(y.c1, p + 18);
    G2Jac j, r;
    g2j_from_affine(j, x, y);
    g2j_mul_scalar(r, j, scalar);
    Fp2 xa, ya;
    if (!g2j_to_affine(xa, ya, r)) return 0;
    Fp one_std, t;
    std::memset(&one_std, 0, sizeof one_std);
    one_std.l[0] = 1;
    fp_mul(t, xa.c0, one_std); std::memcpy(out, t.l, 48);
    fp_mul(t, xa.c1, one_std); std::memcpy(out + 6, t.l, 48);
    fp_mul(t, ya.c0, one_std); std::memcpy(out + 12, t.l, 48);
    fp_mul(t, ya.c1, one_std); std::memcpy(out + 18, t.l, 48);
    return 1;
}

// The curve half of a G2 signature's decompression, for its x (standard
// form, 12 u64): a y with y² = x³ + 4(1 + u), then the endomorphism
// subgroup check ψ(P) == [x]P (x the BLS parameter; host oracle
// `hash_to_curve.g2_subgroup_check_fast`) — membership is the same for
// (x, ±y), so the caller picks the sign its flag bit names.  Writes y
// (standard form, 12 u64).  Returns 0 if x is not on the curve, 2 if the
// point is on it but outside G2, 1 otherwise.
int bls381_g2_y_checked(const uint64_t *x_std, uint64_t *y_out) {
    Fp2 x, rhs, b, y;
    fp_from_limbs(x.c0, x_std);
    fp_from_limbs(x.c1, x_std + 6);
    fp2_sqr(rhs, x);
    fp2_mul(rhs, rhs, x);
    fp_add(b.c0, *fp_one(), *fp_one());
    fp_add(b.c0, b.c0, b.c0);           // 4
    b.c1 = b.c0;                        // 4(1 + u)
    fp2_add(rhs, rhs, b);
    if (fp2_is_zero(rhs)) return 2;     // y = 0: a point of order 2
    if (!fp2_sqrt_or_z(y, rhs)) return 0;
    G2Jac p, xp;
    g2j_from_affine(p, x, y);
    g2j_mul_xabs(xp, p);
    g2j_neg(xp, xp);                    // [x]P, x < 0
    // ψ(P) from the affine P, compared with the jacobian [x]P without an
    // inversion: X == ψx·Z², Y == ψy·Z³.
    Fp2 px, py, z2, z3, t2;
    fp2_conj(px, x);
    fp2_mul(px, *c2(H2C_PSI_CX), px);
    fp2_conj(py, y);
    fp2_mul(py, *c2(H2C_PSI_CY), py);
    if (fp2j_is_inf(xp)) return 2;
    fp2_sqr(z2, xp.Z);
    fp2_mul(z3, z2, xp.Z);
    fp2_mul(t2, px, z2);
    if (!fp2_eq(t2, xp.X)) return 2;
    fp2_mul(t2, py, z3);
    if (!fp2_eq(t2, xp.Y)) return 2;
    Fp one_std, t;
    std::memset(&one_std, 0, sizeof one_std);
    one_std.l[0] = 1;
    fp_mul(t, y.c0, one_std); std::memcpy(y_out, t.l, 48);
    fp_mul(t, y.c1, one_std); std::memcpy(y_out + 6, t.l, 48);
    return 1;
}

// Sum n affine G1 points (12 u64 each, standard form, non-infinity —
// callers filter identities).  Writes the affine sum to out[12]; returns
// 1 on a finite sum, 0 if the sum is the identity (out untouched).
int bls381_g1_aggregate(const uint64_t *pts, uint64_t n, uint64_t *out) {
    G1Jac acc;
    fp_zero(acc.X); fp_zero(acc.Y); fp_zero(acc.Z);
    for (uint64_t i = 0; i < n; i++) {
        Fp qx, qy;
        fp_from_limbs(qx, pts + i * 12);
        fp_from_limbs(qy, pts + i * 12 + 6);
        g1j_add_aff(acc, acc, qx, qy);
    }
    if (fp_is_zero(acc.Z)) return 0;
    // to affine: x = X/Z², y = Y/Z³; then Montgomery -> standard.
    Fp zi, zi2, zi3, ax, ay, one_std;
    fp_inv(zi, acc.Z);
    fp_sqr(zi2, zi);
    fp_mul(zi3, zi2, zi);
    fp_mul(ax, acc.X, zi2);
    fp_mul(ay, acc.Y, zi3);
    std::memset(&one_std, 0, sizeof one_std);
    one_std.l[0] = 1;
    fp_mul(ax, ax, one_std);
    fp_mul(ay, ay, one_std);
    std::memcpy(out, ax.l, 48);
    std::memcpy(out + 6, ay.l, 48);
    return 1;
}

}  // extern "C"
