"""Batched BLS12-381 base-field arithmetic in 16-bit limbs for the TPU VPU.

This is the foundation of the ``tpu`` BLS backend — the device counterpart
of blst's assembly field arithmetic (the backend wrapped by
``/root/reference/crypto/bls/src/impls/blst.rs``).  A TPU has no 64-bit
integer multiplier, so a field element is 26 little-endian 16-bit limbs held
in ``uint32`` lanes (R = 2^416 > 4N), and every operation is elementwise /
batched over arbitrary leading dimensions — thousands of independent field
elements per vector op, which is exactly the shape batched signature
verification produces.

Representation invariants:

- Public values are **Montgomery residues** ``x·R mod N`` with *normalized*
  limbs (< 2^16) and value < 2N (lazy reduction — canonicalised only at the
  host boundary, where python ints take over).
- ``mont_mul`` is schoolbook column products (26×26 outer product, lo/hi
  split so every partial term fits uint32) + word-by-word Montgomery
  reduction, fully unrolled over the 26 limb positions (static slices; the
  batch dimension carries the parallelism, not the limb dimension).
- Sums/differences stay < 4N: with R = 2^416 ≈ 2^35·N there is enormous
  headroom, so no conditional subtractions exist anywhere on the device.

Host conversion helpers use exact python ints; the pure-python tower
(:mod:`..fields`) is the semantics oracle in tests.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from .fields import P as N_INT

LIMB_BITS = 16
LIMBS = 26
MASK = np.uint32(0xFFFF)
R_BITS = LIMB_BITS * LIMBS          # 416
R_INT = 1 << R_BITS
R_MOD_N = R_INT % N_INT
R2_MOD_N = (R_INT * R_INT) % N_INT
RINV_INT = pow(R_INT, -1, N_INT)
# -N^-1 mod 2^16 for the Montgomery word recurrence.
N0_INV = np.uint32((-pow(N_INT, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS))
# -N^-1 mod R for the full-width (single-shot) Montgomery reduction.
NPRIME_INT = (-pow(N_INT, -1, 1 << (LIMB_BITS * LIMBS))) % (1 << (LIMB_BITS * LIMBS))


def int_to_limbs(x: int) -> np.ndarray:
    """Python int (< R) → ``(26,)`` uint32 16-bit limbs, little-endian."""
    if not 0 <= x < R_INT:
        raise ValueError("value out of limb range")
    return np.array([(x >> (LIMB_BITS * i)) & 0xFFFF for i in range(LIMBS)],
                    dtype=np.uint32)


def limbs_to_int(limbs: np.ndarray) -> int:
    """``(..., 26)`` limbs → python int (no modular reduction)."""
    limbs = np.asarray(limbs, dtype=np.uint64)
    return sum(int(v) << (LIMB_BITS * i) for i, v in enumerate(limbs))


N_LIMBS = int_to_limbs(N_INT)
N2_LIMBS = int_to_limbs(2 * N_INT)
N4_LIMBS = int_to_limbs(4 * N_INT)


def to_mont(x: int) -> np.ndarray:
    """Canonical int → Montgomery-domain limbs."""
    return int_to_limbs((x % N_INT) * R_MOD_N % N_INT)


def from_mont(limbs: np.ndarray) -> int:
    """Montgomery-domain limbs (any lazy representative) → canonical int."""
    return limbs_to_int(limbs) * RINV_INT % N_INT


def to_mont_array(xs) -> np.ndarray:
    """Sequence/array of ints → ``(..., 26)`` Montgomery limbs."""
    flat = [to_mont(x) for x in np.asarray(xs, dtype=object).reshape(-1)]
    out = np.stack(flat) if flat else np.zeros((0, LIMBS), np.uint32)
    return out.reshape(np.asarray(xs, dtype=object).shape + (LIMBS,))


def from_mont_array(limbs: np.ndarray) -> np.ndarray:
    """``(..., 26)`` Montgomery limbs → object array of canonical ints."""
    arr = np.asarray(limbs)
    lead = arr.shape[:-1]
    flat = arr.reshape(-1, LIMBS)
    out = np.empty(flat.shape[0], dtype=object)
    for i in range(flat.shape[0]):
        out[i] = from_mont(flat[i])
    return out.reshape(lead)


ZERO = np.zeros(LIMBS, dtype=np.uint32)
ONE_MONT = to_mont(1)


# ---------------------------------------------------------------------------
# Device ops (pure jnp; batched over leading dims; limb axis = -1)
# ---------------------------------------------------------------------------

# Carry resolution without a per-limb ripple: a 26-step unrolled carry
# chain per add made the pairing programs millions of HLO ops (the
# mesh-sharded verify lowered to ~1M lines and compiled for minutes).
# Once every limb carries at most 1, limb k generates (g), propagates
# (p: limb == 0xFFFF) or kills the carry, and one integer add of the
# packed words G + (G|P) runs the whole chain: bit k of
# (G + (G|P) + cin) ^ G ^ (G|P) is the carry INTO limb k.  The results
# are the same limbs as the ripple's.

_POW2 = np.uint32(1) << np.arange(LIMBS, dtype=np.uint32)


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., w ≤ 26) 0/1 limbs → (...,) word, limb k at bit k."""
    w = bits.shape[-1]
    return jnp.sum((bits * _POW2[:w]).astype(jnp.int32), axis=-1
                   ).astype(jnp.uint32)


def _shift_up(x: jnp.ndarray) -> jnp.ndarray:
    """Limb k ← limb k−1, limb 0 ← 0."""
    return jnp.concatenate([jnp.zeros_like(x[..., :1]), x[..., :-1]],
                           axis=-1)


def _resolve01(s: jnp.ndarray, cin=None):
    """(..., w ≤ 26) columns < 2^17 → 16-bit limbs of Σ s_k 2^16k + cin
    (mod 2^16w) and the (...,) carry out."""
    w = s.shape[-1]
    g = _pack_bits(s >> np.uint32(LIMB_BITS))
    b = g | _pack_bits((s == MASK).astype(jnp.uint32))
    x = g + b if cin is None else g + b + cin
    x = x ^ g ^ b
    c = ((x[..., None] & _POW2[:w]) != 0).astype(jnp.uint32)
    return (s + c) & MASK, (x >> np.uint32(w)) & np.uint32(1)


def _carry_cols(t: jnp.ndarray, ncols: int, keep_carry: bool) -> jnp.ndarray:
    """Normalize ``ncols`` uint32 columns (< 2^32) to 16-bit limbs; the final
    carry is appended iff ``keep_carry`` (else reduced mod 2^(16·ncols))."""
    top = t[..., ncols - 1] >> np.uint32(LIMB_BITS)
    s = (t & MASK) + _shift_up(t >> np.uint32(LIMB_BITS))   # < 2^17
    out, cin = [], None
    for g0 in range(0, ncols, LIMBS):
        limbs, cin = _resolve01(s[..., g0:g0 + LIMBS], cin)
        out.append(limbs)
    if keep_carry:
        out.append((top + cin)[..., None])
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=-1)


def _carry_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Normalize uint32 limb values (< 2^32) to 16-bit limbs.  The value
    must fit 26 limbs (guaranteed by the < 4N bound; R = 2^416 leaves
    33+ spare bits)."""
    return _carry_cols(x, LIMBS, keep_carry=False)


def _cond_sub(x: jnp.ndarray, k_limbs: np.ndarray) -> jnp.ndarray:
    """x - K if x ≥ K else x, branch-free (normalized limb input)."""
    k = jnp.asarray(k_limbs, jnp.uint32)
    g = _pack_bits((x < k).astype(jnp.uint32))        # borrow generated
    b = g | _pack_bits((x == k).astype(jnp.uint32))   # … or propagated
    w = (g + b) ^ g ^ b
    borrow = ((w[..., None] & _POW2) != 0).astype(jnp.uint32)
    d_norm = (x - k - borrow) & MASK
    no_borrow = (w >> np.uint32(LIMBS)) == 0
    return jnp.where(no_borrow[..., None], d_norm, x)


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a + b, conditionally reduced — inputs and output < 2N.

    The < 2N invariant everywhere makes bound reasoning trivial: every
    value this module hands out is safe for every other op.  The extra
    conditional-subtract carry pass is ~40 vector ops — noise next to a
    mont_mul, and the alternative (lazy growing bounds) silently corrupted
    curve formulas whose ×12 constants pushed intermediates past the
    subtraction slack."""
    return _cond_sub(_carry_u32(a + b), N2_LIMBS)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b mod N (inputs < 2N → a - b + 2N ∈ (0, 4N) → reduced < 2N),
    computed as a + 2N + ~b + 1 (the 2^416 wrap dropped)."""
    t = a + jnp.asarray(N2_LIMBS) + (MASK - b)          # < 3·2^16
    s = (t & MASK) + _shift_up(t >> np.uint32(LIMB_BITS))
    d, _ = _resolve01(s, jnp.ones_like(s[..., 0]))
    return _cond_sub(d, N2_LIMBS)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    """2N - a ≡ -a (mod N); input < 2N → output ≤ 2N (2N ≡ 0 is a valid
    lazy zero and the next add/mul handles it).  2N + ~a + 1, wrap
    dropped."""
    s = jnp.asarray(N2_LIMBS) + (MASK - a)               # < 2^17
    return _resolve01(s, jnp.ones_like(s[..., 0]))[0]


def muls(a: jnp.ndarray, s: int) -> jnp.ndarray:
    """a · s for a small int 0 ≤ s ≤ 16, reduced back below 2N."""
    if not 0 <= s <= 16:
        raise ValueError("small-scalar multiply supports 0..16")
    x = _carry_u32(a * np.uint32(s))     # < 32N
    x = _cond_sub(x, int_to_limbs(16 * N_INT))
    x = _cond_sub(x, int_to_limbs(8 * N_INT))
    x = _cond_sub(x, N4_LIMBS)
    return _cond_sub(x, N2_LIMBS)


def _band_columns(a: jnp.ndarray, b: jnp.ndarray, ncols: int) -> jnp.ndarray:
    """Column sums of the schoolbook product a·b: out[k] = Σ_{i+j=k} a_i·b_j
    (16-bit partial terms, lo at offset i+j, hi at i+j+1).  Expressed as
    static pads + one big sum — a wide, shallow graph XLA compiles orders of
    magnitude faster than an equivalent chain of slice-updates (which made
    the first version of this kernel take minutes to compile per scan).
    Column values < 52·2^16 < 2^23, comfortably inside uint32."""
    prod = a[..., :, None] * b[..., None, :]          # (..., 26, 26) < 2^32
    lo = prod & MASK
    hi = prod >> np.uint32(LIMB_BITS)
    nd = lo.ndim - 2
    parts = []
    for i in range(LIMBS):
        width = min(LIMBS, ncols - i)
        if width > 0:
            parts.append(jnp.pad(lo[..., i, :width],
                                 [(0, 0)] * nd + [(i, ncols - i - width)]))
        width = min(LIMBS, ncols - i - 1)
        if width > 0:
            parts.append(jnp.pad(hi[..., i, :width],
                                 [(0, 0)] * nd + [(i + 1, ncols - i - 1 - width)]))
    return jnp.sum(jnp.stack(parts), axis=0)


# ---------------------------------------------------------------------------
# MXU band products — the schoolbook column accumulation as ONE matmul
# ---------------------------------------------------------------------------
#
# The pad-and-sum tree above is pure VPU work: ~104 full-plane pads + adds
# per band product, and three band products per mont_mul.  Hardware pairing
# engines win by feeding the wide multiplier structured limb products
# ("A Low-Power BLS12-381 Pairing Crypto-Processor", arXiv:2201.07496);
# the TPU analogue is the MXU.  The accumulation
#     T[k] = Σ_{i+j=k} lo(a_i·b_j)  +  Σ_{i+j+1=k} hi(a_i·b_j)
# is a CONSTANT 0/1 contraction over the 2·26·26 = 1352 partial terms, so
# the whole band collapses to one (batch, 1352) × (1352, ncols) matmul
# against a fixed selection matrix.  Exactness: every partial term is
# < 2^16 (exact in f32) and every column accumulates ≤ 52 of them
# (< 2^22 < 2^24, the f32 integer-exact range), so the f32 MXU result is
# bit-exact — asserted against the VPU path in tests/test_bls_shard.py
# and scripts/validate_bls_shard.py.
#
# Default: on for the TPU backend, off elsewhere (the CPU "matmul" would
# just be a slower BLAS call); override with LIGHTHOUSE_TPU_MXU=0/1.

_MXU_FLAG: bool | None = None


def use_mxu() -> bool:
    """Whether band products route through the MXU matmul formulation."""
    global _MXU_FLAG
    if _MXU_FLAG is None:
        from ..common.knobs import knob_tribool
        forced = knob_tribool("LIGHTHOUSE_TPU_MXU")
        if forced is None:
            import jax
            _MXU_FLAG = jax.default_backend() == "tpu"
        else:
            _MXU_FLAG = forced
    return _MXU_FLAG


def band_sel_matrix(ncols: int) -> np.ndarray:
    """(2·26·26, ncols) f32 selection matrix: row i·26+j → column i+j
    (lo half), row 676+i·26+j → column i+j+1 (hi half)."""
    sel = np.zeros((2 * LIMBS * LIMBS, ncols), np.float32)
    for i in range(LIMBS):
        for j in range(LIMBS):
            if i + j < ncols:
                sel[i * LIMBS + j, i + j] = 1.0
            if i + j + 1 < ncols:
                sel[LIMBS * LIMBS + i * LIMBS + j, i + j + 1] = 1.0
    return sel


from functools import lru_cache as _lru_cache


@_lru_cache(maxsize=4)
def _band_sel(ncols: int) -> np.ndarray:
    # Cached as numpy, not as a jax array: a jnp.asarray made inside one
    # jit trace is that trace's tracer, and a later trace reading the
    # cache would leak it.
    return band_sel_matrix(ncols)


def _band_columns_mxu(a: jnp.ndarray, b: jnp.ndarray,
                      ncols: int) -> jnp.ndarray:
    """MXU twin of :func:`_band_columns` — identical column values."""
    import jax
    prod = a[..., :, None] * b[..., None, :]          # (..., 26, 26) < 2^32
    lead = prod.shape[:-2]
    lo = (prod & MASK).astype(jnp.float32).reshape(lead + (LIMBS * LIMBS,))
    hi = ((prod >> np.uint32(LIMB_BITS))
          .astype(jnp.float32).reshape(lead + (LIMBS * LIMBS,)))
    feat = jnp.concatenate([lo, hi], axis=-1)         # (..., 1352)
    t = jax.lax.dot_general(
        feat, _band_sel(ncols),
        dimension_numbers=(((feat.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return t.astype(jnp.uint32)


def _band(a: jnp.ndarray, b: jnp.ndarray, ncols: int) -> jnp.ndarray:
    return (_band_columns_mxu if use_mxu() else _band_columns)(a, b, ncols)


_NPRIME_LIMBS = int_to_limbs(NPRIME_INT)


def mont_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Batched Montgomery product a·b·R^-1 mod N.

    Inputs: ``(..., 26)`` uint32, normalized limbs, values < 2N.
    Output: normalized limbs, value < 2N.

    Full-width reduction (one m = T·N' mod R, then (T + m·N)/R) instead of
    the textbook word-by-word recurrence: three band-products and three
    carry chains, no 26-step sequential slice-update dependency — the shape
    both the XLA compiler and the VPU prefer.  Bound: T < 4N², so
    (T + mN)/R < 4N²/R + N < 2N because R = 2^416 ≈ 2^35·N.
    """
    t = _band(a, b, 2 * LIMBS)                         # T columns
    t_low = _carry_cols(t[..., :LIMBS], LIMBS, keep_carry=False)
    m = _carry_cols(_band(t_low, jnp.asarray(_NPRIME_LIMBS), LIMBS),
                    LIMBS, keep_carry=False)           # m = T·N' mod R
    s = t + _band(m, jnp.asarray(N_LIMBS), 2 * LIMBS)   # T + mN, < 2^24
    s = (s & MASK) + _shift_up(s >> np.uint32(LIMB_BITS))
    # T + mN ≡ 0 (mod R): the low 26 columns sum to 0 or exactly R, so
    # the carry into limb 26 is 1 iff any of them is non-zero.  The
    # final carry out of (T + mN)/R < 2N is zero.
    cin = jnp.any(s[..., :LIMBS] != 0, axis=-1).astype(jnp.uint32)
    return _resolve01(s[..., LIMBS:], cin)[0]


def select(mask: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Elementwise ``mask ? a : b`` with mask broadcast over the limb axis."""
    return jnp.where(mask[..., None], a, b)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    """Exact zero test (mod N) for lazy values < 8N: true iff the value is
    k·N for k < 8.  One mont_mul by R² would canonicalise, but comparing
    against the eight multiples directly is cheaper and branch-free."""
    out = None
    for k in range(8):
        eq = jnp.all(a == jnp.asarray(int_to_limbs(k * N_INT)), axis=-1)
        out = eq if out is None else (out | eq)
    return out
