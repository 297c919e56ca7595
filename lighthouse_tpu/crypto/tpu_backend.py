"""The ``tpu`` BLS backend — batched signature verification on the device.

This is the role blst plays for the reference (``bls::impls::supranational``,
``/root/reference/crypto/bls/src/impls/blst.rs``): the production backend
behind the backend-registry seam in :mod:`.bls`.  All three public verify
entry points funnel into ONE device pipeline, run as fixed-shape per-chunk
programs (:func:`_pipeline_cells`):

    per-set pubkey tree-aggregation (G1)
      → per-set random-linear-combination scaling (64-bit ladders, G1+G2)
      → signature accumulation (G2 tree sum)
      → batched Miller loops over all pairs
      → one shared final exponentiation of the lane product
      → == 1

replicating ``verify_multiple_aggregate_signatures`` semantics
(``impls/blst.rs:36-119``) including the consensus-critical edge rules:
empty set lists, empty signing-key lists, missing/infinity signatures and
identity aggregate pubkeys all fail verification (host-side pre-checks +
an on-device identity-aggregate flag).

Host work is marshalling only: affine points → Montgomery limb arrays
(memoised per point, the ``validator_pubkey_cache.rs`` role) and
hash-to-curve of messages (host SSWU for now).  Shapes are bucketed to
powers of two so XLA compiles a handful of programs, then every call hits
the jit cache.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from . import curve as C
from . import limb_curve as LC
from . import limb_field as LF
from . import limb_pairing as LP
from ..ops.merkle import _next_pow2
from .hash_to_curve import hash_to_g2

_NEG_G1_GEN = LC.g1_to_limbs(C.g1_neg(C.G1_GEN))
_G1_IDENT = LC.g1_to_limbs(None)
_G2_IDENT = LC.g2_to_limbs(None)


@lru_cache(maxsize=1 << 16)
def _g1_limbs(point) -> bytes:
    return LC.g1_to_limbs(point).tobytes()


@lru_cache(maxsize=1 << 16)
def _g2_limbs(point) -> bytes:
    return LC.g2_to_limbs(point).tobytes()


@lru_cache(maxsize=1 << 14)
def _h_point(message: bytes):
    """Memoised hash-to-curve; both path-specific encodings derive from it."""
    return hash_to_g2(message)


def _h_limbs(message: bytes) -> bytes:
    return LC.g2_to_limbs(_h_point(message)).tobytes()


def _g1_arr(point) -> np.ndarray:
    return np.frombuffer(_g1_limbs(point), np.uint32).reshape(3, LF.LIMBS)


def _g2_arr(point) -> np.ndarray:
    return np.frombuffer(_g2_limbs(point), np.uint32).reshape(3, 2, LF.LIMBS)


def _h_arr(message: bytes) -> np.ndarray:
    return np.frombuffer(_h_limbs(bytes(message)), np.uint32).reshape(3, 2, LF.LIMBS)


@jax.jit
def _verify_sets_kernel(pk, kmask, sig, h, scal, smask):
    """Fused batch verify.  Shapes: pk (S,K,3,26), kmask (S,K) bool,
    sig/h (S,3,2,26) projective, scal (S,2) uint32 lo/hi, smask (S,) bool.
    S and K are powers of two.  Returns a scalar bool."""
    S, K = pk.shape[0], pk.shape[1]
    ident1 = jnp.asarray(_G1_IDENT)
    pkm = LC.point_select(kmask, pk, ident1, LC.G1_OPS)
    agg = LC.tree_sum(LC.G1_OPS, pkm, K)              # (S,3,26)
    # A live set whose aggregate pubkey is the identity is invalid
    # (`PythonBackend.verify_signature_sets` / blst's aggregate move).
    any_bad = jnp.any(smask & LF.is_zero(agg[..., 2, :]))
    aggc = LC.scalar_mul(LC.G1_OPS, agg, scal)        # (S,3,26)
    sigc = LC.scalar_mul(LC.G2_OPS, sig, scal)        # (S,3,2,26)
    sigsum = LC.tree_sum(LC.G2_OPS, sigc, S)          # (3,2,26)
    # Pairing lanes: i<S → (c_i·aggpk_i, H_i); lane S → (−g1, Σc_i·sig_i);
    # the rest of the 2S block is masked padding.
    g1_lanes = jnp.concatenate(
        [aggc, jnp.asarray(_NEG_G1_GEN)[None],
         jnp.broadcast_to(jnp.asarray(_G1_IDENT), (S - 1, 3, LF.LIMBS))])
    g2_lanes = jnp.concatenate(
        [h, sigsum[None],
         jnp.broadcast_to(jnp.asarray(_G2_IDENT), (S - 1, 3, 2, LF.LIMBS))])
    lane_mask = jnp.concatenate(
        [smask, jnp.array([True]), jnp.zeros(S - 1, bool)])
    ok = LP.multi_pairing_is_one(g1_lanes, g2_lanes, lane_mask)
    return ok & ~any_bad


# ---------------------------------------------------------------------------
# Pallas path (production TPU): prepare → miller → product → host final exp
# ---------------------------------------------------------------------------

def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


@lru_cache(maxsize=1 << 16)
def _g1_aff_col(point) -> bytes:
    """Affine G1 → (64,) block-layout column (x at rows 0, y at 32)."""
    col = np.zeros(64, np.uint32)
    col[0:26] = LF.to_mont(point[0])
    col[32:58] = LF.to_mont(point[1])
    return col.tobytes()


@lru_cache(maxsize=1 << 16)
def _g2_aff_col(point) -> bytes:
    """Affine G2 → (128,) block-layout column (x0/x1/y0/y1 at 0/32/64/96)."""
    (x0, x1), (y0, y1) = point
    col = np.zeros(128, np.uint32)
    col[0:26] = LF.to_mont(x0)
    col[32:58] = LF.to_mont(x1)
    col[64:90] = LF.to_mont(y0)
    col[96:122] = LF.to_mont(y1)
    return col.tobytes()


class _DevicePubkeyTable:
    """HBM-resident decompressed pubkey columns — the device half of the
    reference's ``ValidatorPubkeyCache`` (``validator_pubkey_cache.rs:18``):
    each distinct pubkey is marshalled to its (64,) affine limb column
    exactly once; verify calls ship uint32 indices and the device gathers.

    New columns append with a device-side ``.at[].set`` (a 256-byte h2d +
    on-device copy — never a full-table re-upload); capacity doubling pads
    on-device.  Bounded by ``max_keys`` (≈ a registry's worth): at the
    bound the LEAST-RECENTLY-USED half of the keys is evicted and the
    survivors compacted (generational halving), so adversarial never-seen
    keys can't grow the table without bound while hot validator keys stay
    resident; the next ``device()`` call re-uploads the compacted table
    once."""

    def __init__(self, initial: int = 1 << 15, max_keys: int = 1 << 21):
        self._initial = initial
        self._max_keys = max_keys
        self._reset()

    def _reset(self) -> None:
        self._index: dict = {}
        self._last_used: dict = {}
        self._gen = 0
        self._host = np.zeros((64, self._initial), np.uint32)
        self._n = 1  # column 0 stays zero for masked slots
        self._device = None

    def maybe_reset(self) -> None:
        """Call BETWEEN batches only: evicting mid-marshal would
        invalidate indices already recorded for the in-flight batch.

        Generational halving (ADVICE r4): at the bound, keep the most
        recently USED half of the keys and compact, instead of dropping
        the whole table — a full reset would force a re-marshal +
        re-upload of every hot validator key in one latency spike on the
        block-verification path.  Recency (not insertion order) decides
        survival: hot validator keys are touched by every batch they
        appear in, so a flood of adversarial never-seen keys ages out
        while the working set stays resident."""
        self._gen += 1
        if self._n < self._max_keys:
            return
        keep = (self._n - 1) // 2
        survivors = sorted(
            self._index.items(),
            key=lambda kv: (self._last_used.get(kv[0], 0), kv[1]),
            reverse=True)[:keep]
        survivors.sort(key=lambda kv: kv[1])  # stable column order
        cols = [old for _, old in survivors]
        cap = self._initial  # shrink to next pow2 >= survivors (+ col 0)
        while cap < len(cols) + 1:
            cap *= 2
        host = np.zeros((64, cap), np.uint32)
        host[:, 1:len(cols) + 1] = self._host[:, cols]  # one gather
        index = {pt: i + 1 for i, (pt, _) in enumerate(survivors)}
        self._host, self._index, self._n = host, index, len(cols) + 1
        self._last_used = {pt: self._last_used.get(pt, 0) for pt in index}
        self._device = None  # next device() re-uploads the compacted table

    def index_of(self, point) -> int:
        self._last_used[point] = self._gen
        i = self._index.get(point)
        if i is None:
            if self._n == self._host.shape[1]:
                self._host = np.concatenate(
                    [self._host, np.zeros_like(self._host)], axis=1)
                if self._device is not None:
                    self._device = jnp.pad(
                        self._device,
                        ((0, 0), (0, self._device.shape[1])))
            col = np.frombuffer(_g1_aff_col(point), np.uint32)
            self._host[:, self._n] = col
            if self._device is not None:
                self._device = self._device.at[:, self._n].set(
                    jnp.asarray(col))
            i = self._index[point] = self._n
            self._n += 1
        return i

    def device(self):
        if self._device is None:
            self._device = jnp.asarray(self._host)
        return self._device


_PK_TABLE = _DevicePubkeyTable()


def _sigma_g1_cell() -> np.ndarray:
    """(64, 128) Miller-cell G1 input whose lane 0 is the affine −G (the
    pair of the aggregated-signature lane); other lanes are masked."""
    out = np.zeros((64, 128), np.uint32)
    out[:, 0] = np.frombuffer(_g1_aff_col(C.g1_neg(C.G1_GEN)), np.uint32)
    return out


_SIGMA_G1_CELL = _sigma_g1_cell()


# -- per-cell device programs ----------------------------------------------
#
# Every device program of the batch pipeline runs on ONE 128-set chunk,
# ONE 256-lane Miller cell or ONE product pair, at a fixed shape: a
# process traces, lowers and compiles each kernel once (prepare once per
# K bucket) whatever the batch size.  A fused whole-batch program per
# (chunks, keys) bucket re-traced and re-compiled every kernel per
# bucket — minutes of Mosaic compile each, more than a chip run holds.
# The host loops over the chunks; dispatch is asynchronous, so the loop
# only issues work and the device queue stays full.


@partial(jax.jit, static_argnames=("K",))
def _prepare_cell(table, idx, kmask, lo, hi, *, K: int):
    """Pubkey gather + G1 aggregation + RLC ladder + affine for one
    chunk → ((64, 128) affine c_i·aggpk_i, (1, 128) identity flags)."""
    from . import pairing_kernel as PK
    pk = jnp.take(table, idx, axis=1)                   # (64, K·128)
    return PK.prepare_kernel_call(pk, kmask, lo, hi, K=K)


@jax.jit
def _cell_bad(flags, setlive):
    return jnp.any((flags != 0) & (setlive != 0))


@jax.jit
def _sigma_point(partial):
    from . import pairing_kernel as PK
    return PK.sigma_point(partial)


@jax.jit
def _sigma_add(acc, partial):
    from . import pairing_kernel as PK
    return PK.sigma_add(acc, partial)


@jax.jit
def _sigma_block(acc, live):
    """Σ c_i·σ_i → the Miller block that pairs it with −G: (128, 128) G2
    columns (lane 0 live) and its (1, 128) lane mask."""
    from . import pairing_kernel as PK
    col, ident = PK.sigma_column(acc)
    g2 = jnp.zeros((128, PK.PREP_S), jnp.uint32).at[:, 0].set(col)
    mask = jnp.zeros((1, PK.PREP_S), jnp.int32).at[0, 0].set(
        (live & ~ident).astype(jnp.int32))
    return g2, mask


@jax.jit
def _miller_cell(g1a, g2a, ma, g1b, g2b, mb):
    """Two 128-lane blocks → one fused Miller + lane-fold cell →
    (384, 128) residue-class products."""
    from . import pairing_kernel as PK
    return PK.miller_fold_kernel_call(
        jnp.concatenate([g1a, g1b], axis=1),
        jnp.concatenate([g2a, g2b], axis=1),
        jnp.concatenate([ma, mb], axis=1))


@jax.jit
def _fold_pair(a, b):
    from . import pairing_kernel as PK
    return PK.product_chunks_kernel_call(
        jnp.concatenate([a, b], axis=1),
        jnp.ones((1, 2 * PK.LANE_BLOCK), jnp.int32))


def _fold_blocks(blocks: list):
    """Pairwise product fold of (384, 128) blocks down to one — adjacent
    pairs level by level, an odd level padded with Fq12 ONE."""
    one = jnp.asarray(_ONE_BLOCK)
    while len(blocks) > 1:
        if len(blocks) % 2:
            blocks.append(one)
        blocks = [_fold_pair(blocks[i], blocks[i + 1])
                  for i in range(0, len(blocks), 2)]
    return blocks[0]


def _miller_blocks(g1s, g2s, masks) -> list:
    """Pair 128-lane blocks into Miller cells (an odd count padded with
    a masked zero block) → their (384, 128) folded products."""
    if len(g1s) % 2:
        g1s.append(jnp.zeros((64, 128), jnp.uint32))
        g2s.append(jnp.zeros((128, 128), jnp.uint32))
        masks.append(jnp.zeros((1, 128), jnp.int32))
    return [_miller_cell(g1s[i], g2s[i], masks[i],
                         g1s[i + 1], g2s[i + 1], masks[i + 1])
            for i in range(0, len(g1s), 2)]


def _finalize(prod):
    """The shared final exponentiation of one (384, 128) product — the
    donated entry on TPU (the product is batch-local), plain elsewhere
    (CPU donation is a warning-only no-op)."""
    from . import pairing_kernel as PK
    if _use_pallas():
        return PK.finalize_kernel_call_donated(prod)
    return PK.finalize_kernel_call(prod)


def _pipeline_cells(table, staged):
    """Batch verify of one marshalled sub-batch up to its (384, 128)
    residue product: per chunk — prepare, hash-to-curve, σ-side RLC
    fold — then the Miller cells over the chunk blocks plus the ONE σ
    lane (e(−G, Σ c_i·σ_i)), folded pairwise.  Returns (product, [bad
    flags])."""
    from . import htc_kernel as HK
    from . import pairing_kernel as PK

    cells, K, any_sig = staged
    g1s, g2s, masks, bads = [], [], [], []
    acc = None
    for (idx, kmask, lo, hi, u, sig, sigmask, setlive) in cells:
        g1, flags = _prepare_cell(table, idx, kmask, lo, hi, K=K)
        g1s.append(g1)
        g2s.append(HK.hash_g2_kernel_call(u))
        masks.append(setlive)
        part = PK.sigma_kernel_call(sig, sigmask, lo, hi)
        acc = _sigma_point(part) if acc is None else _sigma_add(acc, part)
        bads.append(_cell_bad(flags, setlive))
    g2_sig, sig_mask = _sigma_block(acc, jnp.bool_(any_sig))
    g1s.append(jnp.asarray(_SIGMA_G1_CELL))
    g2s.append(g2_sig)
    masks.append(sig_mask)
    return _fold_blocks(_miller_blocks(g1s, g2s, masks)), bads


@jax.jit
def _combine_verdict(ok, bads):
    return (ok[0, 0] != 0) & ~jnp.any(bads)


def _fq12_one_block() -> np.ndarray:
    """(384, 128) kernel-block-layout Fq12 ONE — pads the cross-group
    product concat to a power of two (acts as a masked-out lane)."""
    out = np.zeros((384, 128), np.uint32)
    out[0:26, :] = np.asarray(LF.ONE_MONT)[:, None]
    return out


_ONE_BLOCK = _fq12_one_block()


def _rlc_message_sig_columns(entries, C, rand_fn):
    """The per-set column marshalling SHARED by the general pipeline and
    the shared-key collapsed path — one definition of the device layout
    (RLC lo/hi scalar words, interleaved HTC u-planes, affine signature
    columns, set-liveness), two consumers.  Returns
    (set_col, lo, hi, u_planes, sig_cols, sigmask, setlive)."""
    from . import htc_kernel as HK
    from . import pairing_kernel as PK

    S = PK.PREP_S
    n = len(entries)
    sets = np.arange(n)
    set_col = (sets // S) * S + (sets % S)

    rands = np.fromiter((rand_fn() for _ in range(n)), np.uint64, n)
    lo = np.zeros((1, C * S), np.uint32)
    hi = np.zeros((1, C * S), np.uint32)
    lo[0, set_col] = (rands & 0xFFFFFFFF).astype(np.uint32)
    hi[0, set_col] = (rands >> 32).astype(np.uint32)

    u_cols = np.frombuffer(
        b"".join(HK._u_cols(bytes(e[2])) for e in entries),
        np.uint32).reshape(n, 2, 2 * HK.BLOCK_ROWS)
    u_planes = np.zeros((2 * HK.BLOCK_ROWS, C * 2 * S), np.uint32)
    ubase = (sets // S) * 2 * S + (sets % S)
    u_planes[:, ubase] = u_cols[:, 0].T
    u_planes[:, ubase + S] = u_cols[:, 1].T

    sig_cols = np.zeros((128, C * S), np.uint32)
    sigmask = np.zeros((1, C * S), np.int32)
    have_sig = np.fromiter((e[0] is not None for e in entries), bool, n)
    if have_sig.any():
        sig_bytes = b"".join(_g2_aff_col(e[0])
                             for e in entries if e[0] is not None)
        cols = np.frombuffer(sig_bytes, np.uint32).reshape(-1, 128).T
        sig_cols[:, set_col[have_sig]] = cols
        sigmask[0, set_col[have_sig]] = 1
    setlive = np.zeros((1, C * S), np.int32)
    setlive[0, set_col] = 1
    return set_col, lo, hi, u_planes, sig_cols, sigmask, setlive


def _cells(C: int, *planes) -> list:
    """Split chunk-major (…, C·w) host planes into C per-chunk tuples."""
    return [tuple(np.ascontiguousarray(
        x[..., c * (x.shape[-1] // C):(c + 1) * (x.shape[-1] // C)])
        for x in planes) for c in range(C)]


def _marshal_planes(entries, rand_fn, C: int):
    """Host marshalling of ``entries`` into ``C`` chunk-major 128-set
    chunks: pubkey-table indices, RLC scalar words, u-values, signature
    columns, masks.  Column placement is vectorized — the only per-entry
    Python work left is the pubkey-table dict lookups, the memoised
    u-column lookups, and ``rand_fn``.  Returns (idx, kmask, lo, hi,
    u_planes, sig_cols, sigmask, setlive, set_col, K)."""
    from . import pairing_kernel as PK

    S = PK.PREP_S
    n = len(entries)
    K = _next_pow2(max(len(e[1]) for e in entries))

    nkeys = np.fromiter((len(e[1]) for e in entries), np.int64, n)
    total_keys = int(nkeys.sum())
    flat_idx = np.fromiter(
        (_PK_TABLE.index_of(kp) for e in entries for kp in e[1]),
        np.int32, total_keys)
    sets = np.arange(n)
    c_arr, s_arr = sets // S, sets % S
    starts = np.concatenate([[0], np.cumsum(nkeys)[:-1]])
    within = np.arange(total_keys) - np.repeat(starts, nkeys)
    kcol = (np.repeat(c_arr * K * S + s_arr, nkeys)
            + within.astype(np.int64) * S)
    idx = np.zeros(C * K * S, np.int32)
    kmask = np.zeros((1, C * K * S), np.int32)
    idx[kcol] = flat_idx
    kmask[0, kcol] = 1

    (set_col, lo, hi, u_planes, sig_cols, sigmask,
     setlive) = _rlc_message_sig_columns(entries, C, rand_fn)
    return (idx, kmask, lo, hi, u_planes, sig_cols, sigmask, setlive,
            set_col, K)


def _marshal_group(entries, rand_fn):
    """One sub-batch's host marshalling (:func:`_marshal_planes`) split
    per 128-set chunk, with the static K bucket and whether any set
    carries a signature: the H2D transfer is a separate pipeline stage
    (async ``device_put`` by the staged executor) so marshalling of the
    next sub-batch overlaps this one's transfer and compute."""
    from . import pairing_kernel as PK

    C = _next_pow2((len(entries) + PK.PREP_S - 1) // PK.PREP_S)
    *planes, _set_col, K = _marshal_planes(entries, rand_fn, C)
    sigmask = planes[6]
    return _cells(C, *planes), K, bool(sigmask.any())


# Stats of the most recent pipelined dispatch, surfaced by bench.py
# (``stage_overlap_efficiency`` et al).
LAST_PIPELINE_STATS: dict = {}


def _pipeline_sets() -> int:
    """Sub-batch size (sets per device dispatch) for the staged
    pipeline.  0 disables sub-batching — one monolithic marshal +
    dispatch per K-group, the pre-pipeline behaviour.

    Default 1024 (was 256): with the fused Miller+fold kernel one
    dispatch carries a C=8 bucket, so the fixed per-dispatch stages
    (finalize's shared final exponentiation, the host sync, the kernel
    launch overheads) amortize over 4× more sets — the r5 stage profile
    put final_exp at 51.7 ms against 32.4 ms of C=2 Miller, i.e. the
    fixed tail dominated narrow buckets."""
    from ..common.knobs import knob_int
    return knob_int("LIGHTHOUSE_TPU_PIPELINE_SETS")


def _split_batches(entries) -> list:
    """Work list for the staged executor: entries group by K =
    next-pow2(signer count) (one 512-key sync-committee set must not pad
    a thousand single-key sets to K=512), and each group splits into
    sub-batches of ≤ ``_pipeline_sets()`` sets so host marshalling of
    sub-batch i+1 overlaps device compute of sub-batch i.

    Sub-batching is only sound when EVERY entry carries its own
    signature (each sub-batch then verifies an independent pairing
    product): ``aggregate_verify`` attaches one signature to the whole
    entry list — splitting it would check ∏ e(pk, H) == 1 without the
    σ lane — so such batches stay monolithic per group."""
    groups: dict = {}
    for e in entries:
        groups.setdefault(_next_pow2(max(1, len(e[1]))), []).append(e)
    sub = _pipeline_sets()
    splittable = sub > 0 and all(e[0] is not None for e in entries)
    work = []
    for k in sorted(groups):
        g = groups[k]
        if splittable:
            work.extend(g[j:j + sub] for j in range(0, len(g), sub))
        else:
            work.append(g)
    return work


def _dispatch_pallas(entries, rand_fn) -> bool:
    """Marshal a batch and run the fused device pipeline:

        ∏ e(c_i·aggpk_i, H(m_i)) · e(−G, Σ c_i·σ_i) == 1

    (the signature side of the RLC collapses to one pairing lane — the
    same aggregation blst's ``verify_multiple_aggregate_signatures``
    performs).  Work splits per :func:`_split_batches` and runs through
    the staged executor: marshalling of sub-batch i+1 overlaps the async
    ``device_put`` + compute of sub-batch i (no ``block_until_ready``
    between stages), and the marshalled arrays are donated to the jit so
    the device reuses their buffers in place.  Every sub-batch's
    (384, 128) residue product concats into ONE shared finalize (fold +
    final exponentiation — its ~13-minute XLA compile happens once
    across all buckets, not per (C, K)), and the host pulls back a
    single bool: still exactly one host sync per verify call.
    Message hashing is host SHA-256 (expand_message_xmd) + the device
    SSWU kernel — no host curve math at all."""
    import time

    from . import pairing_kernel as PK
    from ..common.device_ledger import LEDGER
    from ..parallel.pipeline import StagedExecutor

    t0 = time.perf_counter()
    _PK_TABLE.maybe_reset()
    work = _split_batches(entries)
    ex = StagedExecutor("bls_pipeline", subsystem="bls")

    def dispatch(staged):
        # Table snapshot AFTER this sub-batch's marshalling registered
        # its new keys; later sub-batches' appends build NEW functional
        # arrays and cannot disturb an in-flight dispatch.
        return _pipeline_cells(_PK_TABLE.device(), staged)

    results = ex.map(work, lambda batch: _marshal_group(batch, rand_fn),
                     dispatch)
    prod = _fold_blocks([r[0] for r in results])
    bads = [b for r in results for b in r[1]]
    ok = _finalize(prod)
    verdict = bool(_combine_verdict(ok, jnp.stack(bads)))
    # Self-accounted like the XLA direct path (suppressed, and counted
    # once by the envelope, when a resilience envelope wraps the call).
    LEDGER.note_dispatch("bls", (time.perf_counter() - t0) * 1e3)
    LEDGER.note_transfer("d2h", 1, subsystem="bls")
    eff = ex.overlap_efficiency()
    LAST_PIPELINE_STATS.update(
        dispatches=len(work),
        staging_fallbacks=ex.stats["fallbacks"],
        host_prep_ms=round(ex.stats["host_prep_s"] * 1e3, 1),
        overlap_prep_ms=round(ex.stats["overlap_prep_s"] * 1e3, 1),
        overlap_efficiency=None if eff is None else round(eff, 3))
    return verdict


# Stage decomposition of the most recent shared-key (fast-aggregate)
# dispatch — populated when STAGE_TIMINGS is on (bench.py flips it for
# one attributed run; the throughput runs stay sync-free).
LAST_FAST_AGG_TIMINGS: dict = {}
STAGE_TIMINGS = False


def _dedup_shared_keygroups(entries):
    """Collapse entries sharing an IDENTICAL pubkey list to one
    aggregated key (sync-committee shape: 256 messages × the same 512
    pubkeys — ``fast_aggregate_verify``, BASELINE row 4).  The per-set
    RLC scalar multiplies the SAME aggregate, so aggregating once
    (native jacobian sum, ~3 ms for 512 keys; pure-python fallback when
    the .so is unavailable) replaces 256 × 511 device G1 adds and moves
    the sets into the hot K=1 pipeline bucket — and, when the whole
    batch shares one key, into the collapsed one-Miller-lane path
    (:func:`_dispatch_shared`).

    Returns (entries', all_valid): an infinity aggregate means an
    invalid set → caller returns False (matching
    ``aggregate_public_keys`` → None → False)."""
    import time

    from . import bls
    counts: dict = {}
    for e in entries:
        if len(e[1]) > 4:
            counts[tuple(e[1])] = counts.get(tuple(e[1]), 0) + 1
    shared = {k for k, n in counts.items() if n >= 2}
    if not shared:
        return entries, True
    t0 = time.perf_counter()
    agg: dict = {}
    for k in shared:
        agg[k] = bls.aggregate_points(list(k))
        if agg[k] is None:
            return entries, False
    LAST_FAST_AGG_TIMINGS["aggregate_keys_ms"] = round(
        (time.perf_counter() - t0) * 1e3, 2)
    out = []
    for e in entries:
        key = tuple(e[1])
        if key in shared:
            out.append((e[0], [agg[key]], e[2]))
        else:
            out.append(e)
    return out, True


# ---------------------------------------------------------------------------
# Shared-key collapse: the winning fast_aggregate_verify path
# ---------------------------------------------------------------------------
#
# When every set in the batch signs with the SAME aggregated pubkey P
# (the sync-committee shape after _dedup_shared_keygroups), bilinearity
# collapses the whole batch to TWO Miller lanes:
#
#     ∏_i e(c_i·P, H(m_i)) · e(−G, Σ c_i·σ_i)
#   = e(P, Σ c_i·H(m_i)) · e(−G, Σ c_i·σ_i)          == 1
#
# so the per-set cost drops from a Miller lane + a G1 ladder to one G2
# RLC ladder term (the same σ-side fold the pipeline already runs) —
# hash-to-curve is the only per-set stage left.


def _shared_min_sets() -> int:
    """Batch size from which the collapsed path wins (two fixed Miller
    lanes + final exp amortize); below it the general path's latency is
    comparable and not worth a second compiled program."""
    from ..common.knobs import knob_int
    return knob_int("LIGHTHOUSE_TPU_SHARED_MIN")


def _shared_group_key(entries):
    """The common single pubkey point if the WHOLE batch shares one
    signing key (post-dedup) and every entry carries its own signature;
    None otherwise."""
    if len(entries) < _shared_min_sets():
        return None
    first = entries[0][1]
    if len(first) != 1:
        return None
    pt = first[0]
    for e in entries:
        if e[0] is None or len(e[1]) != 1 or e[1][0] != pt:
            return None
    return pt


@jax.jit
def _verify_shared_kernel(pk1, sig, h, scal, smask):
    """Collapsed batch verify: pk1 (3, 26) shared aggregate pubkey,
    sig/h (S, 3, 2, 26) projective, scal (S, 2), smask (S,) bool; S a
    power of two.  Two Miller lanes total."""
    S = sig.shape[0]
    hc = LC.scalar_mul(LC.G2_OPS, h, scal)            # c_i · H(m_i)
    sigc = LC.scalar_mul(LC.G2_OPS, sig, scal)        # c_i · σ_i
    hsum = LC.tree_sum(LC.G2_OPS, hc, S)              # (3, 2, 26)
    sigsum = LC.tree_sum(LC.G2_OPS, sigc, S)
    # A live batch under an identity aggregate key is invalid (the same
    # rule the general kernel flags per-set).
    bad = jnp.any(smask) & LF.is_zero(pk1[2])
    g1_lanes = jnp.stack([pk1, jnp.asarray(_NEG_G1_GEN)])
    g2_lanes = jnp.stack([hsum, sigsum])
    ok = LP.multi_pairing_is_one(g1_lanes, g2_lanes,
                                 jnp.ones(2, dtype=bool))
    return ok & ~bad


def _stage_sync(timings, name, t0, *values):
    """When STAGE_TIMINGS is on, fence the queued work and record the
    stage's wall time; otherwise leave the dispatch fully async."""
    import time
    if not STAGE_TIMINGS:
        return t0
    jax.block_until_ready(values)
    t1 = time.perf_counter()
    timings[name] = round((t1 - t0) * 1e3, 2)
    return t1


def _dispatch_shared_xla(entries, pk_pt, rand_fn) -> bool:
    """XLA (dry-run / off-TPU) collapsed path."""
    import time

    S = _next_pow2(len(entries))
    sig = np.broadcast_to(_G2_IDENT, (S, 3, 2, LF.LIMBS)).copy()
    h = np.broadcast_to(_G2_IDENT, (S, 3, 2, LF.LIMBS)).copy()
    scal = np.zeros((S, 2), np.uint32)
    smask = np.zeros(S, bool)
    t0 = time.perf_counter()
    for i, (sig_pt, _keys, msg) in enumerate(entries):
        sig[i] = _g2_arr(sig_pt)
        h[i] = _h_arr(msg)
        c = rand_fn()
        scal[i] = (c & 0xFFFFFFFF, c >> 32)
        smask[i] = True
    timings = LAST_FAST_AGG_TIMINGS
    t0 = _stage_sync(timings, "marshal_htc_ms", t0)
    ok = _verify_shared_kernel(jnp.asarray(_g1_arr(pk_pt)),
                               jnp.asarray(sig), jnp.asarray(h),
                               jnp.asarray(scal), jnp.asarray(smask))
    _stage_sync(timings, "rlc_fold_miller_final_ms", t0, ok)
    timings["sets"] = len(entries)
    timings["path"] = "xla_shared"
    return bool(ok)


def _dispatch_shared_pallas(entries, pk_pt, rand_fn) -> bool:
    """Pallas (TPU) collapsed path, built ENTIRELY from the pipeline's
    per-cell programs: hash-to-curve per chunk → two σ-style RLC fold
    passes (one over H columns, one over σ columns) → one Miller cell
    with 2 live lanes → shared finalize."""
    import time

    from . import htc_kernel as HK
    from . import pairing_kernel as PK
    from ..common.device_ledger import LEDGER

    S = PK.PREP_S
    n = len(entries)
    # NOT named C: that would shadow the curve module used below.
    n_chunks = _next_pow2((n + S - 1) // S)

    t0 = t_start = time.perf_counter()
    (_set_col, lo, hi, u_planes, sig_cols, sigmask,
     setlive) = _rlc_message_sig_columns(entries, n_chunks, rand_fn)
    cells = _cells(n_chunks, lo, hi, u_planes, sig_cols, sigmask, setlive)
    timings = LAST_FAST_AGG_TIMINGS
    t0 = _stage_sync(timings, "marshal_ms", t0)

    pk_col = np.zeros((64, S), np.uint32)
    pk_col[:, 0] = np.frombuffer(_g1_aff_col(pk_pt), np.uint32)
    pk_col[:, 1] = np.frombuffer(_g1_aff_col(C.g1_neg(C.G1_GEN)), np.uint32)

    h_cols = [HK.hash_g2_kernel_call(c[2]) for c in cells]
    t0 = _stage_sync(timings, "htc_ms", t0, h_cols)
    h_acc = s_acc = None
    for (lo_c, hi_c, _u, sig_c, sm_c, live_c), h_c in zip(cells, h_cols):
        hp = PK.sigma_kernel_call(h_c, live_c, lo_c, hi_c)
        sp = PK.sigma_kernel_call(sig_c, sm_c, lo_c, hi_c)
        h_acc = _sigma_point(hp) if h_acc is None else _sigma_add(h_acc, hp)
        s_acc = _sigma_point(sp) if s_acc is None else _sigma_add(s_acc, sp)
    t0 = _stage_sync(timings, "rlc_fold_ms", t0, h_acc, s_acc)
    g2, mask = _shared_lanes(h_acc, s_acc)
    zero = np.zeros
    prod = _miller_cell(pk_col, g2, mask, zero((64, S), np.uint32),
                        zero((128, S), np.uint32), zero((1, S), np.int32))
    ok = _finalize(prod)
    _stage_sync(timings, "miller_final_ms", t0, ok)
    verdict = bool(ok[0, 0] != 0)
    LEDGER.note_dispatch("bls", (time.perf_counter() - t_start) * 1e3)
    LEDGER.note_transfer("d2h", 1, subsystem="bls")
    timings["sets"] = n
    timings["path"] = "pallas_shared"
    return verdict


@jax.jit
def _shared_lanes(h_acc, s_acc):
    """The collapsed path's two G2 lanes — Σ c_i·H(m_i) in lane 0 (paired
    with P), Σ c_i·σ_i in lane 1 (paired with −G) — and their mask."""
    from . import pairing_kernel as PK

    h_col, h_ident = PK.sigma_column(h_acc)
    s_col, s_ident = PK.sigma_column(s_acc)
    g2 = jnp.zeros((128, PK.PREP_S), jnp.uint32)
    g2 = g2.at[:, 0].set(h_col).at[:, 1].set(s_col)
    mask = jnp.zeros((1, PK.PREP_S), jnp.int32)
    mask = mask.at[0, 0].set((~h_ident).astype(jnp.int32))
    mask = mask.at[0, 1].set((~s_ident).astype(jnp.int32))
    return g2, mask


def _dispatch_shared(entries, pk_pt, rand_fn) -> bool:
    if pk_pt is None:
        return False  # identity aggregate key — invalid batch
    if _use_pallas():
        return _dispatch_shared_pallas(entries, pk_pt, rand_fn)
    return _dispatch_shared_xla(entries, pk_pt, rand_fn)


def _marshal_xla(entries, rand_fn):
    """Host marshalling for the pure-XLA kernel: limb arrays for one
    (sub-)batch, shapes bucketed to powers of two."""
    S = _next_pow2(len(entries))
    K = _next_pow2(max(len(e[1]) for e in entries))
    pk = np.broadcast_to(_G1_IDENT, (S, K, 3, LF.LIMBS)).copy()
    kmask = np.zeros((S, K), bool)
    sig = np.broadcast_to(_G2_IDENT, (S, 3, 2, LF.LIMBS)).copy()
    h = np.broadcast_to(_G2_IDENT, (S, 3, 2, LF.LIMBS)).copy()
    scal = np.zeros((S, 2), np.uint32)
    smask = np.zeros(S, bool)
    for i, (sig_pt, keys, msg) in enumerate(entries):
        for j, kp in enumerate(keys):
            pk[i, j] = _g1_arr(kp)
        kmask[i, :len(keys)] = True
        if sig_pt is not None:
            sig[i] = _g2_arr(sig_pt)
        h[i] = _h_arr(msg)
        c = rand_fn()
        scal[i] = (c & 0xFFFFFFFF, c >> 32)
        smask[i] = True
    return (pk, kmask, sig, h, scal, smask)


def _dispatch(entries, rand_fn) -> bool:
    """entries: list of (agg_sig_point | None meaning infinity is already
    rejected, [pubkey points], message bytes).  rand_fn() → 64-bit scalar.

    Off-TPU, batches larger than the pipeline sub-batch run through the
    SAME staged executor as the Pallas path (marshal i+1 overlaps the
    kernel on i; each sub-batch is an independent product so the AND of
    the verdicts equals the monolithic verdict) — guarded like
    :func:`_split_batches` to entries that each carry a signature."""
    # Fresh stage split per dispatch — per-key overwrites would otherwise
    # leak keys from a previous dispatch (or a different path's run)
    # into the decomposition bench.py reads back.
    LAST_FAST_AGG_TIMINGS.clear()
    entries, valid = _dedup_shared_keygroups(entries)
    if not valid:
        return False
    shared_pt = _shared_group_key(entries)
    if shared_pt is not None:
        # The whole batch signs under one aggregated key: collapse to
        # e(P, Σ c_i·H_i) · e(−G, Σ c_i·σ_i) — two Miller lanes total.
        return _dispatch_shared(entries, shared_pt, rand_fn)
    if _use_pallas():
        return _dispatch_pallas(entries, rand_fn)
    # Off-TPU XLA path: the SAME K-grouped work list as the Pallas path
    # (`_split_batches`) — a mixed-width batch (the overlapped block
    # batch: ~committee-width attestation sets + single-key proposer/
    # randao/exit sets + a 512-key sync aggregate) no longer pads every
    # set's pubkey lanes to the batch max K.  Each work item is an
    # independent RLC product, so the AND of verdicts equals the
    # monolithic verdict.
    work = _split_batches(entries)
    if len(work) > 1:
        if _pipeline_sets() <= 0:
            # PIPELINE_SETS=0 disables the staged machinery (the
            # debugging oracle): K-groups dispatch sequentially, one
            # monolithic marshal + kernel each.
            return all(_verify_xla_direct(batch, rand_fn)
                       for batch in work)
        from ..parallel.pipeline import StagedExecutor
        ex = StagedExecutor("bls_pipeline", subsystem="bls")
        outs = ex.map(
            work,
            lambda batch: _marshal_xla(batch, rand_fn),
            lambda staged: _verify_sets_kernel(*staged))
        return all(bool(o) for o in outs)
    return _verify_xla_direct(entries, rand_fn)


def _verify_xla_direct(batch, rand_fn) -> bool:
    """One monolithic marshal + XLA kernel call, with the implicit jit
    staging accounted into the device ledger (the staged-executor paths
    account theirs at the staging seam)."""
    import time as _time
    from ..common.device_ledger import LEDGER

    args = _marshal_xla(batch, rand_fn)
    LEDGER.note_transfer(
        "h2d", sum(int(a.nbytes) for a in args if hasattr(a, "nbytes")),
        subsystem="bls")
    t0 = _time.perf_counter()
    ok = bool(_verify_sets_kernel(*args))
    LEDGER.note_dispatch("bls", (_time.perf_counter() - t0) * 1e3)
    LEDGER.note_transfer("d2h", 1, subsystem="bls")
    return ok


def _host_fastpath_max() -> int:
    """Batch sizes up to this verify on the HOST via the native C++
    pairing instead of the device (VERDICT r4 #4): tiny batches (the
    gossip-block proposer check) are latency-bound on the fixed
    per-dispatch cost, not compute.  Default crossover 4 — a value tuned
    on an earlier, slower dispatch path and not yet re-measured on a
    directly attached chip; LIGHTHOUSE_TPU_HOST_FASTPATH_MAX=0 keeps
    everything on-device."""
    from ..common.knobs import knob_int
    return knob_int("LIGHTHOUSE_TPU_HOST_FASTPATH_MAX")


def _host_fast(n_sets: int) -> bool:
    if n_sets > _host_fastpath_max():
        return False
    from . import native
    return native.ready()  # honors the NO_NATIVE kill-switch


class TpuBackend:
    """Device-batched verification registered as ``tpu`` in :mod:`.bls`."""

    name = "tpu"

    def verify(self, signature, pubkeys, message) -> bool:
        if signature.point is None or not pubkeys:
            return False
        if _host_fast(1):
            from .bls import _BACKENDS
            return _BACKENDS["python"].verify(signature, pubkeys, message)
        return _dispatch(
            [(signature.point, [k.point for k in pubkeys], bytes(message))],
            rand_fn=lambda: 1)

    def aggregate_verify(self, signature, pubkeys, messages) -> bool:
        if signature.point is None or not pubkeys \
                or len(pubkeys) != len(messages):
            return False
        if _host_fast(len(messages)):
            from .bls import _BACKENDS
            return _BACKENDS["python"].aggregate_verify(
                signature, pubkeys, messages)
        # Distinct message per signer: one single-key set per message, the
        # aggregate signature attached to the first set, scalars all 1.
        entries = [(None, [pk.point], bytes(m))
                   for pk, m in zip(pubkeys, messages)]
        entries[0] = (signature.point, entries[0][1], entries[0][2])
        return _dispatch(entries, rand_fn=lambda: 1)

    def verify_signature_sets(self, sets) -> bool:
        import secrets
        if not sets:
            return False
        if _host_fast(len(sets)):
            from .bls import _BACKENDS
            return _BACKENDS["python"].verify_signature_sets(sets)
        entries = []
        for s in sets:
            if s.signature is None or s.signature.point is None:
                return False
            if not s.signing_keys:
                return False
            entries.append((s.signature.point,
                            [k.point for k in s.signing_keys],
                            bytes(s.message)))

        def rand_nonzero():
            c = 0
            while c == 0:
                c = secrets.randbits(64)
            return c

        return _dispatch(entries, rand_fn=rand_nonzero)


def register() -> None:
    from . import bls
    bls.register_backend("tpu", TpuBackend())


register()
