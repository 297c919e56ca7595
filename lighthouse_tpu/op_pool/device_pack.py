"""Device greedy-pack — fixed-shape max-cover for block production.

The host :func:`~.max_cover.greedy_pack` is a lazy-exact CELF loop:
pop the heaviest cached candidate, re-evaluate it against the current
coverage bitset, accept iff its weight held up.  That loop is exactly
the EAGER greedy — each round selects the ``(max marginal weight,
earliest index)`` candidate — so it reformulates as a fixed-shape
device program with no heap and no data-dependent control flow:

- the candidate pool is a CSR over flat entry columns (element id,
  weight, segment id) plus two precomputed coverage planes (``word =
  e >> 6``, ``bitmask = 1 << (e & 63)``) against a packed uint64
  coverage bitset;
- per round: one gather of the covered word per entry, a masked
  segment-sum of still-fresh weights per candidate (the marginal), one
  ``argmax`` (first occurrence == earliest-index tie-break, matching
  the CELF heap's ``(−w, i)`` ordering bit for bit), and a scatter-OR
  of the winner's fresh bits back into the bitset;
- the loop runs a fixed ``MAX_ATTESTATIONS`` rounds inside one
  ``fori_loop`` program; a round whose best marginal is ≤ 0 selects
  the ``−1`` sentinel, and coverage is then a fixed point, so trailing
  sentinel rounds are free and termination matches the host's.

Entry counts and candidate counts are bucket-padded to the next power
of two (the ``parallel/bls_shard`` / fork-choice mirror pattern), so
pool growth re-uses compiled programs instead of recompiling.  Like
the fork-choice jit engine, the kernel traces inside a scoped
``jax.enable_x64(True)`` (the bitset is uint64, marginals are
int64) and auto-selects: jitted XLA on a real TPU, an equivalent
vectorized numpy rounds engine elsewhere (CPU jit is correctness-equal
but compile-bound at test shapes).  ``LIGHTHOUSE_TPU_DEVICE_PACK=0``
routes packing back through the host CELF oracle; the differential
suite pins selection-order equality between all three.

Precondition (holds for every real candidate — committee members are
unique within a committee): element ids do not repeat WITHIN one
candidate's segment.  Both paths double-count a repeated element's
weight identically, but the device scatter-OR is an add over fresh
bits, which is only OR-exact when the winner's fresh bits are
distinct.

The staged CSR columns are an accounted device-ledger subsystem
(``op_pool``): pushes/pulls and dispatch wall time land in the warm-
slot budget like every other resident plane.
"""

from __future__ import annotations

import time
from typing import List, Optional, Tuple

import numpy as np

from ..ops.merkle import _next_pow2

__all__ = ["device_pack_enabled", "greedy_pack_device", "modeled_pack_ms",
           "note_adopt"]

# Modeled single-chip HBM stream rate for the rounds kernel (v5e-class
# ~819 GB/s; deliberately conservative).  The kernel is bandwidth-bound:
# per round each entry streams its covered-word gather + bitmask +
# weight + segment id and the scatter writes back — the bench's modeled
# row and scripts/validate_block_production.py share this one model.
PACK_MODELED_HBM_GBPS = 819.0
_BYTES_PER_ENTRY_ROUND = 40.0   # 4B word idx + 8B gather + 8B bitmask
#                                 + 8B weight + 4B seg + 8B scatter
_BYTES_PER_CAND_ROUND = 16.0    # marginal segment-sum + argmax stream

# Stage timings of the LAST pack + production adopt/discard decision —
# read via ``tracing.stage_split("op_pool")`` only (stage-source rule).
LAST_PACK_STATS: dict = {}


def device_pack_enabled() -> bool:
    """The oracle knob: ``LIGHTHOUSE_TPU_DEVICE_PACK=0`` routes
    packing through the host CELF :func:`~.max_cover.greedy_pack`."""
    from ..common.knobs import knob_bool
    return knob_bool("LIGHTHOUSE_TPU_DEVICE_PACK")


_ENGINE_AUTO: Optional[str] = None


def _resolve_engine(engine: Optional[str]) -> str:
    if engine in ("numpy", "jit"):
        return engine
    from ..common.knobs import knob_tribool
    forced = knob_tribool("LIGHTHOUSE_TPU_PACK_JIT")
    if forced is not None:
        return "jit" if forced else "numpy"
    global _ENGINE_AUTO
    if _ENGINE_AUTO is None:
        try:
            import jax
            _ENGINE_AUTO = ("jit" if jax.default_backend() == "tpu"
                            else "numpy")
        except Exception:
            _ENGINE_AUTO = "numpy"
    return _ENGINE_AUTO


def _bucket(k: int, floor: int = 8) -> int:
    return max(_next_pow2(max(int(k), 1)), floor)


def modeled_pack_ms(entries: int, candidates: int, rounds: int,
                    hbm_gbps: float = PACK_MODELED_HBM_GBPS) -> float:
    """Modeled device wall time of the rounds kernel at the PADDED
    shape — bytes streamed per round over the modeled HBM rate."""
    lb = _bucket(entries)
    b = _bucket(candidates)
    per_round = lb * _BYTES_PER_ENTRY_ROUND + b * _BYTES_PER_CAND_ROUND
    return rounds * per_round / (hbm_gbps * 1e9) * 1e3


def note_adopt(adopt_ms: float, adopted: bool) -> None:
    """Production-path hook: record the speculative-state adopt-vs-
    discard decision into this module's stage dict (the defining module
    owns all writes — callers never touch ``LAST_PACK_STATS``)."""
    LAST_PACK_STATS["adopt_ms"] = round(float(adopt_ms), 3)
    LAST_PACK_STATS["adopted"] = int(bool(adopted))
    LAST_PACK_STATS["discarded"] = int(not adopted)


def reset_stats() -> None:
    """Clear the stage dict (bench rows isolating one measurement from
    a previous row's pack; same ownership rule — writes stay here)."""
    LAST_PACK_STATS.clear()


# ---------------------------------------------------------------------------
# numpy rounds engine — the same per-round math as the jit kernel, on
# true (unpadded) shapes with early exit.  This is what the test fleet
# and CPU boxes run; selection order is pinned against both the jit
# kernel and the host CELF oracle.
# ---------------------------------------------------------------------------


def _pack_rounds_numpy(flat_e: np.ndarray, flat_w: np.ndarray,
                       offsets: np.ndarray, n_elements: int,
                       limit: int) -> List[int]:
    n = offsets.shape[0] - 1
    if n <= 0 or limit <= 0:
        return []
    e = flat_e.astype(np.int64, copy=False)
    seg = np.repeat(np.arange(n, dtype=np.int64),
                    np.diff(offsets.astype(np.int64)))
    word = e >> 6
    bit = np.uint64(1) << (e & 63).astype(np.uint64)
    w = flat_w.astype(np.int64, copy=False)
    covered = np.zeros((int(n_elements) + 63) // 64, np.uint64)
    live = np.ones(e.shape[0], bool)
    chosen: List[int] = []
    for _ in range(limit):
        marg = np.zeros(n, np.int64)
        # np.add.at: unbuffered integer accumulation — exact for int64
        # weights where a float64 bincount could round ties apart.
        np.add.at(marg, seg[live], w[live])
        winner = int(np.argmax(marg))   # first occurrence: earliest idx
        if marg[winner] <= 0:
            break
        m = live & (seg == winner)
        np.bitwise_or.at(covered, word[m], bit[m])
        live &= (covered[word] & bit) == 0
        chosen.append(winner)
    return chosen


# ---------------------------------------------------------------------------
# Jitted rounds kernel — one compiled program per
# (entry-bucket, candidate-bucket, word-bucket, rounds) shape.
# ---------------------------------------------------------------------------

_KERNELS: dict = {}


def _get_pack_kernel(lb: int, b: int, words: int, rounds: int):
    key = (lb, b, words, rounds)
    fn = _KERNELS.get(key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    def pack(word, bitmask, wgt, seg, valid):
        covered0 = jnp.zeros(words, jnp.uint64)
        sel0 = jnp.full(rounds, -1, jnp.int32)

        def body(r, carry):
            covered, sel = carry
            fresh = (covered[word] & bitmask) == 0
            live = jnp.where(valid & fresh, wgt, jnp.int64(0))
            marg = jnp.zeros(b, jnp.int64).at[seg].add(live)
            win = jnp.argmax(marg).astype(jnp.int32)
            took = marg[win] > 0
            m = took & valid & fresh & (seg == win)
            covered = covered.at[word].add(
                jnp.where(m, bitmask, jnp.uint64(0)))
            sel = sel.at[r].set(jnp.where(took, win, jnp.int32(-1)))
            return covered, sel

        _, sel = jax.lax.fori_loop(0, rounds, body, (covered0, sel0))
        return sel

    jitted = jax.jit(pack)

    def call(*args):
        with jax.enable_x64(True):
            return jitted(*args)

    _KERNELS[key] = call
    return call


def _pack_rounds_jit(flat_e: np.ndarray, flat_w: np.ndarray,
                     offsets: np.ndarray, n_elements: int,
                     limit: int) -> List[int]:
    import jax
    import jax.numpy as jnp
    from ..common.device_ledger import LEDGER

    n = offsets.shape[0] - 1
    L = int(offsets[-1])
    lb = _bucket(L, floor=64)
    b = _bucket(n)
    words = _bucket((int(n_elements) + 63) // 64)
    e64 = flat_e.astype(np.int64, copy=False)
    word = np.zeros(lb, np.int32)
    word[:L] = (e64 >> 6).astype(np.int32)
    bitmask = np.zeros(lb, np.uint64)
    bitmask[:L] = np.uint64(1) << (e64 & 63).astype(np.uint64)
    wgt = np.zeros(lb, np.int64)
    wgt[:L] = flat_w
    seg = np.zeros(lb, np.int32)
    seg[:L] = np.repeat(np.arange(n, dtype=np.int32),
                        np.diff(offsets.astype(np.int64)))
    valid = np.zeros(lb, bool)
    valid[:L] = True
    t0 = time.perf_counter()
    with LEDGER.attribute("op_pool"):
        with jax.enable_x64(True):
            d_word = jnp.asarray(word)        # device-io: op_pool
            d_bit = jnp.asarray(bitmask)      # device-io: op_pool
            d_wgt = jnp.asarray(wgt)          # device-io: op_pool
            d_seg = jnp.asarray(seg)          # device-io: op_pool
            d_valid = jnp.asarray(valid)      # device-io: op_pool
        LEDGER.note_transfer(
            "h2d", word.nbytes + bitmask.nbytes + wgt.nbytes
            + seg.nbytes + valid.nbytes, subsystem="op_pool")
        LAST_PACK_STATS["stage_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 3)
        t1 = time.perf_counter()
        sel_dev = _get_pack_kernel(lb, b, words, limit)(
            d_word, d_bit, d_wgt, d_seg, d_valid)
        sel = np.asarray(jax.device_get(sel_dev))  # device-io: op_pool
        wall = (time.perf_counter() - t1) * 1e3
        LEDGER.note_transfer("d2h", sel.nbytes, subsystem="op_pool")
        LEDGER.note_dispatch("op_pool", wall)
    return [int(s) for s in sel if 0 <= s < n]


def greedy_pack_device(flat_e: np.ndarray, flat_w: np.ndarray,
                       offsets: np.ndarray, n_elements: int, limit: int,
                       engine: Optional[str] = None,
                       csr_build_ms: Optional[float] = None,
                       coverage_ms: Optional[float] = None) -> List[int]:
    """Fixed-shape greedy max-cover over the CSR candidate columns.

    Same contract as the host :func:`~.max_cover.greedy_pack` (CSR in,
    chosen candidate ids in selection order out) minus the per-choice
    live-element lists the columnar caller never used.  ``csr_build_ms``
    / ``coverage_ms`` let the caller hand its upstream phase timings in
    for the ``op_pool`` stage split without writing this module's
    stage dict from outside.
    """
    eng = _resolve_engine(engine)
    t0 = time.perf_counter()
    if eng == "jit":
        chosen = _pack_rounds_jit(flat_e, flat_w, offsets, n_elements,
                                  limit)
    else:
        LAST_PACK_STATS.pop("stage_ms", None)
        chosen = _pack_rounds_numpy(flat_e, flat_w, offsets, n_elements,
                                    limit)
    LAST_PACK_STATS["select_rounds_ms"] = round(
        (time.perf_counter() - t0) * 1e3, 3)
    if csr_build_ms is not None:
        LAST_PACK_STATS["csr_build_ms"] = round(float(csr_build_ms), 3)
    if coverage_ms is not None:
        LAST_PACK_STATS["coverage_ms"] = round(float(coverage_ms), 3)
    LAST_PACK_STATS["engine"] = eng
    LAST_PACK_STATS["candidates"] = int(offsets.shape[0] - 1)
    LAST_PACK_STATS["entries"] = int(offsets[-1]) if offsets.size else 0
    LAST_PACK_STATS["rounds"] = int(limit)
    LAST_PACK_STATS["selected"] = len(chosen)
    return chosen
