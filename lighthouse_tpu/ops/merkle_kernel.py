"""Pallas TPU kernel for the big SSZ Merkle reductions.

This is the production hot path for registry-scale ``hash_tree_root`` — the
workload the reference parallelises with rayon over 4096-validator arenas
(``/root/reference/consensus/types/src/beacon_state/tree_hash_cache.rs:25-33,
535-556``) and we map onto the VPU as a single fused kernel.

Why a kernel at all: the pure-XLA reduction (:mod:`lighthouse_tpu.ops.merkle`)
rolls the 64 SHA-256 rounds with ``lax.scan``, which materialises the carry to
HBM every round — the whole reduction is HBM-bound (~90 ms on-device for 2^21
leaves).  Here the full 64 rounds ×2 compressions ×``chunk_log2`` tree levels
are unrolled inside one Pallas program, so a chunk's entire sub-tree reduces
in VMEM/registers with exactly one HBM read of the leaves and one 32-byte
write per chunk root (~6 ms on-device for the same tree — ~3 ns/hash,
~13x a single SHA-NI core's ~40 ns/hash).

Layout: digests live as 8 *word planes* — ``planes[w][i]`` = word ``w`` of
digest ``i`` — so every SHA op is a full-width elementwise vector op with the
digest index on the vector lanes (the structure-of-arrays twin of the
registry's SoA columns).

Pairing trick: Mosaic has no strided (de-interleave) lane access, so a level
cannot pair lanes ``(2i, 2i+1)``.  Instead each chunk's leaves are stored in
**bit-reversed order**, which turns the standard adjacent-pairs tree into the
*halves* tree: level ``m`` pairs lane ``i`` with lane ``i + m/2`` — two
contiguous slices, zero shuffles.  Chunks themselves stay in natural order
(a contiguous chunk is exactly an SSZ sub-tree), so only the cheap
within-chunk permutation (one device gather, ~1 ms at 2^21) is ever applied,
and the cross-chunk tail pairs naturally via :func:`..ops.merkle.merkleize`.
"""

from __future__ import annotations

import time
from functools import lru_cache, partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sha256 import _IV, _K, _PAD64_KW, _rotr

U32 = np.uint32

# Default chunk: 2^15 leaves = 1 MiB of VMEM per input block; 15 unrolled
# levels keep the kernel within Mosaic's scoped-VMEM budget (2^16 overflows).
CHUNK_LOG2 = 15


def compress_data_block(state, block16):
    """One SHA-256 compression, fully unrolled, message schedule computed
    on the fly in a rolling 16-word window (keeps ≤24 live vectors — the
    upfront 64-entry schedule blows VMEM at wide lanes).

    ``state``: 8-sequence of same-shaped u32 arrays; ``block16``: 16-sequence.
    """
    a, b, c, d, e, f, g, h = state
    w = list(block16)
    for i in range(64):
        if i < 16:
            wi = w[i]
        else:
            x15, x2 = w[(i - 15) % 16], w[(i - 2) % 16]
            s0 = _rotr(x15, 7) ^ _rotr(x15, 18) ^ (x15 >> U32(3))
            s1 = _rotr(x2, 17) ^ _rotr(x2, 19) ^ (x2 >> U32(10))
            wi = w[i % 16] + s0 + w[(i - 7) % 16] + s1
            w[i % 16] = wi
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + wi + U32(_K[i])
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + s0 + maj
    return tuple(x + y for x, y in zip(state, (a, b, c, d, e, f, g, h)))


def compress_const_block(state, kw):
    """Compression against a constant block whose W+K schedule is
    precomputed (``kw``: 64 scalars) — the fixed padding block of a 64-byte
    message."""
    a, b, c, d, e, f, g, h = state
    for i in range(64):
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + kw[i]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + s0 + maj
    return tuple(x + y for x, y in zip(state, (a, b, c, d, e, f, g, h)))


_PAD64_KW_SCALARS = [U32(v) for v in _PAD64_KW]


def hash64_planes(left, right):
    """``hash32_concat`` over word planes: 8+8 same-shaped u32 arrays in,
    8 out.  Semantics match :func:`..ops.sha256.hash64`."""
    block = list(left) + list(right)
    shp = left[0].shape
    iv = tuple(jnp.full(shp, v, dtype=jnp.uint32) for v in _IV)
    mid = compress_data_block(iv, block)
    return list(compress_const_block(mid, _PAD64_KW_SCALARS))


def _halves_reduce(planes, levels: int):
    """The shared reduction body: ``levels`` rounds of halves pairing over
    2-D ``(1, m)`` word planes in bit-reversed leaf order.

    Used verbatim inside the Pallas kernel AND by the pure-XLA reference
    path, so CPU tests exercise the exact arithmetic the kernel compiles.
    """
    m = planes[0].shape[1]
    for _ in range(levels):
        m //= 2
        left = [p[:, :m] for p in planes]
        right = [p[:, m:] for p in planes]
        planes = hash64_planes(left, right)
    return planes


def _subtree_kernel(in_ref, out_ref, *, levels: int):
    """Reduce one chunk (bit-reversed leaf order) to its sub-tree root.

    ``in_ref``: ``(8, 2^levels)`` u32 word planes; ``out_ref``: ``(G, 8)``
    full output array — each grid cell writes its own row.
    """
    planes = _halves_reduce([in_ref[w:w + 1, :] for w in range(8)], levels)
    i = pl.program_id(0)
    out_ref[pl.ds(i, 1), :] = jnp.concatenate(planes, axis=1)


def chunk_roots(planes: jnp.ndarray, chunk_log2: int = CHUNK_LOG2,
                use_kernel: bool | None = None) -> jnp.ndarray:
    """Sub-tree roots of every ``2^chunk_log2``-leaf chunk.

    ``planes``: ``(8, n)`` u32 word planes, leaves bit-reversed *within* each
    chunk (see :func:`brev_indices`), chunks in natural order.  Returns
    ``(n / 2^chunk_log2, 8)`` u32 chunk roots (digests-major).

    ``use_kernel``: force the Pallas kernel (True) or the pure-XLA shared
    body (False); default picks the kernel off-CPU.  (Pallas interpret mode
    takes minutes to emulate one compression, so CPU tests run the shared
    body directly — same arithmetic, same pairing.)
    """
    n = planes.shape[1]
    c = 1 << chunk_log2
    if n % c or n < c:
        raise ValueError(f"{n} leaves not a multiple of chunk {c}")
    g = n // c
    if use_kernel is None:
        use_kernel = _use_pallas()
    if not use_kernel:
        grouped = planes.reshape(8, g, c)
        cols = _halves_reduce(
            [grouped[w] for w in range(8)], chunk_log2)  # 8 x (g, 1)
        return jnp.concatenate(cols, axis=1)  # (g, 8)
    return pl.pallas_call(
        partial(_subtree_kernel, levels=chunk_log2),
        grid=(g,),
        in_specs=[pl.BlockSpec((8, c), lambda i: (0, i),
                               memory_space=pltpu.VMEM)],
        # One resident output block; each cell stores one row.  (Per-cell
        # (8, 1) column blocks violate Mosaic's lane-divisibility rule and
        # dynamic column stores crash its vector_store lowering.)
        out_specs=pl.BlockSpec((g, 8), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((g, 8), jnp.uint32),
    )(planes)


def _hash64_pallas_kernel(l_ref, r_ref, o_ref):
    left = [l_ref[w:w + 1, :] for w in range(8)]
    right = [r_ref[w:w + 1, :] for w in range(8)]
    o_ref[:] = jnp.concatenate(hash64_planes(left, right), axis=0)


def hash64_pallas(left: jnp.ndarray, right: jnp.ndarray,
                  block_log2: int = 15) -> jnp.ndarray:
    """``hash64`` as a Pallas kernel over word planes: (n, 8) pairs →
    (n, 8) digests with the two compressions fully unrolled in VMEM (the
    XLA-scan ``hash64`` round-trips its 24-word working set through HBM
    every round — ~10× slower at registry widths)."""
    n = left.shape[0]
    b = 1 << block_log2
    if n % b:
        raise ValueError(f"lane count {n} not a multiple of {b}")
    g = n // b
    lp = left.T
    rp = right.T
    out = pl.pallas_call(
        _hash64_pallas_kernel,
        grid=(g,),
        in_specs=[pl.BlockSpec((8, b), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((8, b), lambda i: (0, i),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((8, b), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((8, n), jnp.uint32),
    )(lp, rp)
    return out.T


def _levels_body(leaves: jnp.ndarray, *, use_kernel: bool):
    """All tree levels over ``(w, 8)`` u32 leaves (w a power of two), as one
    traced program: Pallas hash64 for the wide levels, XLA for the tail.
    Returns ``(levels...)`` with ``levels[0] = leaves``, ``levels[-1]``
    the ``(1, 8)`` subtree root."""
    from .sha256 import hash64 as hash64_xla

    pb = 1 << 15

    def h64(a, b):
        if use_kernel and a.shape[0] >= pb and a.shape[0] % pb == 0:
            return hash64_pallas(a, b)
        return hash64_xla(a, b)

    levels = [leaves]
    cur = leaves
    while cur.shape[0] > 1:
        cur = h64(cur[0::2], cur[1::2])
        levels.append(cur)
    return tuple(levels)


_levels_device_jit = None

# H2D streaming granularity for big leaf pushes: 2^18 rows = 8 MiB per
# chunk at (rows, 8) u32.  Overridable (0 disables chunking) via
# LIGHTHOUSE_TPU_PUSH_CHUNK_ROWS.
PUSH_CHUNK_ROWS = 1 << 18

# Accumulated stats of chunked device builds since the last
# :func:`reset_push_stats` (a cold state root runs one build per big
# field, so totals are what bench.py surfaces as ``leaf_push_*``):
# wait_ms is the transfer time left on the critical path, overlap_ms
# the transfer time hidden behind compute.
LAST_PUSH_STATS: dict = {}


def reset_push_stats() -> None:
    LAST_PUSH_STATS.clear()


def _push_chunk_rows() -> int:
    """The env knob, rounded DOWN to a power of two so it always
    divides the (power-of-two) leaf widths — a non-divisor value must
    tune the pipeline, not silently disable it.  ≤ 0 disables."""
    from ..common.knobs import knob_int
    rows = knob_int("LIGHTHOUSE_TPU_PUSH_CHUNK_ROWS",
                    default=PUSH_CHUNK_ROWS)
    return 1 << (rows.bit_length() - 1) if rows > 0 else 0


def _get_levels_jit():
    global _levels_device_jit
    if _levels_device_jit is None:
        _levels_device_jit = jax.jit(_levels_body,
                                     static_argnames=("use_kernel",))
    return _levels_device_jit


def merkle_levels_device(leaves: np.ndarray, chunk_rows: int | None = None):
    """Compute every tree level of ``(w, 8)`` leaves on-device and return
    ``(root_words, device_levels)`` — the root pulled immediately
    (32 bytes), the levels left device-resident for the caller to pull
    lazily (eager per-level pulls are what made the r3 cold state root
    take minutes).

    Wide builds stream the leaves in ``chunk_rows``-row column chunks
    through a background :class:`~lighthouse_tpu.parallel.pipeline.
    ChunkStager`: chunk i+1 transfers while chunk i's sub-tree levels
    already reduce on the device (the level-pull machinery in reverse),
    so the monolithic blocking push disappears from the critical path.
    The chunk sub-tree levels concat per level into the SAME full-tree
    levels the monolithic path produces, then the chunk roots reduce to
    the top — bit-identical output, tested off-device."""
    leaves = np.ascontiguousarray(leaves).astype(np.uint32, copy=False)
    w = leaves.shape[0]
    chunk = _push_chunk_rows() if chunk_rows is None else chunk_rows
    jit = _get_levels_jit()
    use_kernel = _use_pallas()
    if chunk <= 0 or w <= chunk or w % chunk:
        from ..common.device_ledger import LEDGER
        LEDGER.note_transfer("h2d", leaves.nbytes, subsystem="staging")
        dev = jax.device_put(leaves)  # device-io: staging
        levels = jit(dev, use_kernel=use_kernel)
        return np.asarray(levels[-1])[0], levels  # device-io: staging

    from ..parallel.pipeline import ChunkStager

    t0 = time.perf_counter()
    n_chunks = w // chunk
    stager = ChunkStager([leaves[i * chunk:(i + 1) * chunk]
                          for i in range(n_chunks)])
    parts = [jit(dev, use_kernel=use_kernel) for dev in stager]
    # Level l of the full tree is the in-order concat of the chunks'
    # level l (a contiguous chunk is exactly a sub-tree); above the
    # chunk roots the tail reduces as its own (tiny) levels program.
    levels = [jnp.concatenate([p[l] for p in parts], axis=0)
              for l in range(len(parts[0]))]
    tail = jit(levels[-1], use_kernel=use_kernel)
    levels.extend(tail[1:])
    root = np.asarray(levels[-1])[0]  # device-io: staging
    for key, add in (
            ("builds", 1), ("chunks", n_chunks),
            ("staging_fallbacks", stager.fallbacks),
            ("wait_ms", round(stager.wait_s * 1e3, 1)),
            ("overlap_ms", round(
                max(stager.transfer_s - stager.wait_s, 0.0) * 1e3, 1)),
            ("wall_ms", round((time.perf_counter() - t0) * 1e3, 1))):
        LAST_PUSH_STATS[key] = round(LAST_PUSH_STATS.get(key, 0) + add, 1)
    return root, tuple(levels)


@lru_cache(maxsize=8)
def brev_indices(chunk_log2: int) -> np.ndarray:
    """``(2^chunk_log2,) int32``: bit-reversal permutation of chunk slots.

    Self-inverse: ``x[brev] `` both applies and undoes the layout.
    """
    c = 1 << chunk_log2
    idx = np.arange(c, dtype=np.uint32)
    out = np.zeros(c, dtype=np.int32)
    for b in range(chunk_log2):
        out |= (((idx >> b) & 1) << (chunk_log2 - 1 - b)).astype(np.int32)
    return out


def _use_pallas() -> bool:
    """Real kernel only where Mosaic can lower it (TPU); everything else
    takes the XLA/host paths."""
    return jax.default_backend() == "tpu"


def _chunk_roots_natural_impl(leaves: jnp.ndarray, chunk_log2: int,  # device-io: staging
                              use_kernel: bool) -> jnp.ndarray:
    n = leaves.shape[0]
    c = 1 << chunk_log2
    planes = leaves.T  # (8, n)
    brev = jnp.asarray(brev_indices(chunk_log2))
    planes = planes.reshape(8, n // c, c)[:, :, brev].reshape(8, n)
    return chunk_roots(planes, chunk_log2, use_kernel=use_kernel)  # (g, 8)


chunk_roots_natural = partial(jax.jit, static_argnames=(
    "chunk_log2", "use_kernel"))(_chunk_roots_natural_impl)
"""Jitted device pipeline: natural-order ``(n, 8)`` leaves → ``(g, 8)``
chunk sub-tree roots (transpose → within-chunk brev gather → kernel)."""


def merkle_root_chunked(leaves, depth: int,
                        chunk_log2: int = CHUNK_LOG2,
                        use_kernel: bool | None = None) -> np.ndarray:
    """Root of a depth-``depth`` padded tree over ``(n, 8)`` u32 leaves in
    natural order, ``n`` a power of two ≥ the chunk size.  Returns ``(8,)``
    u32 root words on the host.

    Split: the ``n → n/2^chunk_log2`` reduction (99.99% of the hashes) runs
    on-device in one dispatch; the remaining ~``log2(g) + depth - log2(n)``
    single-hash levels run on the host — a few dozen sequential 64-byte
    hashes cost microseconds on CPU but dominate dispatch-bound device time
    as a chain of one-element launches.  (On CPU the device part runs the
    shared body eagerly — XLA-CPU takes minutes to compile the ~1.5k-op
    unrolled compression chain that Mosaic handles in seconds.)
    """
    from .merkle import merkleize_auto

    n = leaves.shape[0]
    if n & (n - 1):
        raise ValueError("pad leaf count to a power of two first")
    c = 1 << chunk_log2
    if n < c:
        raise ValueError(f"use merkleize() below {c} leaves")
    if (n - 1).bit_length() > depth:
        raise ValueError(f"{n} leaves overflow a depth-{depth} tree")
    if use_kernel is None:
        use_kernel = _use_pallas()
    if use_kernel:
        roots = np.asarray(chunk_roots_natural(
            leaves, chunk_log2=chunk_log2, use_kernel=True))
    else:
        roots = np.asarray(_chunk_roots_natural_impl(  # device-io: staging
            jnp.asarray(leaves), chunk_log2, False))  # device-io: staging
    # Tail: a few dozen single-hash levels — host dispatch via merkleize_auto
    # (a chain of one-element device launches would be dispatch-bound).
    return merkleize_auto(roots, depth, base_level=chunk_log2)
