"""Device-resident incremental Merkle trees — zero-push warm roots.

The host :class:`~lighthouse_tpu.ops.tree_cache.IncrementalMerkleCache`
stores every interior level in host numpy and either walks dirty paths with
hashlib or re-pushes the whole leaf set for a device rebuild.  That design
made the *cold* state root 9.2 s of which 5.1 s was one monolithic H2D push
(``state_root_cold_push_ms``) — the state lived on host and was re-staged
for every device pass.  Here the tree levels live in HBM as the source of
truth (the MTU tree-unit shape, arXiv:2507.16793: the whole hash-tree
reduction stays on the accelerator) and a warm root is

    H2D:  k dirty leaf rows (+ their int32 indices)       — bytes ∝ dirty
    one fused program: leaf scatter → per-level re-hash   — k·log n hashes
    D2H:  32 bytes of root

so the full-state push disappears from the warm path instead of merely
being overlapped.  Donation follows the
:class:`~lighthouse_tpu.parallel.pipeline.StagedExecutor` idiom: when a
tree owns its buffers exclusively the update program donates them (true
in-place HBM update); after :meth:`DeviceTree.share` (fork-choice
state-cache clones, ``BeaconState.copy``) the next update runs undonated —
XLA materialises fresh buffers for the mutator and the sibling keeps the
old ones untouched: copy-on-write without duplicating HBM at clone time.

Dirty-index batches are padded to power-of-two buckets so the number of
compiled program shapes stays logarithmic in the update size; padding
duplicates a real (index, row) pair, which is idempotent under both the
scatter and the re-hash.
"""

from __future__ import annotations

from collections.abc import Mapping

import numpy as np

from ..common.device_ledger import LEDGER
from ..common.metrics import REGISTRY
from .merkle import _next_pow2

# Byte accounting for the residency story (surfaced by bench.py as
# ``state_root_device_resident``): every host→device transfer made on
# behalf of device-resident state goes through note_push, every pull of a
# lazily-materialised host view through note_pull.  Since the device
# ledger landed these route into :data:`~lighthouse_tpu.common.
# device_ledger.LEDGER` with the caller's ambient subsystem attribution
# (``device_tree`` when no seam set one), and ``RESIDENCY_STATS`` is a
# ledger-backed VIEW summing exactly its historical feeders — the
# tree/registry/packed/fork-choice residency paths.  BLS/KZG/slasher/
# staging traffic (newly accounted) is visible only through the ledger,
# so every pre-ledger reader keeps its numbers.
# Public: the view's feeder set (bench.py and the residency scripts
# import this — ONE definition, not three drifting copies).
LEGACY_RESIDENCY_SUBSYSTEMS = ("device_tree", "registry_mirror",
                               "packed_cache", "fork_choice")
_LEGACY_SUBSYSTEMS = LEGACY_RESIDENCY_SUBSYSTEMS
_LEGACY_KEYS = {
    "bytes_pushed": "h2d_bytes",
    "bytes_pulled": "d2h_bytes",
    "scatters": "scatters",
    "rebuilds": "rebuilds",
    "materializes": "materializes",
}


class _ResidencyView(Mapping):
    """Read-only legacy view over the ledger (reset = re-base, so the
    ledger itself stays monotonic for Prometheus and the per-slot delta
    ring)."""

    def __init__(self):
        self._base: dict = {}

    def _totals(self) -> dict:
        return LEDGER.subsystem_totals(_LEGACY_SUBSYSTEMS)

    def rebase(self) -> None:
        t = self._totals()
        self._base = {k: t[lk] for k, lk in _LEGACY_KEYS.items()}

    def __getitem__(self, key: str) -> int:
        t = self._totals()[_LEGACY_KEYS[key]]
        return max(int(t - self._base.get(key, 0)), 0)

    def __iter__(self):
        return iter(_LEGACY_KEYS)

    def __len__(self) -> int:
        return len(_LEGACY_KEYS)

    def __repr__(self) -> str:
        return f"ResidencyView({dict(self)})"


RESIDENCY_STATS = _ResidencyView()


def reset_residency_stats() -> None:
    RESIDENCY_STATS.rebase()


def note_push(nbytes: int) -> None:
    LEDGER.note_transfer("h2d", nbytes)


def note_pull(nbytes: int) -> None:
    LEDGER.note_transfer("d2h", nbytes)


def residency_snapshot() -> dict:
    # One totals pass, not one per key (this runs on the traced block-
    # import path via Tracer.residency_mark/record_residency).
    t = RESIDENCY_STATS._totals()
    base = RESIDENCY_STATS._base
    return {k: max(int(t[lk] - base.get(k, 0)), 0)
            for k, lk in _LEGACY_KEYS.items()}


def _donation_works() -> bool:
    """Donate buffers only where XLA honors it (TPU); on CPU jax ignores
    donation with a warning per call — the undonated program is identical
    apart from the in-place aliasing."""
    import jax
    return jax.default_backend() == "tpu"


def _bucket(k: int) -> int:
    """Dirty-batch size bucket: power of two ≥ 8 bounds the number of
    compiled shapes to ~log(max batch) (the ``tree_dirty`` family's
    registered bucket floor)."""
    from ..parallel.mesh import bucket_rows
    return bucket_rows("tree_dirty", k)


def pad_bucket(idx: np.ndarray, rows: np.ndarray) -> tuple:
    """Pad ``(k,)`` indices / ``(k, …)`` rows to the bucket size by
    repeating the first entry — idempotent under scatter + re-hash."""
    k = idx.shape[0]
    b = _bucket(k)
    if k == b:
        return idx.astype(np.int32, copy=False), rows
    pidx = np.empty(b, dtype=np.int32)
    pidx[:k] = idx
    pidx[k:] = idx[0]
    prows = np.empty((b,) + rows.shape[1:], dtype=rows.dtype)
    prows[:k] = rows
    prows[k:] = rows[0]
    return pidx, prows


def scatter_propagate_body(levels, idx, rows, *, use_kernel: bool):
    """The fused warm-root body: scatter ``rows`` into ``levels[0]`` at
    ``idx`` and re-hash exactly the touched ancestor path of every index
    up every level.  Duplicate indices (bucket padding) recompute the same
    parent with the same inputs — wasted lanes, never wrong bits.

    ``use_kernel`` (static) picks the level compression: the unrolled
    Pallas kernel (:func:`_hash64_kernel`) or the ``lax.scan`` ``hash64``
    — bit-identical; :func:`scatter_uses_kernel` decides.

    Shared verbatim by the packed-column trees and the registry mirror
    (which feeds record-mini-tree roots as ``rows``), so one compiled
    artifact per (bucket, width, route) covers both.
    """
    from .sha256 import hash64

    h64 = _get_hash64_kernel_jit() if use_kernel else hash64
    out = [levels[0].at[idx].set(rows)]
    cur = idx
    for lvl in range(1, len(levels)):
        cur = cur >> 1
        below = out[-1]
        h = h64(below[2 * cur], below[2 * cur + 1])
        out.append(levels[lvl].at[cur].set(h))
    return tuple(out)


# The Pallas compression's lane tile: a level's lanes are padded up to it.
_KERNEL_MIN_LANES = 128
# Its widest block (the VMEM bound shared with ``_levels_body``).
_KERNEL_MAX_BLOCK_LOG2 = 15


def _hash64_kernel(left, right):
    """``hash64`` of one level's ``(n, 8)`` child pairs (n a power of two,
    the bucket) through :func:`..ops.merkle_kernel.hash64_pallas`, in
    blocks of ``min(n, 2^15)`` lanes.  A bucket under the 128-lane tile is
    padded with copies of lane 0 and the pad lanes are dropped before the
    level's scatter — the same idempotent padding as :func:`pad_bucket`."""
    import jax.numpy as jnp

    from .merkle_kernel import hash64_pallas

    n = left.shape[0]
    if n < _KERNEL_MIN_LANES:
        def pad(x):
            return jnp.concatenate(
                [x, jnp.broadcast_to(x[:1], (_KERNEL_MIN_LANES - n, 8))])
        return _hash64_kernel(pad(left), pad(right))[:n]
    return hash64_pallas(left, right, block_log2=min(
        n.bit_length() - 1, _KERNEL_MAX_BLOCK_LOG2))


_hash64_kernel_jit = None


def _get_hash64_kernel_jit():
    """Every level of one bucket hashes the same shape: as a nested jit
    the unrolled kernel is traced and lowered once per shape, not once
    per level (inlined into the enclosing program — no dispatch, no
    program of its own)."""
    global _hash64_kernel_jit
    import jax
    if _hash64_kernel_jit is None:
        _hash64_kernel_jit = jax.jit(_hash64_kernel)
    return _hash64_kernel_jit


def scatter_uses_kernel() -> bool:
    """The warm scatter's compression route, from what the process can
    observe — the rule of the rebuild's ``_levels_body``: the Pallas
    kernel where Mosaic lowers it (TPU) and the tree's levels sit on one
    device; the scan body on the CPU and on a sharded mesh."""
    from ..parallel.mesh import axis_size
    return _use_kernel() and axis_size() == 1


_SCATTER_LEVELS = REGISTRY.counter(
    "device_tree_scatter_levels_total",
    "tree levels re-hashed by warm scatters, by compression route",
    labelnames=("route",))


def note_scatter_levels(levels, use_kernel: bool) -> None:
    """Count one warm scatter's re-hashed levels (host side, from the
    static tree depth) under the route it compiled with."""
    _SCATTER_LEVELS.labels(
        route="kernel" if use_kernel else "scan").inc(len(levels) - 1)


_scatter_jit = None
_scatter_jit_donated = None


def _get_scatter_jit(donate: bool):
    global _scatter_jit, _scatter_jit_donated
    import jax
    if donate:
        if _scatter_jit_donated is None:
            _scatter_jit_donated = jax.jit(
                scatter_propagate_body, donate_argnums=(0,),
                static_argnames=("use_kernel",))
        return _scatter_jit_donated
    if _scatter_jit is None:
        _scatter_jit = jax.jit(scatter_propagate_body,
                               static_argnames=("use_kernel",))
    return _scatter_jit


def _levels_body(leaves, *, use_kernel: bool):
    """All levels over ``(w, 8)`` u32 leaves (w pow2) — the same body as
    :func:`..ops.merkle_kernel._levels_body`, re-exported here so the
    device-resident rebuild path has no import-order coupling with the
    Pallas module's jit singletons."""
    from .merkle_kernel import _levels_body as body
    return body(leaves, use_kernel=use_kernel)


_levels_jit = None


def _get_levels_jit():
    global _levels_jit
    import jax
    if _levels_jit is None:
        _levels_jit = jax.jit(_levels_body, static_argnames=("use_kernel",))
    return _levels_jit


def _use_kernel() -> bool:
    from .merkle_kernel import _use_pallas
    return _use_pallas()


def _build_levels(leaves_dev):
    """Every tree level from device-resident leaves: the sharded mesh
    program when the process mesh has >1 shard and the width divides it
    (leaf ranges sharded, top ``log2(ndev)`` levels past the shard
    boundary), else the 1-device fused body — bit-identical stacks."""
    from ..parallel import mesh as pmesh
    if pmesh.axis_size() > 1:
        from ..parallel.merkle_shard import sharded_tree_levels
        levels = sharded_tree_levels(
            leaves_dev, pmesh.get_mesh(), use_kernel=_use_kernel())
        if levels is not None:
            return levels
    return _get_levels_jit()(leaves_dev, use_kernel=_use_kernel())


class DeviceTree:
    """One padded Merkle tree whose every level lives on the device.

    ``levels[0]`` is the ``(w, 8)`` u32 leaf plane (w a power of two),
    ``levels[-1]`` the ``(1, 8)`` subtree root.  Zero-cap folding up to the
    SSZ limit and the length mixin stay host-side (≤ ~40 single hashes),
    exactly like the host cache.
    """

    __slots__ = ("levels", "shared", "_res", "__weakref__")

    def __init__(self, levels, shared: bool = False):
        self.levels = tuple(levels)
        self.shared = shared
        # Residency token created lazily at the first accounting seam:
        # a share() clone holds no token (the parent owns the shared
        # buffers) until its first mutation lands in fresh buffers.
        self._res = None

    def note_residency(self) -> None:
        """Update this tree's HBM-resident byte contribution under the
        ambient ledger attribution (creates the token + its GC drop
        seam on first call)."""
        total = sum(int(lv.nbytes) for lv in self.levels)
        if self._res is None:
            self._res = LEDGER.track(
                self, LEDGER.ambient() or "device_tree", total)
        else:
            self._res.set(total)

    # -- construction --------------------------------------------------------

    @classmethod
    def from_host_leaves(cls, leaves: np.ndarray) -> "DeviceTree":
        """One-time materialization: place the full (w, 8) leaf plane
        through the mesh seam (sharded over ``batch`` when the process
        mesh has >1 shard) and reduce every level on-device.  The ONLY
        full-width push this tree ever makes."""
        from ..parallel.mesh import mesh_put
        leaves = np.ascontiguousarray(leaves, dtype=np.uint32)
        assert leaves.shape[0] == _next_pow2(leaves.shape[0])
        LEDGER.note_event("materializes")
        dev = mesh_put("tree_leaves", leaves)
        tree = cls(_build_levels(dev))
        tree.note_residency()
        return tree

    @classmethod
    def from_device_leaves(cls, leaves) -> "DeviceTree":
        """Rebuild from leaves already resident in HBM — zero push."""
        LEDGER.note_event("rebuilds")
        tree = cls(_build_levels(leaves))
        tree.note_residency()
        return tree

    # -- queries -------------------------------------------------------------

    @property
    def width(self) -> int:
        return self.levels[0].shape[0]

    def root_words(self) -> np.ndarray:
        # 32-byte root read: reviewed seam, deliberately unaccounted.
        return np.asarray(self.levels[-1])[0]  # device-io: device_tree

    def pull_levels(self) -> list:
        """Host copies of every level (de-materialization / oracle)."""
        from ..parallel.mesh import mesh_gather
        return [mesh_gather(lv, name="tree_leaves")
                for lv in self.levels]

    # -- updates -------------------------------------------------------------

    def scatter(self, idx: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Warm update: ``rows`` (k, 8) u32 replace leaves at ``idx``
        (ascending, unique); returns the new subtree root words.  H2D is
        the bucket-padded (idx, rows) pair only (the replicated
        ``tree_dirty`` mesh family)."""
        if idx.size == 0:
            return self.root_words()
        from ..parallel.mesh import mesh_put
        pidx, prows = pad_bucket(np.asarray(idx),
                                 np.ascontiguousarray(rows, dtype=np.uint32))
        return self._propagate(mesh_put("tree_dirty", pidx),
                               mesh_put("tree_dirty", prows))

    def scatter_device(self, idx_dev, rows_dev) -> np.ndarray:
        """Scatter with (idx, rows) already device-resident (registry
        mirror path) — zero push here; the caller accounted its own."""
        return self._propagate(idx_dev, rows_dev)

    def _propagate(self, idx_dev, rows_dev) -> np.ndarray:
        LEDGER.note_event("scatters")
        use_kernel = scatter_uses_kernel()
        note_scatter_levels(self.levels, use_kernel)
        jit = _get_scatter_jit(_donation_works() and not self.shared)
        self.levels = jit(self.levels, idx_dev, rows_dev,
                          use_kernel=use_kernel)
        self.shared = False  # the update produced buffers only we hold
        self.note_residency()
        return self.root_words()

    def rebuild_device(self, leaves) -> np.ndarray:
        """Replace every level from device-resident leaves (dirty fraction
        past the walk/rebuild crossover, or width growth) — zero push."""
        LEDGER.note_event("rebuilds")
        self.levels = _build_levels(leaves)
        self.shared = False
        self.note_residency()
        return self.root_words()

    # -- copy-on-write -------------------------------------------------------

    def share(self) -> "DeviceTree":
        """COW clone: both trees reference the same HBM until either
        mutates (jax arrays are immutable; the next update simply skips
        donation and lands in fresh buffers)."""
        self.shared = True
        return DeviceTree(self.levels, shared=True)


def warmup_scatter(width: int, ks=(1, 8, 64), depth_only: bool = False) -> int:
    """Pre-compile the dirty-propagation program for a ``width``-leaf tree
    at the given dirty-batch bucket sizes (plus the full-levels rebuild
    body) so a fresh node's first warm root is a compile-cache hit.
    Returns the number of programs driven."""
    import jax

    w = _next_pow2(max(width, 1))
    leaves = np.zeros((w, 8), dtype=np.uint32)
    tree = DeviceTree.from_host_leaves(leaves)
    n = 1 if depth_only else 0
    done = set()
    for k in ks:
        b = _bucket(min(k, w))
        if b in done or b > w:
            continue
        done.add(b)
        idx = np.arange(b, dtype=np.int32) % w
        rows = np.zeros((b, 8), dtype=np.uint32)
        tree.scatter(np.unique(idx), rows[:np.unique(idx).shape[0]])
        n += 1
    jax.block_until_ready(tree.levels)
    return n + 1
