"""Sharded Merkle-tree reduction over a device mesh.

The 1M-validator registry tree (depth 40+1,
``/root/reference/consensus/types/src/eth_spec.rs:267``) is the dominant
``hash_tree_root`` workload.  On a multi-chip mesh we split the leaf range
over the ``batch`` axis, reduce each contiguous sub-range to its sub-tree
root entirely on-chip with ``shard_map`` (zero communication — leaf ranges
are power-of-two aligned so each shard owns a whole sub-tree), all-gather
the per-chip roots over ICI, and fold the remaining ``log2(n_chips)`` +
zero-padding levels replicated.  The reference's equivalent is rayon over
4096-validator arenas (``tree_hash_cache.rs:25-33,535-556``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.merkle import merkleize
from .mesh import BATCH_AXIS, axis_size, batch_sharding, mesh_program


def _log2(n: int) -> int:
    assert n & (n - 1) == 0 and n > 0, f"{n} not a power of two"
    return n.bit_length() - 1


def _is_pow2(n: int) -> bool:
    return n > 0 and n & (n - 1) == 0


@partial(jax.jit, static_argnames=("depth", "mesh"))
def sharded_merkle_root(leaves: jnp.ndarray, mesh: Mesh, depth: int) -> jnp.ndarray:
    """Root of a depth-``depth`` padded tree over ``leaves`` ``(n, 8)`` u32.

    ``n`` must be a power of two divisible by the mesh size.  The input is
    (re)sharded contiguously over the ``batch`` axis; output is the
    replicated ``(8,)`` root.
    """
    n = leaves.shape[0]
    ndev = mesh.shape[BATCH_AXIS]
    assert n % ndev == 0, (n, ndev)
    local_n = n // ndev
    local_depth = _log2(local_n)
    assert depth >= local_depth + _log2(ndev)

    leaves = jax.lax.with_sharding_constraint(leaves, batch_sharding(mesh))

    def local_subtree(chunk):
        # chunk: (local_n, 8) — one whole aligned sub-tree per device.
        return merkleize(chunk, local_depth)[None]  # (1, 8)

    # check_vma=False: the SHA round scan seeds its carry with the constant
    # IV (unvarying) and folds in the sharded block, which trips the
    # varying-axes check; semantics are still purely per-shard.
    roots = jax.shard_map(
        local_subtree, mesh=mesh,
        in_specs=P(BATCH_AXIS), out_specs=P(BATCH_AXIS),
        check_vma=False,
    )(leaves)  # (ndev, 8), sharded — the following gather rides ICI.

    return merkleize(roots, depth, base_level=local_depth)


# ---------------------------------------------------------------------------
# Resident-tree levels (PR 20): the DeviceTree / registry-mirror level
# stack as a mesh program, not just a one-shot root
# ---------------------------------------------------------------------------
#
# A contiguous pow2 leaf range per shard means every interior node whose
# level is wider than the mesh has BOTH children on the same shard, so
# levels of width ≥ ndev shard cleanly over ``batch`` (each shard folds
# its own sub-tree, zero communication) and only the top ``log2(ndev)``
# levels cross the shard boundary — they are computed past one implicit
# all-gather of the (ndev, 8) sub-root level.  The fold order is exactly
# ``_levels_body``'s, so the level stack is bit-identical to the
# 1-device build.

_LEVELS_PROGRAMS = {}  # (mesh, local_depth, use_kernel) -> program
_TOP_FOLD_JIT = None


def _get_top_fold():
    global _TOP_FOLD_JIT
    if _TOP_FOLD_JIT is None:
        def top_fold(cur):
            from ..ops.sha256 import hash64
            levels = []
            while cur.shape[0] > 1:
                cur = hash64(cur[0::2], cur[1::2])
                levels.append(cur)
            return tuple(levels)
        _TOP_FOLD_JIT = jax.jit(top_fold)
    return _TOP_FOLD_JIT


def sharded_tree_levels(leaves, mesh: Mesh, *,
                        use_kernel: bool = False):
    """Every level of the padded tree over ``(w, 8)`` u32 leaves as a
    sharded level stack, or ``None`` when the shape doesn't divide the
    mesh (the caller falls back to the 1-device build).

    Returns the same tuple as ``merkle_kernel._levels_body`` — widths
    ``w, w/2, …, 1`` — with levels of width ≥ ndev sharded over
    ``batch`` and the top ``log2(ndev)`` levels replicated.
    """
    w = int(leaves.shape[0])
    ndev = axis_size(mesh)
    if ndev == 1 or not _is_pow2(ndev) or not _is_pow2(w) \
            or w % ndev or w // ndev < 2:
        return None
    local_depth = _log2(w // ndev)

    key = (mesh, local_depth, bool(use_kernel))
    prog = _LEVELS_PROGRAMS.get(key)
    if prog is None:
        from ..ops.merkle_kernel import _levels_body

        def local_levels(chunk):
            # chunk: (local_w, 8) — one whole aligned sub-tree per
            # shard; its full local level stack, sub-root included.
            return _levels_body(chunk, use_kernel=use_kernel)

        prog = mesh_program(
            local_levels, mesh=mesh, in_specs=P(BATCH_AXIS),
            out_specs=tuple(P(BATCH_AXIS)
                            for _ in range(local_depth + 1)))
        _LEVELS_PROGRAMS[key] = prog

    lower = prog(leaves)          # widths w .. ndev, sharded
    tops = _get_top_fold()(lower[-1])  # widths ndev/2 .. 1, replicated
    return tuple(lower) + tuple(tops)
