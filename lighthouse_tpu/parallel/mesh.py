"""The ONE named mesh — residency layer for every device subsystem.

One logical axis, ``batch``: every hot-path workload (signature sets,
Merkle leaves, registry rows, fork-choice votes, slasher planes) is
embarrassingly parallel over its batch dimension, so the natural mesh is
1-D data-parallel over all chips — collectives only appear at the final
cross-chip reduction (sub-tree roots / pairing product / vote-delta
all-reduce).

Since PR 20 this module is the repo's single residency layer, not just a
mesh constructor.  Five subsystems (BLS shard, DeviceTree / registry
mirror, packed-column cache, fork-choice vote columns, slasher planes)
used to own ad-hoc ``jax.device_put`` spellings; they now place every
persistent column through the seams here:

- :func:`get_mesh` — the process-wide named mesh.  Axis size comes from
  the ``LIGHTHOUSE_TPU_MESH_DEVICES`` knob (0 = auto: all local devices
  on a real TPU backend, 1 otherwise), so a CPU test process with 8
  virtual XLA devices still degenerates to the single-device spelling
  unless a test/driver opts in.  1-device meshes degenerate cleanly:
  ``P("batch")`` over one device IS the unsharded placement.
- :func:`register_column` — the per-column PartitionSpec registry.
  Registry rows / balances / participation, fork-choice vote columns
  and slasher planes shard over ``"batch"``; tree upper levels, Fq12
  partials, selection matrices and scatter payloads replicate.
- :func:`mesh_put` / :func:`mesh_place` / :func:`mesh_gather` — the
  resharding seams.  Every placement/pull reports bytes into the device
  ledger per subsystem (host-wire totals, same families as before) AND
  per shard (:meth:`DeviceLedger.note_shard_transfer` — delivered
  bytes: 1/d per shard for a batch-sharded column, full size on every
  shard for a replicated one).  Attribution: explicit ``subsystem=``
  argument > ambient :meth:`DeviceLedger.attribute` scope > the
  column's registered subsystem.
- :func:`mesh_program` — the one ``shard_map`` spelling
  (``jax.shard_map`` + ``check_vma=False``; see merkle_shard's note on
  why the SHA IV constant trips the varying-axes checker) wrapped in
  ``jax.jit``.

The graftlint ``mesh-residency`` checker enforces the contract from the
other side: raw ``jax.device_put`` in the five persistent-residency
modules and ``Mesh(...)`` construction outside this file are findings.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.device_ledger import LEDGER, SUBSYSTEMS


BATCH_AXIS = "batch"


def make_mesh(devices=None) -> Mesh:
    """1-D ``batch`` mesh over ``devices`` (default: all available)."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.array(devices).reshape(-1), (BATCH_AXIS,))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard dim 0 over the batch axis, replicate the rest."""
    return NamedSharding(mesh, P(BATCH_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (constants: zero-hash tables, generators)."""
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# The process mesh
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_MESHES: Dict[int, Mesh] = {}  # axis size -> mesh, guarded-by: _LOCK


def mesh_devices() -> int:
    """Resolved axis size of the process mesh (knob-selectable).

    ``LIGHTHOUSE_TPU_MESH_DEVICES=0`` (auto) means all local devices on
    a real TPU backend and 1 otherwise — the CPU test process exposes 8
    virtual XLA devices for the differential suites, and defaulting the
    whole tree onto them would silently turn every quick-tier test into
    a sharded compile.  Explicit N clamps to the local device count.
    """
    from ..common.knobs import knob_int
    n = knob_int("LIGHTHOUSE_TPU_MESH_DEVICES")
    if n <= 0:
        n = len(jax.devices()) if jax.default_backend() == "tpu" else 1
    return max(1, min(n, len(jax.devices())))


def get_mesh() -> Mesh:
    """The process-wide named mesh every subsystem places residency on.

    Cached per resolved axis size — flipping the knob mid-process (the
    differential tests, validate_mesh) picks up a new mesh on the next
    call without invalidating programs compiled against the old one.
    """
    n = mesh_devices()
    with _LOCK:
        mesh = _MESHES.get(n)
        if mesh is None:
            mesh = _MESHES[n] = make_mesh(jax.devices()[:n])
        return mesh


def axis_size(mesh: Optional[Mesh] = None) -> int:
    """Size of the ``batch`` axis (the shard count)."""
    mesh = get_mesh() if mesh is None else mesh
    return int(mesh.shape[BATCH_AXIS])


def reset_mesh() -> None:
    """Drop the mesh cache (tests flipping the device-count knob)."""
    with _LOCK:
        _MESHES.clear()


# ---------------------------------------------------------------------------
# Per-column PartitionSpec registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ColumnSpec:
    """One registered column family: how its arrays lay out on the mesh.

    ``spec`` is the INTENDED PartitionSpec; placement falls back to
    replicated when a concrete array's sharded dims don't divide the
    axis size (the seams degrade, they never fail).  ``pad_bucket`` is
    the pow2 bucket floor the family's transient payloads pad to
    (:func:`bucket_rows`) — bucketing and divisibility are the same
    concern: a pow2 bucket ≥ the axis size always shards cleanly.
    """
    name: str
    spec: P
    subsystem: str
    dtype: Optional[str] = None
    pad_bucket: Optional[int] = None
    doc: str = ""

    @property
    def sharded(self) -> bool:
        return any(ax is not None for ax in self.spec)


COLUMNS: Dict[str, ColumnSpec] = {}


def register_column(name: str, spec: P, *, subsystem: str,
                    dtype: Optional[str] = None,
                    pad_bucket: Optional[int] = None,
                    doc: str = "") -> ColumnSpec:
    """Declare a column family's mesh layout (idempotent re-register of
    an identical row is allowed; a conflicting one is a bug)."""
    assert subsystem in SUBSYSTEMS, subsystem
    col = ColumnSpec(name, spec, subsystem, dtype, pad_bucket, doc)
    prev = COLUMNS.get(name)
    if prev is not None and prev != col:
        raise ValueError(
            f"column {name!r} already registered with a different "
            f"layout ({prev.spec} vs {spec})")
    COLUMNS[name] = col
    return col


def bucket_rows(name: str, k: int) -> int:
    """Pow2 bucket for ``k`` rows of family ``name`` (floor = the
    registered ``pad_bucket``) — one bucketing rule for every transient
    payload, and the reason sharded dims always divide the mesh."""
    floor = COLUMNS[name].pad_bucket or 1
    return max(floor, 1 << max(int(k) - 1, 0).bit_length())


def _spec_for(col: ColumnSpec, shape: Tuple[int, ...],
              ndev: int) -> P:
    """The column's spec, degraded to replicated when a sharded dim of
    this concrete array doesn't divide the axis size."""
    if ndev == 1:
        return col.spec  # 1-device: any spec is the unsharded placement
    for dim, ax in enumerate(col.spec):
        if ax is None:
            continue
        if dim >= len(shape) or shape[dim] % ndev:
            return P()
    return col.spec


def column_sharding(name: str, shape: Optional[Tuple[int, ...]] = None,
                    mesh: Optional[Mesh] = None) -> NamedSharding:
    """NamedSharding for one concrete array of family ``name``."""
    mesh = get_mesh() if mesh is None else mesh
    col = COLUMNS[name]
    spec = col.spec if shape is None \
        else _spec_for(col, tuple(shape), axis_size(mesh))
    return NamedSharding(mesh, spec)


# ---------------------------------------------------------------------------
# Resharding seams (ALL persistent placement goes through here)
# ---------------------------------------------------------------------------

def _resolve_subsystem(col: Optional[ColumnSpec],
                       subsystem: Optional[str]) -> str:
    if subsystem is not None:
        assert subsystem in SUBSYSTEMS, subsystem
        return subsystem
    amb = LEDGER.ambient()
    if amb is not None:
        return amb
    return col.subsystem if col is not None else "device_tree"


def _note_shards(direction: str, sub: str, nbytes: int,
                 spec: P, ndev: int) -> None:
    """Per-shard delivered bytes for one placement: 1/d per shard when
    sharded, the full size on every shard when replicated (one host
    copy fans out over ICI)."""
    if any(ax is not None for ax in spec):
        per = nbytes // ndev
        LEDGER.note_shard_transfer(
            direction, {i: per for i in range(ndev)}, subsystem=sub)
    else:
        LEDGER.note_shard_transfer(
            direction, {i: nbytes for i in range(ndev)}, subsystem=sub)


def mesh_put(name: str, arr, mesh: Optional[Mesh] = None,
             subsystem: Optional[str] = None) -> jax.Array:
    """Place a host array as column family ``name`` (H2D, accounted
    per subsystem and per shard).  An already-on-device array routes
    through :func:`mesh_place` instead — no host-wire bytes."""
    if isinstance(arr, jax.Array):
        return mesh_place(name, arr, mesh=mesh)
    mesh = get_mesh() if mesh is None else mesh
    col = COLUMNS[name]
    host = np.asarray(arr)
    ndev = axis_size(mesh)
    spec = _spec_for(col, host.shape, ndev)
    out = jax.device_put(host, NamedSharding(mesh, spec))
    sub = _resolve_subsystem(col, subsystem)
    LEDGER.note_transfer("h2d", host.nbytes, subsystem=sub)
    _note_shards("h2d", sub, host.nbytes, spec, ndev)
    return out


def mesh_place(name: str, arr: jax.Array, mesh: Optional[Mesh] = None,
               subsystem: Optional[str] = None,
               h2d_bytes: Optional[int] = None) -> jax.Array:
    """Reshard an array that is ALREADY on device onto the column's
    registered layout (stager concatenations, width growth, adopted jit
    outputs).  Moves no host-wire bytes itself; ``h2d_bytes`` lets a
    caller whose actual push happened upstream UNACCOUNTED (a
    ChunkStager driven with ``subsystem=None``) settle the wire total +
    per-shard split at this seam instead."""
    mesh = get_mesh() if mesh is None else mesh
    col = COLUMNS[name]
    ndev = axis_size(mesh)
    spec = _spec_for(col, arr.shape, ndev)
    want = NamedSharding(mesh, spec)
    out = arr if getattr(arr, "sharding", None) == want \
        else jax.device_put(arr, want)
    if h2d_bytes:
        sub = _resolve_subsystem(col, subsystem)
        LEDGER.note_transfer("h2d", h2d_bytes, subsystem=sub)
        _note_shards("h2d", sub, int(h2d_bytes), spec, ndev)
    return out


def mesh_gather(arr, subsystem: Optional[str] = None,
                name: Optional[str] = None) -> np.ndarray:
    """Pull a device array to host (D2H, accounted per subsystem and
    per shard: bytes read FROM each shard — 1/d each when sharded, all
    from shard 0 when replicated)."""
    col = COLUMNS.get(name) if name else None
    sub = _resolve_subsystem(col, subsystem)
    out = np.asarray(arr)
    sharding = getattr(arr, "sharding", None)
    ndev = len(sharding.device_set) if sharding is not None else 1
    LEDGER.note_transfer("d2h", out.nbytes, subsystem=sub)
    if ndev > 1 and sharding is not None \
            and not sharding.is_fully_replicated:
        per = out.nbytes // ndev
        LEDGER.note_shard_transfer(
            "d2h", {i: per for i in range(ndev)}, subsystem=sub)
    else:
        LEDGER.note_shard_transfer("d2h", {0: out.nbytes}, subsystem=sub)
    return out


# ---------------------------------------------------------------------------
# Mesh programs
# ---------------------------------------------------------------------------

def mesh_program(fn, *, mesh: Optional[Mesh] = None, in_specs,
                 out_specs, **jit_kwargs):
    """The standard sharded-program spelling: ``jax.jit`` around
    ``jax.shard_map(fn, ..., check_vma=False)``.

    ``check_vma=False`` is load-bearing, not a shrug: every kernel here
    closes over replicated constant tables (the SHA-256 IV/round
    constants, curve generators), and the varying-axes checker flags
    those as possibly-divergent per-shard values; see
    ``parallel/merkle_shard.py`` for the full note.  Centralizing the
    spelling keeps the jax-hygiene checker's contract in ONE place.
    """
    mesh = get_mesh() if mesh is None else mesh
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return jax.jit(mapped, **jit_kwargs)


# ---------------------------------------------------------------------------
# The column families (the axis/PartitionSpec table in README "One mesh")
# ---------------------------------------------------------------------------

# DeviceTree: leaf plane + the level stack's wide rows shard over the
# leaf axis (pow2 contiguous ranges keep every child shard-local until
# level width reaches the axis size); scatter payloads replicate.
register_column("tree_leaves", P(BATCH_AXIS), subsystem="device_tree",
                dtype="uint32", pad_bucket=8,
                doc="DeviceTree leaf/level rows (w, 8) u32 words")
register_column("tree_dirty", P(), subsystem="device_tree",
                pad_bucket=8,
                doc="scatter payloads: dirty leaf indices + rows")
# Registry mirror: raw record columns shard over the validator axis;
# scatter payloads replicate.
register_column("registry_cols", P(BATCH_AXIS),
                subsystem="registry_mirror", pad_bucket=8,
                doc="validator-registry raw record columns (w, ...)")
register_column("registry_dirty", P(), subsystem="registry_mirror",
                pad_bucket=8,
                doc="registry scatter payloads: indices + raw rows")
# Packed-column cache: leaf planes shard over the chunk axis (a 2M-
# validator balances plane splits across chips' HBM).
register_column("packed_leaves", P(BATCH_AXIS),
                subsystem="packed_cache", dtype="uint32", pad_bucket=8,
                doc="packed-column leaf planes (w, 8) u32 words")
# Fork choice: vote/balance columns shard over validators (the delta
# segment-sum runs as per-shard partials + one small all-reduce);
# node-indexed topology columns and scatter payloads replicate.
register_column("fc_votes", P(BATCH_AXIS), subsystem="fork_choice",
                pad_bucket=16,
                doc="per-validator vote indices + balances (nv_pad,)")
register_column("fc_topology", P(), subsystem="fork_choice",
                pad_bucket=16,
                doc="per-node parent/depth/weight columns (n_pad,)")
register_column("fc_dirty", P(), subsystem="fork_choice", pad_bucket=8,
                doc="changed-vote scatter payloads: indices + values")
# Slasher: min/max span planes shard over the validator axis; group
# payloads (bit-packed masks, epochs) replicate.
register_column("slasher_planes", P(BATCH_AXIS), subsystem="slasher",
                dtype="uint16",
                doc="min/max span planes (n_validators, history) u16")
register_column("slasher_groups", P(), subsystem="slasher",
                doc="ingest payloads: packed masks, epochs, group ids")
# BLS shard: marshalled signature-set blocks shard over the set axis;
# Fq12 pairing partials and the mont-mul selection matrices replicate.
register_column("bls_sets", P(BATCH_AXIS), subsystem="bls",
                doc="marshalled signature-set limb blocks (n_sets, ...)")
register_column("bls_lanes", P(None, BATCH_AXIS), subsystem="bls",
                doc="Pallas-layout set planes (rows, chunks · 128 lanes)")
register_column("bls_table", P(), subsystem="bls",
                doc="device pubkey table (64, capacity), replicated")
register_column("fq12_partials", P(), subsystem="bls",
                doc="per-shard Fq12 pairing partials (replicated)")
register_column("selection_matrices", P(), subsystem="bls",
                doc="mont-mul limb selection matrices (constants)")
