"""Device-resident slasher span planes — SURVEY §7's designated second
TPU workload (VERDICT r4 #9).

The reference updates chunked min/max-target arrays per validator-chunk ×
epoch-chunk in LMDB (``/root/reference/slasher/src/array.rs:106-116``).
The TPU redesign keeps the WHOLE span plane HBM-resident as two
``(n_validators, history)`` uint16 ring buffers and turns an ingest batch
into ONE fused dispatch:

- attestations are grouped host-side by their (source, target) pair — in
  steady state a slot's batch has a handful of distinct pairs (one per
  recent target), each with the union of its attesters;
- each group becomes a full-plane masked min/max sweep: the candidate
  value at (v, e) is an arithmetic ramp ``t − e`` over the epoch axis,
  gated by a per-validator membership mask and a per-column range mask —
  pure VPU work at HBM bandwidth, no scatters (a gather/scatter of
  |live|×|cols| indices would serialise on TPU; the dense sweep is the
  shape XLA tiles well);
- the G groups run under ``lax.scan`` inside one jit — one device
  roundtrip per ingest batch, G statically padded (pow-2 bucket like the
  BLS pipeline's set counts);
- surround DETECTION needs only the two columns at the new attestation's
  source: those are gathered in the same dispatch and returned (a
  (G, n) slice), so the host touches per-offence evidence only.

Memory: n=2^20, H=1024 → 2 GiB/plane in HBM (v5e has 16 GiB); the ring
layout bounds the epoch axis and `history` bounds total footprint — the
host Slasher's numpy planes stay the ground truth (cross-checked in
tests/test_slasher.py).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

_NO_MIN = np.uint16(0xFFFF)
_NO_MAX = np.uint16(0)

# Static pow-2 bucket sizes for group counts, so recompiles are bounded
# (same discipline as the BLS pipeline's set-count buckets).
_MAX_GROUPS = 16

from ..ops.merkle import _next_pow2  # noqa: E402 (shared helper)


@partial(jax.jit, donate_argnums=(0, 1))
def _ingest_kernel(min_plane, max_plane, masks_packed, sources, targets,
                   live, group_idx):
    """One fused ingest: scan G groups of full-plane masked sweeps.

    min_plane/max_plane: (n, H) uint16 ring buffers (column = epoch % H)
    masks_packed: (G, n/8) uint8 — BIT-PACKED group membership (the
        H2D push is bandwidth-bound; the packed form is 8× smaller and
        is unpacked on device)
    sources: (G,) int32, targets: (G,) int32 (absolute epochs; −1 = pad)
    live:    (G,) bool — group is real
    group_idx: (G, W) int32 — each group's member validator indices,
        zero-padded; the surround gathers return ONLY these positions
        (pulling full (n,) columns back dwarfed the sweep at registry
        scale)

    Returns updated planes + (G, W) pre-update min/max gathers at each
    group's source column.
    """
    n, H = min_plane.shape
    cols = jnp.arange(H, dtype=jnp.int32)  # column index = epoch % H

    def body(planes, group):
        mn, mx = planes
        packed, s, t, ok, gidx = group
        # unpack bits (bitorder='little' matches np.packbits host-side)
        mask = ((packed[:, None] >> jnp.arange(8, dtype=jnp.uint8))
                & 1).astype(bool).reshape(-1)[:n]
        # Mirror the host sweeps exactly (slasher/__init__.py):
        #   min: e ∈ [max(s−H+1, 0), s)  → min_span[e%H] = min(., t−e)
        #   max: e ∈ (s, t)              → max_span[e%H] = max(., t−e)
        # Each column c has at most one representative epoch in a
        # length-≤H range [lo, hi): e(c) = lo + ((c − lo) mod H).
        lo1 = jnp.maximum(s - H + 1, 0)
        e1 = lo1 + ((cols - lo1) % H)          # (H,) candidate epochs
        min_cols = e1 < s                      # range [lo1, s)
        v1 = jnp.clip(t - e1, 0, 0xFFFE).astype(jnp.uint16)
        lo2 = s + 1
        e2 = lo2 + ((cols - lo2) % H)
        max_cols = e2 < t                      # range (s, t)
        v2 = jnp.clip(t - e2, 0, 0xFFFE).astype(jnp.uint16)

        m2 = (mask & ok)[:, None]              # (n, 1)
        mn_new = jnp.where(m2 & min_cols[None, :],
                           jnp.minimum(mn, v1[None, :]), mn)
        mx_new = jnp.where(m2 & max_cols[None, :],
                           jnp.maximum(mx, v2[None, :]), mx)
        # pre-update gathers at the source column, at the group's own
        # member indices only
        sc = (s % H).astype(jnp.int32)
        col_min = lax.dynamic_index_in_dim(mn, sc, axis=1,
                                           keepdims=False)
        col_max = lax.dynamic_index_in_dim(mx, sc, axis=1,
                                           keepdims=False)
        return (mn_new, mx_new), (col_min[gidx], col_max[gidx])

    (mn, mx), (g_min, g_max) = lax.scan(
        body, (min_plane, max_plane),
        (masks_packed, sources, targets, live, group_idx))
    return mn, mx, g_min, g_max


class DeviceSpanPlane:
    """HBM-resident min/max span planes with fused batched ingest."""

    def __init__(self, n_validators: int, history: int = 1024):
        from ..common.device_ledger import LEDGER
        from ..parallel.mesh import mesh_place
        self.n = n_validators
        self.history = history
        # Device-side fills placed on the process mesh — the validator
        # axis shards over ``batch`` when it divides, so each chip holds
        # ``2nH/d`` bytes of plane; zero H2D either way.
        self.min_plane = mesh_place(
            "slasher_planes",
            jnp.full((n_validators, history), _NO_MIN, jnp.uint16))
        self.max_plane = mesh_place(
            "slasher_planes",
            jnp.full((n_validators, history), _NO_MAX, jnp.uint16))
        # (the GC finalizer releases the residency with the plane object)
        self._res = LEDGER.track(
            self, "slasher",
            int(self.min_plane.nbytes) + int(self.max_plane.nbytes))

    @staticmethod
    def group(atts: Sequence[Tuple[int, int, np.ndarray]]
              ) -> List[Tuple[int, int, np.ndarray]]:
        """Group (source, target, indices) attestations by (s, t),
        unioning attester indices — the host-side half of the ingest."""
        by_st: Dict[Tuple[int, int], List[np.ndarray]] = {}
        for s, t, idx in atts:
            by_st.setdefault((s, t), []).append(np.asarray(idx))
        return [(s, t, np.unique(np.concatenate(parts)))
                for (s, t), parts in sorted(by_st.items())]

    def ingest(self, groups: Sequence[Tuple[int, int, np.ndarray]]):  # device-io: slasher
        """Apply grouped updates in fused dispatches of ≤ _MAX_GROUPS.

        Returns one dict (s, t) → (min gather, max gather) at the
        source column, ALIGNED WITH the group's (sorted, unique) member
        index array — positional, not validator-indexed.

        Contract: exact equality with the host Slasher's numpy sweeps
        holds for t − s ≤ min(history, 0xFFFE) — beyond that the ring
        cannot represent the max-sweep range uniquely (and the reference
        saturates spans at the u16 bound anyway, `array.rs` MAX_SPAN
        encoding); such groups are rejected here rather than silently
        diverging.
        """
        for s, t, _ in groups:
            if t - s > min(self.history, 0xFFFE):
                raise ValueError(
                    f"span distance {t - s} exceeds the history window "
                    f"{self.history}; clamp upstream")
        from ..common.device_ledger import LEDGER
        from ..parallel.mesh import mesh_gather, mesh_put
        pre: Dict[Tuple[int, int], Tuple[np.ndarray, np.ndarray]] = {}
        for at in range(0, len(groups), _MAX_GROUPS):
            chunk = groups[at:at + _MAX_GROUPS]
            G = _next_pow2(len(chunk))
            W = _next_pow2(max(len(idx) for _s, _t, idx in chunk))
            masks = np.zeros((G, self.n), bool)
            sources = np.full(G, -1, np.int32)
            targets = np.full(G, -1, np.int32)
            live = np.zeros(G, bool)
            gidx = np.zeros((G, W), np.int32)
            for i, (s, t, idx) in enumerate(chunk):
                masks[i, idx] = True
                sources[i] = s
                targets[i] = t
                live[i] = True
                gidx[i, :len(idx)] = idx
            packed = np.packbits(masks, axis=1, bitorder="little")
            t0 = time.perf_counter()
            self.min_plane, self.max_plane, g_min, g_max = _ingest_kernel(  # device-io: slasher
                self.min_plane, self.max_plane,
                mesh_put("slasher_groups", packed, subsystem="slasher"),
                mesh_put("slasher_groups", sources, subsystem="slasher"),
                mesh_put("slasher_groups", targets, subsystem="slasher"),
                mesh_put("slasher_groups", live, subsystem="slasher"),
                mesh_put("slasher_groups", gidx, subsystem="slasher"))
            g_min = mesh_gather(g_min, subsystem="slasher")
            g_max = mesh_gather(g_max, subsystem="slasher")
            LEDGER.note_dispatch("slasher",
                                 (time.perf_counter() - t0) * 1e3)
            for i, (s, t, idx) in enumerate(chunk):
                pre[(s, t)] = (g_min[i, :len(idx)], g_max[i, :len(idx)])
        return pre

    def to_host(self) -> Tuple[np.ndarray, np.ndarray]:
        from ..parallel.mesh import mesh_gather
        mn = mesh_gather(self.min_plane, subsystem="slasher",
                         name="slasher_planes")
        mx = mesh_gather(self.max_plane, subsystem="slasher",
                         name="slasher_planes")
        return mn, mx


def bench_device_span_update(n_validators: int, history: int,
                             atts: Sequence) -> dict:
    """Device column of :func:`..bench_span_update` — same attestation
    batch through the fused plane kernel; reports the ingest time with
    the result synced (one dispatch per ≤16 groups)."""
    triples = [(int(a.data.source.epoch), int(a.data.target.epoch),
                np.asarray([int(i) for i in a.attesting_indices]))
               for a in atts]
    plane = DeviceSpanPlane(n_validators, history)
    groups = plane.group(triples)
    plane.ingest(groups)  # warm the compile
    del plane  # free before the timed plane (2× planes would double peak)
    plane2 = DeviceSpanPlane(n_validators, history)
    t0 = time.perf_counter()
    plane2.ingest(groups)
    jax.block_until_ready((plane2.min_plane, plane2.max_plane))
    ms = (time.perf_counter() - t0) * 1e3
    return {
        "slasher_device_update_1m_ms": round(ms, 1),
        "slasher_device_groups": len(groups),
    }
