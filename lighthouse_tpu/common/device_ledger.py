"""Device ledger — unified HBM/transfer/compile accounting, per subsystem.

Before this module, five device subsystems (BLS shard, DeviceTree /
registry mirror, packed-column cache, fork-choice vote columns, slasher
planes) each owned ad-hoc residency accounting: ``ops/device_tree.
RESIDENCY_STATS`` covered the tree/registry path only, the BLS pipeline
accounted zero transfer bytes, and nothing in the node could answer
"how many HBM bytes does each subsystem hold, what moved over PCIe this
slot, and what did we recompile?".  The ledger is ONE process-wide,
thread-safe accounting layer every device subsystem reports into,
attributed by the fixed :data:`SUBSYSTEMS` enum:

- **transfers** — H2D/D2H bytes + op counts (:meth:`DeviceLedger.
  note_transfer`).  ``ops/device_tree.note_push/note_pull`` route here
  with the *ambient* attribution (:meth:`DeviceLedger.attribute` — a
  thread-local context the materialize/scatter/pull seams set), so the
  legacy ``RESIDENCY_STATS`` surface becomes a ledger-backed view and
  every existing caller keeps working.
- **dispatches** — device dispatch counts + their host-clock wall time
  (``dispatch_wall_ms``: submit to verdict, host work included — not a
  device time), fed from the existing seams: the verification-service resilience
  envelopes (stream bls / kzg / global), ``sig_dispatch``'s direct
  host-backend path, and the sharded BLS entry points.
- **compiles** — per-program compile events from the jax monitoring
  listener PR 13 already taps for the cache counters
  (``/jax/compilation_cache/compile_requests_use_cache``), attributed
  by the ambient subsystem at compile time (``unattributed`` when a
  compile happens outside any seam — warmups, scripts).
- **HBM residency watermarks** — live resident bytes per subsystem with
  a high-water mark, maintained by :class:`ResidencyToken` handles the
  owning objects (DeviceTree, DeviceRegistryMirror, the fork-choice
  vote mirror, the slasher planes) update at materialize/share/drop
  seams; a dropped owner releases via ``weakref.finalize``.
- **per-shard transfers** — since the PR-20 mesh layer, every
  ``parallel/mesh`` placement seam additionally reports the bytes
  DELIVERED to each mesh shard (:meth:`DeviceLedger.
  note_shard_transfer`).  Shard rows answer "what landed on device i",
  so a replicated column counts its full size on EVERY shard (one host
  copy fans out over ICI) while a batch-sharded column counts 1/d per
  shard — the per-subsystem families above stay the host-wire totals.

Surfaces:

- ``/lighthouse/device`` — the HTTP scoreboard (JSON: per-subsystem
  bytes/ops/watermarks/compiles, plus the per-slot delta ring keyed to
  the slot numbers the trace ring uses; ``chain.per_slot_task`` calls
  :meth:`DeviceLedger.mark_slot` next to ``tracing.set_slot``).
- Prometheus families via ``register_collector``:
  ``device_transfer_bytes_total{subsystem,direction}``,
  ``device_transfer_ops_total{subsystem,direction}``,
  ``device_hbm_resident_bytes{subsystem}``,
  ``device_hbm_high_water_bytes{subsystem}``,
  ``device_dispatches_total{subsystem}``,
  ``device_dispatch_wall_seconds_total{subsystem}``,
  ``device_compiles_total{subsystem}``.
- The ``device_ledger`` tracing stage source (``tracing.stage_split(
  "device_ledger")`` — the bench/scripts read surface), and per-slot
  transfer-delta attributes on block-import/verify spans via
  ``Tracer.record_residency``.
- The **warm-slot transfer budget** (:data:`WARM_SLOT_BUDGET`): a
  declarative per-subsystem per-slot byte budget — warm-path H2D is
  bounded by dirty fractions and signature batches, warm-path pulls are
  ≈ 0 outside the fork-choice weight/best-child/best-descendant reads
  and verdict bytes — checked by the sustained drill
  (:func:`evaluate_budget`, exported as an SLO-style attainment row),
  so "the hot path went host-roundtrip-shaped" is a failing check
  instead of a silent 2× regression.

Knobs: ``LIGHTHOUSE_TPU_DEVICE_LEDGER`` (0 freezes all accounting —
an escape hatch, not a supported mode: the residency view and the
budget check read zeros) and ``LIGHTHOUSE_TPU_DEVICE_LEDGER_SLOTS``
(per-slot delta ring length, default 64 like the trace ring).

This module must stay import-cheap (stdlib + common.metrics only): it
is imported by ``ops/device_tree`` and the crypto dispatch paths.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

# The fixed attribution enum.  Every device subsystem reports under one
# of these; the graftlint ``device-accounting`` checker validates seam
# annotations against this tuple.
SUBSYSTEMS: Tuple[str, ...] = (
    "bls",              # BLS verify pipeline (sharded + staged + stream)
    "device_tree",      # DeviceTree leaf/level planes (direct use)
    "registry_mirror",  # validator-registry HBM columns + record tree
    "packed_cache",     # packed-column device caches (balances, …)
    "fork_choice",      # proto-array vote/topology mirrors
    "slasher",          # min/max span planes
    "kzg",              # Deneb blob verification
    "staging",          # ChunkStager / cold-build streaming pushes
    "proof_engine",     # device Merkle-branch extraction / proof serving
    "op_pool",          # block-packing CSR columns + greedy-pack rounds
    "replay",           # epoch-batched replay windows (catch-up sync)
)

# Compile events that fire outside any attribution seam (conftest
# warmups, standalone scripts) land here — visible, never miscounted.
UNATTRIBUTED = "unattributed"

_TRANSFER_KEYS = ("h2d_bytes", "h2d_ops", "d2h_bytes", "d2h_ops")
# Per-slot delta keys: transfers + the materialize event count (the
# "cold slot" marker — a slot that materialized is start-up/re-stage
# traffic the HTTP budget view may exclude; the drill never does).
_SLOT_KEYS = _TRANSFER_KEYS + ("materializes",)
_COUNTER_KEYS = _TRANSFER_KEYS + (
    "dispatches", "dispatch_wall_ms", "compiles", "compile_hits",
    "scatters", "rebuilds", "materializes")

# ---------------------------------------------------------------------------
# Warm-slot transfer budget — bytes per subsystem per slot on the WARM
# path.  Semantics (README "Device ledger"): once a subsystem is
# materialized, its per-slot H2D is bounded by dirty fractions and the
# slot's signature/blob batches, and its D2H is bounded by verdict/root
# reads plus the fork-choice weight/bc/bd pulls — a full-column
# round-trip inside a warm slot means residency broke.  The sustained
# drill enforces this (exit 1 on violation); the numbers are deliberate
# ceilings, not targets.
# ---------------------------------------------------------------------------

MiB = 1 << 20

WARM_SLOT_BUDGET: Dict[str, Dict[str, int]] = {
    # Signature batches ARE warm traffic: ~50 KB marshalled per 16-key
    # set, a mainnet slot carries ≲ 2k sets.  Verdicts come back as
    # flags.
    "bls": {"h2d_bytes": 256 * MiB, "d2h_bytes": 1 * MiB},
    # Dirty leaf rows + indices only; a root is a 32-byte pull.
    "device_tree": {"h2d_bytes": 4 * MiB, "d2h_bytes": 1 * MiB},
    # Dirty raw records (121 B each, bucket-padded); 32 B down.
    "registry_mirror": {"h2d_bytes": 8 * MiB, "d2h_bytes": 1 * MiB},
    # Dirty chunk rows of the packed columns; adopted device results
    # push nothing.
    "packed_cache": {"h2d_bytes": 8 * MiB, "d2h_bytes": 1 * MiB},
    # Changed-vote scatters + occasional topology push up; the per-round
    # weight/best-child/best-descendant columns down are the ONE
    # legitimate warm-path pull (≤ ~16 B/node · 100k nodes).
    "fork_choice": {"h2d_bytes": 16 * MiB, "d2h_bytes": 32 * MiB},
    # Bit-packed membership masks (n/8 per group) + per-offence
    # evidence gathers down.
    "slasher": {"h2d_bytes": 64 * MiB, "d2h_bytes": 16 * MiB},
    # Blob polynomials up (128 KB/blob mainnet), verdict down.
    "kzg": {"h2d_bytes": 64 * MiB, "d2h_bytes": 1 * MiB},
    # Cold-build streaming belongs OUTSIDE warm slots: a ChunkStager
    # push mid-slot means a full re-stage leaked onto the hot path.
    "staging": {"h2d_bytes": 0, "d2h_bytes": 0},
    # Proof serving: branches are GATHERED from resident levels, never
    # re-hashed — H2D is one small field-root plane per new head state,
    # D2H is sibling rows (32 B each, bucket-padded).  A budget breach
    # means serving went re-stage-shaped instead of gather-shaped.
    "proof_engine": {"h2d_bytes": 2 * MiB, "d2h_bytes": 2 * MiB},
    # Block packing: the candidate CSR columns (element ids, weights,
    # segment ids, precomputed word/bit planes — ≈ 26 B/entry, a
    # backlogged mainnet pool is a few M entries) go up once per
    # produce; the selection vector coming down is rounds × 4 B.
    "op_pool": {"h2d_bytes": 256 * MiB, "d2h_bytes": 1 * MiB},
    # Catch-up replay belongs OUTSIDE warm slots: a node that is in
    # sync imports via the live pipeline (whose signature traffic is
    # the bls family).  Replay-attributed transfers inside a warm slot
    # mean a backfill/range-sync window leaked onto the hot path.
    "replay": {"h2d_bytes": 0, "d2h_bytes": 0},
}

# Per-WINDOW transfer budget for one epoch-batched replay window
# (state_transition/batch_replay.py): the window's signature sets
# marshalled up in one sharded dispatch (~50 KB per 16-key set; a
# 128-block window of full mainnet blocks is ≲ 2k sets), verdict flags
# down.  Evaluated per window by the replayer itself — replay runs at
# catch-up time, not per slot, so the warm-slot ring is the wrong
# denominator.
REPLAY_WINDOW_BUDGET: Dict[str, int] = {
    "h2d_bytes": 256 * MiB, "d2h_bytes": 1 * MiB,
}


class ResidencyToken:
    """Live-resident-bytes handle for one device-owning object.

    ``set(nbytes)`` moves this owner's contribution to ``nbytes``
    (delta-applied to the subsystem's live residency + high-water mark);
    ``release()`` drops it.  Owners register a ``weakref.finalize`` so
    garbage collection releases automatically — the drop seam of every
    subsystem that has no explicit close.
    """

    __slots__ = ("_ledger", "subsystem", "_bytes", "_released",
                 "__weakref__")

    def __init__(self, ledger: "DeviceLedger", subsystem: str):
        self._ledger = ledger
        self.subsystem = subsystem
        self._bytes = 0
        self._released = False
        ledger._tokens.add(self)

    def set(self, nbytes: int) -> None:
        if self._released:
            return
        nbytes = max(int(nbytes), 0)
        delta = nbytes - self._bytes
        self._bytes = nbytes
        if delta:
            self._ledger._adjust_resident(self.subsystem, delta)

    @property
    def nbytes(self) -> int:
        return self._bytes

    def release(self) -> None:
        """Idempotent drop (explicit close paths AND the GC finalizer)."""
        if self._released:
            return
        self._released = True
        if self._bytes:
            self._ledger._adjust_resident(self.subsystem, -self._bytes)
            self._bytes = 0


class DeviceLedger:
    """The process-wide accounting layer (singleton :data:`LEDGER`)."""

    def __init__(self):
        from .knobs import knob_bool, knob_int
        self.enabled = knob_bool("LIGHTHOUSE_TPU_DEVICE_LEDGER")
        self.max_slots = knob_int("LIGHTHOUSE_TPU_DEVICE_LEDGER_SLOTS")
        # Reentrant: ResidencyToken.release runs as a weakref.finalize
        # GC callback, and a collection can trigger inside any locked
        # section of the SAME thread (an allocation under the lock) —
        # release -> _adjust_resident must then re-enter, not deadlock.
        self._lock = threading.RLock()
        self._tls = threading.local()
        self._sub: Dict[str, Dict[str, float]] = {
            s: dict.fromkeys(_COUNTER_KEYS, 0) for s in SUBSYSTEMS
        }  # guarded-by: _lock
        self._sub[UNATTRIBUTED] = dict.fromkeys(_COUNTER_KEYS, 0)
        self._resident: Dict[str, int] = dict.fromkeys(SUBSYSTEMS, 0)
        self._high: Dict[str, int] = dict.fromkeys(SUBSYSTEMS, 0)
        # Per-shard delivered bytes: subsystem -> shard index ->
        # {h2d_bytes, d2h_bytes}.  Fed only by the parallel/mesh seams;
        # empty until the first mesh placement.  guarded-by: _lock
        self._shards: Dict[str, Dict[int, Dict[str, int]]] = {}
        # Per-slot delta ring: slot → {subsystem: {transfer-key deltas}}.
        self._slot_ring: "OrderedDict[int, dict]" = \
            OrderedDict()  # guarded-by: _lock
        self._last_slot: Optional[int] = None
        self._slot_base: Dict[str, Dict[str, float]] = {}
        self._listener_installed = False
        self._collector_registered = False
        # Live residency tokens (weak): reset() re-seeds resident bytes
        # from these so live device objects never under-report after a
        # bench/test reset.
        self._tokens: "weakref.WeakSet[ResidencyToken]" = weakref.WeakSet()

    # -- attribution context -------------------------------------------------

    @contextmanager
    def attribute(self, subsystem: str):
        """Thread-local attribution scope: ``note_push``/``note_pull``
        and compile events inside the ``with`` body charge
        ``subsystem``.  Nests (innermost wins); crosses no threads —
        background stagers take an explicit ``subsystem=`` instead."""
        assert subsystem in SUBSYSTEMS, subsystem
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(subsystem)
        try:
            yield
        finally:
            stack.pop()

    def ambient(self) -> Optional[str]:
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else None

    def _resolve(self, subsystem: Optional[str], default: str) -> str:
        if subsystem is not None:
            assert subsystem in SUBSYSTEMS, subsystem
            return subsystem
        return self.ambient() or default

    # -- recording -----------------------------------------------------------

    def note_transfer(self, direction: str, nbytes: int,
                      subsystem: Optional[str] = None,
                      ops: int = 1) -> None:
        """One H2D (``direction="h2d"``) or D2H (``"d2h"``) transfer of
        ``nbytes`` on behalf of ``subsystem`` (default: the ambient
        attribution, else ``device_tree`` — the pre-ledger owner of the
        residency stats)."""
        if not self.enabled:
            return
        sub = self._resolve(subsystem, "device_tree")
        with self._lock:
            row = self._sub[sub]
            row[f"{direction}_bytes"] += int(nbytes)
            row[f"{direction}_ops"] += int(ops)
        self._maybe_install_listener()

    def note_shard_transfer(self, direction: str,
                            per_shard: Dict[int, int],
                            subsystem: Optional[str] = None) -> None:
        """Per-shard DELIVERED bytes for one mesh placement/pull
        (``parallel/mesh`` seams only).  ``per_shard`` maps mesh shard
        index → bytes landing on (``"h2d"``) or read from (``"d2h"``)
        that shard.  A batch-sharded column delivers 1/d per shard, a
        replicated one its full size on every shard — so shard sums may
        legitimately exceed the host-wire totals in
        :meth:`note_transfer` (one host copy fans out over ICI)."""
        if not self.enabled or not per_shard:
            return
        sub = self._resolve(subsystem, "device_tree")
        key = f"{direction}_bytes"
        with self._lock:
            rows = self._shards.setdefault(sub, {})
            for shard, nbytes in per_shard.items():
                row = rows.setdefault(
                    int(shard), {"h2d_bytes": 0, "d2h_bytes": 0})
                row[key] += int(nbytes)

    def shard_totals(self) -> Dict[str, Dict[int, Dict[str, int]]]:
        """Per-subsystem per-shard delivered-byte totals (deep copy) —
        the mesh-slot bench / validate_mesh read surface."""
        with self._lock:
            return {s: {i: dict(row) for i, row in rows.items()}
                    for s, rows in self._shards.items()}

    def note_dispatch(self, subsystem: str, wall_ms: float,
                      count: int = 1) -> None:
        """One device dispatch (count) + its wall time on the host clock.

        No-op inside a :meth:`suppress_dispatch` scope: the resilience
        envelope wraps device paths that ALSO self-account (the kzg
        pairing seam, the direct XLA verify) and records the dispatch
        itself on success — without suppression every enveloped call
        would count twice."""
        if not self.enabled or getattr(self._tls, "suppress", 0):
            return
        sub = self._resolve(subsystem, "bls")
        with self._lock:
            row = self._sub[sub]
            row["dispatches"] += int(count)
            row["dispatch_wall_ms"] += float(wall_ms)

    @contextmanager
    def suppress_dispatch(self):
        """Scope in which inner ``note_dispatch`` calls are no-ops —
        the OUTER accounting seam (the envelope) owns the dispatch.
        Thread-local; callers that hand the wrapped fn to another
        thread (the deadline watchdog pool) must wrap the FN, not the
        call site, so the flag travels with execution."""
        self._tls.suppress = getattr(self._tls, "suppress", 0) + 1
        try:
            yield
        finally:
            self._tls.suppress -= 1

    def note_host_route(self) -> None:
        """A device verify path just served its call on the host (the
        backend's small-batch fast path): the enclosing seam (the
        envelope) must count no device dispatch for it.  Thread-local
        like :meth:`suppress_dispatch`; read with :meth:`host_routes`
        before and after the call, on the thread that ran it."""
        self._tls.host_routes = getattr(self._tls, "host_routes", 0) + 1

    def host_routes(self) -> int:
        """Host-routed verifies noted on this thread so far."""
        return getattr(self._tls, "host_routes", 0)

    def note_compile(self, subsystem: Optional[str] = None,
                     count: int = 1, key: str = "compiles") -> None:
        """One per-program compile-request event (the jax monitoring
        listener calls this with the ambient attribution).  Both
        ``compiles`` (requests) and ``compile_hits`` (served from the
        persistent cache) are MONOTONIC — net recompiles are derived at
        read time, never decremented, so the Prometheus counters stay
        counters."""
        if not self.enabled:
            return
        assert key in ("compiles", "compile_hits"), key
        sub = subsystem if subsystem in SUBSYSTEMS \
            else (self.ambient() or UNATTRIBUTED)
        with self._lock:
            self._sub[sub][key] += int(count)

    def note_event(self, name: str,
                   subsystem: Optional[str] = None) -> None:
        """Residency protocol events (``scatters`` / ``rebuilds`` /
        ``materializes``) — the legacy RESIDENCY_STATS op counts, now
        attributed."""
        if not self.enabled:
            return
        assert name in ("scatters", "rebuilds", "materializes"), name
        sub = self._resolve(subsystem, "device_tree")
        with self._lock:
            self._sub[sub][name] += 1

    # -- residency watermarks ------------------------------------------------

    def residency(self, subsystem: str) -> ResidencyToken:
        assert subsystem in SUBSYSTEMS, subsystem
        return ResidencyToken(self, subsystem)

    def track(self, owner, subsystem: str, nbytes: int) -> ResidencyToken:
        """Token + GC drop seam in one call: ``owner`` going away
        releases the bytes (``weakref.finalize`` — no explicit close
        needed at knob-off de-materialization / mirror replacement)."""
        tok = self.residency(subsystem)
        tok.set(nbytes)
        weakref.finalize(owner, ResidencyToken.release, tok)
        return tok

    def _adjust_resident(self, subsystem: str, delta: int) -> None:
        if not self.enabled:
            return
        with self._lock:
            cur = self._resident[subsystem] + int(delta)
            self._resident[subsystem] = max(cur, 0)
            if cur > self._high[subsystem]:
                self._high[subsystem] = cur

    # -- jax compile listener ------------------------------------------------

    _COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
    _CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def _maybe_install_listener(self) -> None:
        """Lazy one-shot: transfers imply jax is live, so install the
        monitoring listener at the first note (idempotent; a jax build
        without the API degrades to compiles staying 0).  NOT at import
        — this module is imported by processes that never touch jax."""
        if self._listener_installed:
            return
        import sys
        if "jax" not in sys.modules:
            return
        # Check-and-set under the lock: two threads noting concurrently
        # (a stager thread + the main thread) must not BOTH register —
        # a duplicate listener would double every compile count forever.
        with self._lock:
            if self._listener_installed:
                return
            self._listener_installed = True  # one attempt ever
        try:
            try:
                from jax import monitoring as _mon
            except Exception:
                from jax._src import monitoring as _mon  # older builds
            _mon.register_event_listener(self._on_jax_event)
        except Exception:
            pass

    def _on_jax_event(self, event: str, **_kw) -> None:
        # The request event fires for every cache-eligible compile, the
        # hit event for the ones served from the persistent cache; both
        # fire on the same thread inside one compile call, so the
        # ambient attribution matches.  Net recompiles (requests −
        # hits) are DERIVED at read time — decrementing a counter here
        # would break Prometheus monotonicity (a scrape between the two
        # events would read as a process restart).
        if event == self._COMPILE_EVENT:
            self.note_compile()
        elif event == self._CACHE_HIT_EVENT:
            self.note_compile(key="compile_hits")

    # -- per-slot delta ring -------------------------------------------------

    def mark_slot(self, slot: int) -> None:
        """Slot boundary: fold the transfer deltas since the previous
        mark into the ring under the PREVIOUS slot (the interval they
        belong to).  Idempotent per slot value — multiple nodes in one
        process ticking the same wall-clock slot mark once."""
        if not self.enabled:
            return
        slot = int(slot)
        with self._lock:
            if slot == self._last_slot:
                return
            if self._last_slot is not None:
                delta = self._delta_locked()
                if any(any(row.values()) for row in delta.values()):
                    self._slot_ring[self._last_slot] = delta
                    while len(self._slot_ring) > self.max_slots:
                        self._slot_ring.popitem(last=False)
                else:
                    # A quiet interval must also RETIRE a stale entry
                    # under the same key: drills restart slot numbering
                    # within one process, and a previous run's traffic
                    # surviving under this run's slot number would be
                    # evaluated against this run's budget.
                    self._slot_ring.pop(self._last_slot, None)
            self._slot_base = {
                s: {k: self._sub[s][k] for k in _SLOT_KEYS}
                for s in SUBSYSTEMS}
            self._last_slot = slot

    def _delta_locked(self) -> dict:  # lock-held: _lock
        out = {}
        for s in SUBSYSTEMS:
            base = self._slot_base.get(s, {})
            row = {k: int(self._sub[s][k] - base.get(k, 0))
                   for k in _SLOT_KEYS}
            out[s] = row
        return out

    def slot_deltas(self) -> List[dict]:
        """``[{"slot": s, "cold": bool, "subsystems": {name:
        {h2d/d2h bytes+ops, materializes}}}]`` for every closed slot
        still in the ring, oldest first — the /lighthouse/device
        per-slot view and the budget check's input.  ``cold`` marks a
        slot in which a materialization ran (start-up / re-stage
        traffic).  Only subsystems with nonzero activity appear."""
        with self._lock:
            return [{"slot": s,
                     "cold": any(row.get("materializes")
                                 for row in d.values()),
                     "subsystems": {n: dict(row)
                                    for n, row in d.items()
                                    if any(row.values())}}
                    for s, d in self._slot_ring.items()]

    def current_slot_delta(self) -> dict:
        """Transfer deltas of the OPEN slot (since the last mark)."""
        with self._lock:
            return self._delta_locked()

    def clear_slot_ring(self) -> None:
        """Drop every per-slot delta and the open-slot baseline —
        drivers that restart slot numbering (the sustained drill) call
        this at run start so another run's entries under the same slot
        numbers can never leak into their budget window.  Counters and
        watermarks are untouched."""
        with self._lock:
            self._slot_ring.clear()
            self._slot_base = {}
            self._last_slot = None

    # -- reads ---------------------------------------------------------------

    def snapshot(self) -> dict:
        """Consistent full-ledger copy: per-subsystem counters +
        residency watermarks (the /lighthouse/device body's core and
        the scripts' read surface)."""
        with self._lock:
            subs = {}
            for s in SUBSYSTEMS:
                row = {k: (round(v, 3) if k == "dispatch_wall_ms" else int(v))
                       for k, v in self._sub[s].items()}
                row["resident_bytes"] = self._resident[s]
                row["hbm_high_water_bytes"] = self._high[s]
                # derived: what actually RECOMPILED (requests − cache
                # hits) — the raw pair stays monotonic for Prometheus
                row["compiles_net"] = max(
                    row["compiles"] - row["compile_hits"], 0)
                subs[s] = row
            un = self._sub[UNATTRIBUTED]
            return {
                "enabled": self.enabled,
                "subsystems": subs,
                # String shard keys: this dict is the JSON body of
                # /lighthouse/device and int keys would not round-trip.
                "shards": {s: {str(i): dict(row)
                               for i, row in sorted(rows.items())}
                           for s, rows in self._shards.items()},
                "unattributed_compiles": max(
                    int(un["compiles"] - un["compile_hits"]), 0),
            }

    def subsystem_totals(self, subsystems: Tuple[str, ...]
                         ) -> Dict[str, float]:
        """Counter sums over a subsystem subset (the RESIDENCY_STATS
        view sums only its historical feeders)."""
        with self._lock:
            out = dict.fromkeys(_COUNTER_KEYS, 0.0)
            for s in subsystems:
                for k in _COUNTER_KEYS:
                    out[k] += self._sub[s][k]
            return out

    def transfer_totals(self) -> Dict[str, Tuple[int, int]]:
        """Cheap per-subsystem ``(h2d_bytes, d2h_bytes)`` read — the
        hot-path span-attribution snapshot (no rounding, no nested
        dict copies; the full :meth:`snapshot` is the HTTP/report
        surface)."""
        with self._lock:
            return {s: (int(self._sub[s]["h2d_bytes"]),
                        int(self._sub[s]["d2h_bytes"]))
                    for s in SUBSYSTEMS}

    def stage_dict(self) -> dict:
        """Flat per-subsystem totals for the ``device_ledger`` tracing
        stage source (``<subsystem>_<counter>`` keys; no ``*_ms`` keys —
        these are counters, not a phase decomposition, so the adapter
        attaches them as attributes rather than laying out spans)."""
        with self._lock:
            out = {}
            for s in SUBSYSTEMS:
                row = self._sub[s]
                for k in _TRANSFER_KEYS + ("dispatches", "compiles"):
                    v = int(row[k])
                    if v:
                        out[f"{s}_{k}"] = v
                if row["dispatch_wall_ms"]:
                    # key must NOT end in "_ms": record_stages lays
                    # *_ms keys out as phase spans, and this is a
                    # process-lifetime counter, not a decomposition
                    out[f"{s}_dispatch_wall_ms_total"] = \
                        round(row["dispatch_wall_ms"], 3)
                if self._resident[s]:
                    out[f"{s}_resident_bytes"] = self._resident[s]
            return out

    def reset(self) -> None:
        """Zero every counter and the slot ring (bench rows and tests;
        a live node never resets — Prometheus counters must stay
        monotonic).  Residency is RE-SEEDED from the live tokens, not
        zeroed: device objects created before the reset still hold
        their HBM, and zeroing under them would make every later
        token delta land on a stale base (permanent under-report)."""
        with self._lock:
            for row in self._sub.values():
                for k in row:
                    row[k] = 0
            for s in SUBSYSTEMS:
                self._resident[s] = 0
                self._high[s] = 0
            self._shards.clear()
            self._slot_ring.clear()
            self._slot_base = {}
            self._last_slot = None
        for tok in list(self._tokens):
            if not tok._released and tok._bytes:
                self._adjust_resident(tok.subsystem, tok._bytes)

    # -- Prometheus ----------------------------------------------------------

    def register_metrics(self) -> None:
        """Register the scrape-time collector exporting the labeled
        families (idempotent; called at chain construction so a bare
        library import never touches the registry)."""
        if self._collector_registered:
            return
        self._collector_registered = True
        from .metrics import REGISTRY
        REGISTRY.register_collector(self._collect)

    @staticmethod
    def _set_child(family, labels: tuple, value: float) -> None:
        child = family.labels(*labels)
        with child._lock:
            child.value = float(value)

    def _collect(self) -> None:
        from .metrics import REGISTRY
        snap = self.snapshot()
        f_bytes = REGISTRY.counter(
            "device_transfer_bytes_total",
            "host<->device transfer bytes by subsystem",
            labelnames=("subsystem", "direction"))
        f_ops = REGISTRY.counter(
            "device_transfer_ops_total",
            "host<->device transfer operations by subsystem",
            labelnames=("subsystem", "direction"))
        f_res = REGISTRY.gauge(
            "device_hbm_resident_bytes",
            "live HBM-resident bytes by subsystem",
            labelnames=("subsystem",))
        f_high = REGISTRY.gauge(
            "device_hbm_high_water_bytes",
            "high-water HBM residency by subsystem",
            labelnames=("subsystem",))
        f_disp = REGISTRY.counter(
            "device_dispatches_total",
            "device dispatches by subsystem",
            labelnames=("subsystem",))
        f_verify = REGISTRY.counter(
            "device_dispatch_wall_seconds_total",
            "host-clock wall time of device dispatches by subsystem",
            labelnames=("subsystem",))
        f_comp = REGISTRY.counter(
            "device_compiles_total",
            "per-program compile-request events by subsystem",
            labelnames=("subsystem",))
        f_hits = REGISTRY.counter(
            "device_compile_cache_hits_total",
            "compile requests served from the persistent cache",
            labelnames=("subsystem",))
        with self._lock:
            un_requests = int(self._sub[UNATTRIBUTED]["compiles"])
            un_hits = int(self._sub[UNATTRIBUTED]["compile_hits"])
        for s, row in snap["subsystems"].items():
            self._set_child(f_bytes, (s, "h2d"), row["h2d_bytes"])
            self._set_child(f_bytes, (s, "d2h"), row["d2h_bytes"])
            self._set_child(f_ops, (s, "h2d"), row["h2d_ops"])
            self._set_child(f_ops, (s, "d2h"), row["d2h_ops"])
            self._set_child(f_res, (s,), row["resident_bytes"])
            self._set_child(f_high, (s,), row["hbm_high_water_bytes"])
            self._set_child(f_disp, (s,), row["dispatches"])
            self._set_child(f_verify, (s,), row["dispatch_wall_ms"] / 1e3)
            # BOTH monotonic — net recompiles = requests − hits is a
            # query-time derivation, never a decremented counter.
            self._set_child(f_comp, (s,), row["compiles"])
            self._set_child(f_hits, (s,), row["compile_hits"])
        self._set_child(f_comp, (UNATTRIBUTED,), un_requests)
        self._set_child(f_hits, (UNATTRIBUTED,), un_hits)


# ---------------------------------------------------------------------------
# Warm-slot budget evaluation (the sustained drill's check)
# ---------------------------------------------------------------------------

def evaluate_budget(slot_deltas: List[dict],
                    budget: Optional[Dict[str, Dict[str, int]]] = None,
                    include_cold: bool = True) -> dict:
    """Check per-slot transfer deltas against the warm-slot budget.

    ``slot_deltas`` is :meth:`DeviceLedger.slot_deltas` output (possibly
    filtered to the measured slots).  Returns the SLO-style row: one
    entry per (subsystem, direction) with a declared budget —
    worst-slot bytes, violating slots, ok — plus ``attainment`` (the
    fraction of slot×budget cells inside budget) and the overall
    verdict ``ok``.  An empty window attains 1.0 vacuously (a fresh
    node is not in violation).

    ``include_cold=False`` skips slots in which a materialization ran
    (reported in ``cold_slots_skipped``, never silently) — the HTTP
    scoreboard's view, where a fresh node's start-up staging must not
    read as a warm-path violation.  The sustained drill keeps the
    default: its measured slots follow the warm-up, so a mid-run
    re-materialize is exactly the regression it must catch."""
    budget = WARM_SLOT_BUDGET if budget is None else budget
    cold_skipped = []
    if not include_cold:
        cold_skipped = [d["slot"] for d in slot_deltas if d.get("cold")]
        slot_deltas = [d for d in slot_deltas if not d.get("cold")]
    rows = []
    cells = 0
    ok_cells = 0
    for sub in sorted(budget):
        for direction in ("h2d", "d2h"):
            limit = budget[sub].get(f"{direction}_bytes")
            if limit is None:
                continue
            worst = 0
            worst_slot = None
            violations = []
            for entry in slot_deltas:
                used = entry["subsystems"].get(sub, {}).get(
                    f"{direction}_bytes", 0)
                cells += 1
                if used <= limit:
                    ok_cells += 1
                else:
                    violations.append(entry["slot"])
                if used > worst:
                    worst = used
                    worst_slot = entry["slot"]
            rows.append({
                "subsystem": sub, "direction": direction,
                "budget_bytes": limit, "worst_slot_bytes": worst,
                "worst_slot": worst_slot,
                "violations": violations,
                "ok": not violations,
            })
    return {
        "slots_checked": len(slot_deltas),
        "cold_slots_skipped": cold_skipped,
        "attainment": round(ok_cells / cells, 6) if cells else 1.0,
        "ok": all(r["ok"] for r in rows),
        "rows": rows,
    }


# The process ledger + module-level conveniences (the seam-call idiom
# mirrors tracing's TRACER).
LEDGER = DeviceLedger()

attribute = LEDGER.attribute
note_transfer = LEDGER.note_transfer
note_shard_transfer = LEDGER.note_shard_transfer
note_dispatch = LEDGER.note_dispatch
note_compile = LEDGER.note_compile
note_event = LEDGER.note_event
mark_slot = LEDGER.mark_slot
