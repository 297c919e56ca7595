"""Slot-scope tracing — unified spans from gossip arrival to head.

The hot path's timings used to live in eight disconnected module-global
dicts (``LAST_BLOCK_TIMINGS``, ``LAST_EPOCH_TIMINGS``, ``LAST_COLD_
TIMINGS``, ``LAST_FAST_AGG_TIMINGS``, ``LAST_KZG_TIMINGS``,
``LAST_PUSH_STATS``, the fast-agg ``STAGE_TIMINGS`` profile and
``RESIDENCY_STATS``) that only ``bench.py`` knew how to read, and no
artifact showed one slot end-to-end.  This module is the one
instrument:

- :class:`Tracer` — a low-overhead, thread-safe span system.  Spans
  nest via a thread-local stack; :meth:`Tracer.ctx` captures a
  :class:`SpanContext` token that another thread adopts with
  ``span(..., parent=ctx)`` (the BeaconProcessor worker /
  verification-service pump-thread hops).  A **disabled** tracer is a
  no-op fast path: ``span()`` returns a shared singleton after one
  attribute check and one profiler-session check, and every call site
  that would compute arguments first guards on ``TRACER.enabled``.
- **The device trace's clock** — while a JAX profiler session is
  active (any ``jax.profiler`` session: ``jax.profiler.start_trace`` /
  ``trace``, or a TensorBoard / XProf capture against
  ``jax.profiler.start_server``), every span also opens a
  ``jax.profiler.TraceAnnotation`` named ``lh.<span name>`` (its
  creation-time attributes become the event's stats), whether or not
  the slot ring below is on.  The profiler writes host and device events
  on one clock, so the node's spans lie against the chip's busy time in
  the ``.xplane.pb`` with no offset arithmetic; ``python -m
  lighthouse_tpu.common.profile_spans <log dir>`` charges the device's
  idle time to them.
  Names are dotted by layer: ``verify_dispatch``, ``verify_split``,
  ``bls.host_verify``, ``bls.verdict_sync``, ``<executor>.prep`` /
  ``.stage`` / ``.dispatch`` (the staged executors), ``state_root`` and
  ``state_root.<registry|packed|vectors|small|fold>``, ``merkle.prep`` /
  ``merkle.scatter``.  JAX is never imported here: the annotation class
  is picked up once the process has imported ``jax.profiler``.
- **Slot traces** — every completed span lands in the per-slot trace of
  its resolved slot (explicit argument > parent's slot > the ambient
  slot the chain sets from ``per_slot_task``).  A ring buffer keeps the
  last N fully-assembled slots (``LIGHTHOUSE_TPU_TRACE_RING``,
  default 64).
- **Chrome trace-event export** — :meth:`Tracer.chrome_trace` emits the
  ``{"traceEvents": [...]}`` JSON that opens directly in Perfetto /
  ``chrome://tracing`` (``ph:"X"`` duration events on real thread
  tracks, ``ph:"i"`` instants for gossip-arrival stamps and breaker
  transitions).
- **The stage adapter** — :func:`stage_split` snapshots any of the
  legacy stage dicts by name (ONE read surface: bench.py's
  ``block_phase_split`` / ``epoch`` / ``bls_stage_split`` rows read
  through it), and :func:`record_stages` converts the same dict into
  child spans of the current span, laid out back-to-back ending at the
  call instant — so the per-phase decomposition appears inside the slot
  trace instead of a parallel reporting channel.

Knobs:

====================================  ======================================
``LIGHTHOUSE_TPU_TRACE``              ``1`` enables the slot ring at import
``LIGHTHOUSE_TPU_TRACE_RING``         slot traces kept (default 64)
====================================  ======================================

Surfaced by ``/lighthouse/tracing/slots`` +
``/lighthouse/tracing/slot/{slot}[?format=chrome_trace]`` (HTTP API) and
``scripts/trace_slot.py`` (the CI-able completeness check).
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional

from .metrics import REGISTRY

# The per-slot pipeline stages a fully-assembled trace must cover —
# span categories, used by the completeness check (`scripts/
# trace_slot.py` exits 1 when one is missing).
PIPELINE_STAGES = (
    "gossip_arrival",          # network/: arrival stamps
    "verification_service",    # dispatch/envelope/breaker
    "block_import",            # gossip verify → import pipeline
    "state_transition",        # per-slot/per-block/per-epoch phases
    "fork_choice",             # on_block + deltas/apply/find_head
    "head",                    # head recompute / swap
)

# Spans kept per slot trace before truncation (a hostile gossip flood
# must not grow a slot's trace unboundedly).
MAX_SPANS_PER_SLOT = 8192


class SpanContext:
    """Cross-thread propagation token: enough to parent a span created
    on another thread under the capturing span (id + slot scope)."""

    __slots__ = ("span_id", "slot")

    def __init__(self, span_id: int, slot: int):
        self.span_id = span_id
        self.slot = slot


class _NoopSpan:
    """Shared no-op returned by a disabled tracer — zero allocation on
    the hot path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass

    def ctx(self) -> Optional[SpanContext]:
        return None


_NOOP = _NoopSpan()

# Prefix of every span's name in a JAX profiler trace.
PROFILE_PREFIX = "lh."

_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is imported


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session is
    active, else None.  Never imports JAX: a process that has not
    imported ``jax.profiler`` has no session to annotate."""
    global _ANNOTATION
    ann = _ANNOTATION
    if ann is None:
        mod = sys.modules.get("jax.profiler")
        if mod is None:
            return None
        ann = _ANNOTATION = mod.TraceAnnotation
    return ann if ann.is_enabled() else None


class _ProfiledSpan(_NoopSpan):
    """A span seen only by the profiler (the slot ring is off): the
    ``lh.`` annotation behind the no-op's call surface."""

    __slots__ = ("_ann",)

    def __init__(self, ann):
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False


class Span:
    """A live span (context manager).  Entering pushes it on the
    thread-local stack; exiting records it into its slot's trace."""

    __slots__ = ("_tracer", "name", "cat", "slot", "attrs", "span_id",
                 "parent_id", "t0", "_entered", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, slot: int,
                 parent_id: int, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.slot = slot
        self.parent_id = parent_id
        self.attrs = attrs
        self.span_id = next(tracer._ids)
        self.t0 = 0.0
        self._entered = False
        self._ann = None

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def ctx(self) -> SpanContext:
        return SpanContext(self.span_id, self.slot)

    def __enter__(self) -> "Span":
        ann = _profiler_annotation()
        if ann is not None:
            self._ann = ann(PROFILE_PREFIX + self.name, **self.attrs)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        self._tracer._stack().append(self)
        self._entered = True
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        stack = self._tracer._stack()
        if self._entered and stack and stack[-1] is self:
            stack.pop()
        elif self._entered and self in stack:  # out-of-order exit
            stack.remove(self)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        self._tracer._record(self.slot, {
            "id": self.span_id, "parent": self.parent_id,
            "name": self.name, "cat": self.cat,
            "ts_us": round(self.t0 * 1e6, 1),
            "dur_us": round(dur * 1e6, 1),
            "tid": threading.get_ident(),
            "thread": threading.current_thread().name,
            "attrs": self.attrs,
        })
        return False


class Tracer:
    """Process tracer.  One instance (:data:`TRACER`) serves the whole
    node; everything here is safe under concurrent span completion from
    gossip handlers, processor workers, pump threads and the HTTP API
    reading traces."""

    def __init__(self, max_slots: Optional[int] = None):
        from .knobs import knob_bool, knob_int
        self.enabled = knob_bool("LIGHTHOUSE_TPU_TRACE")
        ring = knob_int("LIGHTHOUSE_TPU_TRACE_RING")
        self.max_slots = max(1, max_slots if max_slots is not None else ring)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._ids = itertools.count(1)
        self._slots: "OrderedDict[int, dict]" = \
            OrderedDict()  # guarded-by: _lock
        self._ambient_slot = 0
        self.evicted_slots = 0
        self.dropped_stale = 0  # spans for slots older than the ring
        self._m_spans = None  # lazy labeled histogram family

    # -- lifecycle -----------------------------------------------------------

    def enable(self, ring: Optional[int] = None) -> None:
        if ring is not None:
            self.max_slots = max(1, int(ring))
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._slots.clear()
            self.evicted_slots = 0
            self.dropped_stale = 0

    # -- slot scope ----------------------------------------------------------

    def set_slot(self, slot: int) -> None:
        """Ambient slot: spans with no explicit/inherited slot attribute
        land in this slot's trace.  The chain's per-slot task calls this
        at every tick; an int store, cheap enough to run unconditionally."""
        self._ambient_slot = int(slot)

    def current_slot(self) -> int:
        return self._ambient_slot

    # -- span creation -------------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, cat: str = "", slot: Optional[int] = None,
             parent: Optional[SpanContext] = None, **attrs):
        """Open a span.  ``parent`` (a :class:`SpanContext`) adopts a
        span captured on another thread; otherwise the parent is the
        thread's innermost open span.  With the ring off, a span is the
        shared no-op unless a profiler session is active (module
        docstring)."""
        if not self.enabled:
            ann = _profiler_annotation()
            if ann is None:
                return _NOOP
            return _ProfiledSpan(ann(PROFILE_PREFIX + name, **attrs))
        stack = self._stack()
        top = stack[-1] if stack else None
        if parent is not None:
            # Context adoption is CAUSAL parenting, not temporal
            # nesting: the parent may have exited before this span
            # starts (submit → async dispatch).  Mark it so trace
            # consumers don't assume interval containment.
            parent_id = parent.span_id
            inherited = parent.slot
            attrs = {"adopted": True, **attrs}
        elif top is not None:
            parent_id = top.span_id
            inherited = top.slot
        else:
            parent_id = 0
            inherited = self._ambient_slot
        return Span(self, name, cat,
                    inherited if slot is None else int(slot),
                    parent_id, attrs)

    def instant(self, name: str, cat: str = "",
                slot: Optional[int] = None, **attrs) -> None:
        """Zero-duration marker (gossip arrival stamps, breaker
        transitions).  Callers computing arguments should guard on
        ``TRACER.enabled`` first."""
        if not self.enabled:
            return
        stack = self._stack()
        top = stack[-1] if stack else None
        self._record(
            (top.slot if top is not None else self._ambient_slot)
            if slot is None else int(slot),
            {"id": next(self._ids),
             "parent": top.span_id if top is not None else 0,
             "name": name, "cat": cat,
             "ts_us": round(time.perf_counter() * 1e6, 1),
             "dur_us": 0.0, "inst": True,
             "tid": threading.get_ident(),
             "thread": threading.current_thread().name,
             "attrs": attrs})

    def ctx(self) -> SpanContext:
        """Capture the current position for another thread (innermost
        open span, or the bare ambient slot)."""
        stack = self._stack()
        if stack:
            return stack[-1].ctx()
        return SpanContext(0, self._ambient_slot)

    # -- recording -----------------------------------------------------------

    def _record(self, slot: int, rec: dict) -> None:
        with self._lock:
            bucket = self._slots.get(slot)
            if bucket is None:
                if len(self._slots) >= self.max_slots \
                        and slot < min(self._slots):
                    # A straggler span for a slot already behind the
                    # ring (e.g. a late streamed verdict whose context
                    # points >ring slots back): drop it outright — a
                    # fresh bucket would just self-evict and churn.
                    self.dropped_stale += 1
                    return
                bucket = self._slots[slot] = {
                    "slot": slot, "spans": [], "truncated": 0,
                    # Aggregates maintained at record time so the slot
                    # summary never scans/copies span lists under the
                    # tracer lock (the lock every hot-path span exit
                    # takes).  "stats" adds per-category duration
                    # aggregates ([count, sum_us, max_us]) — the SLO
                    # engine's worst-offending-slot attribution reads
                    # these, never the span lists.
                    "t0": rec["ts_us"], "t1": 0.0, "cats": set(),
                    "stats": {}}
                while len(self._slots) > self.max_slots:
                    self._slots.pop(min(self._slots))
                    self.evicted_slots += 1
            # Record-time aggregates NEVER truncate (O(1) per span,
            # bounded per slot): a hostile-flood slot past the span cap
            # is exactly the slot the SLO worst-offender attribution
            # must still rank correctly — only span STORAGE is capped.
            bucket["t0"] = min(bucket["t0"], rec["ts_us"])
            bucket["t1"] = max(bucket["t1"],
                               rec["ts_us"] + rec["dur_us"])
            if rec["cat"]:
                bucket["cats"].add(rec["cat"])
                if not rec.get("inst"):
                    st = bucket["stats"].get(rec["cat"])
                    if st is None:
                        st = bucket["stats"][rec["cat"]] = [0, 0.0, 0.0]
                    st[0] += 1
                    st[1] += rec["dur_us"]
                    st[2] = max(st[2], rec["dur_us"])
            if len(bucket["spans"]) >= MAX_SPANS_PER_SLOT:
                # Only span STORAGE is capped: fall through so the
                # labeled histogram below keeps counting too — the
                # Prometheus family and slot_stats() must agree on a
                # flooded slot.
                bucket["truncated"] += 1
            else:
                bucket["spans"].append(rec)
        cat = rec.get("cat")
        if cat and not rec.get("inst"):
            if self._m_spans is None:
                self._m_spans = REGISTRY.histogram(
                    "tracing_span_seconds", "span duration by category",
                    labelnames=("cat",))
            self._m_spans.labels(cat).observe(rec["dur_us"] / 1e6)

    # -- stage-dict adapter --------------------------------------------------

    def stage_split(self, source: str) -> dict:
        """Snapshot one of the legacy stage dicts by name — the ONE read
        surface bench.py and the trace adapter share (see
        :data:`_STAGE_SOURCES` for the names)."""
        return dict(_STAGE_SOURCES[source]())

    def record_stages(self, source: str, cat: Optional[str] = None) -> None:
        """Convert ``source``'s stage dict into child spans of the
        current span.  The dicts carry durations, not start offsets, so
        children are laid out back-to-back ENDING at the call instant
        (they record sequential phase decompositions, so the layout is
        faithful).  Non-``*_ms`` keys become attributes on the parent."""
        if not self.enabled:
            return
        snap = self.stage_split(source)
        if not snap:
            return
        stack = self._stack()
        top = stack[-1] if stack else None
        parent_id = top.span_id if top is not None else 0
        slot = top.slot if top is not None else self._ambient_slot
        if cat is None:
            cat = top.cat if top is not None and top.cat else "stage"
        tid = threading.get_ident()
        tname = threading.current_thread().name
        # "total_ms" is the sum of the others (the dicts' convention) —
        # emitting it as a sibling would double the laid-out time.
        ms = [(k, float(v)) for k, v in snap.items()
              if k.endswith("_ms") and k != "total_ms"
              and isinstance(v, (int, float))]
        other = {k: v for k, v in snap.items() if not k.endswith("_ms")}
        now = time.perf_counter()
        t = now - sum(v for _, v in ms) / 1e3
        for k, v in ms:
            self._record(slot, {
                "id": next(self._ids), "parent": parent_id,
                "name": f"{source}:{k[:-3]}", "cat": cat,
                "ts_us": round(t * 1e6, 1),
                "dur_us": round(v * 1e3, 1),
                "tid": tid, "thread": tname,
                "attrs": {"source": source}})
            t += v / 1e3
        if other and top is not None:
            top.set(**{f"{source}_{k}": v for k, v in other.items()})

    # -- device residency attribution ---------------------------------------

    def residency_mark(self) -> Optional[dict]:
        """Snapshot ``RESIDENCY_STATS`` plus the device ledger's
        per-subsystem transfer totals for delta attribution (pair with
        :meth:`record_residency`)."""
        if not self.enabled:
            return None
        from ..ops.device_tree import residency_snapshot
        from .device_ledger import LEDGER
        mark = residency_snapshot()
        mark["_ledger"] = LEDGER.transfer_totals()
        return mark

    def record_residency(self, span, mark: Optional[dict]) -> None:
        """Attach the device push/pull byte deltas since ``mark`` to
        ``span`` — both the legacy flat ``residency_*`` totals and the
        ledger's per-subsystem ``dev_<subsystem>_<dir>_bytes`` split
        (the device-stage attribution of a transition)."""
        if mark is None or not self.enabled:
            return
        from ..ops.device_tree import residency_snapshot
        from .device_ledger import LEDGER
        ledger_mark = mark.pop("_ledger", {})
        after = residency_snapshot()
        delta = {f"residency_{k}": after[k] - mark[k]
                 for k in mark if after.get(k, 0) != mark[k]}
        for s, (h2d, d2h) in LEDGER.transfer_totals().items():
            b_h2d, b_d2h = ledger_mark.get(s, (0, 0))
            if h2d != b_h2d:
                delta[f"dev_{s}_h2d_bytes"] = h2d - b_h2d
            if d2h != b_d2h:
                delta[f"dev_{s}_d2h_bytes"] = d2h - b_d2h
        if delta:
            span.set(**delta)

    # -- export --------------------------------------------------------------

    def slots(self) -> List[int]:
        with self._lock:
            return sorted(self._slots)

    def slot_summaries(self) -> List[dict]:
        # Reads only the per-bucket aggregates maintained at record
        # time — O(ring) under the lock, never a span-list scan/copy.
        with self._lock:
            out = [{
                "slot": b["slot"],
                "spans": len(b["spans"]),
                "truncated": b["truncated"],
                "wall_ms": round(max(b["t1"] - b["t0"], 0.0) / 1e3, 3),
                "stages": sorted(b["cats"]),
            } for b in self._slots.values()]
        out.sort(key=lambda r: r["slot"])
        return out

    def slot_stats(self) -> List[dict]:
        """Per-slot per-category duration aggregates maintained at
        record time: ``[{"slot", "stats": {cat: {"count", "total_ms",
        "max_ms"}}}]`` — O(ring × cats) under the lock, never a span
        scan.  The SLO engine's worst-offender attribution."""
        with self._lock:
            out = [{
                "slot": b["slot"],
                "stats": {cat: {"count": st[0],
                                "total_ms": round(st[1] / 1e3, 3),
                                "max_ms": round(st[2] / 1e3, 3)}
                          for cat, st in b["stats"].items()},
            } for b in self._slots.values()]
        out.sort(key=lambda r: r["slot"])
        return out

    def slot_trace(self, slot: int) -> Optional[dict]:
        with self._lock:
            bucket = self._slots.get(int(slot))
            if bucket is None:
                return None
            spans = list(bucket["spans"])
            truncated = bucket["truncated"]
        spans.sort(key=lambda s: s["ts_us"])
        return {"slot": int(slot), "truncated": truncated,
                "missing_stages": self._missing(spans), "spans": spans}

    @staticmethod
    def _missing(spans: List[dict]) -> List[str]:
        present = {s["cat"] for s in spans}
        return [st for st in PIPELINE_STAGES if st not in present]

    def missing_stages(self, slot: int) -> List[str]:
        """Pipeline stages absent from ``slot``'s trace (empty = the
        trace covers gossip → head).  A slot never traced reports every
        stage missing."""
        trace = self.slot_trace(slot)
        if trace is None:
            return list(PIPELINE_STAGES)
        return trace["missing_stages"]

    def chrome_trace(self, slot: int) -> Optional[dict]:
        """Chrome trace-event JSON (Perfetto / chrome://tracing).  One
        pid (the node), real thread tracks, ``X`` duration events and
        ``i`` instants."""
        trace = self.slot_trace(slot)
        if trace is None:
            return None
        events: List[dict] = []
        threads: Dict[int, str] = {}
        for s in trace["spans"]:
            threads.setdefault(s["tid"], s["thread"])
        for tid, tname in sorted(threads.items()):
            events.append({"ph": "M", "pid": 0, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": tname}})
        for s in trace["spans"]:
            args = {"slot": trace["slot"], "span_id": s["id"],
                    "parent_id": s["parent"], **s["attrs"]}
            if s.get("inst"):
                events.append({"ph": "i", "pid": 0, "tid": s["tid"],
                               "name": s["name"], "cat": s["cat"] or "-",
                               "ts": s["ts_us"], "s": "t", "args": args})
            else:
                events.append({"ph": "X", "pid": 0, "tid": s["tid"],
                               "name": s["name"], "cat": s["cat"] or "-",
                               "ts": s["ts_us"], "dur": s["dur_us"],
                               "args": args})
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "metadata": {"slot": trace["slot"],
                             "truncated": trace["truncated"],
                             "tool": "lighthouse-tpu tracing"}}


# ---------------------------------------------------------------------------
# Stage-dict source registry (lazy imports: tracing must stay cheap to
# import and cycle-free — the sources import tracing, not vice versa).
# ---------------------------------------------------------------------------

def _src_block() -> dict:
    from ..state_transition.per_block import LAST_BLOCK_TIMINGS
    return LAST_BLOCK_TIMINGS


def _src_epoch() -> dict:
    from ..state_transition.per_epoch import LAST_EPOCH_TIMINGS
    return LAST_EPOCH_TIMINGS


def _src_cold_merkle() -> dict:
    from ..types.validators import LAST_COLD_TIMINGS
    return LAST_COLD_TIMINGS


def _src_leaf_push() -> dict:
    from ..ops.merkle_kernel import LAST_PUSH_STATS
    return LAST_PUSH_STATS


def _src_fast_agg() -> dict:
    from ..crypto.tpu_backend import LAST_FAST_AGG_TIMINGS
    return LAST_FAST_AGG_TIMINGS


def _src_kzg() -> dict:
    from ..kzg.device import LAST_KZG_TIMINGS
    return LAST_KZG_TIMINGS


def _src_bls_kernels() -> dict:
    from ..crypto.profiling import LAST_STAGE_PROFILE
    return LAST_STAGE_PROFILE


def _src_residency() -> dict:
    from ..ops.device_tree import RESIDENCY_STATS
    return RESIDENCY_STATS


def _src_materialize() -> dict:
    from ..types.device_state import LAST_MATERIALIZE_STATS
    return LAST_MATERIALIZE_STATS


def _src_block_sigs() -> dict:
    from ..state_transition.sig_dispatch import LAST_SIG_DISPATCH
    return LAST_SIG_DISPATCH


def _src_device_ledger() -> dict:
    from .device_ledger import LEDGER
    return LEDGER.stage_dict()


def _src_op_pool() -> dict:
    from ..op_pool.device_pack import LAST_PACK_STATS
    return LAST_PACK_STATS


def _src_replay() -> dict:
    from ..state_transition.batch_replay import LAST_REPLAY_TIMINGS
    return LAST_REPLAY_TIMINGS


_STAGE_SOURCES: Dict[str, Callable[[], dict]] = {
    "block": _src_block,
    "epoch": _src_epoch,
    "cold_merkle": _src_cold_merkle,
    "leaf_push": _src_leaf_push,
    "fast_agg": _src_fast_agg,
    "kzg": _src_kzg,
    "bls_kernels": _src_bls_kernels,
    "residency": _src_residency,
    "materialize": _src_materialize,
    "block_sigs": _src_block_sigs,
    "device_ledger": _src_device_ledger,
    "op_pool": _src_op_pool,
    "replay": _src_replay,
}


def register_stage_source(name: str, getter: Callable[[], dict]) -> None:
    """Extension point (tests, future subsystems): add a named stage
    dict to the adapter."""
    _STAGE_SOURCES[name] = getter


# The process tracer + module-level conveniences.
TRACER = Tracer()

span = TRACER.span
instant = TRACER.instant
set_slot = TRACER.set_slot
record_stages = TRACER.record_stages
stage_split = TRACER.stage_split
