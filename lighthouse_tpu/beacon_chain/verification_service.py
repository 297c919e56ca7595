"""Resilient streaming verification — the service between ``network/``
gossip and ``beacon_chain/`` import.

The flagship batch verify is one synchronous device dispatch per block,
but production traffic is a stream: attestations, aggregates and blob
sidecars arrive all slot long.  This module turns the stream into
device-shaped work while keeping a per-message latency SLO, and wraps
every device dispatch in a resilience envelope so a device fault
degrades throughput instead of losing messages:

- :class:`VerificationService` — bounded ingress queues feed
  device-shaped **buckets** (keyed by padded signer count K and, for
  wide shared-key shapes, a key-list fingerprint so the sync-committee
  batches stay pure and the TPU backend's two-Miller-lane fast path
  auto-selects).  A bucket dispatches when it is **full**
  (``max_batch`` — the fat amortized batch under load), when its oldest
  message could no longer meet the SLO after one more wait (the small
  early-slot batch), or when total backlog crosses the drain watermark.
  Dispatch runs through the existing
  :class:`~lighthouse_tpu.parallel.pipeline.StagedExecutor` for its
  pluggable H2D staging seam (the ``h2d`` fault-injection site and the
  sync-staging fallback); verdicts are returned synchronously here, so
  the executor's prep/dispatch overlap is not the draw.
- :class:`ResilienceEnvelope` — deadline timeout (the dispatch runs on
  a watchdog thread; a wedged device call is abandoned, not waited on),
  retry with exponential backoff + deterministic jitter, and a
  :class:`CircuitBreaker` that trips after N consecutive device faults:
  tripped traffic routes to the **host oracle path**
  (``bls.PythonBackend`` / ``kzg`` host pairing) while periodic
  half-open probes test device recovery.  A batch is NEVER dropped on a
  device fault — the claim of this subsystem is *zero valid messages
  lost under injected device failure*, not a throughput number.
- **Overload shedding** — when the attestation backlog exceeds its cap
  the OLDEST individual attestations are shed (their value decays
  fastest and they are re-aggregatable); aggregates, blocks and blob
  batches are never shed.  Never-shed kinds therefore have no hard cap
  — a cap would have to drop them, which the policy forbids; their
  backpressure is the self-pumping submit path (a full bucket
  dispatches inline on the submitting worker, so ingress cannot outrun
  verify throughput for free).

Failure points (dispatch raise, H2D stall, deadline blowout, sustained
outage) are injected through :mod:`lighthouse_tpu.testing.faults`; the
hostile-drill simulator and ``scripts/validate_stream_verify.py`` drive
them deterministically.

Knobs (all per-service constructor args; env defaults listed):

====================================  =======================================
``LIGHTHOUSE_TPU_STREAM_SLO_MS``      per-message latency SLO (default 250)
``LIGHTHOUSE_TPU_STREAM_MAX_BATCH``   bucket dispatch cap (default 256)
``LIGHTHOUSE_TPU_VERIFY_DEADLINE_MS`` dispatch deadline (8000; 0 disables)
``LIGHTHOUSE_TPU_BREAKER_N``          consecutive faults to trip (default 5)
``LIGHTHOUSE_TPU_RESILIENT``          0 disables the global bls envelope
====================================  =======================================

Cold-compile note: the first dispatch of a DISTINCT pairing-shaped
program can trace/compile for minutes.  Under the default deadline the
watchdog abandons it, the breaker trips, and traffic serves from the
host oracle until a recovery probe finds the (by then warm) device —
degraded-but-correct BY DESIGN, but it means a cold node's early slots
are host-verified.  Pre-compile the dispatch shapes with
``python -m lighthouse_tpu.cli warmup`` or
``scripts/validate_stream_verify.py --warmup`` (or raise the deadline)
to start on the device path; bench stage rows carry
``*_breaker_open_during_run`` so a fallback window can't silently skew
device timings.
"""

from __future__ import annotations

import random
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

from ..common.backoff import backoff_delay
from ..common.device_ledger import LEDGER
from ..common.metrics import REGISTRY, Histogram, observe
from ..common.tracing import TRACER
from ..ops.merkle import _next_pow2

# -- message classes ---------------------------------------------------------

KIND_BLOCK = "block"              # never shed, never degraded
KIND_AGGREGATE = "aggregate"      # never shed
KIND_SYNC = "sync_contribution"   # never shed (shared-key shape; a
#   submitter seam — gossip sync messages currently pool unverified in
#   network/service.py, so only direct submitters reach this class)
KIND_ATTESTATION = "attestation"  # sheddable: degrade these FIRST

_NEVER_SHED = (KIND_BLOCK, KIND_AGGREGATE, KIND_SYNC)


class DeadlineExceeded(RuntimeError):
    """A device dispatch exceeded the envelope deadline (the call is
    abandoned on its watchdog thread; its eventual result is dropped)."""


# Knob reads go through the typed registry accessors — malformed
# values raise an actionable KnobError instead of silently running
# with the default.
from ..common.knobs import knob_bool as _knob_bool
from ..common.knobs import knob_float as _knob_float
from ..common.knobs import knob_int as _knob_int


# ---------------------------------------------------------------------------
# Circuit breaker
# ---------------------------------------------------------------------------

# Process-global breaker registry: bench.py's stage-attribution rows ask
# "was any breaker open during this run?" (a host-fallback window would
# silently skew device-stage timings), and stats consumers aggregate it.
# WEAK-valued: a discarded service's breakers drop out on their own, so
# the aggregate never reports a dead drill's tripped breaker and names
# free up for reuse (long pytest sessions create hundreds of services).
_BREAKERS: "weakref.WeakValueDictionary[str, CircuitBreaker]" = \
    weakref.WeakValueDictionary()
_BREAKERS_LOCK = threading.Lock()


def _register_breaker(breaker: "CircuitBreaker") -> str:
    with _BREAKERS_LOCK:
        name, n = breaker.name, 2
        while name in _BREAKERS:
            name = f"{breaker.name}#{n}"
            n += 1
        _BREAKERS[name] = breaker
        return name


def breaker_status() -> Dict[str, dict]:
    """Snapshot of every live breaker — the bench attribution surface."""
    with _BREAKERS_LOCK:
        return {name: b.snapshot() for name, b in list(_BREAKERS.items())}


def any_breaker_open() -> bool:
    with _BREAKERS_LOCK:
        return any(b.state != "closed" for b in list(_BREAKERS.values()))


# Cumulative closed→open transitions, process-wide.  A leaf lock of its
# own (NOT _BREAKERS_LOCK: record() holds the breaker lock and
# breaker_status() takes breaker locks under _BREAKERS_LOCK — sharing
# it would invert that order).  Summing live breakers instead would
# undercount: a drill's breaker that trips and is GC'd within a bench
# row disappears from the weak registry, reading as "no trips".
_TRIPS_LOCK = threading.Lock()
_TRIPS_TOTAL = 0


def total_breaker_trips() -> int:
    """Cumulative trips process-wide — monotonic, survives breaker GC
    (bench attribution computes deltas across a row from this)."""
    with _TRIPS_LOCK:
        return _TRIPS_TOTAL


class CircuitBreaker:
    """closed → (N consecutive faults) → open → (cooldown) → half_open
    probe → closed on success / re-open with doubled cooldown on failure.

    ``route()`` answers where the NEXT dispatch should go: ``"device"``
    (closed), ``"probe"`` (exactly one caller per cooldown expiry gets
    the half-open probe), or ``"host"`` (open / probe already in
    flight)."""

    def __init__(self, name: str, *, threshold: int = 5,
                 cooldown_s: float = 1.0, cooldown_max_s: float = 30.0,
                 clock=time.monotonic):
        self.name = name
        self.threshold = max(1, int(threshold))
        self.base_cooldown_s = cooldown_s
        self.cooldown_s = cooldown_s
        self.cooldown_max_s = cooldown_max_s
        self._clock = clock
        self._lock = threading.Lock()
        self.state = "closed"
        self.consecutive = 0
        self.trips = 0          # closed→open transitions
        self.reopens = 0        # failed probes
        self.recoveries = 0     # →closed transitions after a trip
        self.opened_at: Optional[float] = None
        self._probing = False
        self.registered_name = _register_breaker(self)
        self._m_state = REGISTRY.gauge(
            f"circuit_breaker_open_{self.registered_name}".replace("#", "_"),
            "1 when the breaker is not closed")
        # The registry keeps gauge objects forever; a re-used name (the
        # weak registry freed it) would otherwise inherit the stale
        # value a dead tripped breaker left behind.
        self._m_state.set(0.0)

    def route(self) -> str:
        with self._lock:
            if self.state == "closed":
                return "device"
            now = self._clock()
            if self.state == "open" \
                    and now - self.opened_at >= self.cooldown_s:
                self.state = "half_open"
                self._probing = True
                self._m_state.set(1.0)
                return "probe"
            if self.state == "half_open" and not self._probing:
                self._probing = True
                return "probe"
            return "host"

    def release_probe(self) -> None:
        """A probe attempt ended without a device-health verdict (the
        dispatch raised a passthrough DATA error before proving the
        device either way): free the probe slot so the next caller can
        re-probe.  Without this the breaker wedges in half_open with
        ``_probing`` stuck True — every route() answers "host" forever."""
        with self._lock:
            self._probing = False

    def record(self, ok: bool, *, probe: bool = False) -> None:
        with self._lock:
            if probe:
                self._probing = False
            if ok:
                if self.state != "closed":
                    self.recoveries += 1
                    if TRACER.enabled:
                        TRACER.instant("breaker_closed",
                                       cat="verification_service",
                                       breaker=self.registered_name)
                self.state = "closed"
                self.consecutive = 0
                self.cooldown_s = self.base_cooldown_s
                self.opened_at = None
                self._m_state.set(0.0)
                return
            self.consecutive += 1
            if self.state == "half_open":
                # Failed recovery probe: back off harder.
                self.state = "open"
                self.opened_at = self._clock()
                self.cooldown_s = min(self.cooldown_s * 2,
                                      self.cooldown_max_s)
                self.reopens += 1
                self._m_state.set(1.0)
                if TRACER.enabled:
                    TRACER.instant("breaker_reopen",
                                   cat="verification_service",
                                   breaker=self.registered_name)
            elif self.state == "closed" \
                    and self.consecutive >= self.threshold:
                self.state = "open"
                self.opened_at = self._clock()
                self.trips += 1
                global _TRIPS_TOTAL
                with _TRIPS_LOCK:
                    _TRIPS_TOTAL += 1
                self._m_state.set(1.0)
                if TRACER.enabled:
                    TRACER.instant("breaker_open",
                                   cat="verification_service",
                                   breaker=self.registered_name)

    def snapshot(self) -> dict:
        with self._lock:
            return {"state": self.state, "trips": self.trips,
                    "reopens": self.reopens, "recoveries": self.recoveries,
                    "consecutive_faults": self.consecutive,
                    "cooldown_s": self.cooldown_s}


# ---------------------------------------------------------------------------
# Deadline watchdog pool
# ---------------------------------------------------------------------------


class _WatchdogTask:
    __slots__ = ("fn", "args", "box", "done", "lock", "abandoned")

    def __init__(self, fn, args):
        self.fn = fn
        self.args = args
        self.box: list = []
        self.done = threading.Event()
        self.lock = threading.Lock()
        self.abandoned = False


class _WatchdogPool:
    """Reusable deadline-watchdog threads for device dispatches.

    Every deadlined attempt used to spawn a fresh thread; at gossip
    rates (and in per-message split re-verifies) that is thousands of
    short-lived threads per slot.  Workers that complete before their
    deadline park on a bounded freelist and are reused; an ABANDONED
    worker (deadline hit while the device call is wedged) never parks —
    its thread dies when the wedged call eventually returns, preserving
    the abandon-don't-wait semantics."""

    MAX_IDLE = 8

    def __init__(self):
        self._lock = threading.Lock()
        self._idle: List["_WatchdogWorker"] = []

    def call(self, fn, args, deadline_s: float, name: str):
        task = _WatchdogTask(fn, args)
        with self._lock:
            worker = self._idle.pop() if self._idle else None
        if worker is None:
            worker = _WatchdogWorker(self)
            worker.start()
        worker.assign(task)
        task.done.wait(deadline_s)
        with task.lock:
            if not task.done.is_set():
                task.abandoned = True
                raise DeadlineExceeded(
                    f"{name}: dispatch exceeded {deadline_s}s deadline")
        kind, val = task.box[0]
        if kind == "err":
            raise val
        return val

    def _park(self, worker: "_WatchdogWorker") -> bool:
        with self._lock:
            if len(self._idle) >= self.MAX_IDLE:
                return False
            self._idle.append(worker)
            return True


class _WatchdogWorker(threading.Thread):
    def __init__(self, pool: _WatchdogPool):
        super().__init__(daemon=True, name="verify-watchdog")
        self._pool = pool
        self._wake = threading.Event()
        self._task: Optional[_WatchdogTask] = None

    def assign(self, task: _WatchdogTask) -> None:
        self._task = task
        self._wake.set()

    def run(self) -> None:
        while True:
            self._wake.wait()
            self._wake.clear()
            task, self._task = self._task, None
            try:
                task.box.append(("ok", task.fn(*task.args)))
            except BaseException as e:  # noqa: BLE001 — re-raised in call
                task.box.append(("err", e))
            with task.lock:
                task.done.set()
                abandoned = task.abandoned
            if abandoned or not self._pool._park(self):
                return


_WATCHDOGS = _WatchdogPool()


# ---------------------------------------------------------------------------
# Resilience envelope
# ---------------------------------------------------------------------------


class ResilienceEnvelope:
    """Deadline + retry/backoff/jitter + circuit breaker + host fallback
    around one family of device dispatches (one breaker per family:
    ``bls`` and ``kzg`` fail independently)."""

    def __init__(self, name: str, *, deadline_s: Optional[float] = None,
                 retries: int = 2, backoff_base_s: float = 0.05,
                 backoff_max_s: float = 2.0, breaker_threshold: int = 5,
                 probe_cooldown_s: float = 1.0,
                 cooldown_max_s: float = 30.0, seed: Optional[int] = None,
                 faults=None, fault_site: Optional[str] = None,
                 clock=time.monotonic, sleep=time.sleep):
        self.name = name
        self.deadline_s = deadline_s
        self.retries = max(0, int(retries))
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self._rng = random.Random(seed)
        self._faults = faults
        self._fault_site = fault_site or f"{name}_dispatch"
        self._clock = clock
        self._sleep = sleep
        # Exception types that are DATA errors, not device faults: they
        # propagate immediately (no retry, no breaker count, no host
        # fallback) — a malformed-blob flood must not trip the breaker.
        self.passthrough: tuple = ()
        self.breaker = CircuitBreaker(
            name, threshold=breaker_threshold, cooldown_s=probe_cooldown_s,
            cooldown_max_s=cooldown_max_s, clock=clock)
        self._lock = threading.Lock()
        self.stats = {"device_ok": 0, "device_faults": 0,
                      "deadline_faults": 0, "retries": 0,
                      "host_fallbacks": 0, "probes": 0}
        self.last_error: Optional[str] = None
        # Duration of the most recent SUCCESSFUL attempt (device or
        # host), excluding retry backoff sleeps and failed attempts —
        # the batching policy's dispatch-cost signal (wall time of the
        # whole call would poison the EWMA with seconds of backoff
        # after one fault burst, collapsing batches to singletons).
        self.last_attempt_s: Optional[float] = None
        # Device-ledger attribution: every envelope family is a bls or a
        # kzg dispatch stream (the two device verify families).
        self._ledger_subsystem = "kzg" if "kzg" in name else "bls"
        self._host_span = f"{self._ledger_subsystem}.host_verify"
        self._m_faults = REGISTRY.counter(
            f"{name}_device_faults_total", "device dispatch failures")
        self._m_fallbacks = REGISTRY.counter(
            f"{name}_host_fallbacks_total", "dispatches served by host")

    def _bump(self, key: str, by: int = 1) -> None:
        with self._lock:
            self.stats[key] += by

    def _attempt(self, fn: Callable, args: tuple,
                 deadline_s: Optional[float]):
        """One device attempt; returns ``(result, on_host)``.  The
        fault-injection site fires INSIDE the deadline scope, so an
        injected stall longer than the deadline is observed as
        :class:`DeadlineExceeded` — the blowout scenario."""
        if self._faults is not None:
            inner = self._faults.wrap(self._fault_site, fn)
        else:
            inner = fn

        # The envelope OWNS the dispatch accounting (recorded once on
        # success in _call_inner): suppress the wrapped path's own
        # note_dispatch seams (kzg pairing, direct XLA verify) or every
        # enveloped call counts twice.  Wrap the FN, not the call site —
        # under a deadline the watchdog pool runs it on another thread
        # and the suppression flag is thread-local.
        # The fn also reports whether the device path served the call
        # on the host (the backend's fast path): the ledger's host-route
        # mark is thread-local too, so it is read where the fn ran.
        def guarded(*a):
            with LEDGER.suppress_dispatch():
                routes = LEDGER.host_routes()
                out = inner(*a)
                return out, LEDGER.host_routes() != routes

        if deadline_s is None:
            return guarded(*args)
        # Pooled watchdog: a wedged device call is abandoned (its worker
        # thread dies with it), never waited on; completed workers are
        # reused instead of spawning a thread per attempt.
        return _WATCHDOGS.call(guarded, args, deadline_s, self.name)

    def call(self, device_fn: Callable, host_fn: Optional[Callable],
             args: tuple = (), *, deadline_s=False,
             retries: Optional[int] = None) -> Tuple[object, str]:
        """Run ``device_fn(*args)`` under the envelope; returns
        ``(result, path)`` with path in ``device`` / ``device_retry`` /
        ``probe`` / ``host``.  With no ``host_fn`` a terminal device
        failure re-raises (callers that have no degraded mode keep their
        error semantics)."""
        out, path, _on_host = self.call_routed(
            device_fn, host_fn, args, deadline_s=deadline_s,
            retries=retries)
        return out, path

    def call_routed(self, device_fn: Callable, host_fn: Optional[Callable],
                    args: tuple = (), *, deadline_s=False,
                    retries: Optional[int] = None
                    ) -> Tuple[object, str, bool]:
        """:meth:`call` that also says whether the host computed the
        result: the fallback, or ``device_fn`` taking the backend's host
        fast path (:meth:`DeviceLedger.note_host_route`).  The path
        label of such a fast-path call stays ``device``."""
        with TRACER.span(f"{self.name}_envelope",
                         cat="verification_service") as sp:
            out, path, on_host = self._call_inner(
                device_fn, host_fn, args, deadline_s, retries)
            sp.set(path=path)
            return out, path, on_host

    def _call_inner(self, device_fn, host_fn, args, deadline_s,
                    retries) -> Tuple[object, str, bool]:
        if deadline_s is False:
            deadline_s = self.deadline_s
        if retries is None:
            retries = self.retries
        route = self.breaker.route() if host_fn is not None else "device"
        last: Optional[BaseException] = None
        if route != "host":
            probe = route == "probe"
            attempts = 1 if probe else retries + 1
            if probe:
                self._bump("probes")
            for i in range(attempts):
                t0 = self._clock()
                try:
                    out, on_host = self._attempt(device_fn, args,
                                                 deadline_s)
                    self.last_attempt_s = self._clock() - t0
                except Exception as e:  # noqa: BLE001
                    if self.passthrough and isinstance(e, self.passthrough):
                        if probe:
                            self.breaker.release_probe()
                        raise
                    last = e
                    self.last_error = f"{type(e).__name__}: {e}"
                    self._bump("device_faults")
                    self._m_faults.inc()
                    if isinstance(e, DeadlineExceeded):
                        self._bump("deadline_faults")
                    self.breaker.record(False, probe=probe)
                    if self.breaker.state != "closed" or i == attempts - 1:
                        break  # tripped mid-retry → stop hammering
                    self._bump("retries")
                    self._sleep(backoff_delay(
                        i, base_s=self.backoff_base_s,
                        max_s=self.backoff_max_s, rng=self._rng))
                else:
                    self.breaker.record(True, probe=probe)
                    self._bump("device_ok")
                    # Ledger seam: one successful device dispatch + its
                    # wall time (host fallbacks and the backend's host
                    # fast path don't count — the ledger answers "what
                    # ran on the device").
                    if not on_host:
                        LEDGER.note_dispatch(self._ledger_subsystem,
                                             self.last_attempt_s * 1e3)
                    return out, ("probe" if probe
                                 else "device_retry" if i
                                 else "device"), on_host
        if host_fn is None:
            raise last if last is not None else RuntimeError(
                f"{self.name}: no host fallback")
        self._bump("host_fallbacks")
        self._m_fallbacks.inc()
        t0 = self._clock()
        with TRACER.span(self._host_span, route=route):
            out = host_fn(*args)
        self.last_attempt_s = self._clock() - t0
        return out, "host", True

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self.stats)
        out["breaker"] = self.breaker.snapshot()
        if self.last_error:
            out["last_error"] = self.last_error
        return out


# ---------------------------------------------------------------------------
# The streaming service
# ---------------------------------------------------------------------------


@dataclass
class _Submission:
    kind: str
    sets: List[object]              # bls.SignatureSet(s) of ONE message
    enqueued: float
    deadline: float                 # enqueued + SLO
    arrival: float = 0.0            # gossip-arrival instant (= enqueued
    #   when unknown): the LATENCY accounting clock only — queue policy
    #   (deadline ordering, oldest-first shed) stays keyed on enqueued,
    #   which is monotonic per bucket (submits happen in call order; a
    #   backdated deadline would break the dq[0]-is-oldest invariant
    #   _due_keys/_pop_oldest rely on under the processor's LIFO queues)
    on_result: Optional[Callable[[bool, str], None]] = None
    meta: object = None
    completed: bool = False         # _complete fired (idempotence guard)
    trace_ctx: object = None        # SpanContext captured at submit —
    #   the dispatch span (possibly on a pump thread) parents here, so
    #   the verdict lands in the submitting slot's trace


# Verdict-latency histogram labeled by message kind — the labeled-family
# exposition (`stream_verify_latency_seconds{kind="attestation"}`).
_LATENCY_LABELS = ("kind",)

# Per-SERVICE latency aggregate buckets (seconds): the SLO engine's
# gossip_to_verified feed diffs this record-time histogram between
# window snapshots, so the bounds are dense where per-message budgets
# live (slot/3 at both mainnet 12 s and compressed drill slots).
_SLO_LATENCY_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.075, 0.1, 0.15,
                        0.25, 0.5, 1.0, 2.0, 4.0, 8.0)


# Sync-contribution key lists at least this wide get a content
# fingerprint in their bucket key: every message in the shared-key class
# signs under the SAME wide key list (the 512-key sync-committee shape),
# so fingerprint-pure batches let the backend's shared-key
# two-Miller-lane collapse trigger.  ONLY that class — a wide
# aggregate's signing_keys are the per-message subset its aggregation
# bits select (essentially unique), and fingerprinting those would give
# every aggregate a singleton bucket, defeating micro-batching on the
# never-shed traffic class.
_SHARED_FP_MIN_KEYS = 64


class VerificationService:
    """Streaming signature/KZG verification with SLO-driven adaptive
    micro-batching and graceful host fallback.  One instance per chain;
    pumped by the beacon processor (idle hook) or driven synchronously
    via :meth:`flush`."""

    def __init__(self, *, slo_ms: Optional[float] = None,
                 max_batch: Optional[int] = None,
                 max_pending_attestations: int = 8192,
                 max_pending_total: int = 16384,
                 deadline_ms: Optional[float] = None,
                 retries: int = 2, backoff_base_s: float = 0.05,
                 breaker_threshold: Optional[int] = None,
                 probe_cooldown_s: float = 1.0,
                 cooldown_max_s: float = 30.0,
                 seed: Optional[int] = None, faults=None,
                 device_verify: Optional[Callable] = None,
                 host_verify: Optional[Callable] = None,
                 clock=time.monotonic, sleep=time.sleep,
                 auto_pump: bool = True, name: str = "stream"):
        self.slo_s = (_knob_float("LIGHTHOUSE_TPU_STREAM_SLO_MS")
                      if slo_ms is None else float(slo_ms)) / 1e3
        self.max_batch = (_knob_int("LIGHTHOUSE_TPU_STREAM_MAX_BATCH")
                          if max_batch is None else int(max_batch))
        self.max_pending_attestations = int(max_pending_attestations)
        self.max_pending_total = int(max_pending_total)
        if deadline_ms is None:
            deadline_ms = _knob_float("LIGHTHOUSE_TPU_VERIFY_DEADLINE_MS")
        # 0 (or negative) = deadline DISABLED, not a zero-second
        # deadline: a 0 s watchdog would abandon every attempt at birth
        # and serve all traffic from host fallback while the abandoned
        # threads still run the device call to completion.
        deadline_s = None if deadline_ms <= 0 else deadline_ms / 1e3
        if breaker_threshold is None:
            breaker_threshold = _knob_int("LIGHTHOUSE_TPU_BREAKER_N")
        self._clock = clock
        self._faults = faults
        self._device_verify = device_verify
        self._host_verify = host_verify
        self.auto_pump = bool(auto_pump)
        self.envelope = ResilienceEnvelope(
            f"{name}_bls", deadline_s=deadline_s, retries=retries,
            backoff_base_s=backoff_base_s,
            breaker_threshold=breaker_threshold,
            probe_cooldown_s=probe_cooldown_s,
            cooldown_max_s=cooldown_max_s, seed=seed, faults=faults,
            fault_site="bls_dispatch", clock=clock, sleep=sleep)
        self.kzg_envelope = ResilienceEnvelope(
            f"{name}_kzg", deadline_s=deadline_s, retries=retries,
            backoff_base_s=backoff_base_s,
            breaker_threshold=breaker_threshold,
            probe_cooldown_s=probe_cooldown_s,
            cooldown_max_s=cooldown_max_s,
            seed=None if seed is None else seed + 1, faults=faults,
            fault_site="kzg_dispatch", clock=clock, sleep=sleep)
        self._lock = threading.RLock()
        self._buckets: Dict[tuple, Deque[_Submission]] = {}
        self._pending = 0
        # Messages popped from their bucket but not yet completed (a
        # concurrent pump thread owns them): without this, pending()
        # reads 0 mid-dispatch and the drain contract (flush /
        # run_until_idle) returns while verdicts are still outstanding.
        self._inflight = 0
        self._drained = threading.Condition(self._lock)
        self._pending_by_kind: Dict[str, int] = {}
        self._ewma_dispatch_s: Optional[float] = None
        self.latencies: Deque[float] = deque(maxlen=8192)
        self.batch_sizes: Deque[int] = deque(maxlen=8192)
        # host_verified_sets: sets whose verdict the host computed (the
        # backend's fast path or the envelope's fallback) — the share of
        # the load the chip is not serving.
        self.counters = {"submitted": 0, "verified": 0, "rejected": 0,
                         "shed": 0, "dispatches": 0, "splits": 0,
                         "host_verified_sets": 0,
                         "slo_violations": 0, "kzg_batches": 0,
                         "kzg_blobs": 0}
        self.pipeline_stats = {"items": 0, "fallbacks": 0}
        self._m_latency = REGISTRY.histogram(
            "stream_verify_latency_seconds",
            "submit→verdict latency per message",
            labelnames=_LATENCY_LABELS)
        self._m_shed = REGISTRY.counter(
            "stream_verify_shed_total", "messages shed under overload")
        self._m_host_verified = REGISTRY.counter(
            "stream_verify_host_verified_sets_total",
            "signature sets whose verdict the host computed")
        # Service-LOCAL record-time latency aggregate (unregistered —
        # the process-global family above is shared by every service in
        # the process, so a per-chain SLO feed would mix other nodes'
        # traffic in a simulator/test process).
        self._slo_latency = Histogram(
            "stream_verify_slo_latency_local", "",
            buckets=_SLO_LATENCY_BUCKETS)

    # -- verify fns (resolved per call: the backend can switch) -------------

    def _bls_fns(self) -> Tuple[Callable, Callable]:
        from ..crypto import bls
        if self._device_verify is not None:
            return self._device_verify, (self._host_verify
                                         or self._device_verify)
        backend = bls.get_backend()
        device = backend.verify_signature_sets
        if getattr(backend, "name", "") == "tpu":
            host = bls._BACKENDS["python"].verify_signature_sets
        else:
            # python/fake ARE the host path — fallback is a plain retry.
            host = device
        return device, host

    # -- ingress -------------------------------------------------------------

    def _bucket_key(self, kind: str, sets: Sequence[object]) -> tuple:
        keys = max((len(getattr(s, "signing_keys", ())) for s in sets),
                   default=1)
        k = _next_pow2(max(1, keys))
        fp = None
        if kind == KIND_SYNC and k >= _SHARED_FP_MIN_KEYS:
            first = sets[0].signing_keys
            fp = hash(tuple(p.point[0] for p in first))
        return (kind, k, fp)

    def submit(self, kind: str, sets: Sequence[object],
               on_result: Optional[Callable[[bool, str], None]] = None,
               meta: object = None,
               arrival: Optional[float] = None) -> bool:
        """Enqueue one message's signature set(s).  Returns False when
        the message was shed at the door (attestation overload).

        ``arrival`` optionally backdates the message's LATENCY clock to
        its gossip-arrival instant (``time.monotonic`` domain): the
        latency the SLO accounts then covers the processor queue wait
        too — gossip→verified, not merely submit→verdict.  Batching
        policy (deadline, shed order) stays keyed on the submit instant
        (see :class:`_Submission`).  Ignored when the service runs on
        an injected clock (drills) or when the stamp is in the future
        (a foreign clock domain).  Arbitrarily OLD stamps are accepted:
        a message that waited past the histogram's top bound records as
        overflow (out-of-budget) — an upper cutoff here would blind the
        gossip_to_verified objective to exactly the worst queue waits
        it exists to catch."""
        now = self._clock()
        arr = now
        if arrival is not None and self._clock is time.monotonic \
                and now - arrival >= 0.0:
            arr = arrival
        sub = _Submission(kind=kind, sets=list(sets), enqueued=now,
                          deadline=now + self.slo_s, arrival=arr,
                          on_result=on_result, meta=meta,
                          trace_ctx=TRACER.ctx() if TRACER.enabled
                          else None)
        shed: List[_Submission] = []
        with self._lock:
            self.counters["submitted"] += 1
            att_pending = self._pending_by_kind.get(KIND_ATTESTATION, 0)
            if kind == KIND_ATTESTATION \
                    and att_pending >= self.max_pending_attestations:
                # Oldest-first: stale gossip decays in value (the LIFO
                # discipline of the processor queues, applied to the
                # verify backlog).
                old = self._pop_oldest(KIND_ATTESTATION)
                if old is not None:
                    shed.append(old)
            if self._pending >= self.max_pending_total:
                # Make room by degrading the OLDEST individual
                # attestation (same decay policy as the per-kind cap
                # above — a fresh message outranks a stale one).  Only
                # when the backlog holds nothing sheddable (all
                # never-shed kinds) is an incoming sheddable message
                # itself shed at the door; _NEVER_SHED kinds enter
                # regardless.
                old = self._pop_oldest(KIND_ATTESTATION)
                if old is not None:
                    shed.append(old)
                elif kind not in _NEVER_SHED:
                    shed.append(sub)
                    sub = None
            if sub is not None:
                self._buckets.setdefault(
                    self._bucket_key(kind, sub.sets),
                    deque()).append(sub)
                self._pending += 1
                self._pending_by_kind[kind] = \
                    self._pending_by_kind.get(kind, 0) + 1
            due = self._any_due(now)
        for s in shed:
            self._shed(s)
        # Self-pumping ingress: the processor's idle tick only fires
        # when its queues drain, so under SUSTAINED load the submitter
        # itself dispatches due work (full buckets, SLO-expiring heads)
        # — the fat-batch amortization happens on the submitting worker
        # thread exactly like the synchronous verify path would, and
        # dispatch can never starve behind a busy manager loop.  During
        # a breaker trip window this blocks the worker in envelope
        # deadline/backoff waits — no worse than the synchronous verify
        # it replaces (which held the worker for the full device call),
        # and bounded per pump by the deadline; once tripped, dispatch
        # falls through to the fast host route.
        # (``auto_pump=False`` = externally pumped: unit tests that pin
        # the dispatch policy step it with explicit pump() calls.)
        if due and self.auto_pump:
            self.pump()
        return sub is not None

    def _pop_oldest(self, kind: str) -> Optional[_Submission]:
        """Caller holds the lock.  Remove the oldest pending submission
        of ``kind`` (scan bucket heads — buckets are FIFO deques)."""
        best_key, best = None, None
        for key, dq in self._buckets.items():
            if key[0] != kind or not dq:
                continue
            if best is None or dq[0].enqueued < best.enqueued:
                best_key, best = key, dq[0]
        if best_key is None:
            return None
        sub = self._buckets[best_key].popleft()
        if not self._buckets[best_key]:
            del self._buckets[best_key]
        self._pending -= 1
        self._pending_by_kind[kind] -= 1
        return sub

    def _shed(self, sub: _Submission) -> None:
        with self._lock:
            self.counters["shed"] += 1
        self._m_shed.inc()
        if sub.on_result is not None:
            try:
                sub.on_result(False, "shed")
            except Exception:  # noqa: BLE001 — callback owns its errors
                pass

    def pending(self) -> int:
        """Queued + in-flight: messages whose verdict is still owed."""
        with self._lock:
            return self._pending + self._inflight

    def has_due_work(self) -> bool:
        """Cheap dispatch-due check for external pumpers (the beacon
        processor's idle tick): True only when a pump would actually
        dispatch something — a message merely sitting inside its SLO
        window is not due."""
        with self._lock:
            return self._any_due(self._clock())

    # -- adaptive dispatch ----------------------------------------------------

    def _dispatch_estimate(self) -> float:
        # Until measured, assume a dispatch costs a quarter of the SLO —
        # conservative enough that the first messages still meet it.
        return (self._ewma_dispatch_s if self._ewma_dispatch_s is not None
                else self.slo_s / 4)

    def _any_due(self, now: float) -> bool:
        """Caller holds the lock.  Early-exit form of :meth:`_due_keys`
        for the per-submit check: the hot ingress path only needs the
        boolean, not the sorted dispatch order."""
        est = self._dispatch_estimate()
        drain = self._pending >= self.max_batch
        for dq in self._buckets.values():
            if dq and (drain or len(dq) >= self.max_batch
                       or now + est >= dq[0].deadline):
                return True
        return False

    def _due_keys(self, now: float, force: bool) -> List[tuple]:
        est = self._dispatch_estimate()
        drain = self._pending >= self.max_batch  # backlog → amortize
        due = []
        for key, dq in self._buckets.items():
            if not dq:
                continue
            if force or drain or len(dq) >= self.max_batch \
                    or now + est >= dq[0].deadline:
                due.append(key)
        # Oldest-head bucket first: it is the closest to its SLO.
        due.sort(key=lambda k: self._buckets[k][0].deadline)
        return due

    def pump(self, force: bool = False, max_rounds: int = 64) -> int:
        """Dispatch every due bucket (repeatedly — a backlog deeper than
        ``max_batch`` keeps a bucket due until drained); returns messages
        completed.  The beacon processor calls this from its idle loop;
        ``force`` (used by :meth:`flush`) dispatches everything
        pending."""
        done = 0
        for _ in range(max_rounds):
            n = self._pump_once(force)
            done += n
            if n == 0:
                break
        return done

    def _pump_once(self, force: bool) -> int:
        from ..parallel.pipeline import StagedExecutor, _default_stage

        now = self._clock()
        work: List[Tuple[tuple, List[_Submission]]] = []
        with self._lock:
            for key in self._due_keys(now, force):
                dq = self._buckets[key]
                batch: List[_Submission] = []
                while dq and len(batch) < self.max_batch:
                    batch.append(dq.popleft())
                if not dq:
                    # Prune drained buckets: bucket keys are unbounded
                    # (one per distinct shape ever seen) and _due_keys/
                    # _pop_oldest scan the whole dict under the lock on
                    # every submit.
                    del self._buckets[key]
                self._pending -= len(batch)
                self._pending_by_kind[key[0]] -= len(batch)
                if batch:
                    self._inflight += len(batch)
                    work.append((key, batch))
        if not work:
            return 0
        stage = (self._faults.stage_wrapper(_default_stage)
                 if self._faults is not None else None)
        ex = StagedExecutor("stream_verify", stage=stage,
                            subsystem="bls")
        try:
            sum(ex.map(work, self._prep_bucket, self._dispatch_bucket))
        except Exception:  # noqa: BLE001 — a staging-machinery failure
            # (prep raise, double-failed sync stage) escapes ex.map with
            # popped submissions never completed: deliver error verdicts
            # or _inflight leaks forever and flush() deadlocks.
            # _complete's idempotence guard skips the ones that did
            # finish before the failure.
            for _key, batch in work:
                for s in batch:
                    self._complete(s, False, "error")
        with self._lock:
            self.pipeline_stats["items"] += ex.stats["items"]
            self.pipeline_stats["fallbacks"] += ex.stats["fallbacks"]
        return sum(len(batch) for _key, batch in work)

    def flush(self) -> int:
        """Synchronous drain (tests, simulator, slot-end): dispatch
        until nothing is pending, then wait for messages a CONCURRENT
        pump thread holds in flight — when flush returns, every verdict
        owed at entry has been delivered.  The wait terminates because
        the envelope's deadline bounds each in-flight dispatch; with the
        deadline knob DISABLED (``deadline_ms=0``) a genuinely wedged
        device call blocks this wait too — that is the operator's
        explicit trade (see the cold-compile note in the module
        docstring for why one would disable it)."""
        done = self.pump(force=True)
        with self._lock:
            while self._inflight:
                self._drained.wait(timeout=0.1)
        return done

    def _prep_bucket(self, item):
        key, subs = item
        flat: List[object] = []
        for s in subs:
            flat.extend(s.sets)
        return (subs, flat)

    def _dispatch_bucket(self, staged) -> int:
        subs, sets = staged
        with TRACER.span("verify_dispatch", cat="verification_service",
                         parent=subs[0].trace_ctx, kind=subs[0].kind,
                         batch=len(sets)) as _sp:
            n = self._dispatch_bucket_inner(subs, sets, _sp)
        return n

    def _dispatch_bucket_inner(self, subs, sets, _sp) -> int:
        device, host = self._bls_fns()
        t0 = self._clock()
        if TRACER.enabled:
            _sp.set(queue_wait_ms=round(
                (t0 - min(s.enqueued for s in subs)) * 1e3, 2))
        try:
            ok, path, on_host = self.envelope.call_routed(
                device, host, (sets,))
        except Exception:  # noqa: BLE001 — even a raising HOST path must
            # complete every message (False), never leak into the staged
            # executor's retry (which would double-fire callbacks).
            for s in subs:
                self._complete(s, False, "error")
            return len(subs)
        dt = self._clock() - t0
        # Feed the EWMA the SUCCESSFUL attempt's duration, not the
        # envelope-call wall time: one retried dispatch would otherwise
        # push seconds of backoff sleep into the estimate, making every
        # pending message look SLO-due and collapsing the post-outage
        # backlog — exactly when amortization matters most — into
        # singleton batches for the ~10 dispatches the 0.7 decay needs.
        est = self.envelope.last_attempt_s
        sample = est if est is not None and est <= dt else dt
        with self._lock:
            self.counters["dispatches"] += 1
            self.batch_sizes.append(len(sets))
            self._ewma_dispatch_s = (
                sample if self._ewma_dispatch_s is None
                else 0.3 * sample + 0.7 * self._ewma_dispatch_s)
        if on_host:
            self._count_host_verified(len(sets))
        observe("stream_verify_dispatch_seconds", dt)
        _sp.set(path=path, verdict=bool(ok))
        if ok or len(subs) == 1:
            for s in subs:
                self._complete(s, bool(ok), path)
            return len(subs)
        # Batch verdict False with >1 message: re-verify per message so
        # one junk signature cannot censor the batch (`batch.rs:203`).
        with self._lock:
            self.counters["splits"] += 1
        with TRACER.span("verify_split", cat="verification_service",
                         batch=len(subs)):
            for s in subs:
                try:
                    ok_i, path_i, on_host = self.envelope.call_routed(
                        device, host, (s.sets,))
                except Exception:  # noqa: BLE001
                    ok_i, path_i, on_host = False, "error", False
                if on_host:
                    self._count_host_verified(len(s.sets))
                self._complete(s, bool(ok_i), path_i)
        return len(subs)

    def _count_host_verified(self, n: int) -> None:
        with self._lock:
            self.counters["host_verified_sets"] += n
        self._m_host_verified.inc(n)

    def _complete(self, sub: _Submission, ok: bool, path: str) -> None:
        with self._lock:
            if sub.completed:  # error-sweep vs normal path double-fire
                return
            sub.completed = True
        now = self._clock()
        # Two clocks, two meanings: the SERVICE metrics (labeled family,
        # p50/p99 deque, slo_violations) stay submit→verdict — that is
        # the batching policy's own deadline domain — while the SLO
        # feed measures gossip-arrival→verified (queue wait included),
        # which is the objective the operator cares about.
        lat = now - sub.enqueued
        self._m_latency.labels(sub.kind).observe(lat)
        self._slo_latency.observe(now - (sub.arrival or sub.enqueued))
        with self._lock:
            self.latencies.append(lat)
            self.counters["verified" if ok else "rejected"] += 1
            if lat > self.slo_s:
                self.counters["slo_violations"] += 1
            self._inflight -= 1
            if self._inflight == 0:
                self._drained.notify_all()
        if sub.on_result is not None:
            try:
                sub.on_result(ok, path)
            except Exception:  # noqa: BLE001 — callback owns its errors
                pass

    # -- KZG (blob-sidecar batches) ------------------------------------------

    def verify_blob_batch(self, blobs, commitments, proofs, setup) -> bool:
        """Resilient ``verify_blob_kzg_proof_batch``: the device path
        (auto-routed) under the kzg envelope, host pairing as the
        degraded mode.  Blob batches are never shed — availability gates
        block import.  ``KzgError`` (malformed data) passes straight
        through: data errors are the caller's rejection semantics, not
        device faults."""
        from .. import kzg as KZ

        self.kzg_envelope.passthrough = (KZ.KzgError,)

        def device():
            return KZ.verify_blob_kzg_proof_batch(
                blobs, commitments, proofs, setup)

        def host():
            return KZ.verify_blob_kzg_proof_batch(
                blobs, commitments, proofs, setup, use_device=False)

        ok, _path = self.kzg_envelope.call(device, host)
        with self._lock:
            self.counters["kzg_batches"] += 1
            self.counters["kzg_blobs"] += len(blobs)
        return bool(ok)

    # -- introspection --------------------------------------------------------

    def slo_counters(self) -> dict:
        """Cumulative message counters, cheap enough for the SLO
        engine's per-tick feeds (:meth:`stats` sorts the whole latency
        deque — too heavy to call every evaluation)."""
        with self._lock:
            return dict(self.counters)

    def latency_snapshot(self):
        """Record-time per-service latency aggregate:
        ``(buckets, counts, total, sum)`` — the gossip_to_verified SLO
        feed."""
        return self._slo_latency.snapshot()

    @staticmethod
    def _pct(sorted_vals: List[float], q: float) -> Optional[float]:
        if not sorted_vals:
            return None
        i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1)))
        return sorted_vals[i]

    def stats(self) -> dict:
        with self._lock:
            lats = sorted(self.latencies)
            sizes = list(self.batch_sizes)
            out = dict(self.counters)
            out["pending"] = self._pending + self._inflight
            out["in_flight"] = self._inflight
            out["pipeline"] = dict(self.pipeline_stats)
        out["slo_ms"] = round(self.slo_s * 1e3, 1)
        out["latency_p50_ms"] = (None if not lats else
                                 round(self._pct(lats, 0.50) * 1e3, 2))
        out["latency_p99_ms"] = (None if not lats else
                                 round(self._pct(lats, 0.99) * 1e3, 2))
        out["latency_max_ms"] = (None if not lats else
                                 round(lats[-1] * 1e3, 2))
        hist: Dict[int, int] = {}
        for s in sizes:
            b = _next_pow2(max(1, s))
            hist[b] = hist.get(b, 0) + 1
        out["batch_size_hist"] = {str(k): hist[k] for k in sorted(hist)}
        out["bls"] = self.envelope.snapshot()
        out["kzg"] = self.kzg_envelope.snapshot()
        return out


# ---------------------------------------------------------------------------
# Global BLS envelope — resilience for the non-streamed verify paths
# (block proposer/transition batches, op-pool gossip checks): installed
# as the bls dispatch wrapper so EVERY device dispatch in the process
# gets deadline/retry/breaker/host-fallback, not just the queued ones.
# ---------------------------------------------------------------------------

_GLOBAL_ENVELOPE: Optional[ResilienceEnvelope] = None
_GLOBAL_LOCK = threading.Lock()
_GLOBAL_INSTALLS = 0  # refcount: nodes share the process-wide wrapper


def global_bls_envelope() -> ResilienceEnvelope:
    global _GLOBAL_ENVELOPE
    with _GLOBAL_LOCK:
        if _GLOBAL_ENVELOPE is None:
            d_ms = _knob_float("LIGHTHOUSE_TPU_VERIFY_DEADLINE_MS")
            _GLOBAL_ENVELOPE = ResilienceEnvelope(
                "bls_global",
                deadline_s=None if d_ms <= 0 else d_ms / 1e3,
                retries=2,
                breaker_threshold=_knob_int("LIGHTHOUSE_TPU_BREAKER_N"))
        return _GLOBAL_ENVELOPE


def _global_dispatch(backend, sets):
    """The :func:`bls.set_dispatch_wrapper` hook.  Only the TPU backend
    has a distinct host oracle (and a device to lose): python/fake calls
    pass straight through — wrapping them would re-run slow host
    verifies on a deadline overrun and mask logic errors behind
    retries."""
    if getattr(backend, "name", "") != "tpu":
        return backend.verify_signature_sets(sets)
    from ..crypto import bls
    env = global_bls_envelope()
    ok, _path = env.call(backend.verify_signature_sets,
                         bls._BACKENDS["python"].verify_signature_sets,
                         (sets,))
    return bool(ok)


def block_sig_dispatch(device_fn, sets) -> tuple:
    """Envelope-wrapped dispatch for the OVERLAPPED block-signature
    batch (``state_transition.sig_dispatch``): shares the global BLS
    envelope — and therefore its circuit breaker — with every other
    non-streamed verify, so a device outage degrades block batches to
    the host oracle through the SAME machinery (zero new failure modes)
    and bench's breaker attribution sees the block path too.

    The envelope's deadline bounds ONE device dispatch; a chain
    segment's chunk of sets makes several (the backend's
    :func:`~..crypto.tpu_backend.dispatch_count`), so the batch gets the
    deadline once per dispatch it makes: a sound batch stays on the
    device, and a wedged device is still abandoned.  Returns
    ``(verdict, path)``."""
    from ..crypto import bls, tpu_backend
    env = global_bls_envelope()
    deadline_s = env.deadline_s
    if deadline_s is not None:
        deadline_s *= tpu_backend.dispatch_count(sets)
    ok, path = env.call(device_fn,
                        bls._BACKENDS["python"].verify_signature_sets,
                        (sets,), deadline_s=deadline_s)
    return bool(ok), path


def install_global_envelope() -> bool:
    """Route module-level ``bls.verify_signature_sets`` through the
    global envelope (idempotent; ``LIGHTHOUSE_TPU_RESILIENT=0``
    disables).  Each successful install takes one refcount — pair it
    with :func:`release_global_envelope` at teardown."""
    global _GLOBAL_INSTALLS
    if not _knob_bool("LIGHTHOUSE_TPU_RESILIENT"):
        return False
    from ..crypto import bls
    with _GLOBAL_LOCK:
        _GLOBAL_INSTALLS += 1
    bls.set_dispatch_wrapper(_global_dispatch)
    return True


def release_global_envelope() -> None:
    """Drop one install refcount; the LAST release detaches the wrapper
    (a dead node's accumulated breaker state must not route later
    verifies through watchdogs/host fallback in code that never opted
    in)."""
    global _GLOBAL_INSTALLS
    with _GLOBAL_LOCK:
        if _GLOBAL_INSTALLS > 0:
            _GLOBAL_INSTALLS -= 1
        last = _GLOBAL_INSTALLS == 0
    if last:
        uninstall_global_envelope()


def uninstall_global_envelope() -> None:
    """Unconditionally detach the global dispatch wrapper and drop its
    envelope (breaker state and refcount included).  Prefer the
    refcounted :func:`release_global_envelope` in teardown paths; this
    is the hard reset for tests that must restore pristine ``bls``
    dispatch."""
    global _GLOBAL_ENVELOPE, _GLOBAL_INSTALLS
    from ..crypto import bls
    bls.set_dispatch_wrapper(None)
    with _GLOBAL_LOCK:
        _GLOBAL_ENVELOPE = None
        _GLOBAL_INSTALLS = 0
