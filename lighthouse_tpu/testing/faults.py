"""Deterministic, seedable fault injection for the device dispatch path.

The streaming verification service (:mod:`..beacon_chain.
verification_service`) wraps every device dispatch in a resilience
envelope — deadline, retry-with-backoff, circuit breaker, host fallback.
Proving those paths actually fire needs failures on demand, and proving
the *drill* is reproducible needs them deterministic: this module is the
single failure-point registry both the hostile-drill simulator and the
unit tests drive.

Failure points are named **sites** (``"bls_dispatch"``, ``"kzg_dispatch"``,
``"h2d"``); each site carries a :class:`FaultPlan` deciding, per call and
from a seeded PRNG, whether the call

- raises :class:`InjectedFault` (a dispatch failure — the shape of a
  wedged device surfacing an ``XlaRuntimeError``),
- stalls for ``stall_s`` before proceeding (an H2D stall; with
  ``stall_s`` above the envelope's deadline this is the deadline-blowout
  scenario), or
- proceeds untouched.

An ``outage`` window fails EVERY call whose per-site sequence number
falls inside ``[start, stop)`` — the sustained-outage scenario that must
trip the circuit breaker — independent of the random ``fail_rate``
(which models intermittent 1-in-N faults).  All decisions come from one
``random.Random(seed)``, so a drill replays bit-identically.

Usage::

    inj = FaultInjector(seed=7, plans={
        "bls_dispatch": FaultPlan(fail_rate=0.1, outage=(20, 35)),
        "h2d": FaultPlan(stall_rate=0.05, stall_s=0.2),
    })
    service = VerificationService(..., faults=inj)

The injector also generates the *traffic* side of a drill:
:func:`burst_schedule` produces deterministic message arrival offsets
(steady rate + gossip bursts) shared by ``scripts/validate_stream_verify
.py`` and the hostile-drill test.
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple


class InjectedFault(RuntimeError):
    """A deliberately injected device-dispatch failure."""


@dataclass
class FaultPlan:
    """Per-site failure policy.

    ``fail_rate``   — P(raise InjectedFault) per call (intermittent).
    ``outage``      — (start, stop) half-open window of per-site call
                      sequence numbers that ALL fail (sustained outage).
    ``stall_rate``  — P(sleep ``stall_s`` before proceeding).
    ``stall_s``     — stall duration; combined with an envelope deadline
                      shorter than this it becomes a deadline blowout.
    ``fail_first``  — fail the first N calls unconditionally (a cold
                      start / compile-stall shape).
    """
    fail_rate: float = 0.0
    outage: Optional[Tuple[int, int]] = None
    stall_rate: float = 0.0
    stall_s: float = 0.0
    fail_first: int = 0


class FaultInjector:
    """Seeded failure-point registry; thread-safe (the beacon processor
    dispatches from worker threads)."""

    def __init__(self, seed: int = 0,
                 plans: Optional[Dict[str, FaultPlan]] = None,
                 sleep=time.sleep):
        self._rng = random.Random(seed)
        self.plans: Dict[str, FaultPlan] = dict(plans or {})
        self._sleep = sleep
        self._lock = threading.Lock()
        self.calls: Dict[str, int] = {}     # per-site sequence counter
        self.injected: Dict[str, int] = {}  # per-site raises
        self.stalls: Dict[str, int] = {}    # per-site stalls

    def plan(self, site: str, **kw) -> None:
        """(Re)arm a site — drills flip plans mid-run (outage → recovery)."""
        with self._lock:
            self.plans[site] = FaultPlan(**kw)

    def disarm(self, site: Optional[str] = None) -> None:
        with self._lock:
            if site is None:
                self.plans.clear()
            else:
                self.plans.pop(site, None)

    def check(self, site: str) -> None:
        """One failure-point decision.  Raises or stalls per the site's
        plan; always counts the call."""
        with self._lock:
            seq = self.calls.get(site, 0)
            self.calls[site] = seq + 1
            plan = self.plans.get(site)
            if plan is None:
                return
            # All PRNG draws happen under the lock, in call order — the
            # determinism contract.
            fail = seq < plan.fail_first
            if plan.outage is not None \
                    and plan.outage[0] <= seq < plan.outage[1]:
                fail = True
            if not fail and plan.fail_rate > 0:
                fail = self._rng.random() < plan.fail_rate
            stall = (not fail and plan.stall_rate > 0
                     and self._rng.random() < plan.stall_rate)
        if fail:
            with self._lock:
                self.injected[site] = self.injected.get(site, 0) + 1
            raise InjectedFault(f"injected fault at {site} (call #{seq})")
        if stall:
            with self._lock:
                self.stalls[site] = self.stalls.get(site, 0) + 1
            self._sleep(plan.stall_s)

    def wrap(self, site: str, fn):
        """``fn`` with this site's failure point in front of it."""
        def wrapped(*args, **kw):
            self.check(site)
            return fn(*args, **kw)
        return wrapped

    def stage_wrapper(self, stage_fn):
        """H2D failure point for a ``StagedExecutor(stage=...)`` seam:
        the staging call (async ``device_put``) checks the ``"h2d"``
        site first, so a plan there produces staging failures the
        executor's sync-retry path must absorb."""
        def staged(host):
            self.check("h2d")
            return stage_fn(host)
        return staged

    def stats(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {"calls": dict(self.calls),
                    "injected": dict(self.injected),
                    "stalls": dict(self.stalls)}


def burst_schedule(n: int, rate_per_s: float, *,
                   burst_every: int = 0, burst_size: int = 0,
                   seed: int = 0) -> List[float]:
    """Deterministic arrival offsets (seconds) for a drill's message
    stream: Poisson-ish steady arrivals at ``rate_per_s``, plus, every
    ``burst_every`` messages, ``burst_size`` extra arrivals at the same
    instant (the gossip-burst shape: a whole committee's attestations
    landing in one mesh flush).  Sorted ascending; length ≥ ``n``."""
    rng = random.Random(seed)
    out: List[float] = []
    t = 0.0
    i = 0
    while len(out) < n:
        t += rng.expovariate(rate_per_s) if rate_per_s > 0 else 0.0
        out.append(t)
        i += 1
        if burst_every > 0 and i % burst_every == 0:
            out.extend([t] * burst_size)
    out.sort()
    return out
