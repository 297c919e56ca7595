"""Overlapped block-signature dispatch — the device batch rides under
the host transition instead of trailing it.

``process_block(strategy=VERIFY_BULK)`` used to pay its bulk
``verify_signature_sets`` call as a trailing synchronous step: the whole
transition ran, THEN the batch went to the device and the import waited
out the pairing latency end-to-end.  The committee-consensus study
(arXiv:2302.00418) shows verification throughput under per-slot
committee load — not peak batch size — decides liveness, and the IoT
pairing-processor paper (arXiv:2201.07496) wins by keeping its wide
multiplier saturated instead of idle between dispatches; both argue for
hiding the pairing latency under the transition, which is what this
module does:

- :meth:`BlockSigDispatcher.submit` takes the block's accumulated
  signature sets as soon as the op-accumulation phase has built them
  (before the participation scatters / proposer rewards / sync-aggregate
  balance work / payload header build), drops exact-duplicate sets
  (:func:`~lighthouse_tpu.crypto.bls.dedup_signature_sets`), and
  dispatches verification on a worker thread;
- the device route goes through the mesh-sharded path
  (:func:`~lighthouse_tpu.parallel.bls_shard.
  bucketed_verify_signature_sets` — sets grouped by padded signer count
  K exactly like the verification service's ingress buckets) when a
  multi-chip mesh is attached, and is wrapped in the PR-7 global BLS
  :class:`~lighthouse_tpu.beacon_chain.verification_service.
  ResilienceEnvelope` (via
  :func:`~lighthouse_tpu.beacon_chain.verification_service.
  block_sig_dispatch`), so a tripped device degrades the block batch to
  the host oracle through the SAME breaker every other non-streamed
  verify uses — zero new failure modes;
- :meth:`BlockSigBatch.join` delivers the verdict at
  ``SigAccumulator.finish()`` — on the import pipeline
  (``block_verification.ExecutedBlock``) that is AFTER the post-state
  root hash, so device pairing time hides behind host transition +
  hashing compute and only the remainder (``join_wait_ms``) lands on
  the critical path.

The python/fake backends ARE the host path: they dispatch directly on
the worker thread with no envelope (wrapping them would add
retry/deadline semantics to logic-test verifies — the same rule as
``verification_service._global_dispatch``).  With the fake backend the
whole machinery (dedup, async submit, deferred applies, join) still
runs, which is how the quick tier drives it without compiling any
pairing-shaped program.

Stats of the most recent completed batch land in
:data:`LAST_SIG_DISPATCH` (stage source ``"block_sigs"`` — bench and
the validation script read it through ``tracing.stage_split``):
``sets`` / ``deduped`` / ``path`` / ``device_verify_ms`` /
``join_wait_ms`` / ``overlap_efficiency`` (= 1 − join_wait /
device_verify) / ``overlapped``.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional, Tuple

from ..common.metrics import REGISTRY
from ..common.tracing import TRACER

# Envelope paths on which the device answered.
DEVICE_PATHS = ("device", "device_retry", "probe")

_BATCHES = REGISTRY.counter(
    "block_sig_batches_total",
    "block-signature batches verified, by whether the device or the "
    "host served them", labelnames=("path",))

# Stats of the most recent block-signature verdict (overlapped OR the
# synchronous oracle path) — read via tracing.stage_split("block_sigs").
LAST_SIG_DISPATCH: dict = {}


def overlap_enabled() -> bool:
    """Overlapped dispatch knob: on unless
    ``LIGHTHOUSE_TPU_OVERLAP_BLOCK_SIGS=0`` (the trailing synchronous
    verify is the differential oracle)."""
    from ..common.knobs import knob_bool
    return knob_bool("LIGHTHOUSE_TPU_OVERLAP_BLOCK_SIGS")


def _shard_route() -> bool:
    """Route the device dispatch through the mesh-sharded BLS path?
    ``LIGHTHOUSE_TPU_BLOCK_SIG_SHARD`` forces; auto = TPU backend on a
    multi-chip mesh (a 1-device mesh would only add shard_map overhead
    over the fused single-chip pipeline)."""
    from ..common.knobs import knob_tribool
    forced = knob_tribool("LIGHTHOUSE_TPU_BLOCK_SIG_SHARD")
    if forced is not None:
        return forced
    import jax
    return jax.default_backend() == "tpu" and jax.device_count() > 1


def _device_verify(sets) -> bool:
    """The device leg handed to the resilience envelope: sharded
    K-bucketed dispatch over the mesh when routed, else the TPU
    backend's fused single-chip pipeline."""
    from ..crypto import bls
    if _shard_route():
        from ..parallel.bls_shard import bucketed_verify_signature_sets
        from ..parallel.mesh import make_mesh
        return bucketed_verify_signature_sets(sets, make_mesh())
    return bls._BACKENDS["tpu"].verify_signature_sets(sets)


class BlockSigBatch:
    """The in-flight verdict of one block's signature batch."""

    __slots__ = ("_done", "_verdict", "_error", "stats", "slot")

    def __init__(self, stats: dict, slot: Optional[int] = None):
        self._done = threading.Event()
        self._verdict = False
        self._error: Optional[BaseException] = None
        self.stats = stats
        self.slot = slot

    def done(self) -> bool:
        return self._done.is_set()

    def _complete(self, verdict: bool = False,
                  error: Optional[BaseException] = None) -> None:
        self._verdict = bool(verdict)
        self._error = error
        self._done.set()

    def join(self) -> bool:
        """Block until the verdict is in; publish the join-wait /
        overlap stats.  A verifier-side exception (one that escaped the
        envelope, i.e. a data error or a host-oracle failure) re-raises
        here, on the importing thread."""
        t0 = time.perf_counter()
        with TRACER.span("sig_join", cat="state_transition",
                         slot=self.slot) as sp:
            self._done.wait()
            wait_ms = (time.perf_counter() - t0) * 1e3
            self.stats["join_wait_ms"] = round(wait_ms, 3)
            dv = self.stats.get("device_verify_ms") or 0.0
            self.stats["overlap_efficiency"] = (
                None if dv <= 0.0
                else round(max(0.0, 1.0 - wait_ms / dv), 4))
            LAST_SIG_DISPATCH.clear()
            LAST_SIG_DISPATCH.update(self.stats)
            sp.set(join_wait_ms=self.stats["join_wait_ms"],
                   path=self.stats.get("path"),
                   verdict=self._error is None and self._verdict)
        if self._error is not None:
            raise self._error
        return self._verdict


class BlockSigDispatcher:
    """Asynchronous verifier for one block's accumulated signature sets.

    The default (module-singleton) instance routes by backend: tpu →
    sharded/fused device dispatch under the global BLS envelope,
    python/fake → direct host verify on the worker thread.  Tests and
    bench inject ``device_fn``/``host_fn`` (+ an optional pre-built
    envelope) to drive fault drills and modeled-latency devices through
    the REAL submit/join machinery."""

    def __init__(self, device_fn: Optional[Callable] = None,
                 host_fn: Optional[Callable] = None,
                 envelope=None, name: str = "block_sigs"):
        self._device_fn = device_fn
        self._host_fn = host_fn
        self._envelope = envelope
        self.name = name

    def submit(self, sets: List[object],
               slot: Optional[int] = None) -> BlockSigBatch:
        """Dedup + launch verification of ``sets`` on a worker thread;
        returns immediately with the joinable batch."""
        from ..crypto import bls
        with TRACER.span("sig_dispatch", cat="state_transition",
                         slot=slot) as sp:
            deduped, dropped = bls.dedup_signature_sets(sets)
            stats = {"sets": len(sets), "deduped": dropped,
                     "overlapped": True}
            sp.set(sets=len(sets), deduped=dropped)
            batch = BlockSigBatch(stats, slot=slot)
            ctx = TRACER.ctx() if TRACER.enabled else None
            threading.Thread(target=self._run, args=(deduped, batch, ctx),
                             name="block-sig-verify", daemon=True).start()
        return batch

    # -- worker side ---------------------------------------------------------

    def _run(self, sets, batch: BlockSigBatch, ctx) -> None:
        t0 = time.perf_counter()
        try:
            # cat stays "state_transition": this is the signature leg of
            # the block transition (the "verification_service" category
            # is reserved for the streamed gossip pipeline — a DIRECT
            # import must not fabricate that stage in its trace).
            with TRACER.span("sig_device_verify",
                             cat="state_transition", parent=ctx,
                             sets=len(sets)) as sp:
                ok, path = self._verify(sets)
                sp.set(path=path, verdict=bool(ok))
        except BaseException as e:  # noqa: BLE001 — re-raised at join
            batch.stats["device_verify_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 3)
            batch.stats["path"] = "error"
            batch._complete(False, e)
            return
        batch.stats["device_verify_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 3)
        batch.stats["path"] = path
        _BATCHES.labels(
            path="device" if path in DEVICE_PATHS else "host").inc()
        batch._complete(ok)

    def _verify(self, sets) -> Tuple[bool, str]:
        from ..crypto import bls
        if self._device_fn is not None:
            env = self._ensure_envelope()
            host = (self._host_fn
                    or bls._BACKENDS["python"].verify_signature_sets)
            ok, path = env.call(self._device_fn, host, (sets,))
            return bool(ok), path
        backend = bls.get_backend()
        if getattr(backend, "name", "") != "tpu":
            # Direct host-backend verify: deliberately NOT a ledger
            # dispatch — the ledger answers "what ran on the device",
            # and a python/fake verify never touched one (same rule as
            # the envelope's host-fallback path).
            return (bool(backend.verify_signature_sets(sets)),
                    getattr(backend, "name", "host"))
        from ..beacon_chain.verification_service import block_sig_dispatch
        return block_sig_dispatch(_device_verify, sets)

    def _ensure_envelope(self):
        if self._envelope is None:
            from ..beacon_chain.verification_service import (
                ResilienceEnvelope)
            self._envelope = ResilienceEnvelope(self.name, retries=1)
        return self._envelope


_DEFAULT = BlockSigDispatcher()


def get_dispatcher() -> BlockSigDispatcher:
    return _DEFAULT


def record_sync_verify(n_sets: int, deduped: int,
                       verify_ms: float) -> None:
    """Publish the SYNCHRONOUS (non-overlapped) verify's stats so the
    ``block_sigs`` stage source always reflects the most recent block —
    a sync verify IS its own join wait (overlap efficiency 0).  The
    sync path verifies UN-deduped (it is the differential oracle for
    dedup too), so ``deduped`` is 0 there."""
    LAST_SIG_DISPATCH.clear()
    LAST_SIG_DISPATCH.update(
        sets=n_sets, deduped=deduped, path="sync",
        device_verify_ms=round(verify_ms, 3),
        join_wait_ms=round(verify_ms, 3),
        overlap_efficiency=0.0, overlapped=False)
