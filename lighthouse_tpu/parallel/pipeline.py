"""Staged device executor — overlapped host-prep / H2D staging / compute.

The three device hot paths (batched BLS verify, the cold Merkle build,
the registry cold build) all share one wall-clock pathology: the host
does ALL of its marshalling, then pushes ALL of the bytes, then the
device starts computing — so a 1024-set BLS batch spends ~70% of its
wall time with the device idle, and the cold state root spends 5+ s
blocked on one monolithic leaf push.  This module is the shared staging
layer that removes the serialization:

- :class:`StagedExecutor` — double-buffered ``prep → stage → dispatch``
  over a work list.  ``prep`` (host marshalling) of item *i+1* runs
  while the device computes item *i*: dispatches are issued without any
  ``block_until_ready`` between stages, so JAX's async dispatch keeps
  the device busy under the host loop.  A staging failure (a
  ``device_put`` that raises) falls back to synchronous staging for
  that item — results are identical, only the overlap is
  lost.
- :class:`ChunkStager` — a background thread that pushes host chunks to
  the device IN ORDER while the consumer dispatches compute on earlier
  chunks: the existing background level-pull machinery
  (:func:`~lighthouse_tpu.ops.tree_cache.start_level_pull`) run in
  reverse.  The stager thread blocks on each transfer so the transfer
  time is paid OFF the critical path; the consumer only waits when it
  outruns the uploads.

Every stage boundary is instrumented through
:mod:`~lighthouse_tpu.common.metrics` (``<name>_host_prep_seconds``,
``<name>_h2d_seconds``, ``pipeline_h2d_wait_seconds``) and each
executor keeps a cumulative ``stats`` dict; under a JAX profiler session
:meth:`StagedExecutor.map` also opens the ``<name>.prep`` /
``<name>.stage`` / ``<name>.dispatch`` spans of
:mod:`~lighthouse_tpu.common.tracing`.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, List, Optional, Sequence

from ..common.device_ledger import LEDGER
from ..common.metrics import observe
from ..common.tracing import TRACER


def _put_arrays(host):
    """``jax.device_put`` over the ndarray leaves of an array / dict /
    tuple; non-array leaves (static ints like a K bucket) pass through."""
    import jax
    import numpy as np

    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x) if isinstance(x, np.ndarray) else x,  # device-io: staging
        host)


def _tree_nbytes(host) -> int:
    """Total ndarray bytes in a staged item (the H2D accounting the
    executors report into the device ledger)."""
    import jax
    import numpy as np

    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(host)
               if isinstance(leaf, np.ndarray))


def _default_stage(host):
    """Async H2D staging.  Returns immediately; the transfer completes
    in the background (callers must NOT block between stage and
    dispatch)."""
    return _put_arrays(host)


def _sync_stage(host):
    """Synchronous fallback staging: push and WAIT.  Used when the async
    path failed — correctness never depends on the overlap."""
    import jax
    out = _put_arrays(host)
    jax.block_until_ready(out)
    return out


class StagedExecutor:
    """Double-buffered ``prep → stage → dispatch`` pipeline.

    ``map(items, prep, dispatch)`` runs, for each item::

        host   = prep(item)       # host marshalling (numpy)
        staged = stage(host)      # async H2D (jax.device_put)
        out    = dispatch(staged) # async device dispatch

    and returns the list of ``dispatch`` results (device arrays /
    futures — the caller syncs once at the end).  Because ``dispatch``
    is asynchronous, ``prep`` of the NEXT item executes while the device
    is still computing the current one; that host/device overlap is the
    entire point.  References to ``host`` and ``staged`` are dropped as
    soon as the dispatch is issued, which is what makes buffer donation
    in the dispatched jit safe: nothing on the host can re-read a
    donated buffer.

    ``stage`` is pluggable for tests (inject transfer failures).  A
    failure raised by ``stage`` itself OR surfacing at dispatch time
    (async ``device_put`` defers transfer errors to consumption)
    re-stages that item synchronously and retries the dispatch once
    (``fallbacks`` counts both); errors that only surface at the
    caller's terminal host sync propagate — the caller owns that retry.
    """

    def __init__(self, name: str = "pipeline",
                 stage: Optional[Callable] = None,
                 subsystem: Optional[str] = "staging"):
        self.name = name
        self._stage = stage or _default_stage
        # Device-ledger attribution of the staged H2D bytes ("bls" for
        # the verify pipelines, "staging" for cold builds; None = the
        # caller accounts its own transfers).
        self.subsystem = subsystem
        self._spans = (f"{name}.prep", f"{name}.stage", f"{name}.dispatch")
        self.stats = {
            "items": 0,
            "fallbacks": 0,
            "host_prep_s": 0.0,     # total host marshalling time
            "overlap_prep_s": 0.0,  # marshalling done while device busy
            "wall_s": 0.0,
        }

    def map(self, items: Sequence[Any], prep: Callable[[Any], Any],
            dispatch: Callable[[Any], Any]) -> List[Any]:
        t_wall = time.perf_counter()
        out: List[Any] = []
        in_flight = False  # a dispatch has been issued and not synced
        sp_prep, sp_stage, sp_dispatch = self._spans
        for item in items:
            t0 = time.perf_counter()
            with TRACER.span(sp_prep):
                host = prep(item)
            dt = time.perf_counter() - t0
            observe(f"{self.name}_host_prep_seconds", dt)
            self.stats["host_prep_s"] += dt
            if in_flight:
                # this marshalling ran under an outstanding device
                # dispatch — the overlap the double buffering buys
                self.stats["overlap_prep_s"] += dt
            if self.subsystem is not None:
                LEDGER.note_transfer("h2d", _tree_nbytes(host),
                                     subsystem=self.subsystem)
            t0 = time.perf_counter()
            with TRACER.span(sp_stage):
                try:
                    staged = self._stage(host)
                except Exception:
                    self.stats["fallbacks"] += 1
                    staged = _sync_stage(host)
            observe(f"{self.name}_h2d_seconds",
                    time.perf_counter() - t0)
            try:
                with TRACER.span(sp_dispatch):
                    out.append(dispatch(staged))
            except Exception:
                # An async device_put defers transfer errors to the
                # point of consumption — they surface HERE, not in the
                # staging call above.  Retry once on synchronously
                # staged (transfer-verified) buffers; a second failure
                # is a genuine dispatch error and propagates.
                self.stats["fallbacks"] += 1
                staged = _sync_stage(host)
                out.append(dispatch(staged))
            in_flight = True
            self.stats["items"] += 1
            del host, staged  # donated buffers must never be re-read
        self.stats["wall_s"] += time.perf_counter() - t_wall
        return out

    def overlap_efficiency(self) -> Optional[float]:
        """Fraction of host marshalling hidden behind device compute
        (1.0 = everything after the first dispatch overlapped; None
        until something ran)."""
        total = self.stats["host_prep_s"]
        if not self.stats["items"] or total <= 0:
            return None
        return self.stats["overlap_prep_s"] / total


class ChunkStager:
    """Background H2D staging of an ordered chunk list.

    A non-daemon thread pushes ``host_chunks[i]`` to the device (and
    BLOCKS on the transfer — off the critical path), depositing device
    chunks into a bounded queue; iterating the stager yields them in
    order while the consumer's earlier-chunk dispatches are still
    computing.  The queue depth (default 2) is the double buffer: at
    most one chunk transfers ahead of the one being consumed, bounding
    device memory for staged-but-unconsumed input.

    A failed transfer is retried synchronously by the CONSUMER (the
    host chunk is retained until consumed), so a failed transfer
    degrades to the old serial push instead of failing the build.

    Stats: ``wait_s`` — time the consumer blocked waiting for a staged
    chunk (the only transfer time left on the critical path);
    ``transfer_s`` — total background transfer time (``transfer_s −
    wait_s`` is the push time the overlap hid).
    """

    def __init__(self, host_chunks: Sequence[Any],
                 stage: Optional[Callable] = None, depth: int = 2,
                 subsystem: Optional[str] = "staging"):
        self._chunks = list(host_chunks)
        self._stage = stage or _default_stage
        # Explicit attribution (the stager thread cannot see the
        # caller's thread-local ambient context); None = caller
        # accounted the push itself (the registry-mirror materialize).
        self.subsystem = subsystem
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._abort = threading.Event()
        self.wait_s = 0.0
        self.transfer_s = 0.0
        self.fallbacks = 0
        # Non-daemon like start_level_pull: a daemon thread inside a
        # jax transfer at interpreter shutdown aborts the process.
        self._thread = threading.Thread(target=self._run, daemon=False)
        self._thread.start()

    def _put(self, item) -> bool:
        """Bounded put that gives up when the consumer aborted (a
        consumer dying mid-iteration must not strand a non-daemon
        thread on a full queue)."""
        while not self._abort.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self) -> None:
        import jax
        for i, chunk in enumerate(self._chunks):
            if self._abort.is_set():
                return
            if self.subsystem is not None:
                LEDGER.note_transfer("h2d", _tree_nbytes(chunk),
                                     subsystem=self.subsystem)
            t0 = time.perf_counter()
            try:
                dev = self._stage(chunk)
                jax.block_until_ready(dev)
            except Exception as e:  # consumer re-stages synchronously
                if not self._put((i, e)):
                    return
                continue
            self.transfer_s += time.perf_counter() - t0
            if not self._put((i, dev)):
                return

    def __iter__(self):
        try:
            for i in range(len(self._chunks)):
                t0 = time.perf_counter()
                j, got = self._q.get()
                dt = time.perf_counter() - t0
                self.wait_s += dt
                observe("pipeline_h2d_wait_seconds", dt)
                assert j == i, "chunk stager out of order"
                if isinstance(got, Exception):
                    self.fallbacks += 1
                    got = _sync_stage(self._chunks[i])
                self._chunks[i] = None  # release the host copy
                yield got
        finally:
            self._abort.set()
            self._thread.join()

    def join(self) -> None:
        self._abort.set()
        self._thread.join()
