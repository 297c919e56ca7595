"""Persistent XLA compilation cache + explicit hot-path warmup.

The first BLS batch in a fresh process pays the full XLA/Mosaic compile
of the fused pairing pipeline.  Two pieces keep that off the slot path:

- :func:`enable` points JAX at the ONE persistent on-disk cache
  directory: ``JAX_COMPILATION_CACHE_DIR`` when the deployment sets it
  (JAX reads that variable itself; nothing here overrides it), otherwise
  the fixed ``<repo>/.jax_cache``.  A fixed path matters: the directory
  is part of how a later process finds the entries again.  Safe to call
  from any entry point and idempotent.
- :func:`warmup` pre-compiles the bucketed ``(sets, keys)`` shapes of
  the fused BLS pipeline via ``jit.lower(...).compile()`` — abstract
  shapes only, no device data — so a restarted node (or one warming in
  the background at boot) never pays the cold compile in the slot path:
  with the cache enabled the compiles land on disk, and the first real
  verify of each bucket is a cache hit.  On CPU this is a graceful
  no-op: the Pallas programs only lower on TPU, and the scanned-XLA
  twins take minutes per shape on one core — warming them would cost
  more than it saves.  A persistent cache skips the backend compile
  only: every process still traces and lowers each program it runs.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence, Tuple

_state = {"dir": None, "monitoring": False}


# ---------------------------------------------------------------------------
# Compile-cache observability.  JAX emits monitoring events on every
# cache-eligible compile ('/jax/compilation_cache/compile_requests_use_
# cache') and on every persistent-cache hit ('/jax/compilation_cache/
# cache_hits'); a request without a hit is a miss — which on this box
# costs MINUTES per pairing-scale program.  The listener feeds a labeled
# counter family (`compile_cache_events_total{event="request"|"hit"}`)
# and a scrape-time collector derives the miss count, so a cold-cache
# node is visible on /metrics instead of just "mysteriously slow".
# ---------------------------------------------------------------------------

_EVENT_MAP = {
    "/jax/compilation_cache/compile_requests_use_cache": "request",
    "/jax/compilation_cache/cache_hits": "hit",
}


def _on_jax_event(event: str, **_kw) -> None:
    label = _EVENT_MAP.get(event)
    if label is None:
        return
    from .metrics import REGISTRY
    REGISTRY.counter(
        "compile_cache_events_total",
        "persistent XLA compile-cache activity",
        labelnames=("event",)).labels(label).inc()


def _collect_cache_misses() -> None:
    from .metrics import REGISTRY
    fam = REGISTRY.counter(
        "compile_cache_events_total",
        "persistent XLA compile-cache activity",
        labelnames=("event",))
    requests = fam.labels("request").value
    hits = fam.labels("hit").value
    REGISTRY.gauge(
        "compile_cache_misses",
        "cache-eligible compiles not served from the persistent "
        "cache").set(max(requests - hits, 0.0))


def install_monitoring() -> bool:
    """Register the jax monitoring listener (idempotent; a jax build
    without the monitoring API degrades to counters that stay 0).

    Called from :func:`enable` — the entry points that turn the
    persistent cache on are exactly the processes whose hit/miss
    traffic matters — NOT at module import: this module must stay
    cheap to import (``default_dir`` readers shouldn't pay the
    multi-second jax import)."""
    if _state["monitoring"]:
        return True
    try:
        from jax import monitoring as _mon  # public front
    except Exception:
        try:
            from jax._src import monitoring as _mon  # older builds
        except Exception:
            return False
    try:
        _mon.register_event_listener(_on_jax_event)
    except Exception:
        return False
    from .metrics import REGISTRY
    REGISTRY.register_collector(_collect_cache_misses)
    # The device ledger taps the SAME event stream for per-subsystem
    # compile attribution — one listener each (the ledger's install is
    # idempotent, so the two never double-register).
    from .device_ledger import LEDGER
    LEDGER._maybe_install_listener()
    _state["monitoring"] = True
    return True


REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def default_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``
    — the one cache directory of every entry point (node CLI, tests,
    ``chip_smoke.py``, ``bench.py``, the scripts)."""
    from .knobs import knob_str
    return knob_str("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR


def enable(min_compile_time_secs: float = 2.0) -> Optional[str]:
    """Enable JAX's persistent compilation cache at :func:`default_dir`.

    Returns the cache directory actually configured, or None when the
    running JAX refuses the configuration."""
    import jax

    install_monitoring()  # hit/miss counters ride the cache lifecycle

    cache = os.path.abspath(default_dir())
    try:
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
    except Exception:
        return None
    try:
        # The cache object is lazily initialised ONCE per process; if a
        # compile already ran against another directory, the config
        # update alone is ignored — reset so the new dir takes effect.
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
    except Exception:
        pass  # private API drift: first-configured dir keeps winning
    _state["dir"] = cache
    return cache


def is_enabled() -> bool:
    return _state["dir"] is not None


def cache_dir() -> Optional[str]:
    return _state["dir"]


# The (sets, keys) buckets a mainnet node hits in the slot path: the
# 1024-set aggregate-attestation batch (16-key committees), the 256-set
# sync-committee shape (dedup collapses it to K=1), and the small
# head-of-slot batches.  The device programs are per 128-set chunk, so
# only the key bucket (next-pow2 signer count) selects a program.
DEFAULT_BUCKETS: Tuple[Tuple[int, int], ...] = (
    (256, 16), (256, 1), (8, 16), (8, 1),
)


def warmup(buckets: Sequence[Tuple[int, int]] = DEFAULT_BUCKETS,
           table_cols: int = 1 << 15) -> Dict[str, object]:
    """Pre-compile the BLS pipeline's per-chunk programs — the prepare
    program for each bucket's key count, and the shape-independent
    hash-to-curve, σ-fold, Miller-cell, product-fold and finalize
    programs once.

    Uses ``jit.lower(abstract shapes).compile()`` — no device inputs are
    materialised and nothing executes; with :func:`enable` active every
    compile is persisted, so the next process (or the next call in this
    one) hits the disk cache instead of XLA.  Returns a summary dict;
    ``{"skipped": "cpu"}`` off-TPU (see module docstring).
    """
    import jax

    if jax.default_backend() != "tpu":
        return {"skipped": "cpu", "compiled": []}

    import numpy as np

    from ..crypto import htc_kernel as HK
    from ..crypto import pairing_kernel as PK
    from ..crypto import tpu_backend as TB
    from ..ops.merkle import _next_pow2

    S = PK.PREP_S
    aval = jax.ShapeDtypeStruct
    u32, i32 = np.uint32, np.int32
    compiled = []
    for K in sorted({_next_pow2(max(1, int(keys))) for _s, keys in buckets}):
        TB._prepare_cell.lower(
            aval((64, table_cols), u32), aval((K * S,), i32),
            aval((1, K * S), i32), aval((1, S), u32), aval((1, S), u32),
            K=K).compile()
        compiled.append({"K": K})
    blk = (aval((64, S), u32), aval((128, S), u32), aval((1, S), i32))
    fq12 = aval((PK.FQ12_ROWS, S), u32)
    g2pt = aval((3, 2, PK.LIMBS), u32)
    part = aval((6 * PK.BLOCK_ROWS, S), u32)
    hash_g2, sigma, finalize = TB.kernel_programs()
    for fn, args in (
            (hash_g2, (aval((2 * HK.BLOCK_ROWS, 2 * S), u32),)),
            (sigma, (aval((128, S), u32), aval((1, S), i32),
                     aval((1, S), u32), aval((1, S), u32))),
            (TB._sigma_point, (part,)),
            (TB._sigma_add, (g2pt, part)),
            (TB._sigma_block, (g2pt, aval((), np.bool_))),
            (TB._shared_lanes, (g2pt, g2pt)),
            (TB._cell_bad, (aval((1, S), i32), aval((1, S), i32),
                            aval((), np.bool_))),
            (TB._combine_verdict, (aval((1, 1), i32), aval((), np.bool_))),
            (TB._miller_cell, blk + blk),
            (TB._fold_pair, (fq12, fq12)),
            (finalize, (fq12,))):
        fn.lower(*args).compile()
    return {"cache_dir": cache_dir(), "compiled": compiled}
