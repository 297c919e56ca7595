"""The single typed accessor layer for every ``LIGHTHOUSE_TPU_*`` knob.

Before this module the tree had four truthiness dialects for its ~23
environment knobs — bare-truthy (``os.environ.get(name)``), ``!= "0"``,
``== "1"`` and ``not in ("0", "false", "")`` — which is how
``LIGHTHOUSE_TPU_NO_NATIVE=0`` came to *disable* the native backend.
Every knob is now declared ONCE in :data:`KNOBS` (name, type, default,
doc) and read ONLY through the typed accessors here:

- ``knob_bool``    — one truthiness convention: true ∈ {1, true, yes,
  on}, false ∈ {0, false, no, off}; empty means UNSET (the ``VAR=``
  shell idiom → the default); anything else is a :class:`KnobError`.
- ``knob_tribool`` — three-state for auto-detected features: unset /
  ``auto`` / ``""`` → None (probe the backend), else the bool sets.
- ``knob_int`` / ``knob_float`` — parsed with an actionable error on
  malformed values and clamped to the registry's [min, max] range.
- ``knob_str`` / ``knob_choice`` — the latter validated against the
  registry's choice set.

The ``knob-registry`` checker (:mod:`lighthouse_tpu.analysis`) enforces
that no code outside this module reads ``LIGHTHOUSE_TPU_*`` names from
``os.environ``, and that every literal knob name appearing anywhere in
the tree is declared here — a typo'd knob is a lint failure, not a
silently-ignored setting.  The README knob table is generated from this
registry (``scripts/lint.py --fix-readme``).

This module must stay import-cheap and dependency-free (stdlib only):
it is imported by ``common.tracing`` and the crypto hot paths.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Union


class KnobError(ValueError):
    """A malformed or undeclared knob.  Subclasses ``ValueError`` so
    call sites that historically raised/caught ValueError keep
    working."""


_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")
# An EMPTY value means "unset" (the `VAR= cmd` shell idiom), never
# false: knob_bool falls back to the default, knob_tribool to auto.


@dataclass(frozen=True)
class Knob:
    """One declared environment knob."""
    name: str
    type: str               # bool | tribool | int | float | str | choice
    default: object         # the REAL default the accessors return
    doc: str                # one line, rendered in the README table
    choices: Tuple[str, ...] = ()
    min_value: Optional[float] = None
    max_value: Optional[float] = None
    display_default: Optional[str] = None  # README rendering override
    #   (machine-dependent or multi-site defaults declare their
    #   human-readable form HERE, next to the knob — not in the
    #   renderer)


KNOBS: Dict[str, Knob] = {}


def _declare(name: str, type: str, default: object, doc: str,
             choices: Tuple[str, ...] = (),
             min_value: Optional[float] = None,
             max_value: Optional[float] = None,
             display_default: Optional[str] = None) -> None:
    KNOBS[name] = Knob(name, type, default, doc, choices,
                       min_value, max_value, display_default)


# ---------------------------------------------------------------------------
# The registry.  Every LIGHTHOUSE_TPU_* knob in the tree, plus the
# compile-cache directory.  Keep docs to one line — they render as the
# README knob table.
# ---------------------------------------------------------------------------

# -- crypto / BLS hot path --
_declare("LIGHTHOUSE_TPU_NO_NATIVE", "bool", False,
         "Disable the native C++ BLS library; verify via device/python "
         "fallbacks.")
_declare("LIGHTHOUSE_TPU_MXU", "tribool", "auto",
         "Route band products through the MXU matmul formulation "
         "(auto: on iff the backend is a real TPU).")
_declare("LIGHTHOUSE_TPU_PIPELINE_SETS", "int", 1024,
         "Sub-batch size of the staged BLS executor; 0 disables "
         "pipelining.", min_value=0)
_declare("LIGHTHOUSE_TPU_SHARED_MIN", "int", 8,
         "Batch size from which the collapsed shared-key verify path "
         "wins over the general path.", min_value=1)
_declare("LIGHTHOUSE_TPU_HOST_FASTPATH_MAX", "int", 4,
         "Batches up to this many sets verify on the host native "
         "pairing; 0 keeps everything on-device.", min_value=0)

# -- state transition --
_declare("LIGHTHOUSE_TPU_OVERLAP_BLOCK_SIGS", "bool", True,
         "Overlapped block import: dispatch the block's signature batch "
         "asynchronously before the participation/rewards phase (0 = "
         "trailing synchronous verify, the oracle).")
_declare("LIGHTHOUSE_TPU_BLOCK_SIG_SHARD", "tribool", "auto",
         "Route block signature batches through the mesh-sharded BLS "
         "path (auto: on iff the TPU backend runs on >1 device).")
_declare("LIGHTHOUSE_TPU_BATCHED_ATTS", "bool", True,
         "Columnar batched attestation processing (0 = scalar spec "
         "oracle).")
_declare("LIGHTHOUSE_TPU_SINGLE_PASS_EPOCH", "bool", True,
         "Fused single-pass epoch transition (0 = stepwise oracle).")
_declare("LIGHTHOUSE_TPU_EPOCH_DEVICE", "bool", False,
         "Route the fused epoch rewards/inactivity sweep to the "
         "device.")
_declare("LIGHTHOUSE_TPU_DEVICE_STATE", "bool", True,
         "Device-resident BeaconState: HBM is the hashing source of "
         "truth (0 = host incremental oracle).")
_declare("LIGHTHOUSE_TPU_BATCH_REPLAY", "tribool", "auto",
         "Epoch-batched replay for range sync / recovery / backfill: "
         "one window-wide signature batch, known state roots, one "
         "boundary root (auto: batch windows of >= 4 blocks; 0 = "
         "serial BlockReplayer oracle).")

# -- block production / op pool --
_declare("LIGHTHOUSE_TPU_DEVICE_PACK", "bool", True,
         "Fixed-shape device greedy-pack for attestation max-cover "
         "(0 = host CELF oracle).")
_declare("LIGHTHOUSE_TPU_PACK_JIT", "tribool", "auto",
         "Force the jitted pack engine on/off (auto: jit iff the "
         "backend is a real TPU; numpy rounds engine otherwise).")
_declare("LIGHTHOUSE_TPU_SPECULATIVE_PRODUCE", "bool", True,
         "Pre-advance the next slot's state on a COW share during the "
         "slot tail; production adopts it iff the head is unchanged "
         "(0 = advance serially at production time).")

# -- fork choice --
_declare("LIGHTHOUSE_TPU_DEVICE_FORKCHOICE", "bool", True,
         "Columnar device proto-array (0 = host walk oracle).")
_declare("LIGHTHOUSE_TPU_FORKCHOICE_JIT", "tribool", "auto",
         "Force the jitted fork-choice engine on/off (auto: jit iff "
         "the backend is a real TPU).")
_declare("LIGHTHOUSE_TPU_FORKCHOICE_JIT_MAX_DEPTH", "int", 512,
         "Tree depth past which the jit engine's per-level loop "
         "yields to the host walk.", min_value=1)

# -- merkle / device residency --
_declare("LIGHTHOUSE_TPU_MESH_DEVICES", "int", 0,
         "Axis size of the process-wide named mesh every device "
         "subsystem places residency on (parallel/mesh). 0 = auto: "
         "all local devices on a real TPU backend, 1 otherwise; N "
         "clamps to the local device count. 1 degenerates every "
         "sharded column/program to the single-device spelling.",
         min_value=0, display_default="0 (auto)")
_declare("LIGHTHOUSE_TPU_PUSH_CHUNK_ROWS", "int", 1 << 18,
         "H2D streaming chunk rows for big column pushes (leaf builds "
         "default 2^18, registry builds 2^17); <= 0 disables "
         "chunking.", display_default="2^18 / 2^17")

# -- KZG / Deneb --
_declare("LIGHTHOUSE_TPU_KZG_DEVICE", "tribool", "auto",
         "Force device KZG verification on/off (auto: device iff the "
         "backend is a real TPU).")

# -- store --
_declare("LIGHTHOUSE_TPU_STORE_SYNC", "choice", "normal",
         "SQLite PRAGMA synchronous level for the on-disk store.",
         choices=("off", "normal", "full", "extra"))

# -- streaming verification --
_declare("LIGHTHOUSE_TPU_RESILIENT", "bool", True,
         "Wrap the global BLS backend in the resilience envelope "
         "(deadline/retry/breaker/host fallback).")
_declare("LIGHTHOUSE_TPU_STREAM_SLO_MS", "float", 250.0,
         "Streaming verification per-message latency SLO driving "
         "adaptive micro-batching.", min_value=1.0)
_declare("LIGHTHOUSE_TPU_STREAM_MAX_BATCH", "int", 256,
         "Streaming verification bucket dispatch cap.", min_value=1)
_declare("LIGHTHOUSE_TPU_VERIFY_DEADLINE_MS", "float", 8000.0,
         "Device dispatch watchdog deadline; <= 0 disables the "
         "watchdog entirely.")
_declare("LIGHTHOUSE_TPU_BREAKER_N", "int", 5,
         "Consecutive device faults that trip the circuit breaker to "
         "host fallback.", min_value=1)

# -- proof serving --
_declare("LIGHTHOUSE_TPU_PROOF_DEVICE", "bool", True,
         "Serve Merkle proofs by device gather from the resident field "
         "tree (0 = host-walk oracle path).")
_declare("LIGHTHOUSE_TPU_PROOF_WINDOW_MS", "float", 2.0,
         "Proof-server micro-batching window: concurrent requests "
         "arriving within it coalesce into one device gather.",
         min_value=0.0)
_declare("LIGHTHOUSE_TPU_PROOF_MAX_BATCH", "int", 1024,
         "Distinct gindices that dispatch a proof batch early, before "
         "the window closes.", min_value=1)

# -- observability --
_declare("LIGHTHOUSE_TPU_TRACE", "bool", False,
         "Enable slot-scope tracing at import.")
_declare("LIGHTHOUSE_TPU_TRACE_RING", "int", 64,
         "Fully-assembled slot traces kept in the ring.", min_value=1)
_declare("LIGHTHOUSE_TPU_DEVICE_LEDGER", "bool", True,
         "Device ledger: per-subsystem HBM/transfer/compile accounting "
         "(0 freezes all counters — escape hatch only).")
_declare("LIGHTHOUSE_TPU_DEVICE_LEDGER_SLOTS", "int", 64,
         "Per-slot device-transfer delta entries kept in the ledger "
         "ring.", min_value=1)

# -- SLO engine / node health --
_declare("LIGHTHOUSE_TPU_SLO", "bool", True,
         "Evaluate the declarative SLO registry and publish node "
         "health (0 = engine constructed but never evaluated).")
_declare("LIGHTHOUSE_TPU_SLO_FAST_WINDOW_S", "float", 60.0,
         "Fast-burn rolling attainment window (SRE short window).",
         min_value=0.1)
_declare("LIGHTHOUSE_TPU_SLO_SLOW_WINDOW_S", "float", 360.0,
         "Slow-burn rolling attainment window (SRE long window).",
         min_value=0.1)
_declare("LIGHTHOUSE_TPU_SLO_BLOCK_IMPORT_MS", "float", 150.0,
         "block_import objective: p99 wall budget per block import.",
         min_value=1.0)
_declare("LIGHTHOUSE_TPU_SLO_SHED_PCT", "float", 0.1,
         "shed_rate objective: max percent of submitted messages shed.",
         min_value=0.0)
_declare("LIGHTHOUSE_TPU_SLO_FALLBACK_PCT", "float", 1.0,
         "host_fallback_rate objective: max percent of dispatches "
         "served by the host oracle.", min_value=0.0)
_declare("LIGHTHOUSE_TPU_SLO_PROOF_SERVE_MS", "float", 50.0,
         "proof_serve objective: p99 wall budget per served proof "
         "request.", min_value=1.0)
_declare("LIGHTHOUSE_TPU_SLO_HYSTERESIS", "int", 2,
         "Consecutive evaluations a new health state must hold before "
         "the node transitions.", min_value=1)
# -- toolchain --
# JAX reads this variable itself; the registry entry lets the one
# compile-cache rule (common/compile_cache.default_dir) read it too.
_declare("JAX_COMPILATION_CACHE_DIR", "str", "",
         "Persistent XLA compilation cache directory (read by JAX "
         "itself); unset: <repo>/.jax_cache.", display_default="unset")


# ---------------------------------------------------------------------------
# Accessors
# ---------------------------------------------------------------------------

def _raw(name: str) -> Optional[str]:
    if name not in KNOBS:
        raise KnobError(
            f"undeclared knob {name!r}: every LIGHTHOUSE_TPU_* knob "
            f"must be declared in lighthouse_tpu/common/knobs.py")
    raw = os.environ.get(name)
    # Empty means UNSET for EVERY knob type (the `VAR= cmd` shell
    # idiom) — one rule, not a per-accessor quirk.
    if raw is not None and raw.strip() == "":
        return None
    return raw


def knob_bool(name: str, default: Optional[bool] = None) -> bool:
    """The ONE boolean convention.  Unset or empty → the registry
    default."""
    raw = _raw(name)
    if raw is None:
        return bool(KNOBS[name].default if default is None else default)
    v = raw.strip().lower()
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    raise KnobError(
        f"{name}={raw!r}: expected a boolean — one of "
        f"{_TRUE + _FALSE} (or unset for the default)")


def knob_tribool(name: str) -> Optional[bool]:
    """Three-state knob for auto-detected features: returns None when
    unset / ``auto`` / ``""`` (caller probes the backend), else the
    forced boolean."""
    raw = _raw(name)
    if raw is None:
        return None
    v = raw.strip().lower()
    if v == "auto":
        return None
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    raise KnobError(
        f"{name}={raw!r}: expected 'auto' or a boolean — one of "
        f"{_TRUE + _FALSE}")


def _clamp(name: str, value: float) -> float:
    k = KNOBS[name]
    clamped = value
    if k.min_value is not None and value < k.min_value:
        clamped = k.min_value
    if k.max_value is not None and value > k.max_value:
        clamped = k.max_value
    if clamped != value:
        # Clamping is never silent: the operator asked for a value the
        # registry range rejects — run with the boundary, but say so.
        import warnings
        warnings.warn(
            f"{name}={value} outside the registry range "
            f"[{k.min_value}, {k.max_value}] — clamped to {clamped}",
            stacklevel=3)
    return clamped


def knob_int(name: str, default: Optional[int] = None) -> int:
    """Integer knob, clamped to the registry range.  ``default``
    overrides the registry default for sites with a site-specific one
    (e.g. the two PUSH_CHUNK_ROWS builders)."""
    raw = _raw(name)
    if raw is None:
        return int(KNOBS[name].default if default is None else default)
    try:
        value = int(raw.strip())
    except ValueError:
        raise KnobError(
            f"{name}={raw!r}: expected an integer (default "
            f"{KNOBS[name].default if default is None else default}); "
            f"unset the variable to use the default") from None
    return int(_clamp(name, value))


def knob_float(name: str, default: Optional[float] = None) -> float:
    raw = _raw(name)
    if raw is None:
        return float(KNOBS[name].default if default is None else default)
    try:
        value = float(raw.strip())
    except ValueError:
        raise KnobError(
            f"{name}={raw!r}: expected a number (default "
            f"{KNOBS[name].default if default is None else default}); "
            f"unset the variable to use the default") from None
    return float(_clamp(name, value))


def knob_str(name: str, default: Optional[str] = None) -> str:
    raw = _raw(name)
    if raw is None:
        return str(KNOBS[name].default if default is None else default)
    return raw


def knob_choice(name: str, default: Optional[str] = None) -> str:
    """Validated against the registry's choice set (lower-cased) —
    including an explicitly passed ``default``, so a call-site typo
    cannot smuggle an out-of-set value past the contract."""
    k = KNOBS[name]
    raw = _raw(name)
    if raw is None:
        raw = str(k.default if default is None else default)
    v = raw.strip().lower()
    if v not in k.choices:
        raise KnobError(
            f"{name}={raw!r}: expected one of {sorted(k.choices)}")
    return v


# ---------------------------------------------------------------------------
# README table generation (consumed by scripts/lint.py and the
# readme-drift checker: generated table == committed README section).
# ---------------------------------------------------------------------------

def _default_repr(k: Knob) -> str:
    if k.display_default is not None:
        return k.display_default
    if k.type == "bool":
        return "on" if k.default else "off"
    return str(k.default)


def render_knob_table() -> str:
    """The README knob table, one row per registry entry."""
    rows = ["| Knob | Type | Default | Meaning |",
            "|---|---|---|---|"]
    for name in sorted(KNOBS):
        k = KNOBS[name]
        rows.append(f"| `{k.name}` | {k.type} | `{_default_repr(k)}` "
                    f"| {k.doc} |")
    return "\n".join(rows) + "\n"
