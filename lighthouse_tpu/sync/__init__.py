"""Chain-segment processing seam shared by the catch-up consumers
(range sync, checkpoint backfill continuation, any future batch
importer).

``process_chain_segment`` is the ONE entry point that decides between
the epoch-batched replay engine (:mod:`..state_transition.batch_replay`)
and the serial per-block import oracle, classifies failures into
retryable (bad peer / missing data) vs deterministic (bad BLOCK — the
chain itself is invalid, rotating peers cannot help), and commits
nothing unless the whole segment's verdict passes.

Mirrors the reference's ``beacon_chain::process_chain_segment`` /
``ChainSegmentResult`` split (``beacon_chain/src/chain_segment.rs``):
the caller (``network/range_sync.py``) maps OK → batch processed,
RETRY → rotate peer and re-download, FATAL → fail the whole syncing
chain immediately.
"""

from __future__ import annotations

import enum
import time
from dataclasses import dataclass
from typing import Optional

from ..beacon_chain.block_verification import ExecutedBlock
from ..beacon_chain.errors import (
    BlobsUnavailable,
    BlockError,
    BlockIsAlreadyKnown,
    IncorrectProposer,
    InvalidBlock,
    InvalidSignatures,
    ParentUnknown,
    ProposalSignatureInvalid,
    StateRootMismatch,
)
from ..common.metrics import REGISTRY
from ..common.tracing import TRACER
from ..state_transition.batch_replay import (
    EpochReplayer,
    WindowBlockInvalid,
    WindowRootMismatch,
    WindowSignaturesInvalid,
    batch_replay_enabled,
    note_serial_window,
)

__all__ = ["Outcome", "SegmentResult", "process_chain_segment"]

# Deterministic rejections: the BLOCK is bad under consensus rules, so
# any honest peer would serve the same bytes — retrying against a new
# peer burns attempts without changing the verdict.
_DETERMINISTIC = (InvalidBlock, InvalidSignatures, StateRootMismatch,
                  IncorrectProposer, ProposalSignatureInvalid)


_SETS = REGISTRY.counter(
    "chain_segment_sets_total",
    "signature sets of committed batched segments, by padded signer "
    "count K", labelnames=("k",))
_STAGE_S = REGISTRY.counter(
    "chain_segment_stage_seconds_total",
    "wall seconds of batched segment import, by stage",
    labelnames=("stage",))


class Outcome(enum.Enum):
    OK = "ok"          # segment fully imported
    RETRY = "retry"    # transient / peer-attributable — re-download
    FATAL = "fatal"    # deterministic bad block — fail the chain


@dataclass
class SegmentResult:
    outcome: Outcome
    imported: int = 0
    error: Optional[BaseException] = None
    # Set when a block's blobs are missing: the caller fetches sidecars
    # for THIS block and re-calls (already-imported blocks are skipped
    # on the retry).
    needs_blobs: Optional[object] = None
    batched: bool = False


def _serial_segment(chain, blocks) -> SegmentResult:
    """The per-block oracle: the exact pre-batching import loop, with
    deterministic rejections classified FATAL instead of burning peer
    retries."""
    imported = 0
    for b in blocks:
        try:
            chain.per_slot_task(int(b.message.slot))
            chain.process_block(b)
            imported += 1
        except BlockIsAlreadyKnown:
            continue
        except BlobsUnavailable as e:
            return SegmentResult(Outcome.RETRY, imported, error=e,
                                 needs_blobs=b)
        except _DETERMINISTIC as e:
            return SegmentResult(Outcome.FATAL, imported, error=e)
        except Exception as e:
            return SegmentResult(Outcome.RETRY, imported, error=e)
    note_serial_window()
    return SegmentResult(Outcome.OK, imported)


def _linked(pairs) -> bool:
    for (pr, prev), (_, nxt) in zip(pairs, pairs[1:]):
        if bytes(nxt.message.parent_root) != pr:
            return False
    return True


def process_chain_segment(chain, blocks) -> SegmentResult:
    """Import a slot-ascending run of blocks into ``chain``.

    Batched path (knob auto/on, window long enough, parent-linked):
    apply the whole window through :class:`EpochReplayer` on a copy of
    the parent state — its signature sets verified in chunks under the
    transition, known state roots, ONE boundary root — and only on a
    passing verdict commit every
    block through the chain's atomic import (fork choice, store batch,
    attester caches, head recompute).  A failed window commits NOTHING.
    Serial path otherwise (the differential oracle).

    The batched path runs under the span ``chain_segment`` (children
    ``chain_segment.{apply,boundary_root,sig_join,commit}``); a
    committed segment adds its stage seconds to
    ``chain_segment_stage_seconds_total{stage}`` and its signature sets
    to ``chain_segment_sets_total{k}``."""
    blocks = list(blocks)
    with TRACER.span("chain_segment", cat="sync",
                     blocks=len(blocks)) as sp:
        return _process_segment(chain, blocks, sp)


def _process_segment(chain, blocks, sp) -> SegmentResult:
    if not blocks:
        return SegmentResult(Outcome.OK, 0)

    # Drop already-known blocks (overlapping batch boundaries re-serve
    # the anchor block) — roots are needed for import anyway.
    fresh = []
    for b in blocks:
        root = bytes(b.message.tree_hash_root())
        if not chain.fork_choice.contains_block(root):
            fresh.append((root, b))
    if not fresh:
        return SegmentResult(Outcome.OK, 0)

    if not (batch_replay_enabled(len(fresh)) and _linked(fresh)):
        return _serial_segment(chain, [b for _, b in fresh])

    parent_root = bytes(fresh[0][1].message.parent_root)
    if not chain.fork_choice.contains_block(parent_root):
        return SegmentResult(
            Outcome.RETRY, 0,
            error=ParentUnknown(
                f"segment parent {parent_root.hex()[:16]} unknown"))

    # Availability gate BEFORE any state work: a missing sidecar aborts
    # the window cheaply and names the block to fetch.
    for root, b in fresh:
        try:
            chain.data_availability.check_availability(b, root)
        except BlobsUnavailable as e:
            return SegmentResult(Outcome.RETRY, 0, error=e, needs_blobs=b)

    try:
        # Own copy: the replayer mutates it, and the store/snapshot
        # caches may hand back a shared object.
        pre_state = chain.state_at_block_root(parent_root).copy()
    except Exception as e:
        return SegmentResult(Outcome.RETRY, 0, error=e)

    snapshots: list = []
    rep = EpochReplayer(pre_state, chain.preset, chain.spec, chain.T,
                        verify_signatures=True,
                        pubkey_cache=chain.pubkey_cache,
                        span_prefix="chain_segment")
    rep.post_block_hook = lambda state, signed: snapshots.append(
        state.copy())
    try:
        rep.apply_window([b for _, b in fresh])
    except WindowSignaturesInvalid as e:
        return SegmentResult(Outcome.FATAL, 0,
                             error=InvalidSignatures(str(e)), batched=True)
    except WindowRootMismatch as e:
        return SegmentResult(Outcome.FATAL, 0,
                             error=StateRootMismatch(str(e)), batched=True)
    except WindowBlockInvalid as e:
        return SegmentResult(Outcome.FATAL, 0,
                             error=InvalidBlock(str(e)), batched=True)
    except BlockError as e:
        out = Outcome.FATAL if isinstance(e, _DETERMINISTIC) \
            else Outcome.RETRY
        return SegmentResult(out, 0, error=e, batched=True)
    except Exception as e:
        return SegmentResult(Outcome.RETRY, 0, error=e, batched=True)

    sp.set(sets=sum(rep.sets_by_k.values()))
    timings = rep.timings
    # Window verdict passed — commit every block through the atomic
    # import path (store batch + fork choice + caches + head).
    t0 = time.perf_counter()
    with TRACER.span("chain_segment.commit", cat="sync"):
        res = _commit(chain, fresh, snapshots)
    if res.outcome is Outcome.OK:
        for stage, key in (("apply", "apply_ms"), ("collect", "collect_ms"),
                           ("boundary_root", "root_ms"),
                           ("sig_join", "verify_ms")):
            _STAGE_S.labels(stage=stage).inc(timings.get(key, 0.0) / 1e3)
        _STAGE_S.labels(stage="commit").inc(time.perf_counter() - t0)
        for k, n in rep.sets_by_k.items():
            _SETS.labels(k=k).inc(n)
    return res


def _commit(chain, fresh, snapshots) -> SegmentResult:
    """Import the window's blocks in order.  The head is recomputed once,
    for the last block (and after a failure, for the blocks already in)."""
    imported = 0
    last = len(fresh) - 1
    try:
        for i, ((root, b), state) in enumerate(zip(fresh, snapshots)):
            slot = int(b.message.slot)
            chain.per_slot_task(slot, advance_head=False)
            try:
                chain.observed_block_producers.observe(
                    slot, int(b.message.proposer_index), root)
            except Exception:
                pass  # dedup bookkeeping must not fail a verified window
            ex = ExecutedBlock(signed_block=b, block_root=root,
                               post_state=state)
            try:
                if i == last:
                    # The head at the last block's parent, so its
                    # light-client updates see the head they extend.
                    chain.recompute_head()
                chain._import_block(ex, is_timely=False,
                                    light_client=i == last,
                                    recompute_head=False)
            except BlockIsAlreadyKnown:
                continue
            except _DETERMINISTIC as e:
                return SegmentResult(Outcome.FATAL, imported, error=e,
                                     batched=True)
            except Exception as e:
                return SegmentResult(Outcome.RETRY, imported, error=e,
                                     batched=True)
            imported += 1
    finally:
        chain.recompute_head()
    return SegmentResult(Outcome.OK, imported, batched=True)
