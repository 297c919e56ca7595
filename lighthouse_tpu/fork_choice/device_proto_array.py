"""Device proto-array fork choice — batched score-delta application.

:class:`DeviceProtoArrayForkChoice` is API-compatible with the host
:class:`~.proto_array.ProtoArrayForkChoice` (the bit-for-bit oracle behind
``LIGHTHOUSE_TPU_DEVICE_FORKCHOICE=0``) but holds the tree as
:class:`~.columnar.NodeColumns` and votes in a
:class:`~.columnar.VoteBuffer`, so a whole slot's attestations apply as
ONE batched pass instead of a per-validator python loop.

Two engines share the columnar state:

- ``numpy`` — the vectorized host passes in :mod:`.columnar` (default off
  accelerator; this is what the whole test/sim fleet runs on CPU);
- ``jit`` — ``compute_deltas`` + ``apply_score_changes`` fused into one
  jitted XLA program per (node-bucket, validator-bucket) shape: a
  segment-sum of vote deltas over the registry followed by a bottom-up
  weight/best-child propagation driven by the precomputed level schedule
  (a ``fori_loop`` over tree depth — dynamic trip count, so depth never
  recompiles).  Validator-sized state (current/next votes, persisted
  balances) stays device-resident between flushes alongside the PR 6
  resident registry columns: per flush the host pushes only the CHANGED
  vote scatters, the new justified balances, and n-node-sized masks, and
  pulls back three small node columns (weight/best-child/best-descendant)
  plus nothing else.  Like the epoch sweep, the kernel traces and runs
  inside a local ``jax.enable_x64(True)`` so uint64 balance
  arithmetic matches numpy bit-for-bit.

Engine selection: ``LIGHTHOUSE_TPU_FORKCHOICE_JIT=1`` forces the jitted
engine, ``=0`` forces numpy, unset auto-selects jit only when a TPU is
attached (CPU jit is correctness-equal but compile-bound at test shapes).
All jitted programs here are merkle-scale — seconds to compile on CPU —
so the differential suite is quick-tier safe.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..ops.merkle import _next_pow2
from .columnar import (
    NodeColumns,
    VoteBuffer,
    apply_scores,
    compute_deltas_host,
)
from .proto_array import (
    EXEC_INVALID,
    EXEC_IRRELEVANT,
    EXEC_VALID,
    ProtoArrayError,
    ProtoArrayForkChoice,
    VoteTracker,
    ZERO_ROOT,
)

_ENGINE_AUTO: Optional[str] = None


def device_fork_choice_enabled() -> bool:
    """The oracle knob: ``LIGHTHOUSE_TPU_DEVICE_FORKCHOICE=0`` routes
    :class:`~.fork_choice.ForkChoice` through the host proto-array."""
    from ..common.knobs import knob_bool
    return knob_bool("LIGHTHOUSE_TPU_DEVICE_FORKCHOICE")


def _resolve_engine(engine: Optional[str]) -> str:
    if engine in ("numpy", "jit"):
        return engine
    from ..common.knobs import knob_tribool
    forced = knob_tribool("LIGHTHOUSE_TPU_FORKCHOICE_JIT")
    if forced is not None:
        return "jit" if forced else "numpy"
    global _ENGINE_AUTO
    if _ENGINE_AUTO is None:
        try:
            import jax
            _ENGINE_AUTO = ("jit" if jax.default_backend() == "tpu"
                            else "numpy")
        except Exception:
            _ENGINE_AUTO = "numpy"
    return _ENGINE_AUTO


def _bucket(k: int, floor: int = 16) -> int:
    return max(_next_pow2(max(int(k), 1)), floor)


# ---------------------------------------------------------------------------
# Fused jitted kernel: vote-delta segment sum + level-scheduled propagation.
# One compiled program per (n_pad, nv_pad); cached here, persisted by the
# common compile cache like every other kernel.
# ---------------------------------------------------------------------------

_KERNELS: dict = {}
_MESH_KERNELS: dict = {}
_SCATTERS: dict = {}


def _kernel_parts(n_pad: int):
    """The fused round split at its one mesh-shardable seam: the vote
    segment-sum runs per validator shard (``local_delta``; partials are
    exact under an integer ``psum``) and everything node-indexed —
    proposer boosts included, so a ``psum`` over ``ndev`` shards never
    multiplies them — stays in the replicated ``propagate`` body.  The
    1-device fused kernel composes the same two parts back-to-back, so
    both engines share one arithmetic definition and stay bit-identical.
    """
    import jax
    import jax.numpy as jnp

    i64 = jnp.int64
    dummy = n_pad  # scatter sink for "no parent" / "no node"

    def local_delta(cur, nxt, old_b, new_b):
        # -- vote deltas: two segment scatter-adds over the registry -----
        delta = jnp.zeros(n_pad + 1, i64)
        ci = jnp.where(cur >= 0, cur, dummy)
        delta = delta.at[ci].add(jnp.where(cur >= 0, -old_b, i64(0)))
        ni = jnp.where(nxt >= 0, nxt, dummy)
        delta = delta.at[ni].add(jnp.where(nxt >= 0, new_b, i64(0)))
        return delta

    def propagate(delta, parent, depth, invalid, zroot, viable, rank,
                  weight, bc_in, bd_in, pb_idx, pb_score, b_idx, b_score,
                  max_depth):
        # proposer boost: remove last slot's, add this slot's
        delta = delta.at[jnp.where(pb_idx >= 0, pb_idx, dummy)].add(
            jnp.where(pb_idx >= 0, -pb_score, i64(0)))
        delta = delta.at[jnp.where(b_idx >= 0, b_idx, dummy)].add(
            jnp.where(b_idx >= 0, b_score, i64(0)))
        delta = delta.at[dummy].set(0)

        pidx = jnp.where(parent >= 0, parent, dummy)
        iota = jnp.arange(n_pad, dtype=jnp.int32)

        def body(k, carry):
            weight, delta, bc, bd = carry
            lvl = max_depth - k
            at = depth == lvl  # pad rows carry depth −1: never selected
            d_eff = jnp.where(
                at, jnp.where(zroot, i64(0),
                              jnp.where(invalid, -weight, delta[:n_pad])),
                i64(0))
            weight = jnp.where(
                at, jnp.where(invalid, i64(0),
                              jnp.where(zroot, weight, weight + d_eff)),
                weight)
            delta = delta.at[pidx].add(jnp.where(at, d_eff, i64(0)))
            # leads-to-viable: best descendant viable OR node itself
            bdc = jnp.maximum(bd, 0)
            lead = viable | ((bd >= 0) & viable[bdc])
            child = at & (parent >= 0)
            elig = child & lead

            def seg_argmax(mask):
                wmax = jnp.full(n_pad + 1, -1, i64).at[pidx].max(
                    jnp.where(mask, weight, i64(-1)))
                m2 = mask & (weight == wmax[pidx])
                rmax = jnp.full(n_pad + 1, -1, i64).at[pidx].max(
                    jnp.where(m2, rank, i64(-1)))
                m3 = m2 & (rank == rmax[pidx])
                return jnp.full(n_pad + 1, -1, jnp.int32).at[pidx].max(
                    jnp.where(m3, iota, jnp.int32(-1)))[:n_pad]

            # The host's incremental descending-index sweep, seeded with
            # LAST round's best child, in closed form (see the numpy
            # engine in columnar.apply_scores_host for the derivation).
            win_lead = seg_argmax(elig)
            win_all = seg_argmax(child)
            prev_at_parent = jnp.where(parent >= 0,
                                       bc[jnp.maximum(parent, 0)],
                                       jnp.int32(-1))
            win_ge = seg_argmax(child & (iota >= prev_at_parent))
            has = jnp.zeros(n_pad + 1, bool).at[pidx].max(child)[:n_pad]
            F = jnp.where(win_lead >= 0, win_lead,
                          jnp.where(bc == -1, jnp.int32(-1),
                                    jnp.where(win_ge == bc,
                                              jnp.int32(-1), win_all)))
            Fc = jnp.maximum(F, 0)
            fbd = jnp.where(F >= 0,
                            jnp.where(bd[Fc] >= 0, bd[Fc], F),
                            jnp.int32(-1))
            bc = jnp.where(has, F, bc)
            bd = jnp.where(has, fbd, bd)
            return weight, delta, bc, bd

        weight, delta, bc, bd = jax.lax.fori_loop(
            0, max_depth + 1, body, (weight, delta, bc_in, bd_in))
        neg = jnp.any(weight < 0)
        return weight, bc, bd, neg

    return local_delta, propagate


def _get_kernel(n_pad: int, nv_pad: int):
    key = (n_pad, nv_pad)
    fn = _KERNELS.get(key)
    if fn is not None:
        return fn
    import jax

    local_delta, propagate = _kernel_parts(n_pad)

    def fused(cur, nxt, old_b, new_b, *node_args):
        return propagate(local_delta(cur, nxt, old_b, new_b), *node_args)

    jitted = jax.jit(fused)

    def call(*args):
        with jax.enable_x64(True):
            return jitted(*args)

    _KERNELS[key] = call
    return call


def _get_mesh_kernel(n_pad: int, nv_pad: int):
    """The fused round as a mesh program: vote/balance columns arrive
    sharded over the validator (``batch``) axis, each shard scatter-adds
    its own delta partial, one ``psum`` folds the ``(n_pad + 1,)`` int64
    partials — exact, adds are associative — and the node-level
    propagation runs replicated.  Selected only when ``nv_pad`` divides
    the mesh; caller falls back to :func:`_get_kernel` otherwise."""
    from ..parallel import mesh as pmesh
    mesh = pmesh.get_mesh()
    key = (n_pad, nv_pad, mesh)
    fn = _MESH_KERNELS.get(key)
    if fn is not None:
        return fn
    import jax
    from jax.sharding import PartitionSpec as P

    local_delta, propagate = _kernel_parts(n_pad)

    def mesh_fused(cur, nxt, old_b, new_b, *node_args):
        delta = local_delta(cur, nxt, old_b, new_b)
        delta = jax.lax.psum(delta, pmesh.BATCH_AXIS)
        return propagate(delta, *node_args)

    n_node_args = 14  # parent..max_depth, all replicated
    prog = pmesh.mesh_program(
        mesh_fused, mesh=mesh,
        in_specs=(P(pmesh.BATCH_AXIS),) * 4 + (P(),) * n_node_args,
        out_specs=(P(), P(), P(), P()))

    def call(*args):
        with jax.enable_x64(True):
            return prog(*args)

    _MESH_KERNELS[key] = call
    return call


def _get_scatter(nv_pad: int, k_pad: int):
    key = (nv_pad, k_pad)
    fn = _SCATTERS.get(key)
    if fn is not None:
        return fn
    import jax

    def scatter(nxt, idx, val):
        return nxt.at[idx].set(val)

    jitted = jax.jit(scatter, donate_argnums=())

    def call(*args):
        with jax.enable_x64(True):
            return jitted(*args)

    _SCATTERS[key] = call
    return call


class _DeviceMirror:
    """HBM twins of the validator-sized vote/balance columns and the
    node-topology columns, with push/pull byte accounting routed through
    :mod:`~lighthouse_tpu.ops.device_tree` residency stats."""

    def __init__(self, votes: VoteBuffer, old_balances: np.ndarray,
                 n_nodes: int):
        import jax
        from ..common.device_ledger import LEDGER
        from ..parallel.mesh import mesh_put

        self.nv_pad = _bucket(max(len(votes), 1))
        self.n_pad = _bucket(max(n_nodes, 1))
        with jax.enable_x64(True):
            cur = np.full(self.nv_pad, -1, np.int32)
            cur[:len(votes)] = votes.current
            nxt = np.full(self.nv_pad, -1, np.int32)
            nxt[:len(votes)] = votes.next
            ob = np.zeros(self.nv_pad, np.int64)
            m = min(old_balances.shape[0], len(votes))
            ob[:m] = old_balances[:m].astype(np.int64)
            self.cur = mesh_put("fc_votes", cur)
            self.nxt = mesh_put("fc_votes", nxt)
            self.old_b = mesh_put("fc_votes", ob)
        self.topo_version = -1  # force first topology push
        self.parent = None
        self.depth = None
        self.weight = None
        self._res = LEDGER.track(self, "fork_choice", 0)
        self._note_residency()

    def _note_residency(self) -> None:
        # Dedupe by buffer identity: after a jitted apply `cur` IS
        # `nxt` (the device-side vote move aliases them) — summing both
        # would overstate residency by a full vote column.
        cols = {id(c): c for c in (self.cur, self.nxt, self.old_b,
                                   self.parent, self.depth, self.weight)
                if c is not None}
        self._res.set(sum(int(c.nbytes) for c in cols.values()))

    def fits(self, votes: VoteBuffer, n_nodes: int) -> bool:
        return len(votes) <= self.nv_pad and n_nodes <= self.n_pad

    def fits_pending(self, votes: VoteBuffer, n_nodes: int) -> bool:
        """Like :meth:`fits`, but sized for the POST-flush validator
        count: a buffered vote beyond the bucket would otherwise drop
        the mirror between the fit check and the kernel call."""
        pend = max((int(v.max()) + 1 for v in votes._buf_val
                    if v.shape[0]), default=0)
        return max(len(votes), pend) <= self.nv_pad \
            and n_nodes <= self.n_pad

    def scatter_votes(self, wv: np.ndarray, wn: np.ndarray) -> None:
        if wv.shape[0] == 0:
            return
        import jax
        from ..parallel.mesh import mesh_put
        k_pad = _bucket(wv.shape[0], floor=8)
        idx = np.empty(k_pad, np.int32)
        val = np.empty(k_pad, np.int32)
        idx[:wv.shape[0]] = wv
        idx[wv.shape[0]:] = wv[0]  # duplicate-set padding: idempotent
        val[:wn.shape[0]] = wn
        val[wn.shape[0]:] = wn[0]
        with jax.enable_x64(True):
            self.nxt = _get_scatter(self.nv_pad, k_pad)(
                self.nxt, mesh_put("fc_dirty", idx),
                mesh_put("fc_dirty", val))
        self._note_residency()  # cur/nxt diverge into two buffers here

    def push_topology(self, cols: NodeColumns, version: int) -> None:
        if self.topo_version == version and self.parent is not None:
            return
        import jax
        from ..parallel.mesh import mesh_put
        n = cols.n
        parent = np.full(self.n_pad, -1, np.int32)
        parent[:n] = cols.parent[:n]
        depth = np.full(self.n_pad, -1, np.int32)
        depth[:n] = cols.depth[:n]
        weight = np.zeros(self.n_pad, np.int64)
        weight[:n] = cols.weight[:n]
        with jax.enable_x64(True):
            self.parent = mesh_put("fc_topology", parent)
            self.depth = mesh_put("fc_topology", depth)
            self.weight = mesh_put("fc_topology", weight)
        self.topo_version = version
        self._note_residency()


class DeviceProtoArrayForkChoice:
    """Columnar twin of :class:`~.proto_array.ProtoArrayForkChoice`."""

    def __init__(self, prune_threshold: int = 256,
                 engine: Optional[str] = None,
                 jit_max_depth: Optional[int] = None):
        self.cols = NodeColumns()
        self.votes_store = VoteBuffer()
        self.old_balances = np.zeros(0, np.uint64)
        self.justified_checkpoint: Tuple[int, bytes] = (0, ZERO_ROOT)
        self.finalized_checkpoint: Tuple[int, bytes] = (0, ZERO_ROOT)
        self.prev_boost_root: bytes = ZERO_ROOT
        self.prev_boost_score: int = 0
        self.prune_threshold = prune_threshold
        self.engine = _resolve_engine(engine)
        # The fused kernel's fori_loop serializes one step per tree
        # level; past this depth (chain-shaped trees, long non-finality)
        # the round runs on host instead — mirrors stay in sync.
        from ..common.knobs import knob_int
        self.jit_max_depth = jit_max_depth if jit_max_depth is not None \
            else knob_int("LIGHTHOUSE_TPU_FORKCHOICE_JIT_MAX_DEPTH")
        self._mirror: Optional[_DeviceMirror] = None
        self._topo_version = 0
        self._pending_new_b: Optional[np.ndarray] = None

    # -- host-API parity surface --------------------------------------------

    @property
    def indices(self) -> Dict[bytes, int]:
        return self.cols.indices

    @property
    def equivocating(self) -> set:
        return self.votes_store.equivocating

    @property
    def votes(self) -> VoteTracker:
        """Host-shaped view of the latest-message columns (pending buffered
        votes are merged first so the view is observation-equivalent)."""
        self._flush_votes()
        v = self.votes_store
        return VoteTracker(v.current, v.next, v.next_epoch)

    @property
    def nodes(self) -> List:
        return self.cols.export_nodes()

    def slot_of(self, root: bytes) -> int:
        idx = self.cols.indices.get(bytes(root))
        if idx is None:
            raise ProtoArrayError("unknown block")
        return int(self.cols.slot[idx])

    # -- block tree ----------------------------------------------------------

    def on_block(self, *, slot: int, root: bytes, parent_root: bytes,
                 state_root: bytes, justified_epoch: int,
                 justified_root: bytes, finalized_epoch: int,
                 finalized_root: bytes,
                 execution_status: int = EXEC_IRRELEVANT,
                 execution_block_hash: Optional[bytes] = None) -> None:
        if bytes(root) in self.cols.indices:
            return
        parent = self.cols.indices.get(bytes(parent_root), -1)
        self.cols.append(
            slot=slot, root=root, parent=parent, state_root=state_root,
            justified_epoch=justified_epoch, justified_root=justified_root,
            finalized_epoch=finalized_epoch, finalized_root=finalized_root,
            execution_status=execution_status,
            execution_block_hash=execution_block_hash)
        self._topo_version += 1

    # -- votes ---------------------------------------------------------------

    def process_attestation(self, validator_index: int, block_root: bytes,
                            target_epoch: int) -> None:
        if validator_index in self.votes_store.equivocating:
            return
        idx = self.cols.indices.get(bytes(block_root))
        if idx is None:
            raise ProtoArrayError("attestation for unknown block")
        self.votes_store.push_votes(
            np.asarray([validator_index], np.int64), idx, target_epoch)

    def process_attestation_batch(self, batch) -> None:
        """Whole-slot ingest: ``batch`` is ``[(indices, block_root,
        target_epoch), …]``; each attestation's votes land in the buffer as
        one vectorized push (order preserved — the merge at flush is
        bit-equivalent to the host's sequential fold)."""
        for indices, block_root, target_epoch in batch:
            idx = self.cols.indices.get(bytes(block_root))
            if idx is None:
                # Host raises on the FIRST non-equivocating index; an
                # attestation whose voters all equivocate passes silently.
                if any(int(i) not in self.votes_store.equivocating
                       for i in np.asarray(indices, np.int64)):
                    raise ProtoArrayError("attestation for unknown block")
                continue
            self.votes_store.push_votes(
                np.asarray(indices, np.int64), idx, int(target_epoch))

    def process_equivocation(self, validator_index: int) -> None:
        # Zeroing happens in the host-computed balance column each flush;
        # a growth past the validator bucket rematerializes via fits().
        self.votes_store.push_equivocation(validator_index)

    def _flush_votes(self) -> None:
        wv, wn, _we = self.votes_store.flush()
        if self._mirror is not None and wv.shape[0]:
            if self._mirror.fits(self.votes_store, self.cols.n):
                self._mirror.scatter_votes(wv, wn)
            else:
                self._mirror = None

    # -- score changes -------------------------------------------------------

    def compute_deltas(self, new_balances: np.ndarray):
        """Flush the vote buffer and compute per-node deltas.  The numpy
        engine returns them; the jit engine defers the segment-sum into the
        fused apply program and returns an opaque marker."""
        if self.engine == "jit":
            if self._pending_new_b is not None and self._mirror is not None:
                # compute_deltas without an intervening apply: the host
                # still moves votes/balances — replicate the device move.
                import jax
                from ..parallel.mesh import mesh_put
                nb = np.zeros(self._mirror.nv_pad, np.int64)
                nb[:self._pending_new_b.shape[0]] = \
                    self._pending_new_b.astype(np.int64)
                with jax.enable_x64(True):
                    self._mirror.old_b = mesh_put("fc_votes", nb)
                    self._mirror.cur = self._mirror.nxt
                self._mirror._note_residency()
                self._pending_new_b = None
            if self.cols.max_depth() > self.jit_max_depth:
                # Chain-shaped tree: run this head round on host, but
                # keep the device vote/balance mirrors moving so a later
                # shallow round resumes without a rematerialize.
                return self._compute_deltas_host_round(new_balances)
            if self._mirror is None \
                    or not self._mirror.fits_pending(self.votes_store,
                                                     max(self.cols.n, 1)):
                # (Re)materialize BEFORE the flush so the device copy holds
                # the pre-move current votes the delta pass subtracts —
                # sized for the POST-flush validator count (a buffered
                # vote can cross the pow-2 bucket).
                self._materialize()
            self._flush_votes()
            if self._mirror is None:
                self._materialize()  # flush outgrew the bucket anyway
            nv = len(self.votes_store)
            new_b = np.zeros(nv, np.uint64)
            m = min(np.asarray(new_balances).shape[0], nv)
            new_b[:m] = np.asarray(new_balances, np.uint64)[:m]
            if self.votes_store.equivocating:
                eq = np.fromiter(self.votes_store.equivocating, np.int64,
                                 len(self.votes_store.equivocating))
                new_b[eq[eq < nv]] = 0
            self._pending_new_b = new_b
            # host-mirror move (the device move happens post-kernel)
            self.votes_store.current = self.votes_store.next.copy()
            self.old_balances = new_b.copy()
            return _DEVICE_DELTAS
        self._flush_votes()
        deltas, new_b = compute_deltas_host(
            self.votes_store, self.old_balances,
            np.asarray(new_balances, np.uint64), self.cols.n)
        self.old_balances = new_b.copy()
        return deltas

    def _materialize(self) -> None:
        # Grow-before-flush: the buffer may reference validators beyond the
        # current columns; flush grows them, so bucket on the post-flush
        # size without applying yet.
        pend = (max((int(v.max()) + 1 for v in self.votes_store._buf_val
                     if v.shape[0]), default=0))
        self.votes_store.grow(pend)
        self._mirror = _DeviceMirror(self.votes_store, self.old_balances,
                                     max(self.cols.n, 1))

    def _compute_deltas_host_round(self, new_balances) -> np.ndarray:
        """Deep-tree (or mirror-less) jit round run on host: numpy deltas
        out, device vote/balance mirrors kept in lock-step so the next
        shallow round needs no rematerialize."""
        self._flush_votes()
        deltas, new_b = compute_deltas_host(
            self.votes_store, self.old_balances,
            np.asarray(new_balances, np.uint64), self.cols.n)
        if self._mirror is not None \
                and self._mirror.fits(self.votes_store, 1):
            import jax
            from ..parallel.mesh import mesh_put
            nb = np.zeros(self._mirror.nv_pad, np.int64)
            nb[:new_b.shape[0]] = new_b.astype(np.int64)
            with jax.enable_x64(True):
                self._mirror.old_b = mesh_put("fc_votes", nb)
                self._mirror.cur = self._mirror.nxt
            self._mirror._note_residency()
            # host apply will move weights: force a weight re-push on
            # the next kernel dispatch even if the topology is unchanged
            self._mirror.topo_version = -1
        else:
            self._mirror = None
        self.old_balances = new_b.copy()
        return deltas

    def apply_score_changes(self, deltas, justified_checkpoint,
                            finalized_checkpoint, proposer_boost_root,
                            proposer_boost_score, current_slot) -> None:
        cols = self.cols
        n = cols.n
        self.justified_checkpoint = justified_checkpoint
        self.finalized_checkpoint = finalized_checkpoint
        viable = cols.viable_mask(justified_checkpoint, finalized_checkpoint)
        invalid = cols.exec_status[:n] == EXEC_INVALID
        pb_idx = (cols.indices.get(self.prev_boost_root, -1)
                  if self.prev_boost_root != ZERO_ROOT else -1)
        if pb_idx >= 0 and invalid[pb_idx]:
            pb_idx = -1
        b_idx = (cols.indices.get(bytes(proposer_boost_root), -1)
                 if bytes(proposer_boost_root) != ZERO_ROOT else -1)
        new_boost = 0
        if b_idx >= 0 and invalid[b_idx]:
            b_idx = -1
        elif b_idx >= 0:
            new_boost = proposer_boost_score
        if deltas is _DEVICE_DELTAS and self.engine == "jit":
            self._apply_jit(viable, invalid, pb_idx, self.prev_boost_score,
                            b_idx, proposer_boost_score)
        else:
            if deltas is _DEVICE_DELTAS:
                raise ProtoArrayError("device deltas on a numpy engine")
            if len(deltas) != n:
                raise ProtoArrayError("delta length mismatch")
            apply_scores(cols, np.asarray(deltas, np.int64), viable,
                         pb_idx, self.prev_boost_score,
                         b_idx, proposer_boost_score)
        self.prev_boost_root = bytes(proposer_boost_root)
        self.prev_boost_score = new_boost

    def _apply_jit(self, viable, invalid, pb_idx, pb_score, b_idx,  # device-io: fork_choice
                   b_score) -> None:
        import time as _time
        import jax.numpy as jnp
        import jax
        from ..common.device_ledger import LEDGER
        from ..parallel import mesh as pmesh

        cols = self.cols
        n = cols.n
        mir = self._mirror
        assert mir is not None and self._pending_new_b is not None
        mir.push_topology(cols, self._topo_version)
        n_pad = mir.n_pad
        inv = np.zeros(n_pad, bool)
        inv[:n] = invalid
        zr = np.zeros(n_pad, bool)
        zr[:n] = cols.zero_root_mask()
        via = np.zeros(n_pad, bool)
        via[:n] = viable
        rank = np.full(n_pad, -1, np.int64)
        rank[:n] = cols.ranks()
        bc_in = np.full(n_pad, -1, np.int32)
        bc_in[:n] = cols.best_child[:n]
        bd_in = np.full(n_pad, -1, np.int32)
        bd_in[:n] = cols.best_desc[:n]
        new_b = np.zeros(mir.nv_pad, np.int64)
        new_b[:self._pending_new_b.shape[0]] = \
            self._pending_new_b.astype(np.int64)
        # Clock from the staging/kernel block only (the slasher/kzg/bls
        # convention): the np.full marshalling above is host prep, not
        # device-verify time.
        t_dispatch = _time.perf_counter()
        ndev = pmesh.axis_size()
        use_mesh = ndev > 1 and mir.nv_pad % ndev == 0
        with jax.enable_x64(True):
            kernel = (_get_mesh_kernel(n_pad, mir.nv_pad) if use_mesh
                      else _get_kernel(n_pad, mir.nv_pad))
            new_b_dev = pmesh.mesh_put("fc_votes", new_b)
            weight, bc, bd, negflag = kernel(
                mir.cur, mir.nxt, mir.old_b, new_b_dev,
                mir.parent, mir.depth,
                jnp.asarray(inv), jnp.asarray(zr), jnp.asarray(via),
                jnp.asarray(rank), mir.weight,
                jnp.asarray(bc_in), jnp.asarray(bd_in),
                jnp.int32(pb_idx), jnp.int64(pb_score),
                jnp.int32(b_idx), jnp.int64(b_score),
                jnp.int32(cols.max_depth()))
            # device-side vote move + balance persistence (no pull)
            mir.cur = mir.nxt
            mir.old_b = new_b_dev
            mir.weight = weight
            w_host = np.asarray(weight)[:n]    # device-io: fork_choice
            bc_host = np.asarray(bc)[:n]       # device-io: fork_choice
            bd_host = np.asarray(bd)[:n]       # device-io: fork_choice
            neg = bool(negflag)
        # new_b is settled by mesh_put above; these masks ride plain
        # jnp.asarray into the jit call.
        LEDGER.note_transfer(
            "h2d", inv.nbytes + zr.nbytes + via.nbytes + rank.nbytes
            + bc_in.nbytes + bd_in.nbytes,
            subsystem="fork_choice")
        LEDGER.note_transfer(
            "d2h", w_host.nbytes + bc_host.nbytes + bd_host.nbytes + 1,
            subsystem="fork_choice")
        LEDGER.note_dispatch(
            "fork_choice", (_time.perf_counter() - t_dispatch) * 1e3)
        mir._note_residency()
        cols.weight[:n] = w_host
        cols.best_child[:n] = bc_host
        cols.best_desc[:n] = bd_host
        self._pending_new_b = None
        if neg:
            raise ProtoArrayError("negative node weight")

    # -- head ----------------------------------------------------------------

    def find_head(self, justified_root: bytes, current_slot: int) -> bytes:
        idx = self.cols.indices.get(bytes(justified_root))
        if idx is None:
            raise ProtoArrayError("justified root unknown to fork choice")
        if self.cols.exec_status[idx] == EXEC_INVALID:
            raise ProtoArrayError("justified node has invalid payload")
        best = int(self.cols.best_desc[idx])
        best = idx if best < 0 else best
        viable = self.cols.viable_mask(self.justified_checkpoint,
                                       self.finalized_checkpoint)
        if not viable[best]:
            raise ProtoArrayError("best node not viable for head")
        return self.cols.root_bytes(best)

    # -- pruning -------------------------------------------------------------

    def maybe_prune(self, finalized_root: bytes) -> None:
        fin_idx = self.cols.indices.get(bytes(finalized_root))
        if fin_idx is None or fin_idx < self.prune_threshold:
            return
        # Buffered votes reference node indices: merge them into the
        # latest-message columns first (merge order is flush-point
        # invariant), then remap like the host.
        self._flush_votes()
        old = self.cols
        n = old.n
        remap = np.full(n + 1, -1, np.int32)
        remap[fin_idx:n] = np.arange(n - fin_idx, dtype=np.int32)
        new = NodeColumns(capacity=max(n - fin_idx, 8))
        for i in range(fin_idx, n):
            p = int(old.parent[i])
            p = -1 if p < 0 or remap[p] < 0 else int(remap[p])
            j = new.append(
                slot=int(old.slot[i]), root=old.root_bytes(i), parent=p,
                state_root=old.state_roots[i].tobytes(),
                justified_epoch=int(old.justified_epoch[i]),
                justified_root=old.justified_roots[i].tobytes(),
                finalized_epoch=int(old.finalized_epoch[i]),
                finalized_root=old.finalized_roots[i].tobytes(),
                execution_status=int(old.exec_status[i]),
                execution_block_hash=old.exec_hash[i])
            new.weight[j] = old.weight[i]
            for col in ("best_child", "best_desc"):
                v = int(getattr(old, col)[i])
                getattr(new, col)[j] = -1 if v < 0 or remap[v] < 0 \
                    else int(remap[v])
        self.cols = new
        self.votes_store.remap(remap)
        self._topo_version += 1
        self._mirror = None  # full rematerialize on next jit flush

    # -- execution status (optimistic sync) ----------------------------------

    def on_valid_execution_payload(self, root: bytes) -> None:
        idx = self.cols.indices.get(bytes(root))
        while idx is not None and idx >= 0:
            st = int(self.cols.exec_status[idx])
            if st == EXEC_INVALID:
                raise ProtoArrayError("valid payload above invalid ancestor")
            if st in (EXEC_VALID, EXEC_IRRELEVANT):
                break
            self.cols.exec_status[idx] = EXEC_VALID
            p = int(self.cols.parent[idx])
            idx = None if p < 0 else p

    def on_invalid_execution_payload(self, root: bytes) -> None:
        """Invalidate a node and every descendant — one masked OR per tree
        level below it (weights stay; the next score pass computes
        ``d = -weight`` and propagates the removal to ancestors)."""
        start = self.cols.indices.get(bytes(root))
        if start is None:
            return
        n = self.cols.n
        inv = np.zeros(n, bool)
        inv[start] = True
        parent = self.cols.parent
        for lvl in range(int(self.cols.depth[start]) + 1,
                         self.cols.max_depth() + 1):
            c = self.cols.levels()[lvl]
            pc = parent[c]
            m = (pc >= 0) & inv[pc]
            inv[c[m]] = True
        self.cols.exec_status[:n][inv] = EXEC_INVALID

    # -- host interop ---------------------------------------------------------

    def to_host(self) -> ProtoArrayForkChoice:
        """Bit-exact host snapshot (persistence + differential oracle)."""
        self._flush_votes()
        pa = ProtoArrayForkChoice(prune_threshold=self.prune_threshold)
        pa.nodes = self.cols.export_nodes()
        pa.indices = dict(self.cols.indices)
        v = self.votes_store
        pa.votes = VoteTracker(v.current.copy(), v.next.copy(),
                               v.next_epoch.copy())
        pa.old_balances = self.old_balances.copy()
        pa.equivocating = set(v.equivocating)
        pa.justified_checkpoint = self.justified_checkpoint
        pa.finalized_checkpoint = self.finalized_checkpoint
        pa.prev_boost_root = self.prev_boost_root
        pa.prev_boost_score = self.prev_boost_score
        return pa

    @classmethod
    def from_host(cls, pa: ProtoArrayForkChoice,
                  engine: Optional[str] = None
                  ) -> "DeviceProtoArrayForkChoice":
        self = cls(prune_threshold=pa.prune_threshold, engine=engine)
        for node in pa.nodes:
            i = self.cols.append(
                slot=node.slot, root=node.root,
                parent=-1 if node.parent is None else node.parent,
                state_root=node.state_root,
                justified_epoch=node.justified_epoch,
                justified_root=node.justified_root,
                finalized_epoch=node.finalized_epoch,
                finalized_root=node.finalized_root,
                execution_status=node.execution_status,
                execution_block_hash=node.execution_block_hash)
            self.cols.weight[i] = node.weight
            self.cols.best_child[i] = \
                -1 if node.best_child is None else node.best_child
            self.cols.best_desc[i] = \
                -1 if node.best_descendant is None else node.best_descendant
        v = self.votes_store
        v.current = pa.votes.current.copy()
        v.next = pa.votes.next.copy()
        v.next_epoch = pa.votes.next_epoch.copy()
        v.equivocating = set(pa.equivocating)
        self.old_balances = pa.old_balances.copy()
        self.justified_checkpoint = pa.justified_checkpoint
        self.finalized_checkpoint = pa.finalized_checkpoint
        self.prev_boost_root = pa.prev_boost_root
        self.prev_boost_score = pa.prev_boost_score
        self._topo_version += 1
        return self


class _DeviceDeltasMarker:
    """Sentinel: deltas live on device, fused into apply_score_changes."""

    def __len__(self):  # defensive: host apply on a device marker
        raise ProtoArrayError("device deltas on a numpy engine")


_DEVICE_DELTAS = _DeviceDeltasMarker()


def warmup(n_nodes: int, n_validators: int) -> None:
    """Pre-compile the fused kernel for the given shape buckets (the
    scripts' ``--warmup`` hook; compiles persist via the common cache)."""
    import jax.numpy as jnp
    import jax
    from ..parallel import mesh as pmesh
    n_pad = _bucket(n_nodes)
    nv_pad = _bucket(n_validators)
    ndev = pmesh.axis_size()
    with jax.enable_x64(True):
        kernel = (_get_mesh_kernel(n_pad, nv_pad)
                  if ndev > 1 and nv_pad % ndev == 0
                  else _get_kernel(n_pad, nv_pad))
        i32 = jnp.int32
        kernel(jnp.full(nv_pad, -1, i32), jnp.full(nv_pad, -1, i32),
               jnp.zeros(nv_pad, jnp.int64), jnp.zeros(nv_pad, jnp.int64),
               jnp.full(n_pad, -1, i32), jnp.full(n_pad, -1, i32),
               jnp.zeros(n_pad, bool), jnp.zeros(n_pad, bool),
               jnp.zeros(n_pad, bool), jnp.full(n_pad, -1, jnp.int64),
               jnp.zeros(n_pad, jnp.int64),
               jnp.full(n_pad, -1, i32), jnp.full(n_pad, -1, i32),
               jnp.int32(-1), jnp.int64(0), jnp.int32(-1), jnp.int64(0),
               jnp.int32(0))
