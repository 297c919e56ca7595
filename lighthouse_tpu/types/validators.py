"""Validator registry as a structure-of-arrays (SoA) — the TPU-first redesign
of the reference's ``List<Validator, ValidatorRegistryLimit>``.

The reference stores validators as an array-of-structs and parallelises
hashing with rayon over 4096-record arenas
(``/root/reference/consensus/types/src/beacon_state/tree_hash_cache.rs:25-33,
535-556``).  On TPU the natural layout is columnar: each field is one numpy
array, so

- epoch processing (rewards, effective-balance updates, registry updates)
  is vectorized arithmetic over whole columns (no per-validator Python);
- the registry Merkle root is ONE batched device program: 8 chunk-leaves per
  validator, three ``hash64`` levels to per-validator roots, then the big
  padded reduction to the 2^40-leaf registry root
  (``consensus/types/src/validator.rs`` field order defines the leaves).

``Validator`` (the AoS container) remains the single-record interchange type;
the registry converts at the boundary.
"""

from __future__ import annotations

import numpy as np

from ..ssz.core import Bytes32, Bytes48, SszError
from ..ssz.composite import Container
from ..ssz import boolean, uint64
from ..ops.sha256 import hash64
from .chain_spec import FAR_FUTURE_EPOCH

# Packed wire layout: 121 bytes per record, field order per the spec
# container (``consensus/types/src/validator.rs``).
_VALIDATOR_DTYPE = np.dtype([
    ("pubkey", "u1", (48,)),
    ("withdrawal_credentials", "u1", (32,)),
    ("effective_balance", "<u8"),
    ("slashed", "u1"),
    ("activation_eligibility_epoch", "<u8"),
    ("activation_epoch", "<u8"),
    ("exit_epoch", "<u8"),
    ("withdrawable_epoch", "<u8"),
])
assert _VALIDATOR_DTYPE.itemsize == 121

_EPOCH_FIELDS = ("activation_eligibility_epoch", "activation_epoch",
                 "exit_epoch", "withdrawable_epoch")


class Validator(Container):
    """Single-record AoS form (interchange/SSZ boundary)."""
    pubkey: Bytes48
    withdrawal_credentials: Bytes32
    effective_balance: uint64
    slashed: boolean
    activation_eligibility_epoch: uint64
    activation_epoch: uint64
    exit_epoch: uint64
    withdrawable_epoch: uint64


def u64_to_chunk_words(v: np.ndarray) -> np.ndarray:
    """``(n,) uint64`` → ``(n, 8) uint32`` big-endian words of the 32-byte
    SSZ chunk (value little-endian, zero-padded)."""
    v = np.asarray(v, dtype=np.uint64)
    lo = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (v >> np.uint64(32)).astype(np.uint32)
    out = np.zeros(v.shape + (8,), dtype=np.uint32)
    out[..., 0] = lo.byteswap()
    out[..., 1] = hi.byteswap()
    return out


def bytes_col_to_words(col: np.ndarray) -> np.ndarray:
    """``(n, 4k) uint8`` → ``(n, k) uint32`` big-endian words."""
    col = np.ascontiguousarray(col)
    return col.view(">u4").astype(np.uint32)


class ValidatorRegistry:
    """SoA columns + list-like API.  Mutations go through the columns
    (vectorized) or :meth:`set`; ``append`` amortizes with capacity doubling
    like the reference's ``CacheArena`` grow path."""

    __ssz_mutable__ = True

    def __init__(self, n: int = 0, _cap: int | None = None):
        cap = max(_cap if _cap is not None else n, n, 8)
        self._n = n
        self._pubkey = np.zeros((cap, 48), dtype=np.uint8)
        self._withdrawal_credentials = np.zeros((cap, 32), dtype=np.uint8)
        self._effective_balance = np.zeros(cap, dtype=np.uint64)
        self._slashed = np.zeros(cap, dtype=bool)
        self._activation_eligibility_epoch = np.full(
            cap, FAR_FUTURE_EPOCH, dtype=np.uint64)
        self._activation_epoch = np.full(cap, FAR_FUTURE_EPOCH, dtype=np.uint64)
        self._exit_epoch = np.full(cap, FAR_FUTURE_EPOCH, dtype=np.uint64)
        self._withdrawable_epoch = np.full(cap, FAR_FUTURE_EPOCH,
                                           dtype=np.uint64)
        # Dirty tracking for the incremental tree-hash cache
        # (``cached_tree_hash``'s dirty leaves, at column/row granularity).
        # ``col()`` views are read-only so every write goes through ``wcol``/
        # ``set``/``append`` and is tracked — an unmarked write raises.
        # Marks are CONSUMED by the hash cache at root time: a ``wcol``
        # view is only valid for writing until the next ``hash_tree_root``
        # (every in-tree caller writes immediately; sticky marks meant
        # re-diffing 130 MB of columns on every root at 2^20 validators).
        self._dirty_cols: set = set(self._COLUMNS)
        self._dirty_rows: set = set()
        # Lazy pubkey → index map (the ``ValidatorPubkeyCache`` reverse
        # lookup).  Shared by reference across ``copy()`` (pubkeys are
        # append-only in practice); extension forks the dict first so a
        # sharer never sees rows it does not have, and ``set()`` — the only
        # in-place pubkey overwrite — invalidates.
        self._pk_index: dict | None = None
        self._pk_index_n = 0
        # Device mirror (HBM-resident raw columns + record-root tree),
        # attached by the device-resident hash cache; COW-shared across
        # copy().  None until materialized.
        self._dev_mirror = None

    _COLUMNS = ("pubkey", "withdrawal_credentials", "effective_balance",
                "slashed", "activation_eligibility_epoch", "activation_epoch",
                "exit_epoch", "withdrawable_epoch")

    # -- list-like API -------------------------------------------------------

    def __len__(self) -> int:
        return self._n

    def col(self, name: str) -> np.ndarray:
        """Read-only view of a column, truncated to the real length.
        Writes must go through :meth:`wcol` (which marks the column dirty
        for the incremental hash cache) — writing this view raises, and the
        public column attributes are themselves read-only views so no write
        can bypass the tracking."""
        v = getattr(self, "_" + name)[:self._n]
        v.flags.writeable = False
        return v

    def wcol(self, name: str) -> np.ndarray:
        """Writable column view; marks the whole column dirty (the hash
        cache diffs it against its stored copy at root time, so the cost of
        a column-wide mark is one vectorized compare, not a rehash).

        The view must not be written after the next ``hash_tree_root`` —
        the cache consumes the mark there; re-call ``wcol`` for later
        writes."""
        self._dirty_cols.add(name)
        return getattr(self, "_" + name)[:self._n]

    def __getitem__(self, i: int) -> Validator:
        if not -self._n <= i < self._n:
            raise IndexError(i)
        i %= max(self._n, 1)
        return Validator(
            pubkey=self._pubkey[i].tobytes(),
            withdrawal_credentials=self._withdrawal_credentials[i].tobytes(),
            effective_balance=int(self._effective_balance[i]),
            slashed=bool(self._slashed[i]),
            activation_eligibility_epoch=int(
                self._activation_eligibility_epoch[i]),
            activation_epoch=int(self._activation_epoch[i]),
            exit_epoch=int(self._exit_epoch[i]),
            withdrawable_epoch=int(self._withdrawable_epoch[i]),
        )

    def __iter__(self):
        for i in range(self._n):
            yield self[i]

    def init_columns(self, **arrays) -> None:
        """Bulk-initialise columns on a FRESH registry (genesis fast path).
        All columns start dirty, so no extra marking is needed; using this
        instead of ``wcol`` avoids sticky-marking bulk-written columns."""
        for name, arr in arrays.items():
            if name not in self._COLUMNS:
                raise KeyError(name)
            getattr(self, "_" + name)[:self._n] = arr
        self._pk_index = None

    def pubkey_index(self, pubkey: bytes) -> int | None:
        """Index of ``pubkey`` in the registry (first occurrence), or None.
        One lazy dict build per registry lineage; copies share it and
        appended rows extend it incrementally."""
        d = self._pk_index
        if d is None:
            d = {}
            self._pk_index_n = 0
        if self._pk_index_n < self._n:
            if d:
                d = dict(d)  # fork: never extend a possibly-shared dict
            pks = self._pubkey
            for i in range(self._pk_index_n, self._n):
                d.setdefault(pks[i].tobytes(), i)
            self._pk_index, self._pk_index_n = d, self._n
        idx = d.get(pubkey)
        if idx is None:
            return None
        if idx < self._n and self._pubkey[idx].tobytes() == pubkey:
            return idx
        # Stale entry (row overwritten out from under a shared dict):
        # rebuild this registry's own map once.
        d = {}
        pks = self._pubkey
        for i in range(self._n):
            d.setdefault(pks[i].tobytes(), i)
        self._pk_index, self._pk_index_n = d, self._n
        return d.get(pubkey)

    def set(self, i: int, v: Validator) -> None:
        if not 0 <= i < self._n:
            raise IndexError(i)
        self._pk_index = None  # row overwrite may change a pubkey
        self._dirty_rows.add(i)
        self._pubkey[i] = np.frombuffer(v.pubkey, dtype=np.uint8)
        self._withdrawal_credentials[i] = np.frombuffer(
            v.withdrawal_credentials, dtype=np.uint8)
        self._effective_balance[i] = v.effective_balance
        self._slashed[i] = v.slashed
        self._activation_eligibility_epoch[i] = v.activation_eligibility_epoch
        self._activation_epoch[i] = v.activation_epoch
        self._exit_epoch[i] = v.exit_epoch
        self._withdrawable_epoch[i] = v.withdrawable_epoch

    def _grow(self, need: int) -> None:
        cap = self._effective_balance.shape[0]
        if need <= cap:
            return
        new_cap = max(need, cap * 2)
        for name in self._COLUMNS:
            old = getattr(self, "_" + name)
            new = np.empty((new_cap,) + old.shape[1:], dtype=old.dtype)
            new[:self._n] = old[:self._n]
            if old.dtype == np.uint64 and name in _EPOCH_FIELDS:
                new[self._n:] = FAR_FUTURE_EPOCH
            else:
                new[self._n:] = 0
            setattr(self, "_" + name, new)

    def append(self, v: Validator) -> None:
        self._grow(self._n + 1)
        self._n += 1
        self.set(self._n - 1, v)

    def copy(self) -> "ValidatorRegistry":
        out = ValidatorRegistry.__new__(type(self))
        out._n = self._n
        for name in self._COLUMNS:
            setattr(out, "_" + name, getattr(self, "_" + name)[:self._n].copy())
        out._dirty_cols = set(self._dirty_cols)
        out._dirty_rows = set(self._dirty_rows)
        out._pk_index = self._pk_index  # shared; forked on extension
        out._pk_index_n = self._pk_index_n
        # COW: the clone shares every device buffer; the first mutation of
        # either lineage lands in fresh buffers (undonated update program),
        # so cloning duplicates no HBM and forces no pull.
        out._dev_mirror = (None if self._dev_mirror is None
                           else self._dev_mirror.share())
        return out

    def __eq__(self, other):
        if not isinstance(other, ValidatorRegistry):
            return NotImplemented
        if self._n != other._n:
            return False
        return all(
            np.array_equal(self.col(name), other.col(name))
            for name in self._COLUMNS)

    def __repr__(self):
        return f"ValidatorRegistry(n={self._n})"

    # -- bulk conversion -----------------------------------------------------

    @classmethod
    def from_validators(cls, validators) -> "ValidatorRegistry":
        out = cls(len(validators))
        out._n = len(validators)
        for i, v in enumerate(validators):
            out.set(i, v)
        return out

    def to_packed(self) -> bytes:
        arr = np.empty(self._n, dtype=_VALIDATOR_DTYPE)
        arr["pubkey"] = self._pubkey[:self._n]
        arr["withdrawal_credentials"] = self._withdrawal_credentials[:self._n]
        arr["effective_balance"] = self._effective_balance[:self._n]
        arr["slashed"] = self._slashed[:self._n].astype(np.uint8)
        for f in _EPOCH_FIELDS:
            arr[f] = getattr(self, "_" + f)[:self._n]
        return arr.tobytes()

    @classmethod
    def from_packed(cls, data: bytes) -> "ValidatorRegistry":
        if len(data) % _VALIDATOR_DTYPE.itemsize:
            raise SszError("validator registry bytes not a multiple of 121")
        arr = np.frombuffer(data, dtype=_VALIDATOR_DTYPE)
        n = arr.shape[0]
        out = cls(n)
        out._n = n
        out._pubkey[:n] = arr["pubkey"]
        out._withdrawal_credentials[:n] = arr["withdrawal_credentials"]
        out._effective_balance[:n] = arr["effective_balance"]
        if arr["slashed"].size and (arr["slashed"] > 1).any():
            raise SszError("invalid boolean byte in validator record")
        out._slashed[:n] = arr["slashed"].astype(bool)
        for f in _EPOCH_FIELDS:
            getattr(out, "_" + f)[:n] = arr[f]
        return out

    # -- Merkleization (the hot path) ---------------------------------------

    def record_roots_words(self, indices=None) -> np.ndarray:
        """Per-validator hash_tree_roots as ``(k, 8)`` u32 words — one
        batched device program (vs rayon-per-arena in the reference,
        ``tree_hash_cache.rs:535-556``).  ``indices`` restricts to a subset
        (the incremental cache recomputes only dirty records)."""
        from ..ops.merkle import HOST_DISPATCH_THRESHOLD, hash64_host_words
        from ..ops.tree_cache import HASH_COUNT
        n = self._n
        if indices is None:
            sel = slice(None, n)  # zero-copy column views for full builds
            k = n
        else:
            sel = np.asarray(indices)
            k = sel.shape[0]
        if k == 0:
            return np.zeros((0, 8), dtype=np.uint32)
        inner = (hash64_host_words if k <= HOST_DISPATCH_THRESHOLD
                 else lambda a, b: np.asarray(hash64(a, b)))

        def h64(a, b):
            HASH_COUNT[0] += int(np.prod(a.shape[:-1], dtype=np.int64))
            return inner(a, b)
        pk = self._pubkey[sel]
        pk_hi = np.zeros((k, 32), dtype=np.uint8)
        pk_hi[:, :16] = pk[:, 32:]
        pubkey_root = h64(bytes_col_to_words(pk[:, :32]),
                          bytes_col_to_words(pk_hi))
        leaves = np.stack([
            np.asarray(pubkey_root),
            bytes_col_to_words(self._withdrawal_credentials[sel]),
            u64_to_chunk_words(self._effective_balance[sel]),
            u64_to_chunk_words(self._slashed[sel].astype(np.uint64)),
            u64_to_chunk_words(self._activation_eligibility_epoch[sel]),
            u64_to_chunk_words(self._activation_epoch[sel]),
            u64_to_chunk_words(self._exit_epoch[sel]),
            u64_to_chunk_words(self._withdrawable_epoch[sel]),
        ], axis=1)  # (k, 8, 8)
        l1 = h64(leaves[:, 0::2], leaves[:, 1::2])   # (k, 4, 8)
        l2 = h64(l1[:, 0::2], l1[:, 1::2])           # (k, 2, 8)
        l3 = h64(l2[:, 0], l2[:, 1])                 # (k, 8)
        return np.asarray(l3)

    def hash_tree_root(self, limit: int) -> bytes:
        """Registry root: batched record roots → padded device reduction to
        the ``limit``-leaf tree → length mixin."""
        from .columns import device_merkle_root
        return device_merkle_root(self.record_roots_words(), limit,
                                  length_mixin=self._n)


def _column_property(name: str) -> property:
    def get(self):
        v = getattr(self, "_" + name).view()
        v.flags.writeable = False
        return v
    get.__doc__ = (f"Read-only view of the {name} column storage (full "
                   "capacity); mutate via wcol()/set()/append() so the "
                   "incremental hash cache sees the change.")
    return property(get)


for _cname in ValidatorRegistry._COLUMNS:
    setattr(ValidatorRegistry, _cname, _column_property(_cname))
del _cname


# ---------------------------------------------------------------------------
# Device cold build: every registry tree level in ONE dispatch
# ---------------------------------------------------------------------------
#
# The incremental state cache needs the record roots AND the interior levels
# of the registry tree (to propagate dirty paths on the host).  Computing
# them eagerly level-by-level bounces hundreds of MB between host and device
# (the r3 cold path cost 559 s); host hashlib needs ~8 hashes/record ≈ 10+ s
# at 2^20.  Instead one jitted program computes the per-record mini-trees and
# every registry level on-device (Pallas hash64 for the wide levels), the
# 32-byte root is pulled immediately, and the levels are pulled lazily (a
# background thread hides the pull).

def _bswap32(x):
    import jax.numpy as jnp
    return (((x & np.uint32(0xFF)) << np.uint32(24))
            | (((x >> np.uint32(8)) & np.uint32(0xFF)) << np.uint32(16))
            | (((x >> np.uint32(16)) & np.uint32(0xFF)) << np.uint32(8))
            | (x >> np.uint32(24)))


def _u64_lohi_words(lohi):
    """(n, 2) u32 little-endian (lo, hi) → (n, 8) big-endian chunk words."""
    import jax.numpy as jnp
    z = jnp.zeros_like(lohi[:, 0])
    return jnp.stack([_bswap32(lohi[:, 0]), _bswap32(lohi[:, 1]),
                      z, z, z, z, z, z], axis=-1)


def _registry_raw_columns(reg: "ValidatorRegistry", m: int) -> dict:
    """Host marshalling for the cold build: byte columns as words, u64
    columns as raw (n, 2) u32 views (device expands them — 4× less H2D
    traffic than pushing chunk words), padded to ``m`` rows."""
    n = reg._n

    def pad(a):
        if a.shape[0] == m:
            return a
        out = np.zeros((m,) + a.shape[1:], dtype=a.dtype)
        out[:n] = a
        return out

    def lohi(col):
        return np.ascontiguousarray(col[:n]).view(np.uint32).reshape(n, 2)

    cols = {
        "pubkey": pad(bytes_col_to_words(reg._pubkey[:n])),
        "withdrawal_credentials": pad(
            bytes_col_to_words(reg._withdrawal_credentials[:n])),
        # u8 on the wire (every pushed byte counts); widened on-device.
        "slashed": pad(reg._slashed[:n].astype(np.uint8)),
    }
    for f in ("effective_balance",) + _EPOCH_FIELDS:
        cols[f] = pad(lohi(getattr(reg, "_" + f)))
    return cols


def _h64_device(use_kernel: bool):
    """The shared ``hash64`` selector of the device bodies: Pallas for
    lane counts the kernel can take, XLA scan otherwise."""
    from ..ops.merkle_kernel import hash64_pallas

    PB = 1 << 15  # hash64_pallas lane-count granularity

    def h64(a, b):
        flat_ok = a.shape[0] % PB == 0 and a.shape[0] >= PB and a.ndim == 2
        if use_kernel and flat_ok:
            return hash64_pallas(a, b)
        return hash64(a, b)

    return h64


def _record_roots_body(cols: dict, *, use_kernel: bool):
    """Device body: raw columns (m rows) → (m, 8) record mini-tree roots.
    Jitted per chunk shape so the chunked cold build reduces each staged
    column chunk while later chunks are still in transfer."""
    import jax.numpy as jnp

    h64 = _h64_device(use_kernel)
    pk = cols["pubkey"]                       # (m, 12) words
    m = pk.shape[0]
    pk_lo = pk[:, :8]
    pk_hi = jnp.pad(pk[:, 8:], ((0, 0), (0, 4)))
    pubkey_root = h64(pk_lo, pk_hi)
    sl = cols["slashed"].astype(jnp.uint32)
    z = jnp.zeros_like(sl)
    slashed_words = jnp.stack([_bswap32(sl), z, z, z, z, z, z, z], axis=-1)
    leaves = jnp.stack([
        pubkey_root,
        cols["withdrawal_credentials"],
        _u64_lohi_words(cols["effective_balance"]),
        slashed_words,
        _u64_lohi_words(cols["activation_eligibility_epoch"]),
        _u64_lohi_words(cols["activation_epoch"]),
        _u64_lohi_words(cols["exit_epoch"]),
        _u64_lohi_words(cols["withdrawable_epoch"]),
    ], axis=1)                                # (m, 8, 8)
    l1 = h64(leaves[:, 0::2].reshape(4 * m, 8),
             leaves[:, 1::2].reshape(4 * m, 8)).reshape(m, 4, 8)
    l2 = h64(l1[:, 0::2].reshape(2 * m, 8),
             l1[:, 1::2].reshape(2 * m, 8)).reshape(m, 2, 8)
    return h64(l2[:, 0], l2[:, 1])            # (m, 8) record roots


def _registry_levels_body(cols: dict, *, n: int, w: int, use_kernel: bool):
    """Device body: raw columns (m rows) → tuple of registry tree levels.

    ``levels[0]`` = (w, 8) record roots of the first ``n ≤ m`` records,
    padded with zero CHUNKS (SSZ list semantics) to the power-of-two width
    ``w``; ``levels[-1]`` = (1, 8) root of the w-subtree.  Rows n..m are
    marshalling pad (Pallas needs 2^15-multiples) — their garbage mini-tree
    roots are sliced off before the zero-chunk padding.
    """
    rec = _record_roots_body(cols, use_kernel=use_kernel)
    return _levels_from_records(rec, n, w, _h64_device(use_kernel))


def _levels_combine_body(rec, *, n: int, w: int, use_kernel: bool):
    """Concatenated per-chunk record roots → the registry tree levels
    (the tail of :func:`_registry_levels_body`, as its own jit for the
    chunked cold build)."""
    return _levels_from_records(rec, n, w, _h64_device(use_kernel))


def _levels_from_records(rec, n: int, w: int, h64):
    """Registry levels over ``rec``: keep the first ``n`` REAL record roots
    (rows beyond ``n`` are marshalling-pad garbage — zero-RECORD roots, not
    zero chunks), zero-chunk pad to the power-of-two width ``w``."""
    import jax.numpy as jnp
    rec = rec[:n]
    if n < w:
        rec = jnp.concatenate(
            [rec, jnp.zeros((w - n, 8), jnp.uint32)], axis=0)
    levels = [rec]
    cur = rec
    while cur.shape[0] > 1:
        cur = h64(cur[0::2], cur[1::2])
        levels.append(cur)
    return tuple(levels)


_PALLAS_PAD = 1 << 15
_levels_jit = None
_record_roots_jit = None
_levels_combine_jit = None

# H2D streaming granularity of the chunked cold build: 2^17 records
# ≈ 15 MiB of raw columns per chunk (a multiple of the Pallas pad).
REG_PUSH_CHUNK_ROWS = 1 << 17

# Stage timings of the most recent cold build (ms), for bench reporting:
# the column push can dominate the on-device compute; ``push_ms`` is the transfer time left on the critical
# path and ``push_overlap_ms`` the transfer time the chunked pipeline hid
# behind the earlier chunks' on-device reduction.
LAST_COLD_TIMINGS: dict = {}


def _reg_chunk_rows() -> int:
    """The shared env knob (ROWS, i.e. records — the registry's ~120 B
    rows make a chunk ~2× the byte size of a same-rows leaf chunk),
    clamped to a usable multiple of the Pallas pad so a small-but-
    positive value still chunks instead of silently going monolithic.
    ≤ 0 disables."""
    from ..common.knobs import knob_int
    rows = knob_int("LIGHTHOUSE_TPU_PUSH_CHUNK_ROWS",
                    default=REG_PUSH_CHUNK_ROWS)
    if rows <= 0:
        return 0
    return max((rows // _PALLAS_PAD) * _PALLAS_PAD, _PALLAS_PAD)


def registry_cold_device(reg: "ValidatorRegistry",
                         chunk_rows: int | None = None):
    """Cold build on the attached TPU with a streamed column push.

    Returns ``(root_words, levels)``: ``root_words`` is the (8,) u32 root of
    the occupied power-of-two subtree (host numpy, pulled immediately);
    ``levels`` are the device-resident tree levels for the caller to pull
    lazily into the host incremental cache.

    Registries wider than one push chunk stream their raw columns up in
    row chunks via a background :class:`~lighthouse_tpu.parallel.
    pipeline.ChunkStager`: chunk i+1 transfers while chunk i's record
    mini-trees already reduce on-device, and a final combine program
    builds the registry levels over the concatenated record roots —
    the monolithic blocking push (5+ s of the cold state root at 2^20)
    leaves the critical path.  Small registries keep the one-dispatch
    monolithic body."""
    global _levels_jit, _record_roots_jit, _levels_combine_jit
    import time
    import jax
    import jax.numpy as jnp
    from ..ops.merkle import _next_pow2
    from ..ops.merkle_kernel import _use_pallas

    n = reg._n
    w = _next_pow2(max(n, 1))
    # Pad rows to the Pallas granularity; slice the pad off on-device.
    m = max(-(-n // _PALLAS_PAD) * _PALLAS_PAD, _PALLAS_PAD)
    use_kernel = _use_pallas()
    chunk = _reg_chunk_rows() if chunk_rows is None else chunk_rows
    if chunk <= 0 or m <= chunk or chunk % _PALLAS_PAD:
        from ..parallel.mesh import mesh_put
        t0 = time.perf_counter()
        host_cols = _registry_raw_columns(reg, m)
        cols = {k: mesh_put("registry_cols", v, subsystem="staging")
                for k, v in host_cols.items()}
        jax.block_until_ready(cols)
        t1 = time.perf_counter()
        if _levels_jit is None:
            _levels_jit = jax.jit(_registry_levels_body,
                                  static_argnames=("n", "w", "use_kernel"))
        levels = _levels_jit(cols, n=n, w=w, use_kernel=use_kernel)
        root_words = np.asarray(levels[-1])[0]  # device-io: staging
        t2 = time.perf_counter()
        LAST_COLD_TIMINGS.update(
            push_ms=round((t1 - t0) * 1e3, 1),
            compute_ms=round((t2 - t1) * 1e3, 1),
            push_overlap_ms=0.0, push_chunks=1)
        return root_words, levels

    from ..parallel.pipeline import ChunkStager

    t0 = time.perf_counter()
    host = _registry_raw_columns(reg, m)
    chunks = [{k: v[b:b + chunk] for k, v in host.items()}
              for b in range(0, m, chunk)]
    stager = ChunkStager(chunks, subsystem="staging")
    if _record_roots_jit is None:
        _record_roots_jit = jax.jit(_record_roots_body,
                                    static_argnames=("use_kernel",))
        _levels_combine_jit = jax.jit(
            _levels_combine_body, static_argnames=("n", "w", "use_kernel"))
    recs = [_record_roots_jit(dev, use_kernel=use_kernel)
            for dev in stager]
    rec = recs[0] if len(recs) == 1 else jnp.concatenate(recs, axis=0)
    levels = _levels_combine_jit(rec, n=n, w=w, use_kernel=use_kernel)
    root_words = np.asarray(levels[-1])[0]  # device-io: staging
    wall = time.perf_counter() - t0
    LAST_COLD_TIMINGS.update(
        push_ms=round(stager.wait_s * 1e3, 1),
        compute_ms=round(max(wall - stager.wait_s, 0.0) * 1e3, 1),
        push_overlap_ms=round(
            max(stager.transfer_s - stager.wait_s, 0.0) * 1e3, 1),
        push_chunks=len(chunks),
        push_fallbacks=stager.fallbacks)
    return root_words, levels


# ---------------------------------------------------------------------------
# Device-resident registry Merkleization (one fused dispatch)
# ---------------------------------------------------------------------------
#
# The per-level eager pipeline bounces (n, 8) arrays host↔device between
# launches (hundreds of MB per root).  Production shape: the registry columns live in HBM
# (SURVEY §7 hard-part 3) and ONE jitted program computes record roots,
# the fused chunk reduction and the zero-cap fold, returning 32 bytes.

def registry_device_columns(reg: "ValidatorRegistry") -> dict:
    """Push the registry columns to the device once (HBM residency)."""
    from ..parallel.mesh import mesh_put
    n = reg._n
    host = {
        "pubkey": bytes_col_to_words(reg._pubkey[:n]),
        "withdrawal_credentials":
            bytes_col_to_words(reg._withdrawal_credentials[:n]),
        "effective_balance": u64_to_chunk_words(reg._effective_balance[:n]),
        "slashed": u64_to_chunk_words(reg._slashed[:n].astype(np.uint64)),
        "activation_eligibility_epoch":
            u64_to_chunk_words(reg._activation_eligibility_epoch[:n]),
        "activation_epoch": u64_to_chunk_words(reg._activation_epoch[:n]),
        "exit_epoch": u64_to_chunk_words(reg._exit_epoch[:n]),
        "withdrawable_epoch":
            u64_to_chunk_words(reg._withdrawable_epoch[:n]),
    }
    return {k: mesh_put("registry_cols", v, subsystem="staging")
            for k, v in host.items()}


def _registry_root_fused(cols: dict, *, depth: int, chunk_log2: int,
                         use_kernel: bool):
    """Device body, expansion-tree form: the registry tree over record
    roots is exactly the tree over ``8n`` leaves
    ``[pubkey_root, wc, eff, slashed, 4 epochs] × n`` (a zero record's
    root equals the zero-subtree hash, so padding semantics coincide).
    The Pallas chunk kernel therefore swallows the per-record mini-trees
    and the registry levels in one pass; only the 48-byte pubkey pre-hash
    runs as its own (also Pallas) level."""
    import jax.numpy as jnp
    from ..ops.merkle import ZERO_HASHES
    from ..ops.merkle_kernel import _chunk_roots_natural_impl, hash64_pallas

    pk = cols["pubkey"]                       # (n, 12) words
    n = pk.shape[0]
    pk_lo = pk[:, :8]
    pk_hi = jnp.pad(pk[:, 8:], ((0, 0), (0, 4)))
    if use_kernel and n >= (1 << 15):
        pubkey_root = hash64_pallas(pk_lo, pk_hi)
    else:
        pubkey_root = hash64(pk_lo, pk_hi)
    leaves = jnp.stack([
        pubkey_root,
        cols["withdrawal_credentials"],
        cols["effective_balance"],
        cols["slashed"],
        cols["activation_eligibility_epoch"],
        cols["activation_epoch"],
        cols["exit_epoch"],
        cols["withdrawable_epoch"],
    ], axis=1).reshape(8 * n, 8)              # 8n-leaf expansion tree
    g = _chunk_roots_natural_impl(leaves, chunk_log2, use_kernel)
    lvl = chunk_log2
    while g.shape[0] > 1:
        g = hash64(g[0::2], g[1::2])
        lvl += 1
    root = g[0]
    # Zero caps: the registry list pads with zero CHUNKS at the
    # record-root level, so cap siblings are record-level zero hashes —
    # expansion level ℓ pairs with ZERO_HASHES[ℓ − 3].
    while lvl < depth + 3:
        root = hash64(root, jnp.asarray(ZERO_HASHES[lvl - 3]))  # device-io: registry_mirror
        lvl += 1
    return root


_registry_root_jit = None


def registry_root_device(cols: dict, count: int, limit: int) -> bytes:
    """Registry ``hash_tree_root`` from device-resident columns — one
    dispatch, 32 bytes pulled back.  ``count`` must be a power of two
    ≥ the Pallas chunk size (pad rows to reach it)."""
    import jax
    from functools import partial
    from ..ops.merkle import mix_in_length_host
    from ..ops.merkle_kernel import CHUNK_LOG2, _use_pallas
    from ..ops.sha256 import words_to_bytes

    depth = max((int(limit) - 1).bit_length(), 0)
    if _use_pallas():
        global _registry_root_jit
        if _registry_root_jit is None:
            _registry_root_jit = jax.jit(
                partial(_registry_root_fused),
                static_argnames=("depth", "chunk_log2", "use_kernel"))
        root = _registry_root_jit(cols, depth=depth, chunk_log2=CHUNK_LOG2,
                                  use_kernel=True)
    else:
        # Off-TPU (tests): run eagerly — XLA-CPU takes minutes to compile
        # the jitted unrolled compression chain the Mosaic kernel replaces.
        root = _registry_root_fused(cols, depth=depth,
                                    chunk_log2=CHUNK_LOG2, use_kernel=False)
    return mix_in_length_host(words_to_bytes(np.asarray(root)), count)


# ---------------------------------------------------------------------------
# Device-resident registry mirror: HBM columns + record-root tree as the
# hashing source of truth
# ---------------------------------------------------------------------------
#
# ``registry_cold_device`` (above) pushes the raw columns for EVERY cold
# root and pulls the interior levels back to host — the 5.1 s
# ``state_root_cold_push_ms`` of BENCH_LATEST.  The mirror makes that push a
# ONE-TIME materialization: the raw columns and every tree level stay in
# HBM, ``wcol``/``set``/``append`` dirty marks become per-root record
# scatters (k raw rows up, 32 bytes down), and the rebuild crossover
# (dirty > width/8) re-reduces from the HBM-resident columns with zero
# push.  ``share()`` gives copy-on-write clones for the fork-choice state
# cache: buffers are shared until either lineage mutates (the update
# program runs undonated and lands in fresh buffers).

def _registry_raw_rows(reg: "ValidatorRegistry", idx: np.ndarray) -> dict:
    """Raw-form marshalling of ``idx`` records (same column encodings as
    :func:`_registry_raw_columns`, k rows instead of the full width)."""
    rows = {
        "pubkey": bytes_col_to_words(reg._pubkey[idx]),
        "withdrawal_credentials": bytes_col_to_words(
            reg._withdrawal_credentials[idx]),
        "slashed": reg._slashed[idx].astype(np.uint8),
    }
    for f in ("effective_balance",) + _EPOCH_FIELDS:
        rows[f] = np.ascontiguousarray(
            getattr(reg, "_" + f)[idx]).view(np.uint32).reshape(-1, 2)
    return rows


def _pad_rows_bucket(idx: np.ndarray, rows: dict) -> tuple:
    """Bucket-pad a record scatter — :func:`..ops.device_tree.pad_bucket`
    applied per raw column (duplicating the first (index, raw row) pair is
    idempotent: it scatters the same record and re-hashes the same path)."""
    from ..ops.device_tree import pad_bucket
    pidx = idx.astype(np.int32, copy=False)
    out = {}
    for name, arr in rows.items():
        pidx, out[name] = pad_bucket(idx, arr)
    return pidx, out


def _mirror_scatter_body(levels, cols, idx, rows):
    """The fused warm-root program: scatter the raw rows into the HBM
    columns, re-hash exactly those records' 8-leaf mini-trees, and
    propagate their ancestor paths through the record-root tree — leaf
    re-hash → level propagation as ONE jitted dispatch."""
    from ..ops.device_tree import scatter_propagate_body
    new_cols = {k: cols[k].at[idx].set(rows[k]) for k in cols}
    rec = _record_roots_body(rows, use_kernel=False)  # k records: XLA h64
    return new_cols, scatter_propagate_body(levels, idx, rec)


def _mirror_rebuild_body(cols, n_arr, *, use_kernel: bool):
    """Full re-reduction from the HBM-resident columns (dirty fraction
    past the walk/rebuild crossover, or width growth) — zero push.  Rows
    at or beyond the dynamic record count ``n_arr`` are masked to zero
    CHUNKS (SSZ list padding), so one compiled artifact per width serves
    every count."""
    import jax.numpy as jnp
    rec = _record_roots_body(cols, use_kernel=use_kernel)
    w = rec.shape[0]
    keep = (jnp.arange(w, dtype=jnp.uint32) < n_arr)[:, None]
    rec = jnp.where(keep, rec, jnp.zeros_like(rec))
    h64 = _h64_device(use_kernel)
    levels = [rec]
    cur = rec
    while cur.shape[0] > 1:
        cur = h64(cur[0::2], cur[1::2])
        levels.append(cur)
    return tuple(levels)


_mirror_scatter_jits: dict = {}
_mirror_rebuild_jit = None


def _get_mirror_scatter_jit(donate: bool):
    import jax
    jit = _mirror_scatter_jits.get(donate)
    if jit is None:
        jit = (jax.jit(_mirror_scatter_body, donate_argnums=(0, 1))
               if donate else jax.jit(_mirror_scatter_body))
        _mirror_scatter_jits[donate] = jit
    return jit


def _get_mirror_rebuild_jit():
    global _mirror_rebuild_jit
    import jax
    if _mirror_rebuild_jit is None:
        _mirror_rebuild_jit = jax.jit(_mirror_rebuild_body,
                                      static_argnames=("use_kernel",))
    return _mirror_rebuild_jit


_mirror_rebuild_mesh_programs: dict = {}


def _get_mirror_rebuild_mesh(mesh, local_w: int):
    """The rebuild as a mesh program: record mini-trees + the level fold
    are per-shard over a contiguous record range (the SSZ count mask
    needs GLOBAL row indices, so the shard offsets its ``arange`` by
    ``axis_index * local_w``); the top ``log2(ndev)`` levels fold past
    the shard boundary.  Bit-identical to ``_mirror_rebuild_body``
    (same fold order; XLA hash64 — the Pallas lane floor exceeds a
    shard's rows at differential widths)."""
    key = (mesh, local_w)
    prog = _mirror_rebuild_mesh_programs.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import BATCH_AXIS, mesh_program
    from ..parallel.merkle_shard import _get_top_fold
    from ..ops.sha256 import hash64

    def local_levels(cols, n_arr):
        rec = _record_roots_body(cols, use_kernel=False)
        base = jax.lax.axis_index(BATCH_AXIS).astype(jnp.uint32) \
            * jnp.uint32(local_w)
        keep = (base + jnp.arange(local_w, dtype=jnp.uint32)
                < n_arr)[:, None]
        rec = jnp.where(keep, rec, jnp.zeros_like(rec))
        levels = [rec]
        cur = rec
        while cur.shape[0] > 1:
            cur = hash64(cur[0::2], cur[1::2])
            levels.append(cur)
        return tuple(levels)

    n_local = local_w.bit_length()  # log2(local_w) + 1 local levels
    lower = mesh_program(
        local_levels, mesh=mesh,
        in_specs=(P(BATCH_AXIS), P()),
        out_specs=tuple(P(BATCH_AXIS) for _ in range(n_local)))

    def run(cols, n_arr):
        low = lower(cols, n_arr)
        return tuple(low) + tuple(_get_top_fold()(low[-1]))

    _mirror_rebuild_mesh_programs[key] = run
    return run


def _mirror_levels(cols: dict, n: int):
    """Every record-tree level from the HBM columns: the sharded mesh
    program when the process mesh has >1 shard and the width divides it,
    else the 1-device fused body."""
    from ..ops.merkle_kernel import _use_pallas
    from ..parallel import mesh as pmesh
    w = cols["slashed"].shape[0]
    ndev = pmesh.axis_size()
    if ndev > 1 and ndev & (ndev - 1) == 0 and w % ndev == 0 \
            and w // ndev >= 2:
        return _get_mirror_rebuild_mesh(pmesh.get_mesh(), w // ndev)(
            cols, np.uint32(n))
    return _get_mirror_rebuild_jit()(cols, np.uint32(n),
                                     use_kernel=_use_pallas())


class DeviceRegistryMirror:
    """HBM-resident raw columns + record-root tree for one registry
    lineage (COW across :meth:`ValidatorRegistry.copy`)."""

    __slots__ = ("cols", "tree", "shared", "_res", "__weakref__")

    def __init__(self, cols: dict, tree, shared: bool = False):
        self.cols = cols
        self.tree = tree
        self.shared = shared
        self._res = None

    @property
    def width(self) -> int:
        return self.cols["slashed"].shape[0]

    def note_residency(self) -> None:
        """Ledger watermark seam: this mirror's HBM columns + record
        tree (a share() clone counts nothing until it diverges — the
        parent owns the shared buffers)."""
        from ..common.device_ledger import LEDGER
        total = sum(int(v.nbytes) for v in self.cols.values()) \
            + sum(int(lv.nbytes) for lv in self.tree.levels)
        if self._res is None:
            self._res = LEDGER.track(self, "registry_mirror", total)
        else:
            self._res.set(total)

    @classmethod
    def materialize(cls, reg: "ValidatorRegistry") -> "DeviceRegistryMirror":
        """One-time column push (chunk-staged for big registries, like the
        cold build) + in-HBM level reduction.  This is the LAST full-width
        push this lineage ever makes."""
        import jax.numpy as jnp
        from ..common.device_ledger import LEDGER
        from ..ops.device_tree import DeviceTree
        from ..ops.merkle import _next_pow2
        from ..parallel.mesh import mesh_place, mesh_put

        n = reg._n
        w = _next_pow2(max(n, 1))
        with LEDGER.attribute("registry_mirror"):
            host = _registry_raw_columns(reg, w)
            LEDGER.note_event("materializes")
            chunk = _reg_chunk_rows()
            if chunk > 0 and w > chunk and w % chunk == 0:
                from ..parallel.pipeline import ChunkStager
                chunks = [{k: v[b:b + chunk] for k, v in host.items()}
                          for b in range(0, w, chunk)]
                # subsystem=None: the streamed push settles its wire
                # total + per-shard split at the mesh_place seam below —
                # the stager must not double-count it.
                parts = list(ChunkStager(chunks, subsystem=None))
                cols = {k: mesh_place(
                            "registry_cols",
                            jnp.concatenate([p[k] for p in parts],
                                            axis=0),
                            h2d_bytes=host[k].nbytes)
                        for k in host}
            else:
                cols = {k: mesh_put("registry_cols", v)
                        for k, v in host.items()}
            levels = _mirror_levels(cols, n)
            from ..ops.tree_cache import HASH_COUNT
            HASH_COUNT[0] += 8 * w + (w - 1)
            mirror = cls(cols, DeviceTree(levels), False)
            mirror.note_residency()
            return mirror

    def scatter_records(self, reg: "ValidatorRegistry",
                        idx: np.ndarray) -> np.ndarray:
        """Land ``idx`` dirty records as one fused device dispatch; returns
        the new subtree root words.  H2D = the bucket-padded raw rows
        (the replicated ``registry_dirty`` mesh family)."""
        from ..common.device_ledger import LEDGER
        from ..ops.device_tree import _donation_works
        from ..ops.tree_cache import HASH_COUNT
        from ..parallel.mesh import mesh_put

        with LEDGER.attribute("registry_mirror"):
            pidx, rows = _pad_rows_bucket(np.asarray(idx),
                                          _registry_raw_rows(reg, idx))
            LEDGER.note_event("scatters")
            HASH_COUNT[0] += pidx.shape[0] * (8 + len(self.tree.levels) - 1)
            jit = _get_mirror_scatter_jit(
                _donation_works() and not self.shared
                and not self.tree.shared)
            self.cols, self.tree.levels = jit(
                self.tree.levels, self.cols,
                mesh_put("registry_dirty", pidx),
                {k: mesh_put("registry_dirty", v)
                 for k, v in rows.items()})
            self.shared = False
            self.tree.shared = False
            self.note_residency()
            return self.tree.root_words()

    def scatter_cols(self, reg: "ValidatorRegistry",
                     idx: np.ndarray) -> None:
        """Update only the HBM columns at ``idx`` (no tree propagation) —
        the prelude to :meth:`rebuild` when the dirty fraction or a width
        change makes path-walking the wrong tool."""
        from ..common.device_ledger import LEDGER
        from ..parallel.mesh import mesh_put

        with LEDGER.attribute("registry_mirror"):
            pidx, rows = _pad_rows_bucket(np.asarray(idx),
                                          _registry_raw_rows(reg, idx))
            idx_dev = mesh_put("registry_dirty", pidx)
            for k in self.cols:
                self.cols[k] = self.cols[k].at[idx_dev].set(
                    mesh_put("registry_dirty", rows[k]))
            self.shared = False

    def rebuild(self, n: int) -> np.ndarray:
        """Re-reduce every level from the HBM columns — zero push (a
        sharded mesh program when the process mesh has >1 shard)."""
        from ..common.device_ledger import LEDGER
        from ..ops.tree_cache import HASH_COUNT

        LEDGER.note_event("rebuilds", subsystem="registry_mirror")
        w = self.width
        HASH_COUNT[0] += 8 * w + (w - 1)
        self.tree.levels = _mirror_levels(self.cols, n)
        self.tree.shared = False
        self.note_residency()
        return self.tree.root_words()

    def ensure_width(self, new_w: int) -> bool:
        """Grow the HBM columns to ``new_w`` rows (device-side zero pad —
        pad rows are masked at rebuild, their values never hashed).
        Returns True when the width changed (caller must rebuild)."""
        import jax.numpy as jnp
        from ..parallel.mesh import mesh_place
        w = self.width
        if new_w <= w:
            return False
        for k, v in self.cols.items():
            pad = jnp.zeros((new_w - w,) + v.shape[1:], dtype=v.dtype)
            self.cols[k] = mesh_place(
                "registry_cols", jnp.concatenate([v, pad], axis=0))
        self.shared = False  # concat produced buffers only we hold
        self.note_residency()
        return True

    def share(self) -> "DeviceRegistryMirror":
        self.shared = True
        return DeviceRegistryMirror(dict(self.cols), self.tree.share(),
                                    shared=True)


_registry_type_cache: dict[int, type] = {}


def ValidatorRegistryList(limit: int) -> type:
    """SSZ type for ``List[Validator, limit]`` backed by the SoA registry."""
    cls = _registry_type_cache.get(limit)
    if cls is not None:
        return cls

    from ..ssz.core import SszType

    class _RegistryList(SszType):
        ELEM = Validator
        LIMIT = limit

        @classmethod
        def is_fixed_size(cls) -> bool:
            return False

        @classmethod
        def serialize(cls, value) -> bytes:
            if isinstance(value, ValidatorRegistry):
                if len(value) > cls.LIMIT:
                    raise SszError("validator registry exceeds limit")
                return value.to_packed()
            return ValidatorRegistry.from_validators(value).to_packed()

        @classmethod
        def deserialize(cls, data: bytes) -> ValidatorRegistry:
            out = ValidatorRegistry.from_packed(data)
            if len(out) > cls.LIMIT:
                raise SszError("validator registry exceeds limit")
            return out

        @classmethod
        def hash_tree_root(cls, value) -> bytes:
            if not isinstance(value, ValidatorRegistry):
                value = ValidatorRegistry.from_validators(value)
            return value.hash_tree_root(cls.LIMIT)

        @classmethod
        def default(cls) -> ValidatorRegistry:
            return ValidatorRegistry()

    _RegistryList.__name__ = f"ValidatorRegistryList[{limit}]"
    _registry_type_cache[limit] = _RegistryList
    return _RegistryList
