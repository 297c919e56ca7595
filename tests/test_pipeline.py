"""The staged device pipeline (`lighthouse_tpu/parallel/pipeline.py`):
overlap correctness under injected transfer failure, donation safety,
chunked-push equivalence, and the persistent compile-cache round trip.

Everything here runs under ``JAX_PLATFORMS=cpu`` (the conftest forces
it): the pipeline's *structure* — splitting, staging, fallback, combine
— is backend-independent, and the heavy crypto kernels are pinned by
their own suites, so these tests mock them where a real compile would
cost minutes on one CPU core.
"""

import gc
import weakref

import numpy as np
import pytest

from lighthouse_tpu.parallel.pipeline import ChunkStager, StagedExecutor


def _failing_stage(host):
    raise RuntimeError("injected transfer failure")


def test_staged_executor_end_to_end_cpu():
    """Tier-1 smoke: prep → async device_put → jitted dispatch, results
    correct and every item accounted for."""
    import jax
    import jax.numpy as jnp

    ex = StagedExecutor("test_smoke")
    items = [np.arange(16, dtype=np.int32) + i for i in range(5)]
    outs = ex.map(items,
                  prep=lambda x: {"a": x, "b": x * 2},
                  dispatch=lambda s: jax.jit(
                      lambda a, b: (a + b).sum())(s["a"], s["b"]))
    got = [int(jnp.asarray(o)) for o in outs]
    want = [int((x + x * 2).sum()) for x in items]
    assert got == want
    assert ex.stats["items"] == 5
    assert ex.stats["fallbacks"] == 0
    # everything after the first dispatch marshalled under an in-flight
    # device call
    eff = ex.overlap_efficiency()
    assert eff is not None and 0.0 <= eff <= 1.0


def test_staged_executor_fallback_identical():
    """A failed async transfer falls back to synchronous staging: the
    results are bit-identical to the healthy pipeline, only the overlap
    is lost (and counted)."""
    items = [np.arange(8, dtype=np.uint32) * (i + 1) for i in range(4)]

    def run(stage):
        ex = StagedExecutor("test_fb", stage=stage)
        outs = ex.map(items, prep=lambda x: x + 1,
                      dispatch=lambda d: np.asarray(d).sum())
        return [int(o) for o in outs], ex.stats["fallbacks"]

    healthy, fb0 = run(None)
    degraded, fb1 = run(_failing_stage)
    assert healthy == degraded
    assert fb0 == 0 and fb1 == len(items)


def test_staged_executor_fallback_on_deferred_transfer_failure():
    """An async device_put defers transfer errors to the point of
    consumption — i.e. they surface inside DISPATCH, not the staging
    call.  The executor must re-stage synchronously and retry the
    dispatch once, yielding results identical to a healthy run."""
    items = [np.arange(8, dtype=np.uint32) * (i + 1) for i in range(3)]

    def poisoned_stage(host):
        return object()  # "transfer" that breaks when consumed

    def dispatch(staged):
        return staged.sum()  # consumption raises on the poisoned object

    ex = StagedExecutor("test_deferred", stage=poisoned_stage)
    outs = ex.map(items, prep=lambda x: x + 1, dispatch=dispatch)
    assert [int(o) for o in outs] == [int((x + 1).sum()) for x in items]
    assert ex.stats["fallbacks"] == len(items)


def test_staged_executor_releases_host_buffers():
    """Donation safety: the executor drops its references to the
    marshalled host arrays and the staged buffers as soon as the
    dispatch is issued — nothing can re-read a donated buffer."""
    refs = []

    def prep(i):
        arr = np.full(64, i, dtype=np.uint32)
        refs.append(weakref.ref(arr))
        return arr

    ex = StagedExecutor("test_drop")
    outs = ex.map(range(3), prep=prep,
                  dispatch=lambda d: int(np.asarray(d)[0]))
    assert outs == [0, 1, 2]
    gc.collect()
    assert all(r() is None for r in refs), \
        "executor retained marshalled host buffers after dispatch"


def test_chunk_stager_orders_chunks_and_survives_failure():
    """ChunkStager yields staged chunks in order; a background transfer
    failure degrades that chunk to a synchronous push with identical
    data."""
    chunks = [np.arange(8, dtype=np.uint32) + 10 * i for i in range(5)]
    got = [np.asarray(c) for c in ChunkStager(list(chunks))]
    assert all(np.array_equal(g, c) for g, c in zip(got, chunks))

    st = ChunkStager(list(chunks), stage=_failing_stage)
    got = [np.asarray(c) for c in st]
    assert all(np.array_equal(g, c) for g, c in zip(got, chunks))
    assert st.fallbacks == len(chunks)


def test_merkle_levels_device_chunked_identical():
    """The chunked streamed build produces bit-identical levels to the
    monolithic push (same tree, different transfer schedule)."""
    from lighthouse_tpu.ops import merkle_kernel as MK

    MK.reset_push_stats()
    leaves = (np.arange(64 * 8, dtype=np.uint32) * 2654435761).reshape(
        64, 8).astype(np.uint32)
    r_mono, lv_mono = MK.merkle_levels_device(leaves, chunk_rows=0)
    r_chunk, lv_chunk = MK.merkle_levels_device(leaves, chunk_rows=16)
    assert np.array_equal(r_mono, r_chunk)
    assert len(lv_mono) == len(lv_chunk)
    for a, b in zip(lv_mono, lv_chunk):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert MK.LAST_PUSH_STATS["chunks"] == 4
    # host-reference root: same leaves through the incremental cache path
    from lighthouse_tpu.ops.merkle import hash64_host_words
    cur = leaves
    while cur.shape[0] > 1:
        cur = hash64_host_words(cur[0::2], cur[1::2])
    assert np.array_equal(cur[0], r_chunk)


def test_registry_cold_chunked_identical(monkeypatch):
    """The chunked registry cold build (streamed columns + per-chunk
    record-root programs + combine) equals the monolithic device body
    AND the host-spec record roots."""
    from lighthouse_tpu.types import validators as V

    rng = np.random.default_rng(7)
    n = 60
    reg = V.ValidatorRegistry(n)
    reg._n = n
    reg.init_columns(
        pubkey=rng.integers(0, 256, (n, 48), dtype=np.uint8),
        withdrawal_credentials=rng.integers(0, 256, (n, 32),
                                            dtype=np.uint8),
        effective_balance=rng.integers(0, 2**35, n).astype(np.uint64),
        slashed=rng.integers(0, 2, n).astype(bool),
        activation_eligibility_epoch=rng.integers(
            0, 2**20, n).astype(np.uint64),
        activation_epoch=rng.integers(0, 2**20, n).astype(np.uint64),
        exit_epoch=rng.integers(0, 2**20, n).astype(np.uint64),
        withdrawable_epoch=rng.integers(0, 2**20, n).astype(np.uint64))
    # shrink the Pallas row pad so the chunked path runs at test scale
    monkeypatch.setattr(V, "_PALLAS_PAD", 8)
    r_mono, lv_mono = V.registry_cold_device(reg, chunk_rows=0)
    r_chunk, lv_chunk = V.registry_cold_device(reg, chunk_rows=16)
    assert np.array_equal(r_mono, r_chunk)
    assert len(lv_mono) == len(lv_chunk)
    for a, b in zip(lv_mono, lv_chunk):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(lv_chunk[0])[:n],
                          reg.record_roots_words())
    assert V.LAST_COLD_TIMINGS["push_chunks"] == 4
    assert "push_overlap_ms" in V.LAST_COLD_TIMINGS


def test_bls_split_batches_grouping_and_guard(monkeypatch):
    """Sub-batching groups by the K bucket and splits at the pipeline
    size — EXCEPT when one signature covers the whole entry list
    (aggregate_verify), where splitting would drop the σ lane from all
    but one sub-batch."""
    from lighthouse_tpu.crypto import tpu_backend as TB

    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "2")
    entries = [("sig%d" % i, ["k"] * (3 if i % 2 else 1), b"m")
               for i in range(10)]
    work = TB._split_batches(entries)
    assert [len(b) for b in work] == [2, 2, 1, 2, 2, 1]  # per K group
    # every sub-batch is K-homogeneous
    for batch in work:
        ks = {len(e[1]) for e in batch}
        assert len(ks) == 1
    agg = [(None, ["k"], b"m") for _ in range(10)]
    agg[0] = ("sig", ["k"], b"m")
    assert [len(b) for b in TB._split_batches(agg)] == [10]
    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "0")
    assert [len(b) for b in TB._split_batches(entries)] == [5, 5]


def test_bls_pipeline_verdicts_bit_identical(monkeypatch):
    """The pipelined dispatch (sub-batch split + staged executor + AND
    combine) returns the same verdict as the monolithic path for both
    accepting and rejecting batches.  The pairing kernel is mocked — a
    real CPU compile costs minutes and the kernel's arithmetic is pinned
    by its own suite; this pins the ORCHESTRATION."""
    from lighthouse_tpu.crypto import curve as C
    from lighthouse_tpu.crypto import tpu_backend as TB

    poison = TB._h_arr(b"poison")

    def fake_kernel(pk, kmask, sig, h, scal, smask):
        # reject iff any live set carries the poison message
        h = np.asarray(h)
        return not any(np.array_equal(h[i], poison)
                       for i in range(h.shape[0]))

    monkeypatch.setattr(TB, "_verify_sets_kernel", fake_kernel)
    good = [(C.G2_GEN, [C.G1_GEN], b"msg-%d" % i) for i in range(5)]
    bad = list(good)
    bad[3] = (C.G2_GEN, [C.G1_GEN], b"poison")
    for entries, want in ((good, True), (bad, False)):
        monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "0")
        mono = TB._dispatch(list(entries), lambda: 1)
        monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "2")
        piped = TB._dispatch(list(entries), lambda: 1)
        assert mono == piped == want


@pytest.mark.parametrize("flags,want", [
    ((0, 0, 0), True), ((0, 1, 0), False), ((1, 0, 0), False),
    ((0, 0, 1), False)])
def test_identity_flag_carried_through_sub_batches(monkeypatch, flags,
                                                   want):
    """The device route carries ONE fixed-shape identity-key flag through
    its sub-batches in dispatch order (``_cell_bad`` ORs each chunk in),
    and the verdict is the product check AND NOT the flag: a flag raised
    by any sub-batch refuses the call, whatever the call's chunk count.
    The per-chunk kernels are stood in for; the flag programs are real."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto import tpu_backend as TB

    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "2")
    carried = []

    def cells(table, staged, bad):
        carried.append(bool(bad))
        live = np.ones((1, 128), np.int32)
        flag = np.full((1, 128), flags[len(carried) - 1], np.int32)
        return np.zeros((384, 128), np.uint32), TB._cell_bad(flag, live,
                                                             bad)

    monkeypatch.setattr(TB, "_pipeline_cells", cells)
    monkeypatch.setattr(TB, "_fold_blocks", lambda blocks: blocks[0])
    monkeypatch.setattr(TB, "_finalize",
                        lambda prod: np.ones((1, 1), np.int32))
    sk = bls.SecretKey(4242)
    entries = [(sk.sign(m).point, [sk.public_key().point], m)
               for m in (b"flag-%d" % i for i in range(6))]
    assert TB._dispatch_pallas(entries, lambda: 1) is want
    assert carried == [any(flags[:i]) for i in range(3)]


def test_donated_entry_points_exist(monkeypatch):
    """The hot path's finalize carries buffer donation on TPU (the
    folded product is batch-local), while the reusable-input entry stays
    undonated for profiling/tests — and is the one selected off-TPU
    (donation is a warning-only no-op on CPU, but the intent is
    explicit)."""
    from lighthouse_tpu.crypto import pairing_kernel as PK
    from lighthouse_tpu.crypto import tpu_backend as TB

    assert PK.finalize_kernel_call_donated is not PK.finalize_kernel_call
    monkeypatch.setattr(PK, "finalize_kernel_call", lambda x: "plain")
    monkeypatch.setattr(PK, "finalize_kernel_call_donated",
                        lambda x: "donated")
    assert TB._finalize(None) == "plain"
    monkeypatch.setattr(TB, "_use_pallas", lambda: True)
    assert TB._finalize(None) == "donated"


def test_compile_cache_roundtrip(tmp_path, monkeypatch):
    """Round trip of the persistent compile cache: a fresh compile lands
    in the directory JAX_COMPILATION_CACHE_DIR names; after
    ``jax.clear_caches()`` (a stand-in for a restarted process sharing
    the dir) the same program compiles WITHOUT adding files — a disk
    hit, not an XLA recompile."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.common import compile_cache as CC

    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cache = CC.enable(min_compile_time_secs=0.0)
    assert cache == str(tmp_path)
    try:
        def fn(x):
            return (x * jnp.float32(3.0) + jnp.float32(1.5)).sum()

        arg = np.arange(41, dtype=np.float32)
        jax.jit(fn)(arg).block_until_ready()
        n1 = len(list(tmp_path.iterdir()))
        assert n1 > 0, "compile did not persist to the cache dir"
        jax.clear_caches()
        jax.jit(fn)(arg).block_until_ready()
        n2 = len(list(tmp_path.iterdir()))
        assert n2 == n1, "second compile missed the persistent cache"
    finally:
        _restore_cache(old_dir, old_min)


def test_warmup_is_graceful_noop_on_cpu():
    """The warmup API must not try to lower the Pallas pipeline off-TPU
    (Mosaic can't, and the XLA twins cost minutes/core): it reports the
    skip instead."""
    import jax

    from lighthouse_tpu.common import compile_cache as CC

    assert jax.default_backend() != "tpu"
    out = CC.warmup()
    assert out.get("skipped") == "cpu"
    assert out.get("compiled") == []


def test_cli_warmup_subcommand(capsys, tmp_path, monkeypatch):
    """`lighthouse-tpu warmup` wires the cache rule + warmup API (CPU:
    reports the no-op and the configured cache dir)."""
    import json

    import jax

    from lighthouse_tpu.cli import main

    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert main(["warmup", "--shapes", "8x1"]) == 0
    finally:
        _restore_cache(old_dir, old_min)
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["skipped"] == "cpu"
    assert out["cache_dir"] == str(tmp_path)


def _restore_cache(old_dir, old_min) -> None:
    """Point the live cache object back at the suite's directory."""
    import jax
    from jax._src import compilation_cache as _cc

    jax.config.update("jax_compilation_cache_dir", old_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)
    _cc.reset_cache()


def test_pipeline_metrics_instrumented():
    """Stage boundaries surface in the Prometheus registry."""
    from lighthouse_tpu.common.metrics import REGISTRY

    ex = StagedExecutor("test_metrics")
    ex.map([np.arange(4)], prep=lambda x: x,
           dispatch=lambda d: np.asarray(d).sum())
    text = REGISTRY.encode()
    assert "test_metrics_host_prep_seconds" in text
    assert "test_metrics_h2d_seconds" in text
