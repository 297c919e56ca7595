"""The warm scatter's two compression routes give the same bits.

``scatter_propagate_body`` re-hashes each level's dirty paths either with
the ``lax.scan`` ``hash64`` or, on one TPU device, with the unrolled
Pallas compression (``merkle_kernel.hash64_pallas``).  The kernel cannot
run here, so it is stood in for two ways:

- eagerly, by the kernel's own body (``_hash64_pallas_kernel``) over each
  block with NumPy arrays as its refs — the arithmetic the chip compiles;
- inside a jitted (donated or undonated) program, by a host callback into
  hashlib.

Both stand-ins refuse a block the chip's tiling would refuse (lanes not a
multiple of the block, a block under 128 lanes or over 2^15), so the
route's lane padding and block choice are checked too.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.ops import device_tree as DT
from lighthouse_tpu.ops import merkle_kernel as MK
from lighthouse_tpu.ops.sha256 import (bytes_to_words, sha256_host,
                                       words_to_bytes)


def _check_block(n: int, block_log2: int) -> None:
    b = 1 << block_log2
    assert 128 <= b <= 1 << 15 and n % b == 0, (n, b)


def _kernel_body(left, right, block_log2=15):
    """``hash64_pallas`` with the kernel body run eagerly per block."""
    n = left.shape[0]
    _check_block(n, block_log2)
    b = 1 << block_log2
    lp, rp = np.asarray(left).T, np.asarray(right).T
    out = np.empty((8, n), dtype=np.uint32)
    for i in range(0, n, b):
        MK._hash64_pallas_kernel(lp[:, i:i + b], rp[:, i:i + b],
                                 out[:, i:i + b])
    return jnp.asarray(out.T)


def _host_pairs(left, right):
    return np.stack([
        bytes_to_words(sha256_host(words_to_bytes(a) + words_to_bytes(c)))
        for a, c in zip(np.asarray(left), np.asarray(right))])


def _kernel_callback(left, right, block_log2=15):
    """``hash64_pallas`` as a host callback (traceable under jit)."""
    _check_block(left.shape[0], block_log2)
    return jax.pure_callback(
        _host_pairs, jax.ShapeDtypeStruct(left.shape, jnp.uint32),
        left, right)


@pytest.fixture
def stand_in(monkeypatch):
    """Install a kernel stand-in with fresh jit caches (a traced stand-in
    must not leak into another test's program)."""
    def install(fake):
        jax.clear_caches()
        monkeypatch.setattr(MK, "hash64_pallas", fake)
        for name in ("_hash64_kernel_jit", "_scatter_jit",
                     "_scatter_jit_donated"):
            monkeypatch.setattr(DT, name, None)
    yield install
    jax.clear_caches()


def _tree(width_log2: int, rng):
    leaves = rng.integers(0, 2**32, size=(1 << width_log2, 8),
                          dtype=np.uint32)
    return DT._get_levels_jit()(jnp.asarray(leaves), use_kernel=False)


def _dirty(width_log2: int, bucket: int, rng):
    """A bucket of dirty (index, row) pairs: unique real indices, padded
    to ``bucket`` (which may pass the width) by repeating the first, as
    ``pad_bucket`` does."""
    w = 1 << width_log2
    k = max(1, min(w, bucket) - 3)
    idx = np.sort(rng.choice(w, size=k, replace=False)).astype(np.int32)
    rows = rng.integers(0, 2**32, size=(k, 8), dtype=np.uint32)
    pidx = np.concatenate([idx, np.full(bucket - k, idx[0], np.int32)])
    prows = np.concatenate([rows, np.repeat(rows[:1], bucket - k, axis=0)])
    return pidx, prows


# (log2 width, bucket): buckets below, at and above the width, under the
# kernel's 128-lane tile (padded) and at or over it.
CASES = [(3, 8), (3, 16), (6, 8), (6, 64), (6, 128), (9, 8), (9, 128),
         (9, 512), (9, 1024), (12, 64), (12, 8192)]


@pytest.mark.parametrize("width_log2,bucket", CASES)
def test_kernel_route_levels_match_scan(stand_in, width_log2, bucket):
    stand_in(_kernel_body)
    rng = np.random.default_rng(width_log2 * 100_003 + bucket)
    levels = _tree(width_log2, rng)
    idx, rows = _dirty(width_log2, bucket, rng)
    scan = DT._get_scatter_jit(False)(levels, jnp.asarray(idx),
                                      jnp.asarray(rows), use_kernel=False)
    with jax.disable_jit():
        kern = DT.scatter_propagate_body(levels, jnp.asarray(idx),
                                         jnp.asarray(rows), use_kernel=True)
    assert len(kern) == len(scan) == width_log2 + 1
    for a, b in zip(kern, scan):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Both against a full rebuild over the updated leaves.
    full = DT._get_levels_jit()(scan[0], use_kernel=False)
    for a, b in zip(full, scan):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _levels_counter(route: str) -> float:
    return REGISTRY.counter(
        "device_tree_scatter_levels_total",
        labelnames=("route",)).labels(route=route).value


@pytest.mark.parametrize("donate", [False, True])
@pytest.mark.parametrize("width_log2,k", [(5, 3), (10, 200)])
def test_kernel_route_scatter_program(stand_in, monkeypatch, donate,
                                      width_log2, k):
    """The jitted warm scatter on the kernel route, through DeviceTree:
    the same root and levels as the scan route, donated or not, and each
    level counted once under the route taken."""
    stand_in(_kernel_callback)
    rng = np.random.default_rng(width_log2 + 17 * k)
    leaves = rng.integers(0, 2**32, size=(1 << width_log2, 8),
                          dtype=np.uint32)
    idx = np.sort(rng.choice(1 << width_log2, size=k, replace=False))
    rows = rng.integers(0, 2**32, size=(k, 8), dtype=np.uint32)

    monkeypatch.setattr(DT, "_donation_works", lambda: donate)
    roots = {}
    for route in ("scan", "kernel"):
        monkeypatch.setattr(DT, "_use_kernel", lambda r=route: r == "kernel")
        tree = DT.DeviceTree.from_host_leaves(leaves)
        before = {r: _levels_counter(r) for r in ("scan", "kernel")}
        roots[route] = (tree.scatter(idx, rows), tree.pull_levels())
        assert _levels_counter(route) - before[route] == width_log2
        other = "scan" if route == "kernel" else "kernel"
        assert _levels_counter(other) == before[other]
    np.testing.assert_array_equal(roots["kernel"][0], roots["scan"][0])
    for a, b in zip(roots["kernel"][1], roots["scan"][1]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("tpu,mesh_devices,expected", [
    (False, 1, False), (True, 1, True), (True, 4, False)])
def test_route_rule(monkeypatch, tpu, mesh_devices, expected):
    """The kernel only where Mosaic lowers it and the levels sit on one
    device; the scan body on the CPU and on a sharded mesh."""
    from lighthouse_tpu.parallel import mesh as pmesh

    monkeypatch.setattr(DT, "_use_kernel", lambda: tpu)
    monkeypatch.setattr(pmesh, "axis_size", lambda mesh=None: mesh_devices)
    assert DT.scatter_uses_kernel() is expected
