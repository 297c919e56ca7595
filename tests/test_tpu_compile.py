"""The main path's Pallas programs compiled for a TPU v5e that is
described, not attached: what the chip's compiler refuses (scoped VMEM
over the limit, slices off the tiling, casts Mosaic cannot lower) fails
here at no chip time.  Nothing runs, so this pins compilability, not
results — the differential suites and ``chip_smoke.py`` pin those.

The programs are compiled with the MXU band products on (the chip's
default) and the persistent compilation cache off: a described-device
executable is written to the cache but cannot be read back without a
chip.  The quick tier holds the kernels that compile in seconds plus the
Merkle kernel at the 2^20-leaf state width and the warm root's scatter at
the state cell's tree shapes; the BLS kernels that take
tens of seconds to minutes (hash-to-curve, σ fold, Miller cell,
finalize) and the 4-chip sharded verify on the described 2x2 are
``slow``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

S = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_compile(one_chip):
    """``compile(fn, shapes, **static)`` → the compiled text, with the
    MXU band products on and the persistent cache off."""
    from jax._src import compilation_cache as cc

    from lighthouse_tpu.crypto import limb_field as LF

    old_cache = jax.config.jax_enable_compilation_cache
    old_mxu = LF._MXU_FLAG
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    LF._MXU_FLAG = True

    def compile_(fn, shapes, **static):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return fn.lower(*args, **static).compile().as_text()

    yield compile_
    LF._MXU_FLAG = old_mxu
    jax.config.update("jax_enable_compilation_cache", old_cache)
    cc.reset_cache()


U32, I32 = jnp.uint32, jnp.int32


def test_merkle_kernel_state_width(chip_compile):
    from lighthouse_tpu.ops import merkle_kernel as MK

    text = chip_compile(MK.chunk_roots_natural, [((1 << 20, 8), U32)],
                        chunk_log2=MK.CHUNK_LOG2, use_kernel=True)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width_log2,bucket", [
    (15, 1 << 15), (18, 1 << 10), (18, 8)])
def test_warm_scatter_kernel_route(chip_compile, one_chip, width_log2,
                                   bucket):
    """The warm root's donated scatter program on the Pallas route at the
    state cell's two trees (participation 2^15 leaves with a full bucket,
    balances 2^18 with 1,024 dirty chunks) and a bucket padded to the
    kernel's 128-lane tile: one kernel per level."""
    from lighthouse_tpu.ops import device_tree as DT

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    levels = tuple(arg((1 << (width_log2 - i), 8), U32)
                   for i in range(width_log2 + 1))
    text = DT._get_scatter_jit(True).lower(
        levels, arg((bucket,), I32), arg((bucket, 8), U32),
        use_kernel=True).compile().as_text()
    assert text.count("tpu_custom_call") == width_log2


@pytest.mark.parametrize("K", [1, 16])
def test_prepare_cell(chip_compile, K):
    from lighthouse_tpu.crypto import tpu_backend as TB

    text = chip_compile(TB._prepare_cell,
                        [((64, 1 << 15), U32), ((K * S,), I32),
                         ((1, K * S), I32), ((1, S), U32), ((1, S), U32)],
                        K=K)
    assert text.count("tpu_custom_call") >= 1


def test_prepare_cell_committee_width(one_chip):
    """The prepare program at K = 512 (a mainnet committee's aggregate,
    the range-sync cell's bucket) over a 2^20-key table: the 16 MiB
    gathered key block fits the kernel's VMEM and the program the chip."""
    from jax._src import compilation_cache as cc

    from lighthouse_tpu.crypto import limb_field as LF
    from lighthouse_tpu.crypto import tpu_backend as TB

    K = 512
    old_cache, old_mxu = jax.config.jax_enable_compilation_cache, \
        LF._MXU_FLAG
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    LF._MXU_FLAG = True
    try:
        args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
                for shape, dt in (((64, 1 << 21), U32), ((K * S,), I32),
                                  ((1, K * S), I32), ((1, S), U32),
                                  ((1, S), U32))]
        compiled = TB._prepare_cell.lower(*args, K=K).compile()
    finally:
        LF._MXU_FLAG = old_mxu
        jax.config.update("jax_enable_compilation_cache", old_cache)
        cc.reset_cache()
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 4 << 30


def test_product_fold_pair(chip_compile):
    from lighthouse_tpu.crypto import tpu_backend as TB

    text = chip_compile(TB._fold_pair, [((384, S), U32)] * 2)
    assert "tpu_custom_call" in text


def test_exported_fold_pair_for_the_chip(chip_compile, one_chip):
    """The exported-program cache's chip path: the fold program exported
    for the TPU here, read back, and its same-named caller compiled for
    the described v5e — the module keeps the jitted name the device
    trace reads, and the Mosaic kernel survives the round trip."""
    from lighthouse_tpu.common import program_cache as PC
    from lighthouse_tpu.crypto import tpu_backend as TB

    avals = [jax.ShapeDtypeStruct((384, S), U32)] * 2
    exp = jax.export.export(TB._fold_pair.jitted,
                            platforms=("tpu",))(*avals)
    back = jax.export.deserialize(exp.serialize())
    caller = jax.jit(PC._named(back.call, TB._fold_pair.name))
    args = [jax.ShapeDtypeStruct((384, S), U32, sharding=one_chip)] * 2
    text = caller.lower(*args).compile().as_text()
    assert "HloModule jit__fold_pair" in text
    assert "tpu_custom_call" in text


def test_sigma_glue(chip_compile):
    """The XLA glue between the σ cells and the Miller cell."""
    from lighthouse_tpu.crypto import tpu_backend as TB

    part, pt = ((192, S), U32), ((3, 2, 26), U32)
    chip_compile(TB._sigma_point, [part])
    chip_compile(TB._sigma_add, [pt, part])
    chip_compile(TB._sigma_block, [pt, ((), jnp.bool_)])
    chip_compile(TB._shared_lanes, [pt, pt])


@pytest.mark.slow
def test_hash_to_curve_cell(chip_compile):
    from lighthouse_tpu.crypto import htc_kernel as HK

    text = chip_compile(HK.hash_g2_kernel_call,
                        [((2 * HK.BLOCK_ROWS, 2 * S), U32)])
    assert "tpu_custom_call" in text


@pytest.mark.slow
def test_sigma_cell(chip_compile):
    from lighthouse_tpu.crypto import pairing_kernel as PK

    text = chip_compile(PK.sigma_kernel_call,
                        [((128, S), U32), ((1, S), I32), ((1, S), U32),
                         ((1, S), U32)])
    assert "tpu_custom_call" in text


@pytest.mark.slow
def test_miller_cell(chip_compile):
    from lighthouse_tpu.crypto import tpu_backend as TB

    text = chip_compile(TB._miller_cell,
                        [((64, S), U32), ((128, S), U32), ((1, S), I32)] * 2)
    assert "tpu_custom_call" in text


@pytest.mark.slow
@pytest.mark.parametrize("m", [128, 512])
def test_finalize(chip_compile, m):
    from lighthouse_tpu.crypto import pairing_kernel as PK

    text = chip_compile(PK.finalize_kernel_call_donated, [((384, m), U32)])
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.slow
def test_sharded_verify_four_chips(topo, chip_compile):
    """The mesh-sharded batch verify's TPU program (Pallas kernels under
    shard_map) on the described 2x2 at the 1024 x 16 flagship shape."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lighthouse_tpu.parallel import bls_shard as BS

    mesh = Mesh(np.array(topo.devices[:4]), ("batch",))
    C, K = 8, 16
    lanes = NamedSharding(mesh, P(None, "batch"))
    shapes = [((64, 1 << 15), U32, NamedSharding(mesh, P())),
              ((C * K * S,), I32, NamedSharding(mesh, P("batch"))),
              ((1, C * K * S), I32, lanes)] + [
        (shape, dt, lanes) for shape, dt in (
            ((1, C * S), U32), ((1, C * S), U32), ((128, C * S), U32),
            ((128, C * S), U32), ((1, C * S), I32), ((1, C * S), I32))]
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d, sh in shapes]
    text = BS._pallas_verify_fn(mesh, K).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 4 and "all-gather" in text


def test_final_exp_program_shape():
    """The finalize step table: 6-int rows, every register index in
    range, the result in X — checked without a chip."""
    from lighthouse_tpu.crypto import pairing_kernel as PK

    prog = PK.FINAL_EXP_PROGRAM.reshape(-1, 6)
    assert prog.dtype == np.int32 and len(prog) == 366
    assert set(prog[:, 0]) <= {PK._OP_MUL, PK._OP_COPY}
    for col in (1, 2, 4):
        assert prog[:, col].min() >= 0 and prog[:, col].max() < PK._N_REGS
    assert prog[-1, 1] == PK._R_X
