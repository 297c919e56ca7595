"""Differential suite for the mesh-sharded flagship BLS verify.

``sharded_verify_signature_sets`` runs the whole ``verify_signature_sets``
pipeline sets-axis data-parallel over the 8 virtual CPU devices
(conftest) — per-chip aggregation/RLC/Miller/local fold, all-gathered
Fq12 partials, ONE replicated final exponentiation — and must agree with
the pure-python host oracle verdict-for-verdict.  Also pins the MXU
band-product formulation (bit-exact vs the VPU path) and the shared-key
collapsed fast path.

Shape discipline: every test that compiles a sharded pairing program
lives under the ``slow`` marker — the 16-set/8-device flagship
(valid/tampered/uneven all reuse it) takes over ten minutes of cold
XLA:CPU compile, the 1-device degenerate mesh, the shared-key collapsed
kernel and the fused-fold differential more again.  The host-side
logic (pre-checks, group detection, aggregation fallback) and the MXU
band formulation keep cheap quick coverage.

The module compiles with the persistent compilation cache OFF: on the
installed jax, XLA:CPU's write of the flagship executable into the
cache crashed the process (a segfault inside
``compilation_cache.put_executable_and_time``).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.fields import R
from lighthouse_tpu.parallel.mesh import make_mesh
from lighthouse_tpu.parallel.bls_shard import sharded_verify_signature_sets


def _mk_sets(n, kps, tag=b"shard-smoke", key0=0x3000):
    sk_ints = [key0 + 5 * i for i in range(n * kps)]
    sks = [bls.SecretKey(v) for v in sk_ints]
    pks = [k.public_key() for k in sks]
    sets = []
    for i in range(n):
        lo, hi = i * kps, (i + 1) * kps
        m = tag + b"-%02d" % i
        agg = bls.SecretKey(sum(sk_ints[lo:hi]) % R).sign(m)
        sets.append(bls.SignatureSet(agg, list(pks[lo:hi]), m))
    return sets


def _tamper(sets, i, j):
    """Set i keeps its signature but claims set j's signing keys."""
    bad = list(sets)
    bad[i] = bls.SignatureSet(sets[i].signature, sets[j].signing_keys,
                              sets[i].message)
    return bad


@pytest.fixture(scope="module", autouse=True)
def _uncached_compiles():
    from jax._src import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(jax.devices()[:8])


@pytest.mark.slow
def test_sharded_valid_batch_matches_host(mesh8):
    sets = _mk_sets(16, 2)
    assert bls._BACKENDS["python"].verify_signature_sets(sets) is True
    assert sharded_verify_signature_sets(sets, mesh8) is True


@pytest.mark.slow
def test_sharded_tampered_set_rejected(mesh8):
    bad = _tamper(_mk_sets(16, 2), 5, 6)
    assert bls._BACKENDS["python"].verify_signature_sets(bad) is False
    assert sharded_verify_signature_sets(bad, mesh8) is False


@pytest.mark.slow
def test_sharded_uneven_remainder(mesh8):
    # 13 sets over 8 chips: pads to 16 (2/chip) with masked lanes — the
    # same compiled program as the even tests.
    sets = _mk_sets(13, 2)
    assert sharded_verify_signature_sets(sets, mesh8) is True
    assert sharded_verify_signature_sets(_tamper(sets, 12, 3), mesh8) is False


@pytest.mark.slow
def test_sharded_single_device_mesh():
    # Degenerate 1-chip mesh: collectives over an axis of one.  Its own
    # compiled program (~2.5 min/process even cache-warm) → slow tier;
    # the quick tier's masking/padding coverage rides the 8-device
    # program above.
    mesh1 = make_mesh(jax.devices()[:1])
    sets = _mk_sets(3, 1, tag=b"shard-d1", key0=0x5000)
    assert sharded_verify_signature_sets(sets, mesh1) is True
    assert sharded_verify_signature_sets(_tamper(sets, 2, 0), mesh1) is False


def test_sharded_empty_and_missing_signature(mesh8):
    assert sharded_verify_signature_sets([], mesh8) is False
    sets = _mk_sets(16, 2)
    sets[7] = bls.SignatureSet(None, sets[7].signing_keys, sets[7].message)
    assert sharded_verify_signature_sets(sets, mesh8) is False


# ---------------------------------------------------------------------------
# Shared-key collapse (the fast_aggregate_verify winning path)
# ---------------------------------------------------------------------------


def test_shared_group_detection_and_host_aggregate():
    """Quick host-side coverage of the collapsed path's plumbing: group
    detection + the pure-python aggregation fallback (the device
    differential is the slow test below + validate_bls_shard.py)."""
    from lighthouse_tpu.crypto import curve as C
    from lighthouse_tpu.crypto import tpu_backend as TB

    pts = [bls.SecretKey(0x4242 + i).public_key().point for i in range(6)]
    acc = None
    for p in pts:
        acc = C.g1_add(acc, p)
    assert bls.aggregate_points(pts) == acc
    assert bls.aggregate_points([pts[0], C.g1_neg(pts[0])]) is None

    sig = bls.SecretKey(1).sign(b"m").point
    shared = [(sig, [pts[0]], b"m%d" % i) for i in range(8)]
    assert TB._shared_group_key(shared) == pts[0]
    # Below the min batch, mixed keys, a missing signature, or a
    # multi-key entry all refuse the collapse.
    assert TB._shared_group_key(shared[:4]) is None
    assert TB._shared_group_key(shared[:7] + [(sig, [pts[1]], b"x")]) is None
    assert TB._shared_group_key(shared[:7] + [(None, [pts[0]], b"x")]) is None
    assert TB._shared_group_key(
        shared[:7] + [(sig, [pts[0], pts[1]], b"x")]) is None
    # Dedup collapses identical >4-key lists to one aggregated key and
    # records the aggregation time for the bench stage split.
    entries = [(sig, pts, b"m%d" % i) for i in range(8)]
    out, valid = TB._dedup_shared_keygroups(entries)
    assert valid and all(len(e[1]) == 1 for e in out)
    assert out[0][1][0] == acc
    assert TB._shared_group_key(out) == acc


def test_marshal_planes_chunk_major_layout():
    """The Pallas paths' host layout (one-chip cells and sharded planes):
    set s of chunk c sits at lane c·128 + s, its key k at column
    c·K·128 + k·128 + s of the table-index plane."""
    from lighthouse_tpu.crypto import tpu_backend as TB

    S = 128
    pts = [bls.SecretKey(0x6100 + i).public_key().point for i in range(3)]
    sig = bls.SecretKey(3).sign(b"x").point
    n = S + 2                                   # spills into chunk 1
    entries = [(None if i == 1 else sig, pts[:1 + i % 3], b"m%d" % (i % 4))
               for i in range(n)]
    (idx, kmask, lo, hi, _u, _sig, sigmask, setlive, set_col,
     K) = TB._marshal_planes(entries, lambda: (7 << 32) | 9, 2)
    assert K == 4 and idx.shape == (2 * K * S,)
    assert list(set_col[[0, S, S + 1]]) == [0, S, S + 1]
    assert setlive.sum() == n and sigmask.sum() == n - 1
    assert sigmask[0, 1] == 0
    assert (lo[0, set_col] == 9).all() and (hi[0, set_col] == 7).all()
    for i in (0, 2, S + 1):
        c, s = divmod(i, S)
        nk = 1 + i % 3
        cols = c * K * S + np.arange(K) * S + s
        assert list(kmask[0, cols]) == [1] * nk + [0] * (K - nk)
        assert list(idx[cols[:nk]]) == [TB._PK_TABLE.index_of(p)
                                        for p in pts[:nk]]
    cells, K1, any_sig = TB._marshal_group(entries, lambda: 5)
    assert len(cells) == 2 and K1 == K and any_sig
    assert int(cells[1][7].sum()) == 2          # chunk 1's live sets


def test_sharded_pallas_planes_land_on_every_shard(mesh8, monkeypatch):
    """Host side of the TPU sharded verify on the 8 virtual devices: the
    planes it hands the Pallas program are whole 128-set chunks per
    device (lane axis sharded, table replicated), H(m) columns hashed on
    the host, and every shard's ledger row is fed."""
    from lighthouse_tpu.common.device_ledger import LEDGER
    from lighthouse_tpu.crypto import tpu_backend as TB
    from lighthouse_tpu.parallel import bls_shard as BS

    seen = {}

    def fake(mesh, K):
        def run(table, *planes):
            seen.update(K=K, table=table.sharding.spec,
                        shapes=[p.shape for p in planes],
                        specs={str(p.sharding.spec) for p in planes[1:]},
                        h=np.asarray(planes[4]))
            return np.bool_(True)
        return run

    monkeypatch.setattr(TB, "_use_pallas", lambda: True)
    monkeypatch.setattr(BS, "_pallas_verify_fn", fake)
    before = LEDGER.shard_totals().get("bls", {})
    sets = _mk_sets(20, 2, tag=b"shard-pl", key0=0x7000)
    assert sharded_verify_signature_sets(sets, mesh8) is True
    S, C = 128, 8                                 # one chunk per device
    assert seen["K"] == 2 and seen["table"] == jax.sharding.PartitionSpec()
    assert seen["shapes"][0] == (C * 2 * S,)
    assert seen["shapes"][1:] == [(1, C * 2 * S), (1, C * S), (1, C * S),
                                  (128, C * S), (128, C * S), (1, C * S),
                                  (1, C * S)]
    assert seen["specs"] == {"PartitionSpec(None, 'batch')"}
    want = np.frombuffer(TB._g2_aff_col(TB._h_point(bytes(sets[3].message))),
                         np.uint32)
    assert (seen["h"][:, 3] == want).all() and not seen["h"][:, 20:].any()
    after = LEDGER.shard_totals()["bls"]
    assert all(after[i]["h2d_bytes"] > before.get(i, {}).get("h2d_bytes", 0)
               for i in range(8))


@pytest.mark.slow
def test_shared_key_collapse_matches_oracle(monkeypatch):
    from lighthouse_tpu.crypto import tpu_backend as TB

    monkeypatch.setenv("LIGHTHOUSE_TPU_HOST_FASTPATH_MAX", "0")
    kps, n_msgs = 6, 8  # > 4 keys → dedup aggregates; 8 sets ≥ SHARED_MIN
    sk_ints = [0x7000 + 3 * i for i in range(kps)]
    pks = [bls.SecretKey(v).public_key() for v in sk_ints]
    fsum = sum(sk_ints) % R
    msgs = [b"sync-comm-%02d" % i for i in range(n_msgs)]
    fsets = [bls.SignatureSet(bls.SecretKey(fsum).sign(m), list(pks), m)
             for m in msgs]
    tpu = bls._BACKENDS["tpu"]
    monkeypatch.setattr(TB, "STAGE_TIMINGS", True)
    assert tpu.verify_signature_sets(fsets) is True
    assert TB.LAST_FAST_AGG_TIMINGS.get("path") == "xla_shared", \
        "batch did not take the collapsed shared-key path"
    assert bls._BACKENDS["python"].verify_signature_sets(fsets) is True
    # One tampered signature sinks the whole collapsed batch.
    bad = list(fsets)
    bad[3] = bls.SignatureSet(fsets[4].signature, fsets[3].signing_keys,
                              fsets[3].message)
    assert tpu.verify_signature_sets(bad) is False
    assert bls._BACKENDS["python"].verify_signature_sets(bad) is False
    # A wrong-key batch must fail too (binding to P, not just to σ).
    other = bls.SecretKey(0x9999).public_key()
    bad2 = [bls.SignatureSet(s.signature, [other] * kps, s.message)
            for s in fsets]
    assert tpu.verify_signature_sets(bad2) is False


# ---------------------------------------------------------------------------
# MXU band-product formulation (bit-exact vs the VPU path)
# ---------------------------------------------------------------------------


def test_mxu_band_columns_bit_exact():
    from lighthouse_tpu.crypto import limb_field as LF

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 2**16, (37, LF.LIMBS)).astype(np.uint32))
    b = jnp.asarray(rng.integers(0, 2**16, (37, LF.LIMBS)).astype(np.uint32))
    for ncols in (LF.LIMBS, 2 * LF.LIMBS):
        vpu = np.asarray(LF._band_columns(a, b, ncols))
        mxu = np.asarray(LF._band_columns_mxu(a, b, ncols))
        assert (vpu == mxu).all()


def test_mxu_mont_mul_exact(monkeypatch):
    from lighthouse_tpu.crypto import fields as F
    from lighthouse_tpu.crypto import limb_field as LF

    monkeypatch.setenv("LIGHTHOUSE_TPU_MXU", "1")
    monkeypatch.setattr(LF, "_MXU_FLAG", None)
    assert LF.use_mxu()
    rng = np.random.default_rng(1)
    vals = [int(x) for x in rng.integers(1, 2**60, 8)] + [F.P - 1, 1]
    try:
        for x in vals:
            got = LF.from_mont(np.asarray(LF.mont_mul(
                jnp.asarray(LF.to_mont(x)), jnp.asarray(LF.to_mont(x + 7)))))
            assert got == x * (x + 7) % F.P
    finally:
        monkeypatch.setattr(LF, "_MXU_FLAG", None)


def test_mxu_mont_mul_across_jit_traces(monkeypatch):
    """The MXU selection matrix is cached across programs: a cache
    filled inside one jit trace must not leak that trace's tracer into
    the next program's trace (the σ-fold programs trace back to back)."""
    from lighthouse_tpu.crypto import fields as F
    from lighthouse_tpu.crypto import limb_field as LF

    monkeypatch.setattr(LF, "_MXU_FLAG", True)
    LF._band_sel.cache_clear()
    a = jnp.asarray(LF.to_mont(5))
    b = jnp.asarray(LF.to_mont(9))
    first = jax.jit(lambda x, y: LF.mont_mul(x, y))
    second = jax.jit(lambda x, y: LF.mont_mul(LF.mont_mul(x, y), y))
    assert LF.from_mont(np.asarray(first(a, b))) == 45
    assert LF.from_mont(np.asarray(second(a, b))) == 5 * 81 % F.P


def test_mxu_k_band_bit_exact():
    from lighthouse_tpu.crypto import limb_field as LF
    from lighthouse_tpu.crypto import pairing_kernel as PK

    PK._bind_consts(
        jnp.asarray(PK.CONSTS_PLANES),
        jnp.asarray(PK.X_BITS_FULL.reshape(-1, 1).astype(np.int32)),
        jnp.asarray(PK.P_MINUS_2_BITS.reshape(-1, 1).astype(np.int32)),
        jnp.asarray(PK.BAND_SEL_T))
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.integers(0, 2**16, (PK.LIMBS, 4)).astype(np.uint32))
    b = jnp.asarray(rng.integers(0, 2**16, (PK.LIMBS, 4)).astype(np.uint32))
    for ncols in (PK.LIMBS, 2 * PK.LIMBS):
        assert (np.asarray(PK.k_band(a, b, ncols))
                == np.asarray(PK.k_band_mxu(a, b, ncols))).all()


def test_mxu_k_band_in_kernel_refs(monkeypatch):
    """k_band_mxu traced INSIDE a pallas kernel, where the selection
    matrix arrives as a memory Ref rather than an eager array — a raw
    (unloaded) Ref fed to dot_general aborts the trace of every TPU
    kernel, and only this interpret-mode drive can catch that on CPU."""
    from jax.experimental import pallas as pl

    from lighthouse_tpu.crypto import limb_field as LF
    from lighthouse_tpu.crypto import pairing_kernel as PK

    monkeypatch.setattr(LF, "_MXU_FLAG", True)
    # The in-kernel _bind_consts writes traced Refs into the module
    # global; give the trace its own dict so they can't leak out.
    monkeypatch.setattr(PK, "_KC", dict(PK._KC))
    M = 8
    rng = np.random.default_rng(5)
    a = rng.integers(0, 2**16, (PK.LIMBS, M)).astype(np.uint32)
    b = rng.integers(0, 2**16, (PK.LIMBS, M)).astype(np.uint32)

    def kern(cref, xref, pref, bandref, aref, bref, out26, out52):
        PK._bind_consts(cref, xref, pref, bandref)
        out26[...] = PK.k_band_mxu(aref[...], bref[...], PK.LIMBS)
        out52[...] = PK.k_band_mxu(aref[...], bref[...], 2 * PK.LIMBS)

    out26, out52 = pl.pallas_call(
        kern,
        out_shape=[jax.ShapeDtypeStruct((PK.LIMBS, M), jnp.uint32),
                   jax.ShapeDtypeStruct((2 * PK.LIMBS, M), jnp.uint32)],
        interpret=True,
    )(*PK._const_args(), jnp.asarray(a), jnp.asarray(b))
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    assert (np.asarray(out26)
            == np.asarray(PK.k_band(aj, bj, PK.LIMBS))).all()
    assert (np.asarray(out52)
            == np.asarray(PK.k_band(aj, bj, 2 * PK.LIMBS))).all()


# ---------------------------------------------------------------------------
# Fused Miller+fold kernel (new Miller batch shape → slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="pallas pairing kernels need a real TPU (Mosaic; "
                           "CPU pallas_call is interpret-only in this jax)")
def test_miller_fold_fused_matches_unfused():
    """The fused kernel's output must be byte-identical to
    miller_kernel_call + product_chunks_kernel_call on the same lanes
    (identical op sequence, VMEM-resident intermediate)."""
    from lighthouse_tpu.crypto import pairing_kernel as PK

    rng = np.random.default_rng(3)
    M = 2 * PK.LANE_BLOCK
    g1 = jnp.asarray(rng.integers(0, 2**16, (64, M)).astype(np.uint32))
    g2 = jnp.asarray(rng.integers(0, 2**16, (128, M)).astype(np.uint32))
    mask = np.zeros((1, M), np.int32)
    mask[0, :5] = 1
    mask = jnp.asarray(mask)
    f = PK.miller_kernel_call(g1, g2)
    want = np.asarray(PK.product_chunks_kernel_call(f, mask))
    got = np.asarray(PK.miller_fold_kernel_call(g1, g2, mask))
    assert (got == want).all()


# ---------------------------------------------------------------------------
# In-kernel field arithmetic: packed-word carries and looped products
# ---------------------------------------------------------------------------


def _bind_eager():
    from lighthouse_tpu.crypto import pairing_kernel as PK

    PK._bind_consts(
        jnp.asarray(PK.CONSTS_PLANES),
        jnp.asarray(PK.X_BITS_FULL.reshape(-1, 1).astype(np.int32)),
        jnp.asarray(PK.P_MINUS_2_BITS.reshape(-1, 1).astype(np.int32)))
    return PK


def _ripple(t, ncols, signed=False):
    """The row-by-row ripple the kernels used to trace (reference)."""
    rows, c = [], jnp.zeros_like(t[0:1])
    for k in range(ncols):
        v = t[k:k + 1] + c
        rows.append(v & (0xFFFF if signed else np.uint32(0xFFFF)))
        c = v >> (16 if signed else np.uint32(16))
    return jnp.concatenate(rows, axis=0), c


def _ripple_cond_sub(x, k):
    d = x.astype(jnp.int32) - k.astype(jnp.int32)
    norm, c = _ripple(d, 26, signed=True)
    return jnp.where(c == 0, norm.astype(jnp.uint32), x)


def _ripple_ops(PK):
    n2 = PK._KC["N2"]

    def add(a, b):
        return _ripple_cond_sub(_ripple(a + b, 26)[0], n2)

    def sub(a, b):
        d = (a.astype(jnp.int32) + n2.astype(jnp.int32)
             - b.astype(jnp.int32))
        return _ripple_cond_sub(_ripple(d, 26, signed=True)[0]
                                .astype(jnp.uint32), n2)

    def neg(a, _b):
        d = n2.astype(jnp.int32) - a.astype(jnp.int32)
        return _ripple(d, 26, signed=True)[0].astype(jnp.uint32)

    def muls(a, _b):
        x = _ripple(a * np.uint32(12), 26)[0]
        for k in (16, 8, 4, 2):
            x = _ripple_cond_sub(x, PK._KC[f"K{k}"])
        return x

    def mont_mul(a, b):
        t = PK.k_band(a, b, 52)
        t_low = _ripple(t[:26], 26)[0]
        m = _ripple(PK.k_band(t_low, PK._KC["NPRIME"], 26), 26)[0]
        full, _ = _ripple(t + PK.k_band(m, PK._KC["N"], 52), 52)
        return full[26:]

    return {"add": (add, PK.k_add), "sub": (sub, PK.k_sub),
            "neg": (neg, lambda a, b: PK.k_neg(a)),
            "muls": (muls, lambda a, b: PK.k_muls(a, 12)),
            "cond_sub": (lambda a, b: _ripple_cond_sub(a, PK._KC["N"]),
                         lambda a, b: PK._cond_sub_raw(a, PK._KC["N"])),
            "mont_mul": (mont_mul, PK.k_mont_mul)}


def _edge_planes(seed):
    """(26, 64) lazy residues < 2N with the carry-chain corners: zero,
    2N − 1, p, runs of 0xFFFF limbs, plus random values."""
    from lighthouse_tpu.crypto import fields as F
    from lighthouse_tpu.crypto import limb_field as LF

    rng = np.random.default_rng(seed)
    vals = [0, 1, 2 * F.P - 1, F.P, F.P - 1, 2 * F.P - 2, (1 << 380) - 1,
            (1 << 381), 0xFFFF]
    vals += [int.from_bytes(rng.bytes(48), "little") % (2 * F.P)
             for _ in range(64 - len(vals))]
    a = np.stack([LF.int_to_limbs(v) for v in vals], axis=1)
    return jnp.asarray(a), jnp.asarray(a[:, rng.permutation(64)])


@pytest.mark.parametrize("op", ["add", "sub", "neg", "muls", "cond_sub",
                                "mont_mul"])
def test_kernel_carries_match_ripple(op):
    """The packed-word carry resolution gives the ripple's limbs
    exactly (not just the same residue): the final exponentiation and
    Miller loop are pinned bit-for-bit through these."""
    PK = _bind_eager()
    ref, new = _ripple_ops(PK)[op]
    for seed in (0, 1):
        a, b = _edge_planes(seed)
        assert (np.asarray(ref(a, b)) == np.asarray(new(a, b))).all()


def test_looped_products_in_kernel_refs(monkeypatch):
    """:func:`_mont_many` with a bound product scratch (the in-kernel
    fori_loop over one narrow mont_mul) equals the eager lane-concat
    path, traced inside a pallas kernel with real memory Refs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from lighthouse_tpu.crypto import pairing_kernel as PK

    monkeypatch.setattr(PK, "_KC", dict(PK._KC))
    M = 8
    a, b = _edge_planes(2)
    a, b = a[:, :3 * M], b[:, :3 * M]

    def kern(cref, xref, pref, bandref, aref, bref, out, *mm):
        PK._bind_consts(cref, xref, pref, bandref, mm)
        x, y = aref[...], bref[...]
        pairs = [(x[:, i * M:(i + 1) * M], y[:, i * M:(i + 1) * M])
                 for i in range(3)]
        out[...] = jnp.concatenate(PK._mont_many(pairs), axis=1)

    got = pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((26, 3 * M), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((PK.MM_MAX, 26, M), jnp.uint32)] * 3,
        interpret=True)(*PK._const_args(), a, b)
    _bind_eager()
    want = PK._mont_many([(a[:, i * M:(i + 1) * M], b[:, i * M:(i + 1) * M])
                          for i in range(3)])
    assert (np.asarray(got) == np.asarray(jnp.concatenate(want, 1))).all()


def test_final_exp_step_program_transforms():
    """The finalize step machine (conj / frob1 / frob2 operands, copy)
    reproduces the straight-line Fq12 ops limb for limb."""
    from lighthouse_tpu.crypto import fields as F
    from lighthouse_tpu.crypto import limb_field as LF

    PK = _bind_eager()
    rng = np.random.default_rng(4)

    def plane():
        return jnp.asarray(np.stack([LF.to_mont(int(v) % F.P) for v in
                                     rng.integers(1, 2**62, 2)], axis=1))

    f = tuple(tuple((plane(), plane()) for _ in range(3)) for _ in range(2))
    fi = tuple(tuple((plane(), plane()) for _ in range(3)) for _ in range(2))
    R = PK
    prog = np.array([
        (R._OP_MUL, R._R_M, R._R_F, R._T_CONJ, R._R_I, R._T_ID),
        (R._OP_MUL, R._R_M, R._R_M, R._T_ID, R._R_M, R._T_FROB2),
        (R._OP_COPY, R._R_A, R._R_M, R._T_CONJ, R._R_M, R._T_ID),
        (R._OP_MUL, R._R_X, R._R_A, R._T_ID, R._R_M, R._T_FROB1),
    ], np.int32).reshape(-1)
    one = R.fq12_one_like(2)
    regs = jnp.stack([R.pack_fq12(v) for v in (f, fi, one, one, one, one,
                                               one)])
    get = lambda r, i: R.unpack_fq12(jax.lax.dynamic_index_in_dim(  # noqa
        r, i, 0, False))
    put = lambda r, i, v: jax.lax.dynamic_update_index_in_dim(  # noqa
        r, R.pack_fq12(v), i, 0)
    for j in range(4):                 # eager steps: no loop compile
        regs = R._fq12_step(prog, j, get, put, regs)

    m = R.fq12_mul(R.fq12_conj(f), fi)
    m = R.fq12_mul(R.fq12_frobenius(m, 2), m)
    x = R.fq12_mul(R.fq12_conj(m), R.fq12_frobenius(m, 1))
    assert (np.asarray(regs[R._R_M]) == np.asarray(R.pack_fq12(m))).all()
    assert (np.asarray(regs[R._R_X]) == np.asarray(R.pack_fq12(x))).all()
