"""Differential driver for the mesh-sharded flagship BLS verify.

Mirrors ``validate_pairing_kernels.py``: runs the sharded
``verify_signature_sets`` over an N-device virtual CPU mesh against the
pure-python host oracle (valid batch, tampered set, uneven remainder),
plus the MXU band-product bit-exactness check and (optionally) the fused
Miller+fold kernel differential.

Modes:

    python scripts/validate_bls_shard.py --sets 64 --devices 8
        Differential run at the given shape.

    python scripts/validate_bls_shard.py --warmup
        Compile-cache warmup hook: compiles every sharded/shared-key
        program the QUICK test tier and the multichip dry run use
        (16-set/8-dev, 4-set/1-dev, 8-set shared-key, 64-set/8-dev
        flagship), so tier-1 wall time replays executables from
        ``.jax_cache`` instead of paying minutes of XLA-CPU compile
        per shape.

    python scripts/validate_bls_shard.py --fused
        Adds the fused Miller+fold vs unfused kernel differential
        (compiles a 256-lane Pallas Miller shape — minutes, cold).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_N_DEV = "8"
if "--devices" in sys.argv:
    _N_DEV = sys.argv[sys.argv.index("--devices") + 1]
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={_N_DEV}").strip()

import numpy as np  # noqa: E402
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from lighthouse_tpu.common.compile_cache import enable as _cache_enable  # noqa: E402

_cache_enable()

import jax.numpy as jnp  # noqa: E402

from lighthouse_tpu.crypto import bls  # noqa: E402
from lighthouse_tpu.crypto.fields import R  # noqa: E402
from lighthouse_tpu.parallel.mesh import make_mesh  # noqa: E402
from lighthouse_tpu.parallel.bls_shard import (  # noqa: E402
    sharded_verify_signature_sets)

print("devices:", jax.devices(), flush=True)


def mk_sets(n, kps, tag=b"shard-smoke", key0=0x3000):
    sk_ints = [key0 + 5 * i for i in range(n * kps)]
    sks = [bls.SecretKey(v) for v in sk_ints]
    pks = [k.public_key() for k in sks]
    out = []
    for i in range(n):
        lo, hi = i * kps, (i + 1) * kps
        m = tag + b"-%02d" % i
        agg = bls.SecretKey(sum(sk_ints[lo:hi]) % R).sign(m)
        out.append(bls.SignatureSet(agg, list(pks[lo:hi]), m))
    return out


def tamper(sets, i, j):
    bad = list(sets)
    bad[i] = bls.SignatureSet(sets[i].signature, sets[j].signing_keys,
                              sets[i].message)
    return bad


def differential(n_sets, n_devices, kps=2, tag=b"shard-smoke", key0=0x3000):
    from lighthouse_tpu.parallel.bls_shard import _next_pow2
    mesh = make_mesh(jax.devices()[:n_devices])
    sets = mk_sets(n_sets, kps, tag=tag, key0=key0)
    host = bls._BACKENDS["python"]
    # The uneven case only runs when dropping a set keeps the padded
    # shape (same compiled program — this is a masking test, not an
    # excuse to compile another Miller shape).
    uneven = sets[:-1] if (
        n_sets > 1 and _next_pow2(n_sets - 1) == _next_pow2(n_sets)) else sets
    cases = [("valid", sets, True)]
    if n_sets >= 2:  # the key-swap tamper needs two distinct-key sets
        cases.append(
            ("tampered", tamper(sets, n_sets // 3, n_sets // 3 + 1), False))
    cases.append(("uneven", uneven, True))
    for name, batch, want in cases:
        t0 = time.time()
        dev = sharded_verify_signature_sets(batch, mesh)
        t_dev = time.time() - t0
        t0 = time.time()
        oracle = host.verify_signature_sets(batch)
        t_host = time.time() - t0
        assert dev == oracle == want, (
            f"{name}: sharded={dev} host={oracle} want={want}")
        print(f"{name} ({len(batch)} sets / {n_devices} dev): "
              f"sharded={dev} ({t_dev:.1f}s) == host ({t_host:.1f}s)",
              flush=True)
    print(f"sharded flagship == host oracle over {n_devices} devices OK",
          flush=True)


def check_mxu_band():
    from lighthouse_tpu.crypto import limb_field as LF
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 2**16, (64, LF.LIMBS)).astype(np.uint32))
    b = jnp.asarray(rng.integers(0, 2**16, (64, LF.LIMBS)).astype(np.uint32))
    for ncols in (LF.LIMBS, 2 * LF.LIMBS):
        assert (np.asarray(LF._band_columns(a, b, ncols))
                == np.asarray(LF._band_columns_mxu(a, b, ncols))).all()
    print("MXU band product bit-exact vs VPU", flush=True)


def check_fused_miller_fold():
    if jax.default_backend() != "tpu":
        print("fused miller+fold differential SKIPPED: pallas kernels "
              "need a real TPU (CPU pallas_call is interpret-only)",
              flush=True)
        return
    from lighthouse_tpu.crypto import pairing_kernel as PK
    rng = np.random.default_rng(3)
    M = 2 * PK.LANE_BLOCK
    g1 = jnp.asarray(rng.integers(0, 2**16, (64, M)).astype(np.uint32))
    g2 = jnp.asarray(rng.integers(0, 2**16, (128, M)).astype(np.uint32))
    mask = np.zeros((1, M), np.int32)
    mask[0, :7] = 1
    mask = jnp.asarray(mask)
    t0 = time.time()
    f = PK.miller_kernel_call(g1, g2)
    want = np.asarray(PK.product_chunks_kernel_call(f, mask))
    got = np.asarray(PK.miller_fold_kernel_call(g1, g2, mask))
    assert (got == want).all()
    print(f"fused miller+fold == unfused ({time.time() - t0:.1f}s)",
          flush=True)


def shared_key_check(n_msgs=8, kps=6):
    os.environ["LIGHTHOUSE_TPU_HOST_FASTPATH_MAX"] = "0"
    from lighthouse_tpu.crypto import tpu_backend as TB  # noqa: F401
    sk_ints = [0x7000 + 3 * i for i in range(kps)]
    pks = [bls.SecretKey(v).public_key() for v in sk_ints]
    fsum = sum(sk_ints) % R
    msgs = [b"sync-comm-%02d" % i for i in range(n_msgs)]
    fsets = [bls.SignatureSet(bls.SecretKey(fsum).sign(m), list(pks), m)
             for m in msgs]
    tpu = bls._BACKENDS["tpu"]
    assert tpu.verify_signature_sets(fsets) is True
    # Tamper the SIGNATURE (all sets share the same keys, so a key swap
    # would be a no-op): set 1 carries set 2's signature.
    bad = list(fsets)
    bad[1] = bls.SignatureSet(fsets[2].signature, fsets[1].signing_keys,
                              fsets[1].message)
    assert tpu.verify_signature_sets(bad) is False
    print(f"shared-key collapsed path OK ({n_msgs} sets × {kps} keys)",
          flush=True)


if __name__ == "__main__":
    if "--warmup" in sys.argv:
        # The quick-suite programs + the dry-run flagship shape.
        t0 = time.time()
        differential(16, min(8, len(jax.devices())))
        differential(3, 1, kps=1, tag=b"shard-d1", key0=0x5000)
        shared_key_check()
        differential(64, min(8, len(jax.devices())))
        print(f"warmup complete in {time.time() - t0:.0f}s "
              "(executables persisted to .jax_cache)", flush=True)
        sys.exit(0)
    n_sets = int(sys.argv[sys.argv.index("--sets") + 1]) \
        if "--sets" in sys.argv else 16
    check_mxu_band()
    differential(n_sets, int(_N_DEV))
    shared_key_check()
    if "--fused" in sys.argv:
        check_fused_miller_fold()
