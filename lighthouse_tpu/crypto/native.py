"""ctypes bindings for the native BLS12-381 runtime (``native/bls381.cpp``).

The native library is the HOST fast path: single-set / small-batch
verification where the device's fixed dispatch latency dominates, and
the fast oracle for tests.  Large batches stay on the TPU
(`pairing_kernel.py`).  This is the tpu-native analogue of the
reference's blst host calls (``crypto/bls/src/impls/blst.rs``) —
portable C++ (no asm), built on demand with g++.

Build model: the checked-in source is compiled lazily to
``native/libbls381.so`` keyed on a source hash; rebuilds happen only when
``bls381.cpp`` / ``bls381_consts.h`` change.  If no compiler is available
the loader degrades to ``available() == False`` and callers fall back to
the pure-python pairing.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Sequence, Tuple

_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    os.pardir, os.pardir, "native")
_SRC = os.path.join(_DIR, "bls381.cpp")
_HDR = os.path.join(_DIR, "bls381_consts.h")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _source_tag() -> str:
    h = hashlib.sha256()
    for path in (_SRC, _HDR):
        with open(path, "rb") as f:
            h.update(f.read())
    # -march=native binaries are host-specific: fingerprint the CPU's
    # feature flags so a .so baked on one machine (e.g. into an image)
    # is rebuilt rather than SIGILL-ing on a lesser deploy host.
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    h.update(line.encode())
                    break
    except OSError:
        import platform
        h.update(platform.processor().encode())
    return h.hexdigest()[:16]


def _prune_stale(keep: str) -> None:
    """Delete completed build artifacts for OTHER source/CPU tags — the
    loader keys on the current tag, so they are dead weight that
    otherwise accumulates forever (and must never be committed:
    ``native/*.so`` is gitignored).  ``.tmp*`` files are deliberately
    NOT touched: one may be a CONCURRENT builder's in-progress output
    (deleting it would break its atomic ``os.replace``).  ``_build``
    unlinks its own tmp on failure; only a hard mid-build crash can
    orphan one."""
    import glob
    for path in glob.glob(os.path.join(_DIR, "libbls381-*.so")):
        if os.path.abspath(path) == os.path.abspath(keep):
            continue
        try:
            os.unlink(path)
        except OSError:
            pass  # concurrent process still loading it


def _build() -> Optional[str]:
    tag = _source_tag()
    so = os.path.join(_DIR, f"libbls381-{tag}.so")
    if os.path.exists(so):
        _prune_stale(so)
        return so
    tmp = so + ".tmp%d" % os.getpid()
    cmd = ["g++", "-O3", "-march=native", "-shared", "-fPIC",
           "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError):
        try:
            os.unlink(tmp)  # partial compiler output
        except OSError:
            pass
        return None
    try:
        os.replace(tmp, so)  # atomic vs concurrent builders
    except OSError:
        # Our finished build can't land (e.g. unwritable dir entry).
        # Don't leak it as an orphan the prune pass never touches.
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return so if os.path.exists(so) else None
    _prune_stale(so)
    return so


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SRC):
            return None
        so = _build()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        lib.bls381_multi_pairing_is_one.restype = ctypes.c_int
        lib.bls381_multi_pairing_is_one.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64]
        lib.bls381_multi_pairing_gt.restype = None
        lib.bls381_multi_pairing_gt.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.bls381_g1_aggregate.restype = ctypes.c_int
        lib.bls381_g1_aggregate.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.bls381_hash_to_g2_u.restype = ctypes.c_int
        lib.bls381_hash_to_g2_u.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.bls381_g2_mul.restype = ctypes.c_int
        lib.bls381_g2_mul.argtypes = [
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64)]
        _lib = lib
        return _lib


def ready() -> bool:
    """The standard hot-path gate: honors LIGHTHOUSE_TPU_NO_NATIVE,
    kicks the async build, and answers WITHOUT blocking — callers fall
    back to pure python until the build lands."""
    from ..common.knobs import knob_bool
    # A typed read, not bare truthiness: NO_NATIVE=0 must keep the
    # native backend ENABLED (the bare-truthy read treated "0" as set).
    if knob_bool("LIGHTHOUSE_TPU_NO_NATIVE"):
        return False
    prebuild_async()
    return available(block=False)


def available(block: bool = True) -> bool:
    """Whether the native library is loadable.

    ``block=False`` never compiles or waits: it answers from the cached
    state only (hot paths use this — a fresh checkout answers False and
    batches stay on the device until :func:`prebuild_async` finishes)."""
    if _lib is not None:
        return True
    if not block:
        return False if not _tried else _lib is not None
    return _load() is not None


def prebuild_async() -> None:
    """Kick the g++ build/load on a daemon thread so the first verify
    never pays the compile synchronously (started at backend import)."""
    if _lib is not None or _tried:
        return
    threading.Thread(target=_load, name="bls381-native-build",
                     daemon=True).start()


def _limbs(x: int) -> Tuple[int, ...]:
    return tuple((x >> (64 * i)) & 0xFFFFFFFFFFFFFFFF for i in range(6))


def _pack(pairs: Sequence[Tuple[tuple, tuple]]):
    n = len(pairs)
    g1 = (ctypes.c_uint64 * (12 * n))()
    g2 = (ctypes.c_uint64 * (24 * n))()
    for i, (p, q) in enumerate(pairs):
        g1[i * 12:(i + 1) * 12] = _limbs(p[0]) + _limbs(p[1])
        g2[i * 24:(i + 1) * 24] = (_limbs(q[0][0]) + _limbs(q[0][1]) +
                                   _limbs(q[1][0]) + _limbs(q[1][1]))
    return g1, g2


def multi_pairing_is_one(pairs: Sequence[Tuple[tuple, tuple]]) -> bool:
    """prod_i e(P_i, Q_i) == 1 for AFFINE non-infinity pairs (validated
    upstream — the python seam filters identities before calling)."""
    lib = _load()
    assert lib is not None, "call available() first"
    g1, g2 = _pack(pairs)
    return bool(lib.bls381_multi_pairing_is_one(g1, g2, len(pairs)))


def g1_aggregate(points: Sequence[tuple]) -> Optional[tuple]:
    """Affine sum of non-infinity G1 points (None = identity sum) —
    the jacobian accumulation behind ``bls.aggregate_public_keys`` and
    the shared-keygroup dedup (~5 µs/point vs ~500 µs python)."""
    lib = _load()
    assert lib is not None, "call available() first"
    n = len(points)
    buf = (ctypes.c_uint64 * (12 * n))()
    for i, (x, y) in enumerate(points):
        buf[i * 12:(i + 1) * 12] = _limbs(x) + _limbs(y)
    out = (ctypes.c_uint64 * 12)()
    if not lib.bls381_g1_aggregate(buf, n, out):
        return None
    x = sum(int(out[j]) << (64 * j) for j in range(6))
    y = sum(int(out[6 + j]) << (64 * j) for j in range(6))
    return (x, y)


def _pack_g2_affine(point: tuple):
    buf = (ctypes.c_uint64 * 24)()
    buf[0:6] = _limbs(point[0][0])
    buf[6:12] = _limbs(point[0][1])
    buf[12:18] = _limbs(point[1][0])
    buf[18:24] = _limbs(point[1][1])
    return buf


def _unpack_g2_affine(out) -> tuple:
    v = [sum(int(out[o * 6 + j]) << (64 * j) for j in range(6))
         for o in range(4)]
    return ((v[0], v[1]), (v[2], v[3]))


def hash_to_g2_u(u0: tuple, u1: tuple) -> tuple:
    """SSWU → 3-isogeny → cofactor clearing for two Fq2 field elements
    (the curve half of RFC 9380 hash_to_curve; ~1.5 ms vs ~20 ms python).
    Returns the affine ((x0, x1), (y0, y1)) G2 point."""
    lib = _load()
    assert lib is not None, "call available() first"
    u = _pack_g2_affine((u0, u1))  # same 4×Fq layout as an affine point
    out = (ctypes.c_uint64 * 24)()
    if not lib.bls381_hash_to_g2_u(u, out):
        return None  # pathological infinity; callers treat like python's
    return _unpack_g2_affine(out)


def g2_mul(point: tuple, scalar: int) -> Optional[tuple]:
    """[scalar]P for affine G2 (256-bit ladder; ~1.5 ms vs ~10 ms
    python) — the sign/RLC hot path."""
    lib = _load()
    assert lib is not None, "call available() first"
    p = _pack_g2_affine(point)
    s = (ctypes.c_uint64 * 4)(
        *((scalar >> (64 * i)) & 0xFFFFFFFFFFFFFFFF for i in range(4)))
    out = (ctypes.c_uint64 * 24)()
    if not lib.bls381_g2_mul(p, s, out):
        return None
    return _unpack_g2_affine(out)


def multi_pairing_gt(pairs: Sequence[Tuple[tuple, tuple]]) -> tuple:
    """The CUBED GT value (matches ``pairing.final_exponentiation_cubed``
    of the Miller product) — oracle cross-checks in tests."""
    lib = _load()
    assert lib is not None, "call available() first"
    g1, g2 = _pack(pairs)
    out = (ctypes.c_uint64 * 144)()
    lib.bls381_multi_pairing_gt(g1, g2, len(pairs), out)
    f = [sum(int(out[i * 6 + j]) << (64 * j) for j in range(6))
         for i in range(12)]
    return (((f[0], f[1]), (f[2], f[3]), (f[4], f[5])),
            ((f[6], f[7]), (f[8], f[9]), (f[10], f[11])))
