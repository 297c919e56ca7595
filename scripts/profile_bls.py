"""Per-stage profile of the fused TPU BLS batch-verify pipeline.

The production path is ONE jit (`tpu_backend._fused_verify`) with a single
host sync, so end-to-end stage costs can't be timed from outside; this
script times (a) the host marshalling pieces, (b) each kernel queued N×
with one sync (device cost, amortizing the per-sync dispatch
round-trip), and (c) the fused call end-to-end.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
from lighthouse_tpu.common.compile_cache import enable
enable()

import jax
import jax.numpy as jnp

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto import tpu_backend as TB
from lighthouse_tpu.crypto import pairing_kernel as PK
from lighthouse_tpu.crypto import htc_kernel as HK

N_SETS = 256
S = PK.PREP_S
sks = [bls.SecretKey(0x1000 + i) for i in range(8)]
pks = [k.public_key() for k in sks]
msgs = [b"bench-msg-%02d" % i for i in range(64)]
sets = [bls.SignatureSet(sks[i % 8].sign(msgs[i % 64]), [pks[i % 8]],
                         msgs[i % 64]) for i in range(N_SETS)]

tpu = bls._BACKENDS["tpu"]
assert tpu.verify_signature_sets(sets)  # warm every kernel + the table

# --- host marshalling cost --------------------------------------------------
entries = [(s.signature.point, [k.point for k in s.signing_keys],
            bytes(s.message)) for s in sets]
t0 = time.perf_counter()
messages = [(i // S, i % S, e[2]) for i, e in enumerate(entries)]
u = HK.u_planes_for_messages(messages, 2)
print(f"u_planes (64 msgs × reuse): {(time.perf_counter()-t0)*1e3:8.2f} ms")

# --- per-kernel device cost: queue N, sync once -----------------------------
from lighthouse_tpu.crypto.profiling import profile_stages

for name, val in profile_stages().items():
    if name.startswith("stage_") and name.endswith("_ms"):
        print(f"{name[6:-3]:22s} {val:8.2f} ms/call")

# --- end-to-end fused verify ------------------------------------------------
for _ in range(3):
    t0 = time.perf_counter()
    assert tpu.verify_signature_sets(sets)
    dt = time.perf_counter() - t0
    print(f"fused verify {N_SETS} sets: {dt*1e3:8.1f} ms "
          f"({N_SETS/dt:6.0f} sets/s)")
