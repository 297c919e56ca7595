"""Per-stage device profile of the fused BLS verify pipeline — the
measured decomposition VERDICT r4 #2 asks for in the bench JSON.

The production path is ONE jit (a single host sync), so stage costs are
measured by queueing each kernel N× and syncing once (amortizing the
per-sync dispatch round-trip).  Shapes default to
the 256-set C=2 bucket (comparable with the r5 baselines: final_exp
51.7 ms / HTC 44.3 ms / Miller 32.4 ms) and can be widened to the C=8
bucket the 1024-set row now dispatches as one program; inputs are
synthetic limb planes — the kernels' CORRECTNESS is pinned elsewhere
(host oracles + RFC anchors); this measures device time only.

Stages:

- the r5-comparable unfused rows (``miller`` / ``product_fold``), and
- ``miller_fold_fused`` — the fused Miller+fold program that replaced
  the two separate dispatches in the production pipeline.

Used by ``bench.py`` (the ``bls_stage_split`` row) and
``scripts/profile_bls.py`` (human-readable breakdown).
"""

from __future__ import annotations

import time
from typing import Dict

# Most recent :func:`profile_stages` output — the adapter-readable twin
# (``common.tracing.stage_split("bls_kernels")``) of the other LAST_*
# stage dicts, so bench.py's ``bls_stage_split`` row reads through the
# same surface as the tracer.
LAST_STAGE_PROFILE: Dict[str, float] = {}


def profile_stages(n: int = 10, C: int = 2) -> Dict[str, float]:
    """ms/call per pipeline stage at the C-chunk (C·128-lane) shape."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from . import htc_kernel as HK
    from . import pairing_kernel as PK

    S = PK.PREP_S
    rng = np.random.default_rng(0)
    pk = jnp.asarray(rng.integers(0, 2**16, (64, C * S)).astype(np.uint32))
    kmask = jnp.ones((1, C * S), jnp.int32)
    lo = jnp.ones((1, C * S), jnp.uint32)
    hi = jnp.zeros((1, C * S), jnp.uint32)
    g2 = jnp.asarray(rng.integers(0, 2**16, (128, C * S)).astype(np.uint32))
    lm = jnp.ones((1, C * S), jnp.int32)
    msgs = [(i // S, i % S, b"stage-msg-%03d" % (i % 64))
            for i in range(C * S)]
    ud = jnp.asarray(HK.u_planes_for_messages(msgs, C))

    g1_aff, _fl = PK.prepare_kernel_call(pk, kmask, lo, hi, K=1)
    f = PK.miller_kernel_call(g1_aff, g2)
    prod = PK.product_chunks_kernel_call(f, lm)
    fused = PK.miller_fold_kernel_call(g1_aff, g2, lm)
    ok = PK.finalize_kernel_call(prod)
    h = HK.hash_g2_kernel_call(ud)
    jax.block_until_ready((ok, h, fused))

    stages = {
        "hash_to_curve": lambda: HK.hash_g2_kernel_call(ud),
        "prepare_gather_rlc": lambda: PK.prepare_kernel_call(
            pk, kmask, lo, hi, K=1)[0],
        "miller": lambda: PK.miller_kernel_call(g1_aff, g2),
        "product_fold": lambda: PK.product_chunks_kernel_call(f, lm),
        "miller_fold_fused": lambda: PK.miller_fold_kernel_call(
            g1_aff, g2, lm),
        "final_exp": lambda: PK.finalize_kernel_call(prod),
    }
    out: Dict[str, float] = {}
    for name, fn in stages.items():
        t0 = time.perf_counter()
        outs = [fn() for _ in range(n)]
        jax.block_until_ready(outs)
        out[f"stage_{name}_ms"] = round(
            (time.perf_counter() - t0) * 1e3 / n, 2)
    out["stage_shape"] = f"C={C} ({C * S} lanes), K=1"
    LAST_STAGE_PROFILE.clear()
    LAST_STAGE_PROFILE.update(out)
    return out
