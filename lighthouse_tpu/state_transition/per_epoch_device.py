"""Jitted device variant of the fused epoch sweep.

One XLA program computes the inactivity-score update, the per-flag
rewards/penalties, the inactivity penalty, and the balance application for
every validator — the device-side twin of
:func:`per_epoch._fused_inactivity_and_rewards`, enabled with
``LIGHTHOUSE_TPU_EPOCH_DEVICE=1``.

Exactness: the sweep is u64 arithmetic with spec wrap/floor semantics, and
this process runs without global ``jax_enable_x64`` (the crypto kernels are
explicit-dtype 32-bit limb code).  The kernel therefore traces AND executes
inside ``jax.enable_x64(True)``, where jnp uint64 matches numpy
uint64 bit-for-bit (asserted against the numpy sweep in tests).  Compiles
land in the persistent compile cache (``common/compile_cache``) like every
other kernel; :func:`warmup` pre-lowers a given registry size so the first
real epoch of a fresh node is a cache hit.
"""

from __future__ import annotations

import numpy as np

from ..types.chain_spec import (
    PARTICIPATION_FLAG_WEIGHTS,
    TIMELY_HEAD_FLAG_INDEX,
    TIMELY_TARGET_FLAG_INDEX,
    WEIGHT_DENOMINATOR,
)

_KERNEL = None
_WARNED = False


def _get_kernel():
    """Build (once) the jitted fused sweep.  Returns None when JAX or the
    x64 context is unavailable — callers fall back to the numpy sweep."""
    global _KERNEL
    if _KERNEL is not None:
        return _KERNEL
    try:
        import jax
        import jax.numpy as jnp
    except Exception:  # pragma: no cover - jax always present in-tree
        return None

    def sweep(act, ext, wd, slashed, eff, prev_part, scores, balances,
              prev, bias, recovery, in_leak, per_inc, increment,
              active_increments, quotient):
        u64 = jnp.uint64
        one = u64(1)
        active_prev = (act <= prev) & (prev < ext)
        eligible = active_prev | (slashed & (prev + one < wd))
        not_slashed = ~slashed
        flags = [(prev_part & jnp.uint8(1 << f)) != 0
                 for f in range(len(PARTICIPATION_FLAG_WEIGHTS))]
        unslashed = [active_prev & fl & not_slashed for fl in flags]
        target = unslashed[TIMELY_TARGET_FLAG_INDEX]

        # inactivity scores (process_inactivity_updates order)
        dec = jnp.minimum(one, scores)
        scores = jnp.where(eligible & target, scores - dec, scores)
        scores = jnp.where(eligible & ~target, scores + bias, scores)
        rec = jnp.minimum(recovery, scores)
        scores = jnp.where(~in_leak & eligible, scores - rec, scores)

        base = (eff // increment) * per_inc
        n = eff.shape[0]
        rewards = jnp.zeros(n, dtype=u64)
        penalties = jnp.zeros(n, dtype=u64)
        for flag_index, weight in enumerate(PARTICIPATION_FLAG_WEIGHTS):
            participating = unslashed[flag_index]
            part_bal = jnp.maximum(
                jnp.where(participating, eff, u64(0)).sum(dtype=u64),
                increment)
            unslashed_increments = part_bal // increment
            reward_num = base * u64(weight) * unslashed_increments
            flag_rewards = jnp.where(
                eligible & participating,
                reward_num // (active_increments * u64(WEIGHT_DENOMINATOR)),
                u64(0))
            rewards = jnp.where(in_leak, rewards, rewards + flag_rewards)
            if flag_index != TIMELY_HEAD_FLAG_INDEX:
                penalties += jnp.where(
                    eligible & ~participating,
                    base * u64(weight) // u64(WEIGHT_DENOMINATOR),
                    u64(0))
        inact = eff * scores // quotient
        penalties += jnp.where(eligible & ~target, inact, u64(0))

        balances = balances + rewards
        balances = jnp.where(balances >= penalties, balances - penalties,
                             u64(0))
        return scores, rewards, penalties, balances

    jitted = jax.jit(sweep)

    def call(*args):
        with jax.enable_x64(True):
            return jitted(*args)

    _KERNEL = call
    return _KERNEL


def fused_sweep(state, fork, preset, spec, ctx, summary, in_leak: bool,
                timings: dict) -> bool:
    """Run the device sweep; True on success (state/summary updated),
    False to make the caller fall back to the numpy sweep."""
    import time
    from .per_epoch import (_full_column, base_reward_per_increment,
                            inactivity_penalty_quotient)

    kernel = _get_kernel()
    if kernel is None:
        return False
    n = len(state.validators)
    reg = state.validators
    u64 = np.uint64
    t0 = time.perf_counter()
    try:
        scores, rewards, penalties, balances = kernel(
            reg.col("activation_epoch"), reg.col("exit_epoch"),
            reg.col("withdrawable_epoch"), reg.col("slashed"),
            ctx.eff, ctx.prev_part,
            _full_column(state.inactivity_scores, n, np.uint64),
            _full_column(state.balances, n, np.uint64),
            u64(ctx.prev), u64(spec.inactivity_score_bias),
            u64(spec.inactivity_score_recovery_rate), bool(in_leak),
            u64(base_reward_per_increment(ctx.total_active_balance, preset)),
            u64(preset.EFFECTIVE_BALANCE_INCREMENT),
            u64(ctx.total_active_balance
                // preset.EFFECTIVE_BALANCE_INCREMENT),
            u64(spec.inactivity_score_bias
                * inactivity_penalty_quotient(fork, preset)))
    except Exception:
        global _WARNED
        if not _WARNED:  # surface the degradation once, then fall back
            _WARNED = True
            import logging
            logging.getLogger(__name__).warning(
                "device epoch sweep failed; falling back to numpy",
                exc_info=True)
        return False
    # summary columns are consumed by host passes either way; the state
    # columns are ADOPTED on a device-resident state (the jax outputs
    # become the columns — no pull, the next root re-reduces in HBM).
    from ..types.device_state import store_column
    summary.rewards = np.asarray(rewards, dtype=np.uint64)
    summary.penalties = np.asarray(penalties, dtype=np.uint64)
    store_column(state, "inactivity_scores", scores)
    store_column(state, "balances", balances)
    ms = (time.perf_counter() - t0) * 1e3
    timings["inactivity_ms"] = 0.0
    timings["rewards_ms"] = ms
    timings["device"] = True
    return True


def warmup(n: int) -> bool:
    """Pre-compile the sweep for an ``n``-validator registry (abstract
    shapes only); with the persistent compile cache enabled the artifact
    lands on disk for future processes."""
    kernel = _get_kernel()
    if kernel is None:
        return False
    z64 = np.zeros(n, dtype=np.uint64)
    z8 = np.zeros(n, dtype=np.uint8)
    zb = np.zeros(n, dtype=bool)
    u64 = np.uint64
    kernel(z64, z64, z64, zb, z64, z8, z64, z64,
           u64(0), u64(4), u64(16), False, u64(1), u64(10 ** 9),
           u64(1), u64(1 << 26))
    return True
