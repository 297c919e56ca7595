"""Hostile-drill validator for the streaming verification service.

Replays a deterministic message stream — steady Poisson-ish arrivals at
``--rate`` plus gossip bursts (``--burst``), from
``testing.faults.burst_schedule`` — through a
``beacon_chain.verification_service.VerificationService``, with seeded
fault injection (``--faults``) on the device-dispatch site, and checks
the subsystem's headline claim: **zero valid messages lost** under
injected device failure.  Every message must complete verified (device /
retry / probe / host-fallback path), nothing shed, nothing rejected, and
after a sustained outage the circuit breaker must have re-closed.
Prints one JSON summary (p50/p99 latency vs the SLO, batch-size
histogram, shed/fallback counts, breaker transitions, injector
counters); exit 1 on any loss, exit 0 otherwise.

Flags:
    --messages N   stream length (default 96)
    --rate R       steady arrival rate, messages/s (default 200)
    --burst E:S    every E messages add a burst of S simultaneous
                   arrivals (default 16:8; "0:0" disables)
    --faults SPEC  "RATE[,START:STOP]" — intermittent device-dispatch
                   fail rate, plus an optional sustained-outage window
                   of per-site call sequence numbers (default
                   "0.1,3:9"; "0" disables injection entirely)
    --stall R:S    H2D staging stall: probability R, duration S seconds
                   (default 0:0; exercises the StagedExecutor's
                   sync-staging fallback)
    --slo-ms MS    per-message latency SLO (default 250)
    --max-batch N  bucket dispatch cap (default 32)
    --backend B    bls backend for the drill: fake|python|tpu (default
                   fake — the drill exercises the RESILIENCE machinery;
                   python verifies real host pairings, tpu the device
                   path)
    --keys K       signers per message (default 1)
    --seed S       schedule + injector seed (default 0)
    --compressed   replay arrivals back-to-back instead of against the
                   wall clock (fast; latency percentiles then measure
                   dispatch cost only, not SLO policy)
    --warmup       pre-compile the service's dispatch shapes (every
                   pow-2 bucket width up to --max-batch, --keys signers)
                   through the active backend into ``.jax_cache``, then
                   exit.  Compile-cache note (mirrors tests/conftest.py
                   and scripts/validate_bls_shard.py): cache entries do
                   NOT transfer between processes with different XLA
                   flags — to warm the cache the test suite reads, run

            JAX_PLATFORMS=cpu \
            XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                python scripts/validate_stream_verify.py --warmup \
                    --backend tpu --max-batch 32

Usage:
    python scripts/validate_stream_verify.py
    python scripts/validate_stream_verify.py --rate 2000 --burst 32:16 \
        --faults 0.1,20:28 --slo-ms 50
    python scripts/validate_stream_verify.py --backend python \
        --messages 12 --rate 50 --compressed
"""

import sys; sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))  # noqa: E402

import argparse
import json
import os
import time


def _configure_jax() -> None:
    """Repo-standard persistent compile cache (device backends only)."""
    from lighthouse_tpu.common.compile_cache import enable
    enable()


def _parse_burst(spec: str):
    e, _, s = spec.partition(":")
    return int(e), int(s or 0)


def _parse_faults(spec: str):
    rate_s, _, window = spec.partition(",")
    rate = float(rate_s)
    outage = None
    if window:
        a, _, b = window.partition(":")
        outage = (int(a), int(b))
    return rate, outage


def _warmup(backend: str, max_batch: int, keys: int) -> int:
    """Drive every bucket width the service can dispatch (pow-2 sizes up
    to ``max_batch``) through the active backend once, so a node's first
    streamed dispatch is a persistent-cache hit."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.testing.stream_drill import build_sets

    if backend == "tpu":
        from lighthouse_tpu.crypto import tpu_backend  # noqa: F401
    bls.set_backend(backend)
    real = backend != "fake"
    width = 1
    widths = []
    while width <= max_batch:
        widths.append(width)
        width <<= 1
    for w in widths:
        sets = build_sets(w, keys_per_set=keys, real_keys=real)
        t0 = time.monotonic()
        ok = bls.get_backend().verify_signature_sets(sets)
        print(json.dumps({"warmup_width": w, "keys": keys, "ok": bool(ok),
                          "s": round(time.monotonic() - t0, 2)}),
              flush=True)
        if not ok:
            print(f"FAIL: warmup batch of width {w} rejected",
                  file=sys.stderr)
            return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(
        description="streaming-verification hostile drill")
    ap.add_argument("--messages", type=int, default=96)
    ap.add_argument("--rate", type=float, default=200.0)
    ap.add_argument("--burst", default="16:8")
    ap.add_argument("--faults", default="0.1,3:9")
    ap.add_argument("--stall", default="0:0")
    ap.add_argument("--slo-ms", type=float, default=250.0)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--backend", default="fake",
                    choices=("fake", "python", "tpu"))
    ap.add_argument("--keys", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compressed", action="store_true")
    ap.add_argument("--warmup", action="store_true")
    args = ap.parse_args()

    if args.backend == "tpu" or args.warmup:
        _configure_jax()
    if args.warmup:
        return _warmup(args.backend, args.max_batch, args.keys)
    if args.backend == "tpu":
        from lighthouse_tpu.crypto import tpu_backend  # noqa: F401

    from lighthouse_tpu.testing.stream_drill import run_drill

    burst_every, burst_size = _parse_burst(args.burst)
    fail_rate, outage = _parse_faults(args.faults)
    stall_rate_s, _, stall_dur_s = args.stall.partition(":")
    out = run_drill(
        n_messages=args.messages, rate_per_s=args.rate,
        burst_every=burst_every, burst_size=burst_size,
        fail_rate=fail_rate, outage=outage,
        h2d_stall=(float(stall_rate_s), float(stall_dur_s or 0)),
        slo_ms=args.slo_ms, max_batch=args.max_batch,
        keys_per_set=args.keys, backend=args.backend,
        real_keys=args.backend != "fake",
        realtime=not args.compressed, seed=args.seed)
    print(json.dumps(out, indent=2))

    ok = bool(out["zero_loss"])
    breaker = out["envelope"]["breaker"]
    if breaker["trips"] >= 1 and not out["recovered"]:
        print("FAIL: circuit breaker never re-closed after the outage",
              file=sys.stderr)
        ok = False
    print("ZERO-LOSS DRILL PASSED" if ok
          else f"FAIL: {out['lost']} valid message(s) lost "
               f"(shed={out['shed']} rejected={out['rejected']})",
          file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
