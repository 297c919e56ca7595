"""The ``lighthouse-tpu`` command-line tool.

One binary, subcommands — mirroring the reference's CLI tree
(``/root/reference/lighthouse/src/main.rs:315-319``: ``beacon_node``,
``validator_client``, ``account_manager``, ``database_manager``) plus the
``lcli`` developer tools (``transition-blocks``/``skip-slots`` per-phase
profilers, ``lcli/src/transition_blocks.rs:229,308-396``).

Run as ``python -m lighthouse_tpu.cli <subcommand> ...``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--preset", choices=["minimal", "mainnet"],
                   default="minimal")
    p.add_argument("--validators", type=int, default=64)
    p.add_argument("--spec-config", default="",
                   help="chain spec config.yaml (overrides the preset's "
                        "built-in spec)")
    p.add_argument("--dump-config", default="",
                   help="write the effective chain spec YAML to PATH and "
                        "exit (`clap_utils` --dump-config)")
    p.add_argument("--no-compile-cache", action="store_true",
                   help="run without JAX's persistent compilation cache "
                        "(by default it lives at JAX_COMPILATION_CACHE_DIR, "
                        "else <repo>/.jax_cache, so a restarted node never "
                        "re-pays the cold XLA compile of the device "
                        "pipelines)")


def _maybe_enable_compile_cache(args) -> None:
    if getattr(args, "no_compile_cache", False):
        return
    from .common.compile_cache import enable

    enable()


def _effective_spec(args):
    from .types.chain_spec import ChainSpec

    if getattr(args, "spec_config", ""):
        return ChainSpec.from_yaml(open(args.spec_config).read())
    return None  # harness default for the preset


def _setup(args):
    from .crypto import bls
    from .testing.harness import StateHarness
    from .types.presets import MAINNET, MINIMAL

    _maybe_enable_compile_cache(args)
    bls.set_backend(args.backend if hasattr(args, "backend") else "fake")
    preset = MINIMAL if args.preset == "minimal" else MAINNET
    spec = _effective_spec(args)
    kwargs = {}
    if spec is not None:
        # The genesis state's fork follows the LOADED spec's schedule —
        # building (say) a Capella state under a config whose forks sit at
        # far-future would split the state shape from the transition code.
        kwargs["fork"] = spec.fork_name_at_epoch(0)
    return StateHarness(n_validators=args.validators, preset=preset,
                        spec=spec, **kwargs)


def cmd_transition_blocks(args) -> int:
    """Per-phase block-application profiler (`lcli transition-blocks`)."""
    from .state_transition import SignatureStrategy
    from .state_transition.per_block import process_block
    from .state_transition.per_slot import process_slots

    h = _setup(args)
    h.extend_chain(args.warmup_blocks)
    signed = h.build_block()
    pre_state = h.state
    fork = h.fork_at(int(signed.message.slot))
    strategy = (SignatureStrategy.VERIFY_BULK if args.backend != "fake"
                else SignatureStrategy.NO_VERIFICATION)

    phases = {"slot_advance": [], "block_processing": [], "state_root": []}
    for _ in range(args.runs):
        state = pre_state.copy()
        t0 = time.perf_counter()
        state = process_slots(state, int(signed.message.slot), h.preset,
                              h.spec, h.T)
        t1 = time.perf_counter()
        process_block(state, signed, fork, h.preset, h.spec, h.T,
                      strategy=strategy)
        t2 = time.perf_counter()
        state.tree_hash_root()
        t3 = time.perf_counter()
        phases["slot_advance"].append((t1 - t0) * 1e3)
        phases["block_processing"].append((t2 - t1) * 1e3)
        phases["state_root"].append((t3 - t2) * 1e3)

    out = {name: {"min_ms": round(min(v), 3),
                  "mean_ms": round(sum(v) / len(v), 3)}
           for name, v in phases.items()}
    out["runs"] = args.runs
    out["attestations_in_block"] = len(signed.message.body.attestations)
    print(json.dumps(out, indent=2))
    return 0


def cmd_skip_slots(args) -> int:
    """`lcli skip-slots`: cost of empty-slot advance (epoch boundaries)."""
    from .state_transition.per_slot import process_slots

    h = _setup(args)
    state = h.state
    t0 = time.perf_counter()
    process_slots(state.copy(), int(state.slot) + args.slots, h.preset,
                  h.spec, h.T)
    dt = (time.perf_counter() - t0) * 1e3
    print(json.dumps({"slots": args.slots, "total_ms": round(dt, 3),
                      "ms_per_slot": round(dt / args.slots, 3)}))
    return 0


def _load_identity(datadir: str) -> bytes:
    """Load (or mint + persist) the node's static X25519 identity key —
    the reference persists its libp2p keypair at ``<datadir>/beacon/
    network/key`` so the node id survives restarts; same deal here.
    Without a datadir the identity is ephemeral."""
    import os
    import secrets as pysecrets

    path = os.path.join(datadir, "node_key")
    if os.path.exists(path):
        try:
            with open(path) as f:
                key = bytes.fromhex(f.read().strip())
            if len(key) == 32:
                return key
        except ValueError:
            pass
        # Truncated/corrupt key file (e.g. a crash mid-write before the
        # atomic-rename scheme below existed): the identity is already
        # lost — mint a new one instead of bricking startup forever.
        print(f"warning: corrupt identity key at {path}; regenerating "
              f"(node id will change)")
    key = pysecrets.token_bytes(32)
    os.makedirs(datadir, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(key.hex())
    os.chmod(tmp, 0o600)
    os.replace(tmp, path)  # atomic: never a half-written identity
    return key


def cmd_beacon_node(args) -> int:
    """Run an interop beacon node + HTTP API (demo/devnet mode)."""
    from .api import HttpApiServer
    from .beacon_chain import BeaconChain
    from .common.slot_clock import SystemTimeSlotClock
    from .store import HotColdDB, SqliteStore
    from .validator_client import (
        InProcessBeaconNode, ValidatorClient, ValidatorStore)
    from .state_transition.genesis import interop_secret_key

    h = _setup(args)
    hdr = h.state.latest_block_header.copy()
    hdr.state_root = h.state.tree_hash_root()
    store = (HotColdDB(SqliteStore(args.datadir + "/beacon.sqlite"),
                       h.preset, h.spec, h.T) if args.datadir
             else HotColdDB.memory(h.preset, h.spec, h.T))
    # Resume from a previous run's persisted chain when the datadir holds
    # one (`ClientBuilder.build_beacon_chain` resume branch); otherwise
    # boot from interop genesis.
    chain = None
    if args.datadir:
        from .beacon_chain.errors import BlockError
        from .store import StoreCorruption
        try:
            chain = BeaconChain.resume(store=store, preset=h.preset,
                                       spec=h.spec, T=h.T)
            print(f"resumed chain at slot {chain.head.slot} "
                  f"head={chain.head.root.hex()[:12]}")
            rec = chain.last_recovery
            if rec is not None and (rec.quarantined or rec.replayed
                                    or rec.rebuilt_fork_choice):
                print(f"startup recovery: {rec.summary()}")
        except StoreCorruption:
            # Do NOT fall back to a fresh genesis chain here: the
            # BeaconChain constructor persists (overwriting the
            # fork-choice snapshot and clearing the journal), which
            # would destroy exactly the bytes the operator needs to
            # restore from.  Surface the actionable error instead.
            raise
        except BlockError:
            chain = None  # virgin datadir: no persisted chain yet
    if chain is None:
        chain = BeaconChain(store=store, genesis_state=h.state.copy(),
                            genesis_block_root=hdr.tree_hash_root(),
                            preset=h.preset, spec=h.spec, T=h.T)
    if args.validator_monitor_auto:
        from .beacon_chain.validator_monitor import ValidatorMonitor
        chain.validator_monitor = ValidatorMonitor(auto_register=True)
    # Wire networking: encrypted by default (`--insecure` keeps the
    # plaintext framing for debugging).  The identity key persists in
    # the datadir so scores/bans keyed on the node id survive restarts.
    net = None
    disco = None
    if args.listen_port is not None or args.boot_node:
        from .network.transport import WireNetwork

        static_key = _load_identity(args.datadir) if args.datadir else None
        net = WireNetwork(chain, name="bn",
                          port=args.listen_port or 0,
                          secure=not args.insecure,
                          static_key=static_key)
        mode = "plaintext (INSECURE)" if args.insecure else "noise-xx"
        print(f"wire transport up: tcp://127.0.0.1:{net.port} "
              f"[{mode}] node_id={net.node_id.hex()}")
        if args.boot_node:
            host, _, port_s = args.boot_node.rpartition(":")
            disco = net.discover(host or "127.0.0.1", int(port_s))
            print(f"discovery up: udp://127.0.0.1:{disco.udp_port} "
                  f"boot={args.boot_node}")
    api = HttpApiServer(chain, port=args.http_port)
    api.start()
    print(f"beacon node up: http://127.0.0.1:{api.port} "
          f"(validators={args.validators}, preset={args.preset})")
    vc = None
    km = None
    if args.with_validators:
        vstore = ValidatorStore()
        for i in range(args.validators):
            vstore.add_validator(interop_secret_key(i), index=i)
        vc = ValidatorClient(vstore, [InProcessBeaconNode(chain)], h.preset)
        if args.keymanager_port is not None:
            from .validator_client.keymanager import KeymanagerServer
            km = KeymanagerServer(
                vstore, port=args.keymanager_port,
                genesis_validators_root=bytes(
                    h.state.genesis_validators_root))
            km.start()
            print(f"keymanager API up: http://127.0.0.1:{km.port} "
                  f"token={km.token}")
    # Graceful-shutdown service (`environment`'s shutdown-signal task +
    # `beacon_chain` persist-on-drop): SIGTERM must reach the persist
    # path below, not kill the process mid-write.  Service threads run
    # under the TaskExecutor so shutdown signals, joins, and reports
    # stragglers (`common/task_executor` role).
    import signal

    from .common.task_executor import TaskExecutor

    executor = TaskExecutor()

    def _term(_sig, _frm):
        raise SystemExit(0)
    try:
        signal.signal(signal.SIGTERM, _term)
    except ValueError:
        pass  # non-main thread (embedded use) — rely on finally

    # 3/4-slot state-advance timer as a managed service thread
    # (`state_advance_timer.rs` spawn).
    def _advance_timer(stop):
        fired = -1
        consecutive_failures = 0
        while not stop.wait(0.1):
            try:
                s_now = clock.now()
                if clock.slot_progress() >= 0.75 and fired < s_now:
                    fired = s_now
                    chain.on_three_quarters_slot(s_now)
                consecutive_failures = 0
            except Exception as e:
                # transient failures are tolerated; a persistent one
                # surfaces through the executor's died-task report
                consecutive_failures += 1
                print(f"state-advance timer error: {e!r}")
                if consecutive_failures >= 3:
                    raise

    # Devnet clock: start at the next slot AFTER the (possibly resumed)
    # head — restarting at slot 0 against a resumed head would have the VC
    # proposing slot-1 blocks onto a later state.
    clock = SystemTimeSlotClock(
        genesis_time=int(time.time())
        - chain.head.slot * args.seconds_per_slot,
        seconds_per_slot=args.seconds_per_slot)
    last = chain.head.slot
    try:
        deadline = (time.time() + args.run_for) if args.run_for else None
        executor.spawn(_advance_timer, "state_advance_timer")
        while deadline is None or time.time() < deadline:
            slot = clock.now()
            if slot > last:
                last = slot
                chain.per_slot_task(slot)
                if vc is not None:
                    vc.on_slot(slot)
                print(f"slot {slot} head={chain.head.root.hex()[:12]} "
                      f"(slot {chain.head.slot})")
            time.sleep(0.1)
    except KeyboardInterrupt:
        pass
    finally:
        stragglers = executor.shutdown(timeout=3)
        if stragglers:
            print(f"warning: tasks did not stop: {stragglers}")
        if args.datadir:
            chain.persist()  # graceful-shutdown persistence
        if disco is not None:
            disco.close()
        if net is not None:
            net.close()
    if km is not None:
        km.stop()
    api.stop()
    return 0


def cmd_boot_node(args) -> int:
    """`boot_node`: run the standalone discovery registry."""
    from .network.discovery import BootNode

    boot = BootNode(port=args.port)
    print(f"boot node up: udp://127.0.0.1:{boot.port}")
    try:
        if args.run_for:
            time.sleep(args.run_for)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    boot.close()
    return 0


def cmd_account(args) -> int:
    """`account_manager`: create/import EIP-2335 keystores."""
    import getpass
    import os
    import secrets as pysecrets

    from .crypto import bls
    from .crypto.key_derivation import derive_path, validator_signing_path
    from .crypto.keystore import Keystore

    os.makedirs(args.dir, exist_ok=True)
    if args.account_cmd == "create":
        password = args.password or getpass.getpass("keystore password: ")
        seed = pysecrets.token_bytes(32)
        for i in range(args.count):
            sk_int = derive_path(seed, validator_signing_path(i))
            sk = bls.SecretKey(sk_int)
            ks = Keystore.encrypt(
                sk.serialize(), password,
                pubkey=sk.public_key().serialize(),
                path=validator_signing_path(i), scrypt_n=args.scrypt_n)
            out = os.path.join(args.dir, f"keystore-{i}.json")
            with open(out, "w") as f:
                f.write(ks.to_json())
            print(f"wrote {out} pubkey=0x{ks.pubkey[:16]}…")
        return 0
    if args.account_cmd == "list":
        for name in sorted(os.listdir(args.dir)):
            if name.endswith(".json"):
                with open(os.path.join(args.dir, name)) as f:
                    ks = Keystore.from_json(f.read())
                print(f"{name}: 0x{ks.pubkey} path={ks.path}")
        return 0
    print("unknown account command", file=sys.stderr)
    return 1


def cmd_warmup(args) -> int:
    """Pre-compile the device hot paths into the persistent cache
    (``JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``), so the
    next node process pays disk reads, not the cold XLA compile, on its
    first slot.  Off-TPU this is a no-op (the warmup API reports it)."""
    from .common.compile_cache import DEFAULT_BUCKETS, enable, warmup

    cache = enable()
    buckets = []
    for part in (args.shapes.split(",") if args.shapes else []):
        sets, _, keys = part.partition("x")
        buckets.append((int(sets), int(keys or 1)))
    out = warmup(buckets or DEFAULT_BUCKETS)
    out["cache_dir"] = cache
    print(json.dumps(out))
    return 0


def cmd_db(args) -> int:
    """`database_manager`: inspect a store."""
    from .store import DBColumn, SqliteStore

    kv = SqliteStore(args.path)
    out = {}
    for col in DBColumn:
        n = sum(1 for _ in kv.iter_column(col))
        if n:
            out[col.name] = n
    print(json.dumps(out, indent=2))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="lighthouse-tpu", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    bn = sub.add_parser("bn", help="run a beacon node (interop/devnet)")
    _add_common(bn)
    bn.add_argument("--backend", default="fake",
                    choices=["fake", "python", "tpu"])
    bn.add_argument("--http-port", type=int, default=5052)
    bn.add_argument("--seconds-per-slot", type=int, default=2)
    bn.add_argument("--with-validators", action="store_true")
    bn.add_argument("--keymanager-port", type=int, default=None,
                    help="serve the keymanager API (`--http` on the "
                         "reference VC; prints the bearer token)")
    bn.add_argument("--validator-monitor-auto", action="store_true",
                    help="track every observed validator "
                         "(`--validator-monitor-auto`)")
    bn.add_argument("--datadir", default="")
    bn.add_argument("--run-for", type=float, default=0,
                    help="seconds to run (0 = forever)")
    bn.add_argument("--listen-port", type=int, default=None,
                    help="TCP wire-transport listen port (0 = ephemeral; "
                         "omit to run without wire networking)")
    bn.add_argument("--boot-node", default="",
                    help="bootstrap UDP endpoint host:port (a boot-node "
                         "process or any node's discovery port)")
    bn.add_argument("--insecure", action="store_true",
                    help="disable the noise-xx encrypted transport and "
                         "speak legacy plaintext frames (debugging / "
                         "simulator escape hatch)")
    bn.set_defaults(fn=cmd_beacon_node)

    tb = sub.add_parser("transition-blocks",
                        help="per-phase block application profiler")
    _add_common(tb)
    tb.add_argument("--backend", default="fake",
                    choices=["fake", "python", "tpu"])
    tb.add_argument("--runs", type=int, default=5)
    tb.add_argument("--warmup-blocks", type=int, default=2)
    tb.set_defaults(fn=cmd_transition_blocks)

    ss = sub.add_parser("skip-slots", help="empty slot advance profiler")
    _add_common(ss)
    ss.add_argument("--backend", default="fake")
    ss.add_argument("--slots", type=int, default=8)
    ss.set_defaults(fn=cmd_skip_slots)

    ac = sub.add_parser("account", help="keystore management")
    ac.add_argument("account_cmd", choices=["create", "list"])
    ac.add_argument("--dir", default="validator_keys")
    ac.add_argument("--count", type=int, default=1)
    ac.add_argument("--password", default="")
    ac.add_argument("--scrypt-n", type=int, default=16384)
    ac.set_defaults(fn=cmd_account)

    db = sub.add_parser("db", help="database inspection")
    db.add_argument("path")
    db.set_defaults(fn=cmd_db)

    wu = sub.add_parser("warmup",
                        help="pre-compile the device hot paths into the "
                             "persistent compilation cache")
    wu.add_argument("--shapes", default="",
                    help="comma-separated (sets)x(keys) buckets, e.g. "
                         "'256x16,256x1' (default: the slot-path buckets)")
    wu.set_defaults(fn=cmd_warmup)

    bnode = sub.add_parser("boot-node",
                           help="standalone discovery registry "
                                "(`boot_node` subcommand / discv5 role)")
    bnode.add_argument("--port", type=int, default=15000)
    bnode.add_argument("--run-for", type=float, default=0)
    bnode.set_defaults(fn=cmd_boot_node)

    args = ap.parse_args(argv)
    if getattr(args, "dump_config", ""):
        from .types.chain_spec import ChainSpec
        spec = _effective_spec(args) or (
            ChainSpec.minimal() if getattr(args, "preset", "") == "minimal"
            else ChainSpec.mainnet())
        with open(args.dump_config, "w") as f:
            f.write(spec.to_yaml())
        print(f"wrote effective chain spec to {args.dump_config}")
        return 0
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
