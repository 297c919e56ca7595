"""Range-sync import: 2-epoch batches of full mainnet blocks through
``sync.process_chain_segment(chain, blocks)``, back to back (closed
loop), on a ``BeaconChain`` over a Capella state of 2^20 validators.

The anchor state, the keys and every block come from the seed
(``harness/chain_data.py``); the blocks are made in a process of their
own (``harness/chain_maker.py``) while this one builds the chain and
warms the device, and that process is stopped before the window opens.
Batches are epoch-aligned as range sync forms them
(``EPOCHS_PER_BATCH`` = 2): the first after the anchor has 63 blocks and
one epoch transition; every later one has 64, two epoch transitions and
~8,400 signature sets.  Set-up imports the first batch, which runs
every program of a batch (the transitions, the boundary root, both
signature buckets); the window's batches are fresh, and none of their
signatures has been seen.  The chain's pubkey cache and the ``tpu``
backend's device key table hold every validator's key, filled from the
seed's affine points, as a running node's do.  After the window, a bad
peer's copy of the next batch (two aggregates of its last block with
swapped signatures) must be refused with nothing committed.

The window runs whole batches and closes at the first batch committed at
or after ``--seconds``; ``sets_per_s`` is the sets of the batches
committed with their signatures verified on the device path, over window
open → last commit.  Set-up takes the window's batches from the maker at
twice the warm-up batch's pace; a window that runs out of them before
``--seconds`` fails ``window_short``.  The comparison checks, all limits
0: every window batch's outcome and the tampered copy's refusal
(``wrong_outcomes``), batches the device path did not serve
(``host_served_batches``), the head state's root against the last
block's claim and against the plain reference's root of the head's
values (``root_mismatches``), a seeded sample of the window's signature
sets — the program's message and aggregate key for each, and the plain
BLS verify of its signature (``reference_disagreements``) — and the
window's length (``window_short``).

``attempted`` counts the sets of the batches begun; ``failed`` those of
batches refused, served off the device or not committed.  The cell reads
the program's ``chain_segment`` counters; a program without them ends at
once (exit 1).  A watchdog ends the process (exit 1, stacks on stderr)
if set-up, or the window and the comparison after it, overrun their
stated limits; the maker process ends with it.
"""

from __future__ import annotations

import concurrent.futures as cf
import faulthandler
import gc
import math
import multiprocessing as mp
import os
import random
import sys
import time

import numpy as np

from ref import curve as RC
from ref import keys as K

from .chain_data import (BlockBuilder, anchor_data, anchor_state,
                         public_keys, state_values)
from .chain_maker import MakerProcess
from .common import annotate

DEVICE_PATHS = ("device", "device_retry", "probe")
CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".cache")
SAMPLE_KINDS = ("attestation", "sync", "proposal", "randao")


# The program's counters the cell reads: sets by padded signer count,
# stage seconds, and the path that served each block-signature batch.
COUNTERS = ("chain_segment_sets_total", "chain_segment_stage_seconds_total",
            "block_sig_batches_total")


def _require_counters() -> None:
    """End the run (exit 1) before the long set-up when the program does
    not count what the cell reports (``COUNTERS``)."""
    import lighthouse_tpu.state_transition.sig_dispatch  # noqa: F401
    import lighthouse_tpu.sync  # noqa: F401
    from lighthouse_tpu.common.metrics import REGISTRY
    missing = [n for n in COUNTERS if n not in REGISTRY._metrics]
    if missing:
        print(f"benchmark: the program does not count {missing}; the "
              f"chain_segment cell reads them", file=sys.stderr)
        raise SystemExit(1)


def _counter(name: str) -> dict:
    """``{label value: count}`` of a one-label counter family, or {}."""
    from lighthouse_tpu.common.metrics import REGISTRY
    fam = REGISTRY._metrics.get(name)
    if fam is None:
        return {}
    return {k[0]: c.value for k, c in list(fam._children.items())}


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0)}


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, *,
                 control: str | None = None, fault: str | None = None):
        if control is not None:
            raise ValueError(f"the chain_segment cell has no control "
                             f"{control!r}")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.fault = fault
        d = cfg["deployment"]
        self.n = d["validators"]
        self.spe = d["slots_per_epoch"]
        self.p = {**d["preset"], "start_epoch": d["start_epoch"],
                  "fork_epoch": d["fork_epoch"],
                  "previous_version": d["previous_version"],
                  "current_version": d["current_version"],
                  "slots_per_epoch": self.spe}
        self.backend = cfg["service"]["backend"]
        self.batches: list = []      # window batches: blocks, meta, sets
        self.captured: list = []     # the program's sets of each dispatch
        self._patched: list = []
        self.maker = None

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        _require_counters()
        faulthandler.dump_traceback_later(self.mix["setup_limit_s"],
                                          exit=True)
        from lighthouse_tpu.crypto import bls, native
        from lighthouse_tpu.types import presets
        from lighthouse_tpu.types.chain_spec import ChainSpec
        from lighthouse_tpu.types.device_state import materialize_state

        t = self.timings = {}
        t0 = time.monotonic()
        preset_name = self.cfg["deployment"]["preset_name"]
        # The anchor's first batch, the window's and the one after them.
        self.maker = MakerProcess(
            os.path.join(CACHE_DIR, f"chain_maker-{os.getpid()}"),
            seed=self.seed, validators=self.n, p=self.p,
            preset_name=preset_name,
            participation=self.mix["participation"],
            batches=self.mix["max_batches"] + 2)
        # The node's host library is built before it syncs (signing here,
        # signature decompression in the program).
        native.available(block=True)
        base, step = K.key_schedule(self.seed)
        with annotate("bench.generate"):
            self.pks = public_keys(base, step, self.n)
            data = anchor_data(self.seed, self.n, self.p, self.pks)
            anchor, T = anchor_state(data, preset_name)
        del data
        t["keys_and_anchor_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        bls.set_backend(self.backend)
        if not materialize_state(anchor):
            raise RuntimeError("materialize_state refused the state")
        self.preset = getattr(presets, preset_name)
        self.spec = ChainSpec.mainnet()
        self.builder = BlockBuilder(anchor, T, self.preset, self.spec,
                                    base, step)
        self.chain = self._chain(anchor, T)
        del anchor
        self._fill_pubkey_caches()
        t["chain_and_caches_s"] = time.monotonic() - t0
        gc.freeze()
        t0 = time.monotonic()
        self._compile()
        t["compile_s"] = time.monotonic() - t0

        self._capture()
        # The warm-up: the anchor's first batch.
        t0 = time.monotonic()
        with annotate("bench.generate"):
            meta = self.maker.batch(0)
            blocks = self.builder.blocks(meta)
        t["wait_warm_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        self._trace_verify(meta)
        t["trace_verify_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        got = self._import(blocks)
        self.warm_s = t["warm_batch_s"] = time.monotonic() - t0
        # The warm-up's dispatches: a process's first batch may still
        # compile under the envelope's deadline, and a chunk the host
        # served there is the envelope's documented cold start, not the
        # window's (``host_served_batches`` holds the window to the
        # device).
        self.warm = {"paths": got["paths"], "dispatches": [
            {k: self.captured[d][1].stats.get(k)
             for k in ("sets", "path", "device_verify_ms")}
            for d in got["dispatches"]]}
        if got["outcome"] != "OK" or got["imported"] != len(blocks):
            raise RuntimeError(f"the warm-up batch was not imported: {got}")
        # Batches for the window at twice the warm-up batch's pace (which
        # pays first-use costs besides) and at least two, within the mix's
        # cap; and one more: the batch after the window, whose tampered
        # copy a bad peer sends.
        need = min(self.mix["max_batches"], max(2, math.ceil(
            2 * self.mix["window_hint_s"] / max(self.warm_s, 1e-3))))
        t0 = time.monotonic()
        with annotate("bench.generate"):
            metas = [self.maker.batch(i) for i in range(1, need + 2)]
            t["maker_made_s"] = self.maker.made_s
            self.maker.stop()
            self.maker = None
            self.pending = [(self.builder.blocks(m), m) for m in metas]
        t["wait_window_s"] = time.monotonic() - t0
        gc.collect()
        gc.freeze()

    def _chain(self, anchor, T):
        """A ``BeaconChain`` anchored at ``anchor`` (its block is the
        state's latest header with the state's root filled in)."""
        from lighthouse_tpu.beacon_chain import BeaconChain
        from lighthouse_tpu.store import HotColdDB

        hdr = anchor.latest_block_header.copy()
        hdr.state_root = anchor.tree_hash_root()
        return BeaconChain(
            store=HotColdDB.memory(self.preset, self.spec, T),
            genesis_state=anchor, genesis_block_root=hdr.tree_hash_root(),
            preset=self.preset, spec=self.spec, T=T)

    def _fill_pubkey_caches(self) -> None:
        """Every validator's key in the chain's pubkey cache and, on the
        ``tpu`` backend, in its device key table (the table's bulk fill),
        from the seed's affine points (no decompression)."""
        from lighthouse_tpu.crypto import bls

        cache = self.chain.pubkey_cache
        raw = np.ascontiguousarray(
            self.chain.head.state.validators.col("pubkey")).tobytes()
        cache._by_index.update(enumerate(map(bls.PublicKey, self.pks)))
        cache._index_by_pubkey.update(
            (raw[48 * i:48 * i + 48], i) for i in range(len(self.pks)))
        self.table_cols = None
        if self.backend == "tpu":
            import jax
            from lighthouse_tpu.crypto import tpu_backend
            table = tpu_backend._PK_TABLE
            table.add_all(self.pks)
            self.table_cols = int(jax.block_until_ready(
                table.device()).shape[1])

    def _compile(self) -> None:
        """The node's boot-time compile of the verify programs at the
        table's width: the block batch's two buckets, K = 512 and 1."""
        if self.backend != "tpu":
            return
        from lighthouse_tpu.common.compile_cache import warmup
        from lighthouse_tpu.common.knobs import knob_int
        sub = knob_int("LIGHTHOUSE_TPU_PIPELINE_SETS")
        warmup(((sub, 512), (128, 1)), table_cols=self.table_cols)

    def _trace_verify(self, meta: list) -> None:
        """Run every verify program once, outside the envelope's
        deadline: a direct device verify of the warm-up batch's first
        window chunk (``batch_replay._dispatch_chunk()`` sets in block
        order: both signer-count buckets, full sub-batches), the shapes
        every later chunk takes.  The boot-time compile above compiles
        the programs from abstract shapes; a process still traces each
        on its first call with device arrays."""
        if self.backend != "tpu":
            return
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.state_transition.batch_replay import \
            _dispatch_chunk
        keys = self.chain.pubkey_cache._by_index
        sets = [s for m in meta for s in m["sets"]][:_dispatch_chunk()]
        batch = [bls.SignatureSet(
            bls.Signature.deserialize(s["signature"]),
            [keys[int(i)] for i in s["signers"]], s["message"])
            for s in sets]
        if not bls._BACKENDS["tpu"].verify_signature_sets(batch):
            raise RuntimeError("the device refused sound warm-up sets")

    def _capture(self) -> None:
        """Keep the sets and the verdict handle of every block-signature
        dispatch (the comparison reads the program's messages and keys,
        and the path that served each)."""
        from lighthouse_tpu.state_transition import sig_dispatch as SD
        disp = SD.get_dispatcher()
        submit = disp.submit

        def capture(sets, slot=None):
            batch = submit(sets, slot=slot)
            self.captured.append((list(sets), batch))
            return batch

        disp.submit = capture
        self._patched.append(lambda: delattr(disp, "submit"))

    def _on_device(self, path) -> bool:
        if self.backend == "tpu":
            return path in DEVICE_PATHS
        return path == self.backend   # a host backend stands in

    def _import(self, blocks) -> dict:
        """One segment through ``process_chain_segment``; its outcome and
        the dispatches (``captured`` indices) that verified its sets."""
        from lighthouse_tpu.sync import process_chain_segment
        n0 = len(self.captured)
        res = process_chain_segment(self.chain, blocks)
        dispatches = range(n0, len(self.captured))
        paths = sorted({str(self.captured[j][1].stats.get("path"))
                        for j in dispatches})
        return {"outcome": res.outcome.name, "imported": res.imported,
                "batched": res.batched,
                "error": type(res.error).__name__ if res.error else None,
                "paths": paths, "dispatches": dispatches,
                "on_device": bool(paths) and all(
                    self._on_device(p) for p in paths)}

    # -- the window -----------------------------------------------------------

    def _break_device(self) -> None:
        """The faults planted under the timed path: ``flipped_verdict``
        inverts the backend's verdict; ``first_key`` verifies each set
        with its first signing key alone."""
        if self.fault not in ("flipped_verdict", "first_key"):
            return
        from lighthouse_tpu.crypto import bls
        be = bls.get_backend()
        verify = be.verify_signature_sets
        if self.fault == "flipped_verdict":
            be.verify_signature_sets = lambda sets: not verify(sets)
        else:
            be.verify_signature_sets = lambda sets: verify(
                [bls.SignatureSet(s.signature, list(s.signing_keys[:1]),
                                  s.message) for s in sets])
        self._patched.append(lambda: delattr(be, "verify_signature_sets"))

    def run_window(self, t0: float, seconds: float) -> None:
        faulthandler.dump_traceback_later(self.mix["window_limit_s"],
                                          exit=True)
        self._break_device()
        self.t0, self.seconds = t0, seconds
        t_end = t0 + seconds
        sets0 = _counter("chain_segment_sets_total")
        stage0 = _counter("chain_segment_stage_seconds_total")
        paths0 = _counter("block_sig_batches_total")
        self.t_last = t0
        for j, (blocks, meta) in enumerate(self.pending[:-1]):
            if self.fault == "dropped_block" and j == 0:
                blocks = blocks[:-1]
            with annotate("bench.batch"):
                got = self._import(blocks)
            self.t_last = time.monotonic()
            got.update(blocks=len(blocks), sets=sum(len(m["sets"])
                                                   for m in meta),
                       seconds=self.t_last - (self.batches[-1]["end"]
                                              if self.batches else t0),
                       end=self.t_last, meta=meta)
            self.batches.append(got)
            if self.t_last >= t_end:
                break
        self.exhausted = self.t_last < t_end
        self.sets_by_k = _delta(_counter("chain_segment_sets_total"), sets0)
        self.stage_s = _delta(
            _counter("chain_segment_stage_seconds_total"), stage0)
        self.paths = _delta(_counter("block_sig_batches_total"), paths0)

    def _refuse_tampered(self) -> None:
        """A bad peer's copy of the batch after the window: two
        aggregates of its last block have swapped signatures (re-signed
        by its proposer).  It must be refused with nothing committed."""
        tampered = self.builder.tampered(self.tampered_meta)
        self.head_before = self.chain.head.root
        t0 = time.monotonic()
        self.tampered_result = self._import(tampered)
        self.tampered_s = time.monotonic() - t0
        self.head_after = self.chain.head.root

    def _committed(self, b: dict) -> bool:
        return (b["outcome"] == "OK" and b["imported"] == b["blocks"]
                and b["on_device"])

    def close(self) -> dict:
        """The window's numbers; the head's root and values and the
        sampled sets, whose plain-reference checks start in worker
        processes while the tampered copy is refused."""
        from lighthouse_tpu.state_transition.helpers import increase_balance
        good = [b for b in self.batches if self._committed(b)]
        window_s = self.t_last - self.t0
        self.attempted = sum(b["sets"] for b in self.batches)
        self.failed = self.attempted - sum(b["sets"] for b in good)
        blocks = sum(b["blocks"] for b in self.batches)
        counters = {
            "batches": len(self.batches), "blocks": blocks,
            "sets_committed": sum(b["sets"] for b in good),
            "window_s": window_s, "window_exhausted": self.exhausted,
            "batch_s": [round(b["seconds"], 3) for b in self.batches],
            "warm_batch_s": self.warm_s,
            "warm_up": self.warm,
            "chain_segment_sets_total": self.sets_by_k,
            "chain_segment_stage_seconds_total": self.stage_s,
            "block_sig_batches_total": self.paths,
            "outcomes": [f"{b['outcome']}/{b['imported']}/"
                         f"{'+'.join(b['paths'])}" for b in self.batches],
            "dispatches_per_batch": [len(b["dispatches"])
                                     for b in self.batches],
            "dispatch_ms_max": max((self.captured[d][1].stats.get(
                "device_verify_ms", 0.0) for b in self.batches
                for d in b["dispatches"]), default=0.0),
        }
        if self.fault == "wrong_head_root":
            increase_balance(self.chain.head.state, 0, 1)
        committed = [b for b in self.batches if b["outcome"] == "OK"]
        last = (committed[-1]["meta"][-1]["block"] if committed
                else None)
        self.claim = last["state_root"] if last else None
        st = self.chain.head.state
        self.head_root = bytes(st.tree_hash_root())
        self.tampered_meta = self.pending[len(self.batches)][1]
        self._start_reference(state_values(st))
        self._refuse_tampered()
        counters["tampered_batch_s"] = self.tampered_s
        return {"e2e": {"sets_per_s": (sum(b["sets"] for b in good)
                                       / window_s if good else 0.0)},
                "counters": counters}

    def release(self) -> None:
        for undo in reversed(self._patched):
            undo()
        self._patched = []
        self.chain = self.pending = None
        gc.collect()

    # -- the comparison -------------------------------------------------------

    def check(self) -> dict:
        wrong = sum(1 for b in self.batches
                    if b["outcome"] != "OK" or b["imported"] != b["blocks"]
                    or b["blocks"] != 2 * self.spe)
        tb = self.tampered_result
        if (tb["outcome"] != "FATAL" or tb["error"] != "InvalidSignatures"
                or tb["imported"] != 0 or self.head_after != self.head_before):
            wrong += 1
        host = sum(1 for b in self.batches if not b["on_device"])
        mism = int(self.claim is None or self.head_root != self.claim)
        ref_root = self.ref_root.result()
        mism += int(self.claim is None or ref_root != self.claim)
        disagree = self.ref_missing + sum(f.result() for f in self.ref_sets)
        self.ref_pool.shutdown()
        faulthandler.cancel_dump_traceback_later()
        return {"wrong_outcomes": (wrong, 0),
                "host_served_batches": (host, 0),
                "root_mismatches": (mism, 0),
                "reference_disagreements": (disagree, 0),
                "window_short": (int(self.exhausted), 0)}

    def _start_reference(self, head_values: dict) -> None:
        """The plain reference's root of the head's values, and its
        checks of seeded sets of the committed window batches — each
        against the program's set of the same signature (its message and
        aggregate key) and the plain BLS verify — and of the tampered
        batch's two swapped aggregates, which the plain verify must
        refuse; started in worker processes (``check`` collects them)."""
        rng = random.Random(self.seed ^ 0x5A5A)
        pool = {k: [] for k in SAMPLE_KINDS}
        for j, b in enumerate(self.batches):
            if b["outcome"] != "OK" or not b["dispatches"]:
                continue
            for m in b["meta"]:
                for s in m["sets"]:
                    pool[s["kind"]].append((j, s))
        want = self.mix["reference_sample"]
        sample = []
        for kind in SAMPLE_KINDS:
            sample += rng.sample(pool[kind], min(want[kind],
                                                 len(pool[kind])))
        self.ref_missing = sum(want[k] for k in SAMPLE_KINDS) - len(sample)
        by_sig: dict = {}
        jobs = []
        for j, s in sample:
            if j not in by_sig:
                by_sig[j] = {RC.g2_compress(x.signature.point): x
                             for d in self.batches[j]["dispatches"]
                             for x in self.captured[d][0]}
            prog = by_sig[j].get(s["signature"])
            jobs.append((_reference_set, (
                self._points(s["signers"]), s["message"], s["signature"],
                None if prog is None else bytes(prog.message),
                None if prog is None else [k.point
                                           for k in prog.signing_keys])))
        last = self.tampered_meta[-1]
        swapped = [s for s in last["sets"] if s["kind"] == "attestation"][:2]
        for s, other in ((swapped[0], swapped[1]), (swapped[1], swapped[0])):
            jobs.append((_reference_accepts, (
                self._points(s["signers"]), s["message"],
                other["signature"])))
        self.ref_pool = cf.ProcessPoolExecutor(
            min(4, os.cpu_count() or 1), mp_context=mp.get_context("spawn"))
        from ref.ssz_chain import state_root
        self.ref_root = self.ref_pool.submit(state_root, head_values)
        self.ref_sets = [self.ref_pool.submit(fn, *a) for fn, a in jobs]

    def _points(self, indices) -> list:
        return [self.pks[int(i)] for i in indices]


def _sum_points(points):
    acc = None
    for p in points:
        acc = RC.g1_add(acc, p)
    return acc


def _reference_set(signers, message, signature, prog_message,
                   prog_keys) -> int:
    """1 where the program's set (``prog_*``; None if it had none for this
    signature) disagrees with the plain reference's, or the plain verify
    refuses the signature."""
    agg = _sum_points(signers)
    if (prog_message is None or prog_message != message
            or _sum_points(prog_keys) != agg):
        return 1
    return int(not K.verify(agg, message, RC.g2_decompress(signature)))


def _reference_accepts(signers, message, signature) -> int:
    """1 where the plain verify accepts a signature it must refuse."""
    return int(K.verify(_sum_points(signers), message,
                        RC.g2_decompress(signature)))
