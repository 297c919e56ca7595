"""Gossip signature verification: single-key attestation sets offered on
a slot-aligned, open-loop schedule to ``VerificationService.submit``.

Keys, messages and signatures come from the seed (``ref/keys.py``).  A
slot's committees each sign one 32-byte message; each validator attests
once per epoch.  The mix may swap the signatures of a pair of adjacent
messages in a slot: both are then invalid, and a batch verify without its
random linear combination would accept the pair together.  The pair sits
in the second half of the slot's first batch (``invalid_pair_ranks``):
the window opens on an empty queue and the first ``max_batch`` arrivals
fill one batch, so a batch verify that checks only the first half of its
sets would accept the pair too.

The window offers every message at its due time from one thread.  The
service pumps on the submitting thread, as it does in the node, so while
a dispatch runs the generator falls behind; the latency clock of each
message starts at its due time (``submit(arrival=due)``).  Messages still
unsubmitted at the close were never answered; messages submitted but not
answered by the close are answered after it (the service is flushed) and
compared, but miss the window.  ``attempted`` counts every message due in
the window; ``failed`` counts wrong verdicts and submitted messages never
answered, so a sound run reads 0 however far behind the service falls.
"""

from __future__ import annotations

import functools
import gc
import hashlib
import os
import random
import secrets
import time

import numpy as np

from ref import keys as K

from .common import annotate
from .schedule import arrivals

DEVICE_PATHS = ("device", "device_retry", "probe")


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, *,
                 control: str | None = None, fault: str | None = None):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.control, self.fault = control, fault
        d = cfg["deployment"]
        self.committees = d["subnets_subscribed"]
        self.committee_size = d["validators"] // (
            d["slots_per_epoch"] * d["max_committees_per_slot"])
        self.slots = mix["slots"]
        self.slot_s = d["seconds_per_slot"]
        self.per_slot = self.committees * self.committee_size

    # -- set-up ---------------------------------------------------------------

    def _make_traffic(self, base: int, step: int) -> None:
        n = self.slots * self.per_slot
        self.messages, sigs = [], []
        for s in range(self.slots):
            for c in range(self.committees):
                m = hashlib.sha256(b"attestation-data %d %d %d" % (
                    self.seed, s, c)).digest()
                self.messages.append(m)
                first = (s * self.committees + c) * self.committee_size
                sigs.extend(K.signatures(base, step, first,
                                         self.committee_size, m))
        self.sigs = sigs
        self.valid = np.ones(n, bool)
        rng = random.Random(self.seed)
        offsets = arrivals(self.mix["arrivals"], self.per_slot, self.slots,
                           self.slot_s)
        order, due = [], []
        inv = self.mix["invalid_pairs_per_slot"]
        lo, hi = self.mix["invalid_pair_ranks"]
        for s in range(self.slots):
            ids = list(range(s * self.per_slot, (s + 1) * self.per_slot))
            rng.shuffle(ids)
            for _ in range(inv):
                r = rng.randrange(lo, hi - 1)
                a, b = ids[r], ids[r + 1]
                if a // self.committee_size == b // self.committee_size:
                    ids[r + 1], ids[r + 2] = ids[r + 2], ids[r + 1]
                    b = ids[r + 1]
                sigs[a], sigs[b] = sigs[b], sigs[a]
                self.valid[a] = self.valid[b] = False
            order.extend(ids)
            due.extend(offsets[s])
        self.order = order           # message ids in arrival order
        self.due = due               # offsets from the window's start

    def message_of(self, i: int) -> bytes:
        return self.messages[i // self.committee_size]

    def setup(self) -> None:
        """Keys of the whole registry, the node's pubkey cache, the warm-up
        of every program the window runs, then the window's signatures and
        sets.  The objects
        made so far are moved out of the collector's sight (``gc.freeze``)
        before each stage that traces or allocates at length."""
        svc_cfg = self.cfg["service"]
        os.environ["LIGHTHOUSE_TPU_HOST_FASTPATH_MAX"] = str(
            svc_cfg["host_fastpath_max_sets"])
        from lighthouse_tpu.beacon_chain.verification_service import (
            VerificationService)
        from lighthouse_tpu.crypto import bls

        if self.control == "rlc_off":
            # The control: every random-linear-combination scalar is 1.
            self._randbits = secrets.randbits
            secrets.randbits = lambda _bits: 1
        t = self.timings = {}
        t0 = time.monotonic()
        base, step = K.key_schedule(self.seed)
        n = self.slots * self.per_slot
        with annotate("bench.generate"):
            self.pks = K.public_keys(base, step, 0,
                                     self.cfg["deployment"]["validators"])
        bls.set_backend(svc_cfg["backend"])
        if svc_cfg["backend"] == "tpu":
            self._fill_pubkey_cache()
        t["keys_and_pubkey_cache_s"] = time.monotonic() - t0
        gc.freeze()
        t0 = time.monotonic()
        warm = self._warm_up(base, step)
        t["warm_up_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        with annotate("bench.generate"):
            self._make_traffic(base, step)
            self.sets = [bls.SignatureSet(bls.Signature(self.sigs[i]),
                                          [bls.PublicKey(self.pks[i])],
                                          self.message_of(i))
                         for i in range(n)]
        gc.freeze()
        t["signatures_s"] = time.monotonic() - t0
        device_verify = None
        if self.fault is not None:
            device_verify = _faulty(bls.get_backend().verify_signature_sets,
                                    self.fault)
        self.svc = VerificationService(
            slo_ms=svc_cfg["slo_ms"], max_batch=svc_cfg["max_batch"],
            deadline_ms=svc_cfg["deadline_ms"],
            max_pending_attestations=svc_cfg["max_pending_attestations"],
            seed=self.seed % 2**32, device_verify=device_verify,
            host_verify=None if device_verify is None
            else bls._BACKENDS["python"].verify_signature_sets)
        for s in warm[2:8]:
            self.svc.submit("attestation", [s])
        self.svc.flush()

    def _fill_pubkey_cache(self) -> None:
        """The node's pubkey cache holds every validator's key, as a
        running node's does after an epoch: the device pubkey table of the
        ``tpu`` backend, filled and uploaded once."""
        import jax
        from lighthouse_tpu.crypto import tpu_backend

        table = tpu_backend._PK_TABLE
        for pk in self.pks:
            table.index_of(pk)
        self.table_cols = int(jax.block_until_ready(table.device()).shape[1])

    def _warm_up(self, base: int, step: int) -> list:
        """Every program the window runs, once, on a service of its own
        with no deadline: a full batch of the registry's last keys
        signing a message of their own, with one pair of signatures
        swapped, so the batch, its split and the host path for single
        sets all run.  Returns the batch."""
        from lighthouse_tpu.beacon_chain.verification_service import (
            VerificationService)
        from lighthouse_tpu.crypto import bls

        batch = self.cfg["service"]["max_batch"]
        if self.cfg["service"]["backend"] == "tpu":
            # The node's boot-time compile of the verify programs, at the
            # width of the filled pubkey table.
            from lighthouse_tpu.common.compile_cache import warmup
            warmup(((batch, 1),), table_cols=self.table_cols)
        first = len(self.pks) - batch
        m = hashlib.sha256(b"warm-up %d" % self.seed).digest()
        sigs = K.signatures(base, step, first, batch, m)
        sigs[0], sigs[1] = sigs[1], sigs[0]
        sets = [bls.SignatureSet(bls.Signature(sig),
                                 [bls.PublicKey(self.pks[first + k])], m)
                for k, sig in enumerate(sigs)]
        svc = VerificationService(
            slo_ms=self.cfg["service"]["slo_ms"], max_batch=batch,
            deadline_ms=0, seed=0)
        got = {}
        for k, s in enumerate(sets):
            svc.submit("attestation", [s],
                       on_result=lambda ok, path, k=k: got.__setitem__(
                           k, (ok, path)))
        svc.flush()
        want = [k > 1 for k in range(batch)]
        if self.control is None and [got[k][0] for k in range(batch)] != want:
            raise RuntimeError("the warm-up batch's verdicts are wrong")
        return sets

    # -- the window -----------------------------------------------------------

    def _done(self, i: int, ok: bool, path: str) -> None:
        self.answer[i] = (bool(ok), path, time.monotonic())

    def run_window(self, t0: float, seconds: float) -> None:
        t_end = t0 + seconds
        self.t0, self.t_end, self.seconds = t0, t_end, seconds
        self.answer: dict = {}
        self.submitted = 0
        self.lateness: list = []
        svc = self.svc
        counters0, batches0 = dict(svc.counters), len(svc.batch_sizes)
        order, due, sets, done = self.order, self.due, self.sets, self._done
        for k, i in enumerate(order):
            at = t0 + due[k]
            if at >= t_end:
                break
            now = time.monotonic()
            if now >= t_end:
                break
            if at > now:
                with annotate("bench.wait"):
                    time.sleep(at - now)
            self.lateness.append(max(0.0, time.monotonic() - at))
            with annotate("bench.submit"):
                svc.submit("attestation", [sets[i]],
                           on_result=functools.partial(done, i), arrival=at)
            self.submitted += 1
        now = time.monotonic()
        if now < t_end:
            with annotate("bench.wait"):
                time.sleep(t_end - now)
        self.counters_window = {k: v - counters0.get(k, 0)
                                for k, v in svc.counters.items()}
        self.batch_sizes = list(svc.batch_sizes)[batches0:]

    def close(self) -> dict:
        """Answer every submitted message (a minute past the close at the
        most) and return the window's numbers."""
        self.svc.flush()
        deadline = time.monotonic() + 60.0
        while len(self.answer) < self.submitted and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        t_end = self.t_end
        due_ids = [i for k, i in enumerate(self.order)
                   if self.due[k] < self.seconds]
        in_window = [i for i in due_ids
                     if i in self.answer and self.answer[i][2] <= t_end
                     and self.answer[i][1] in DEVICE_PATHS
                     and self.answer[i][0] == bool(self.valid[i])]
        c = self.counters_window
        shed = sum(1 for a in self.answer.values() if a[1] == "shed")
        lat = sorted(self.lateness)
        # Failed: a wrong verdict, or a submitted message never answered.
        # The cell runs above capacity, so messages shed, answered late or
        # never offered are the rate's shortfall (``sets_per_s`` and
        # ``answered_in_window_pct``), not failures.
        wrong = sum(1 for i, (ok, path, _t) in self.answer.items()
                    if path != "shed" and ok != bool(self.valid[i]))
        self.attempted = len(due_ids)
        self.failed = wrong + self.submitted - len(self.answer)
        return {
            "e2e": {"sets_per_s": len(in_window) / self.seconds},
            "counters": {
                "sets_in_window": len(in_window),
                "not_in_window": len(due_ids) - len(in_window),
                "verified_sets": c["verified"] + c["rejected"],
                "dispatches": c["dispatches"], "splits": c["splits"],
                "shed": shed, "submitted": self.submitted,
                "due": len(due_ids),
                "answered_after_close": sum(
                    1 for a in self.answer.values() if a[2] > t_end),
                "generator_late_p50_s": lat[len(lat) // 2] if lat else 0.0,
                "generator_late_max_s": lat[-1] if lat else 0.0,
                "mean_batch": (sum(self.batch_sizes) / len(self.batch_sizes)
                               if self.batch_sizes else None),
                "first_batches": self.batch_sizes[:3],
            },
        }

    def release(self) -> None:
        self.svc = None
        self.sets = None
        if hasattr(self, "_randbits"):
            secrets.randbits = self._randbits

    # -- the comparison -------------------------------------------------------

    def check(self) -> dict:
        """Every answer against the label the traffic was made with, and
        the labels of a sample drawn from the seed against the plain BLS
        verify: every answered invalid message and a dozen valid ones."""
        answered = {i: a for i, a in self.answer.items() if a[1] != "shed"}
        wrong = sum(1 for i, (ok, _p, _t) in answered.items()
                    if ok != bool(self.valid[i]))
        lost = self.submitted - len(self.answer)
        rng = random.Random(self.seed ^ 0x5A5A)
        invalid = sorted(i for i in answered if not self.valid[i])
        valid = sorted(i for i in answered if self.valid[i])
        sample = invalid + rng.sample(valid, min(len(valid),
                                                 self.mix["reference_sample"]))
        disagree = sum(
            1 for i in sample
            if K.verify(self.pks[i], self.message_of(i), self.sigs[i])
            != bool(self.valid[i]))
        return {"wrong_verdicts": (wrong, 0), "lost_messages": (lost, 0),
                "reference_disagreements": (disagree, 0)}


def _faulty(verify, fault: str):
    """The device verify broken underneath the service, for the fault
    tests: ``altered_answer`` flips every verdict; ``half_batch`` verifies
    the first half of each batch and returns that verdict for all."""
    if fault == "altered_answer":
        return lambda sets: not verify(sets)
    if fault == "half_batch":
        return lambda sets: verify(sets[:max(1, len(sets) // 2)])
    raise ValueError(f"unknown fault {fault!r}")
