"""Per-slot state roots: the slot's writes into a device-resident Capella
``BeaconState``, then ``tree_hash_root()``, slot after slot (closed loop).

The state and every slot's writes come from the seed
(``harness/state_data.py``).  A slot's latency runs from the start of its
writes to the root's return.  The comparison replays the same writes into
the plain reference (``ref/ssz_state.py``) and compares the roots of the
set-up and of a sample of the window's slots drawn from the seed.
"""

from __future__ import annotations

import gc
import random
import time

import numpy as np

from ref.ssz_state import StateReference

from .common import annotate, percentile
from .state_data import SlotWrites, make_state_data

STATE_SUBSYSTEMS = ("device_tree", "registry_mirror", "packed_cache",
                    "staging")


def _ledger_h2d() -> int:
    from lighthouse_tpu.common.device_ledger import LEDGER
    subs = LEDGER.snapshot()["subsystems"]
    return sum(int(subs[s]["h2d_bytes"]) for s in STATE_SUBSYSTEMS)


def _program_state(data: dict, preset_name: str):
    """The system's Capella state holding ``data``'s values."""
    from lighthouse_tpu.types import presets
    from lighthouse_tpu.types.chain_spec import ForkName
    from lighthouse_tpu.types.factory import spec_types
    from lighthouse_tpu.types.validators import ValidatorRegistry

    T = spec_types(getattr(presets, preset_name))
    state = T.state_cls(ForkName.CAPELLA)()
    n = len(data["balances"])
    reg = ValidatorRegistry(n)
    reg._n = n
    reg.init_columns(**{k: v.copy() for k, v in data["registry"].items()})
    state.validators = reg
    for f in ("balances", "previous_epoch_participation",
              "current_epoch_participation", "inactivity_scores",
              "slashings"):
        setattr(state, f, data[f].copy())
    for f in ("block_roots", "state_roots", "randao_mixes"):
        getattr(state, f)[:] = data[f]
    sm = data["small"]
    state.genesis_time = sm["genesis_time"]
    state.genesis_validators_root = sm["genesis_validators_root"]
    state.slot = sm["slot"]
    state.fork = T.Fork(**sm["fork"])
    state.latest_block_header = T.BeaconBlockHeader(
        **sm["latest_block_header"])
    state.eth1_data = T.Eth1Data(**sm["eth1_data"])
    state.eth1_deposit_index = sm["eth1_deposit_index"]
    state.justification_bits = list(sm["justification_bits"])
    for f in ("previous_justified_checkpoint", "current_justified_checkpoint",
              "finalized_checkpoint"):
        setattr(state, f, T.Checkpoint(**sm[f]))
    for f in ("current_sync_committee", "next_sync_committee"):
        setattr(state, f, T.SyncCommittee(
            pubkeys=list(sm[f]["pubkeys"]),
            aggregate_pubkey=sm[f]["aggregate_pubkey"]))
    state.latest_execution_payload_header = T.ExecutionPayloadHeaderCapella(
        **sm["latest_execution_payload_header"])
    state.next_withdrawal_index = sm["next_withdrawal_index"]
    state.next_withdrawal_validator_index = \
        sm["next_withdrawal_validator_index"]
    return state, T


class Cell:
    def __init__(self, cfg: dict, mix: dict, seed: int, *,
                 control: str | None = None, fault: str | None = None):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.control, self.fault = control, fault
        d = cfg["deployment"]
        self.n = d["validators"]
        self.p = {**d["preset"], "start_epoch": d["start_epoch"],
                  "fork_epoch": d["fork_epoch"],
                  "previous_version": d["previous_version"],
                  "current_version": d["current_version"],
                  "slots_per_epoch": d["slots_per_epoch"]}
        self.roots: list = []        # (slot index, root) of every slot run
        self._lagged = None

    def setup(self) -> None:
        from lighthouse_tpu.types.device_state import materialize_state

        with annotate("bench.generate"):
            data = make_state_data(self.seed, self.n, self.p)
            self.writes = SlotWrites(self.seed, data, self.p)
            self.state, self.T = _program_state(
                data, self.cfg["deployment"]["preset_name"])
        del data
        if not materialize_state(self.state):
            raise RuntimeError("materialize_state refused the state")
        self.cold_root = self.state.tree_hash_root()
        self.next_slot = 0
        for _ in range(self.mix["warmup_slots"]):
            self._slot()

    def _apply(self, w: dict) -> None:
        st = self.state
        idx, flags = w["participation"]
        if self.fault == "half_writes":
            idx, flags = idx[:idx.size // 2], flags[:flags.size // 2]
        if self.control == "lagged_writes":
            # The control: each slot's participation lands after its root.
            if self._lagged is not None:
                st.current_epoch_participation[self._lagged[0]] = \
                    self._lagged[1]
            self._lagged = (idx, flags)
        else:
            st.current_epoch_participation[idx] = flags
        bidx, bvals = w["balances"]
        st.balances[bidx] = bvals
        for f in ("block_roots", "state_roots", "randao_mixes"):
            i, root = w[f]
            getattr(st, f)[i] = np.frombuffer(root, np.uint8)
        st.slot = w["slot"]
        st.latest_block_header = self.T.BeaconBlockHeader(
            **w["latest_block_header"])

    def _slot(self) -> float:
        s = self.next_slot
        with annotate("bench.generate"):
            w = self.writes(s)
        t0 = time.monotonic()
        with annotate("bench.mutate"):
            self._apply(w)
        with annotate("bench.root"):
            if self.fault == "stale_root" and self.roots:
                root = self.roots[-1][1]
            else:
                root = self.state.tree_hash_root()
        dt = time.monotonic() - t0
        if self.fault == "altered_answer":
            root = bytes([root[0] ^ 1]) + root[1:]
        self.roots.append((s, root))
        self.next_slot += 1
        return dt

    def run_window(self, t0: float, seconds: float) -> None:
        self.seconds = seconds
        self.first_window_slot = self.next_slot
        self.h2d0 = _ledger_h2d()
        t_end = t0 + seconds
        self.latencies = []
        while time.monotonic() < t_end:
            self.latencies.append(self._slot())
        self.h2d_bytes = _ledger_h2d() - self.h2d0

    def close(self) -> dict:
        lat = self.latencies
        self.attempted, self.failed = len(lat), 0
        return {
            "e2e": {"slot_root_p95_ms": percentile(lat, 95) * 1e3},
            "counters": {"roots": len(lat), "h2d_bytes": self.h2d_bytes,
                         "slot_root_p50_ms": percentile(lat, 50) * 1e3,
                         "slot_root_max_ms": max(lat) * 1e3,
                         "validators": self.n},
        }

    def release(self) -> None:
        self.state = None
        gc.collect()

    def check(self) -> dict:
        """The cold root, every warm-up root, the window's last root and a
        sample of its other roots drawn from the seed, each against the
        reference fed the same writes."""
        window = [s for s, _r in self.roots[self.first_window_slot:]]
        rng = random.Random(self.seed ^ 0xA5A5)
        want = set(range(self.first_window_slot)) | {window[-1]} | set(
            rng.sample(window, min(len(window),
                                   self.mix["reference_sample"])))
        data = make_state_data(self.seed, self.n, self.p)
        ref = StateReference(data)
        writes = SlotWrites(self.seed, data, self.p)
        mismatches = int(ref.root() != self.cold_root)
        got = dict(self.roots)
        for s in range(max(want) + 1):
            ref.apply(writes(s))
            if s in want:
                mismatches += int(ref.root() != got[s])
        return {"root_mismatches": (mismatches, 0)}
