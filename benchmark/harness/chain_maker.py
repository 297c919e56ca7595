"""The range-sync cell's blocks, made in a process of their own while the
cell's process builds its chain and warms the device.

The maker process rebuilds the anchor from the seed exactly as the cell
does (``chain_data.anchor_data``), carries its own copy forward with the
program's ``NO_VERIFICATION`` transition on the CPU (``JAX_PLATFORMS``
is ``cpu`` there: the chip stays the cell's), and writes each batch's
plain values (``ChainMaker.batch``) and the seconds it took to make it
and those before it to ``<dir>/b<i>.pkl`` as soon as it is made, in the
order the batches are imported.  The cell reads them
with :meth:`MakerProcess.batch` and stops the process before its window
opens, so no generation runs inside the window.

    cd benchmark && JAX_PLATFORMS=cpu python3 -m harness.chain_maker <dir>

reads ``<dir>/params.json`` (seed, validators, the state's preset
values, preset name, participation, batches, and the pid of the cell's
process, whose end ends this one).
"""

from __future__ import annotations

import atexit
import json
import os
import pickle
import shutil
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _dump(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(obj, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)


def _end_with(parent: int) -> None:
    """End this process when the cell's process ``parent`` has ended
    (its watchdog exits without running exit handlers)."""
    while os.getppid() == parent:
        time.sleep(0.2)
    os._exit(1)


def make(out: str) -> None:
    """Every batch of ``<out>/params.json``, one file each."""
    from ref import keys as K

    from lighthouse_tpu.crypto import native
    from lighthouse_tpu.types import presets
    from lighthouse_tpu.types.chain_spec import ChainSpec

    from .chain_data import (ChainMaker, anchor_data, anchor_state,
                             batch_plan, public_keys)

    t0 = time.monotonic()
    with open(os.path.join(out, "params.json")) as f:
        prm = json.load(f)
    threading.Thread(target=_end_with, args=(prm["parent"],),
                     daemon=True).start()
    # The cell's process first: its set-up compiles and imports while
    # this one works ahead of it.
    os.nice(10)
    native.available(block=True)
    base, step = K.key_schedule(prm["seed"])
    pks = public_keys(base, step, prm["validators"])
    data = anchor_data(prm["seed"], prm["validators"], prm["p"], pks)
    del pks
    state, T = anchor_state(data, prm["preset_name"])
    del data
    preset = getattr(presets, prm["preset_name"])
    maker = ChainMaker(prm["seed"], state, T, preset, ChainSpec.mainnet(),
                       base, step, prm["participation"])
    plan = batch_plan(int(state.slot), preset.SLOTS_PER_EPOCH,
                      prm["batches"])
    for i, (first, count) in enumerate(plan):
        meta = maker.batch(first, count)
        _dump(os.path.join(out, f"b{i}.pkl"),
              (meta, time.monotonic() - t0))
    maker.close()


class MakerProcess:
    """The maker process of one run, and the batches it has written."""

    def __init__(self, out: str, **params):
        self.out = out
        self.made_s: list = []
        shutil.rmtree(out, ignore_errors=True)
        os.makedirs(out)
        with open(os.path.join(out, "params.json"), "w") as f:
            json.dump(dict(params, parent=os.getpid()), f)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.log = open(os.path.join(out, "maker.log"), "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "harness.chain_maker", out], cwd=BENCH,
            env=env, stdin=subprocess.DEVNULL, stdout=self.log,
            stderr=subprocess.STDOUT)
        atexit.register(self.stop)

    def batch(self, i: int) -> list:
        """Batch ``i``'s plain values, once the process has written them;
        ``made_s[i]`` is when, in seconds from the process's start."""
        path = os.path.join(self.out, f"b{i}.pkl")
        while not os.path.exists(path):
            if self.proc.poll() is not None and not os.path.exists(path):
                raise RuntimeError(
                    f"the block maker ended (exit {self.proc.returncode}) "
                    f"without batch {i}: {self._tail()}")
            time.sleep(0.05)
        with open(path, "rb") as f:
            meta, made_s = pickle.load(f)
        self.made_s.append(round(made_s, 3))
        return meta

    def _tail(self) -> str:
        self.log.flush()
        with open(os.path.join(self.out, "maker.log"), "rb") as f:
            return f.read()[-2000:].decode(errors="replace")

    def stop(self) -> None:
        """End the process (it may still be making later batches) and
        remove its files."""
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        if not self.log.closed:
            self.log.close()
            shutil.rmtree(self.out, ignore_errors=True)


if __name__ == "__main__":
    sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
    make(sys.argv[1])
