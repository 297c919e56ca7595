"""Differential driver for the PR 20 mesh residency layer.

Runs every mesh-resident subsystem's deterministic scenario on an
N-device virtual CPU mesh AND forced to one device, and requires
bit-identical outputs (the sharded programs reuse the 1-device fold
order, so equality is exact, not approximate).  ``all`` additionally
drives the full modeled slot (registry scatter/rebuild -> packed state
root -> fork-choice head -> slasher ingest) and enforces the warm-slot
transfer budget on the measured slots.

Modes:

    python scripts/validate_mesh.py --devices 8 --subsystem all
        Full differential + modeled-slot run; exit 1 on any digest
        mismatch or budget breach.

    python scripts/validate_mesh.py --devices 8 --subsystem forkchoice
        One subsystem only: tree | registry | packed | forkchoice |
        slasher | all.

    python scripts/validate_mesh.py --devices 8 --warmup
        Compile-cache warmup hook: traces/compiles every mesh program
        the quick tier and the dry run use, so later runs replay
        executables from ``.jax_cache``.

    ... --json
        Emit one machine-readable JSON object (the bench `mesh_slot`
        row shells out with this).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_N_DEV = "8"
if "--devices" in sys.argv:
    _N_DEV = sys.argv[sys.argv.index("--devices") + 1]
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={_N_DEV}").strip()
# The process-wide mesh knob sizes get_mesh(); the scenarios flip it to
# 1 themselves for the reference side.
os.environ["LIGHTHOUSE_TPU_MESH_DEVICES"] = _N_DEV

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from lighthouse_tpu.common.compile_cache import enable as _cache_enable  # noqa: E402

_cache_enable()

from lighthouse_tpu.parallel import mesh_slot as MS  # noqa: E402
from lighthouse_tpu.parallel.mesh_slot import SUBSYSTEM_CHOICES  # noqa: E402


def main() -> int:
    argv = sys.argv[1:]
    emit_json = "--json" in argv
    warmup = "--warmup" in argv
    subsystem = "all"
    if "--subsystem" in argv:
        subsystem = argv[argv.index("--subsystem") + 1]
    if subsystem not in SUBSYSTEM_CHOICES:
        print(f"validate_mesh: unknown subsystem {subsystem!r} "
              f"(choices: {', '.join(SUBSYSTEM_CHOICES)})",
              file=sys.stderr)
        return 2
    n_dev = int(_N_DEV)
    if not emit_json:
        print(f"devices: {jax.devices()}", flush=True)

    names = ([s for s in SUBSYSTEM_CHOICES if s != "all"]
             if subsystem == "all" else [subsystem])

    if warmup:
        # One pass per scenario at both device counts traces every
        # sharded program into the persistent cache; nothing asserted.
        for name in names:
            MS.check_subsystem(name)
        if subsystem == "all":
            MS.run_slot_model()
            with MS.forced_devices(1):
                MS.run_slot_model()
        print(json.dumps({"warmup": True, "devices": n_dev,
                          "subsystems": names}), flush=True)
        return 0

    out = {"devices": n_dev, "subsystems": {}, "ok": True}
    for name in names:
        res = MS.check_subsystem(name)
        out["subsystems"][name] = res["match"]
        out["ok"] = out["ok"] and res["match"]
        if not emit_json:
            print(f"{name}: {'OK' if res['match'] else 'MISMATCH'} "
                  f"({n_dev}-device vs 1-device)", flush=True)

    if subsystem == "all":
        mesh_run = MS.run_slot_model()
        with MS.forced_devices(1):
            ref_run = MS.run_slot_model()
        slot_ok = mesh_run["digest"] == ref_run["digest"]
        budget_ok = bool(mesh_run["budget"]["ok"]
                         and ref_run["budget"]["ok"])
        out["slot_digest_match"] = slot_ok
        out["slot_budget_ok"] = budget_ok
        out["slot_row_1dev"] = ref_run["rows"][-1]
        out["slot_row_projected"] = MS.projected_slot_row(
            ref_run["rows"][-1], n_dev)
        out["shard_rows"] = {k: len(v)
                             for k, v in mesh_run["shards"].items()}
        out["shards"] = mesh_run["shards"]
        out["ok"] = out["ok"] and slot_ok and budget_ok
        if not emit_json:
            print(f"modeled slot: digest "
                  f"{'OK' if slot_ok else 'MISMATCH'}, budget "
                  f"{'OK' if budget_ok else 'BREACHED'}", flush=True)
            if not budget_ok:
                print(json.dumps({"budget": mesh_run["budget"]}),
                      flush=True)

    if emit_json:
        print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
