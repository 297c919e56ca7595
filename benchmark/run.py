#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process holds.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration (``configs[].file``), its traffic mix
(``benchmark/traffic/<traffic>.json``) and the per-layer metrics' readers
(``benchmark/metrics/<metric>.py``) are files of their own.  The mix's
``kind`` names the generator that drives it (``benchmark/harness/<kind>.py``).

Set-up builds the inputs from ``--seed`` and warms every program the
window runs; the window then measures for ``--seconds``.  With
``--trace 1`` the window runs under the JAX profiler and the result holds
the per-layer metrics instead of the end-to-end ones.  After the window
the plain reference checks what the timed path produced; ``correct`` is
true when every compared number is within its limit.  The last line of
standard output is one JSON object; with no TPU, or fewer chips than the
cell asks for, the run exits 2 and prints no result.

``--control`` and ``--fault`` break the timed path on purpose, for the
tests that show the comparison fails them; the benchmark's runs never
pass them.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness.common import (CompileClock, device_record,  # noqa: E402
                            process_start_monotonic)

T_PROCESS = process_start_monotonic()
CACHE_DIR = os.path.join(HERE, ".cache")


def parse(argv):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", default=None)
    return ap.parse_args(argv)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell_spec(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, mix and metric entries."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    wl = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    return {
        "workload": wl,
        "config": load_json(os.path.join(root, cfg_entry["file"])),
        "mix": load_json(os.path.join(root, "benchmark", "traffic",
                                      wl["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def load_reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices_or_exit(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"benchmark: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache where the program keeps it:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's fixed
    ``.jax_cache``; every program cached."""
    from lighthouse_tpu.common.compile_cache import enable
    enable(min_compile_time_secs=0.0)


def run(argv=None, *, require_tpu: bool = True, root: str = ROOT,
        overrides: dict | None = None) -> dict:
    """One run; returns the result object (also printed).  ``overrides``
    updates the configuration's and the mix's keys (tests at small
    sizes)."""
    args = parse(argv)
    spec = load_cell_spec(args.workload, root)
    for part, upd in (overrides or {}).items():
        spec[part] = _merged(spec[part], upd)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = devices_or_exit(spec["workload"]["chips"], require_tpu)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.listen)
    enable_compile_cache()

    kind = importlib.import_module("harness." + spec["mix"]["kind"])
    cell = kind.Cell(spec["config"], spec["mix"], args.seed,
                     control=args.control, fault=args.fault)
    timings = {"before_setup_s": time.monotonic() - T_PROCESS}
    cell.setup()
    timings.update(getattr(cell, "timings", {}))
    trace_dir = os.path.join(CACHE_DIR, "trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    # A traced run measures the mix's ``trace_seconds`` at most: the
    # profiler's buffer holds a few seconds of the busiest cells.
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, spec["mix"].get("trace_seconds", seconds))
    t0 = time.monotonic()
    setup_s = t0 - T_PROCESS
    with jax.profiler.TraceAnnotation("bench.window"):
        cell.run_window(t0, seconds)
    t1 = time.monotonic()
    summary = None
    if args.trace:
        jax.profiler.stop_trace()
    timings["stop_trace_s"] = time.monotonic() - t1
    device = device_record(devices)
    tc = time.monotonic()
    out = cell.close()
    timings["close_s"] = time.monotonic() - tc
    if args.trace:
        from harness.trace import find_xplane, reduce_file
        tc = time.monotonic()
        summary = reduce_file(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        timings["reduce_s"] = time.monotonic() - tc
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    cell.release()
    gc.collect()
    tc = time.monotonic()
    checks = cell.check()
    timings["reference_s"] = time.monotonic() - tc
    correct = all(v <= lim for v, lim in checks.values())

    if args.trace:
        ctx = {"counters": out["counters"], "trace": summary}
        metrics = {}
        for m in spec["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(out["e2e"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    result = {"correct": correct, "attempted": cell.attempted,
              "failed": cell.failed, "metrics": metrics, "device": device,
              "setup": clock.between(T_PROCESS, t0),
              "window_compiles": clock.count("compile_s", t0, t1),
              "counters": out["counters"]}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
        result["trace_edges"] = summary.edges
        result["trace_programs"] = dict(sorted(
            summary.program_s.items(), key=lambda kv: -kv[1])[:12])
    timings["total_s"] = time.monotonic() - T_PROCESS
    result["timings"] = timings
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return result


def _merged(base, upd):
    if isinstance(base, dict) and isinstance(upd, dict):
        out = dict(base)
        for k, v in upd.items():
            out[k] = _merged(base.get(k), v)
        return out
    return upd


def main(argv=None) -> int:
    try:
        run(argv)
    except SystemExit as e:
        return e.code if isinstance(e.code, int) else 1
    except Exception:  # noqa: BLE001 — the run failed: no result line
        traceback.print_exc()
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
