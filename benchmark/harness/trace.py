"""Reduction of one JAX profiler trace to the device's busy time, the
device time of named programs, and the idle gaps with what the host was
doing in them.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  Devices are the planes named
``/device:<KIND>:<n>``; an operation's interval is an event of the
plane's ``XLA Ops`` line (every line of the plane where it has none), and
a program's is an event of its ``XLA Modules`` line.  The window is the
host span ``bench.window`` that the harness opens around the measured
window; the harness's other ``bench.*`` host spans name the idle gaps.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def program_name(event_name: str) -> str:
    """``jit__miller_cell(123)`` → ``_miller_cell``: the jitted function's
    name without the ``jit_`` prefix and the execution id."""
    name = re.sub(r"\(\d+\)$", "", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%miller_fold_kernel_call.1 = u32[...] custom-call(...)`` →
    ``miller_fold_kernel_call``: an XLA op's name without its HLO text
    and instance number."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def union(intervals) -> list:
    """Sorted, merged ``[(start, end), ...]``."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # mean over the devices traced
    devices: int
    program_s: dict = field(default_factory=dict)   # name → device s
    program_calls: dict = field(default_factory=dict)
    op_s: dict = field(default_factory=dict)        # op name → device s
    idle_gaps: list = field(default_factory=list)   # [(label, s)], longest
    # Seconds from the window's start to the first device operation, and
    # from the last one to the window's end.
    edges: dict = field(default_factory=dict)

    def programs_s(self, names) -> float | None:
        """Summed device seconds of the named programs (mean over the
        devices), or None when none of them ran."""
        hit = [self.program_s[n] for n in names if n in self.program_s]
        return sum(hit) if hit else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]]}


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_planes(planes) -> TraceSummary:
    """The summary of already-read planes: ``planes`` is an iterable of
    objects with ``name`` and ``lines``, each line with ``name`` and
    ``events`` of ``name``, ``start_ns`` and ``duration_ns``."""
    host_spans, device_planes = [], []
    window = None
    for plane in planes:
        if plane.name.startswith("/device:"):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == WINDOW_SPAN:
                    window = span
                else:
                    name = ev.name[len(HOST_SPAN_PREFIX):]
                    host_spans.append((name,) + span)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} host span")
    lo, hi = window
    busy_total, busy_union_all = 0.0, []
    program_s: dict = {}
    program_calls: dict = {}
    op_s: dict = {}
    used = 0
    for plane in device_planes:
        lines = {ln.name: ln for ln in plane.lines}
        op_lines = [lines[OPS_LINE]] if OPS_LINE in lines else list(
            plane.lines)
        ops = []
        for ln in op_lines:
            for ev in ln.events:
                iv = _clip([(ev.start_ns, ev.start_ns + ev.duration_ns)],
                           lo, hi)
                if iv:
                    ops.extend(iv)
                    if ln.name == OPS_LINE:
                        name = op_name(ev.name)
                        op_s[name] = op_s.get(name, 0.0) + \
                            (iv[0][1] - iv[0][0]) / 1e9
        if not ops:
            continue
        used += 1
        merged = union(ops)
        busy_total += sum(b - a for a, b in merged) / 1e9
        busy_union_all.extend(merged)
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                iv = _clip([(ev.start_ns, ev.start_ns + ev.duration_ns)],
                           lo, hi)
                if iv:
                    name = program_name(ev.name)
                    program_s[name] = program_s.get(name, 0.0) + \
                        (iv[0][1] - iv[0][0]) / 1e9
                    program_calls[name] = program_calls.get(name, 0) + 1
    n = max(used, 1)
    busy = union(busy_union_all)
    edges = ({"first_op_after_start_s": (busy[0][0] - lo) / 1e9,
              "last_op_before_end_s": (hi - busy[-1][1]) / 1e9}
             if busy else {})
    program_s = {k: v / n for k, v in program_s.items()}
    op_s = {k: v / n for k, v in op_s.items()}
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / n, devices=used,
        program_s=program_s, program_calls=program_calls, op_s=op_s,
        idle_gaps=_idle_gaps(busy, lo, hi, host_spans), edges=edges)


def _idle_gaps(busy, lo, hi, host_spans, top: int = 10) -> list:
    """The ``top`` longest gaps of the window in which no device ran an
    operation, longest first, each named by the host span that covers
    most of it and by where it starts in the window."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        cover: dict = {}
        for name, s, e in host_spans:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[name] = cover.get(name, 0) + ov
        label = max(cover, key=cover.get) if cover else "other"
        out.append((f"{label} at {(a - lo) / 1e9:.2f}s", (b - a) / 1e9))
    out.sort(key=lambda g: -g[1])
    return out


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)
