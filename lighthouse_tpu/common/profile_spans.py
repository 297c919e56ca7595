"""Charge a JAX profiler trace's device-idle time to the node's spans.

    python -m lighthouse_tpu.common.profile_spans <log dir | .xplane.pb> \
        [--window bench.window] [--also bench.wait ...]

Reads the ``.xplane.pb`` that any ``jax.profiler`` session writes.  The
device is busy where any ``/device:*`` plane runs an operation: an event
of its ``XLA Ops`` line (of every line where a plane has none).  The
node's spans are the host events named ``lh.<span>``
(:mod:`~lighthouse_tpu.common.tracing` opens them while a session is
active); they share the profiler's clock with the device events.  Per
span name, inside the window:

- ``span_s`` — seconds of the union of its events, on any thread;
- ``calls`` — its events;
- ``idle_under_s`` — seconds in which no device ran an operation and an
  event of the span was open;
- ``idle_under_children_s`` — the part of ``idle_under_s`` that a span
  one layer down (``<name>.<child>``) also covers.

Besides: the window's busy and idle seconds, the idle seconds no ``lh.``
span (nor a host event named by ``--also``) covers, and the longest idle
gaps with the seconds each span covers in them.  The window is the host
event named by ``--window``, else the trace's extent.  The reduction
takes already-read planes (objects with ``name`` and ``lines``; lines
with ``name`` and ``events`` of ``name``, ``start_ns``, ``duration_ns``)
and imports nothing heavy; only reading a file imports ``jax``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .tracing import PROFILE_PREFIX

OPS_LINE = "XLA Ops"
GAPS = 8  # longest idle gaps listed

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged ``[(start, end), ...]``."""
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def intersect(xs: Sequence[Interval],
              ys: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted, merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(xs: Sequence[Interval], lo: float,
               hi: float) -> List[Interval]:
    """``[lo, hi)`` less a sorted, merged interval list."""
    out, t = [], lo
    for a, b in xs:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if hi > t:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def _length(xs: Iterable[Interval]) -> float:
    return sum(b - a for a, b in xs)


def reduce_planes(planes, window: Optional[str] = None,
                  also: Sequence[str] = ()) -> dict:
    """The span table of already-read planes (module docstring); times
    in seconds."""
    spans: Dict[str, list] = {}
    extra: List[Interval] = []
    busy_all: List[Interval] = []
    win: Optional[Interval] = None
    lo, hi = float("inf"), float("-inf")
    for plane in planes:
        device = plane.name.startswith("/device:")
        lines = list(plane.lines)
        if device and any(ln.name == OPS_LINE for ln in lines):
            lines = [ln for ln in lines if ln.name == OPS_LINE]
        for ln in lines:
            for ev in ln.events:
                iv = (float(ev.start_ns),
                      float(ev.start_ns) + float(ev.duration_ns))
                lo, hi = min(lo, iv[0]), max(hi, iv[1])
                if device:
                    busy_all.append(iv)
                elif ev.name == window:
                    win = iv
                elif ev.name.startswith(PROFILE_PREFIX):
                    spans.setdefault(
                        ev.name[len(PROFILE_PREFIX):], []).append(iv)
                elif ev.name in also:
                    extra.append(iv)
    if window is not None and win is None:
        raise ValueError(f"the trace has no {window!r} host event")
    if win is not None:
        lo, hi = win
    if hi <= lo:
        raise ValueError("the trace holds no events")
    clip = [(lo, hi)]
    busy = intersect(union(busy_all), clip)
    idle = complement(busy, lo, hi)
    table = {}
    covered = []
    span_u = {}
    for name, ivs in spans.items():
        u = intersect(union(ivs), clip)
        span_u[name] = u
        covered.extend(u)
        calls = sum(1 for a, b in ivs if min(b, hi) > max(a, lo))
        table[name] = {"span_s": _length(u) / 1e9, "calls": calls,
                       "idle_under_s": _length(intersect(u, idle)) / 1e9}
    for name, row in table.items():
        kids = union(iv for child, u in span_u.items()
                     if child.startswith(name + ".") for iv in u)
        if kids:
            row["idle_under_children_s"] = _length(intersect(
                intersect(span_u[name], idle), kids)) / 1e9
    cover_u = union(covered + extra)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:GAPS]
    gap_rows = []
    for a, b in longest:
        g = [(a, b)]
        cover = {name: _length(intersect(u, g)) / 1e9
                 for name, u in span_u.items()}
        gap_rows.append({
            "at_s": (a - lo) / 1e9, "s": (b - a) / 1e9,
            "cover_s": {k: v for k, v in sorted(
                cover.items(), key=lambda kv: -kv[1]) if v > 0}})
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": _length(busy) / 1e9,
        "idle_s": _length(idle) / 1e9,
        "uncovered_idle_s": _length(intersect(
            idle, complement(cover_u, lo, hi))) / 1e9,
        "spans": dict(sorted(table.items())),
        "longest_idle_gaps": gap_rows,
    }


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def reduce_file(path: str, **kw) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(find_xplane(path)).planes,
                         **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("trace", help="profiler log dir or .xplane.pb file")
    ap.add_argument("--window", default=None,
                    help="host event bounding the window (default: the "
                         "trace's extent)")
    ap.add_argument("--also", action="append", default=[],
                    help="host event name that also counts as covering "
                         "idle time (repeatable)")
    args = ap.parse_args(argv)
    out = reduce_file(args.trace, window=args.window, also=args.also)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
