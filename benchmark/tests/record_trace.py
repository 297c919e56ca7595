"""Record the small profiler trace that ``test_trace.py`` reads.

    python3 benchmark/tests/record_trace.py <out_dir>

On the chip: a few small jitted programs under the harness's host spans
(``bench.window`` around them, ``bench.root`` / ``bench.wait`` inside),
traced with ``jax.profiler``; the ``.xplane.pb`` is copied to
``<out_dir>/tpu_window.xplane.pb`` and its planes, lines and first events
are printed, with the reduction's summary of it.
"""

from __future__ import annotations

import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from harness.trace import find_xplane, reduce_planes

    os.makedirs(out_dir, exist_ok=True)
    square = jax.jit(lambda x: (x @ x).sum())
    shift = jax.jit(lambda x: jnp.roll(x, 1, axis=0) * 3 + 1)
    x = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready((square(x), shift(x)))
    log = os.path.join(out_dir, "log")
    shutil.rmtree(log, ignore_errors=True)
    jax.profiler.start_trace(log)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.root"):
                jax.block_until_ready(square(x))
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.01)
            with jax.profiler.TraceAnnotation("bench.mutate"):
                x = jax.block_until_ready(shift(x))
    jax.profiler.stop_trace()
    path = find_xplane(log)
    dst = os.path.join(out_dir, "tpu_window.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(log, ignore_errors=True)
    planes = ProfileData.from_file(dst).planes
    for plane in planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])
    s = reduce_planes(ProfileData.from_file(dst).planes)
    print("SUMMARY", s.window_s, s.busy_s, s.devices, s.program_s,
          s.program_calls, s.breakdown())
    print("bytes", os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
