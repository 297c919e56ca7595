"""The trace reduction, on a small trace recorded on a TPU v5e by
``record_trace.py`` and on hand-made planes."""

import os
from types import SimpleNamespace as NS

import pytest

from harness.trace import op_name, program_name, reduce_file, reduce_planes

DATA = os.path.join(os.path.dirname(__file__), "data", "tpu_window.xplane.pb")


def test_recorded_tpu_trace():
    s = reduce_file(DATA)
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.03631888)
    # Six executions of the two programs ran inside the window; the
    # device clock of this trace reads about 1.2 ms behind the host's, so
    # the first execution lands before the window's host span opens.
    assert s.program_calls == {"_lambda": 5}
    assert 0 < s.busy_s < s.window_s
    assert s.busy_s == pytest.approx(s.programs_s(["_lambda"]), rel=0.05)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "fusion"
    labels = [g[0].split(" at ")[0] for g in b["idle_gaps"]]
    assert set(labels) <= {"root", "wait", "mutate", "other"}
    assert "wait" in labels


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        _ev("bench.window", 1000, 10000),
        _ev("bench.submit", 1000, 3000),
        _ev("bench.wait", 4000, 4000),
        _ev("bench.submit", 8000, 3000),
        _ev("something.else", 1000, 9000),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit__miller_cell(7)", 500, 2500),
            _ev("jit_hash_g2_kernel_call(9)", 8500, 1000)]),
        NS(name="XLA Ops", events=[
            _ev("%miller_fold_kernel_call.1 = u32[1] custom-call()", 500,
                2500),
            _ev("%hash_g2_kernel_call.3 = u32[1] custom-call()", 8500, 1000),
            _ev("%fusion.2 = u32[1] fusion()", 9000, 300)]),
    ])
    return [host, dev]


def test_reduce_planes_by_hand():
    s = reduce_planes(_planes())
    assert s.window_s == 10000 / 1e9
    # Busy: [1000, 3000) clipped to the window, and [8500, 9500).
    assert s.busy_s == pytest.approx(3000 / 1e9)
    assert s.program_s == {"_miller_cell": 2000 / 1e9,
                           "hash_g2_kernel_call": 1000 / 1e9}
    assert s.programs_s(["_miller_cell", "absent"]) == 2000 / 1e9
    assert s.programs_s(["absent"]) is None
    assert s.op_s["fusion"] == pytest.approx(300 / 1e9)
    assert s.edges == {"first_op_after_start_s": 0.0,
                       "last_op_before_end_s": 1500 / 1e9}
    # Gaps: [3000, 8500) mostly under "wait", [9500, 11000) under submit.
    assert s.idle_gaps[0] == ("wait at 0.00s", 5500 / 1e9)
    assert s.idle_gaps[1][0].startswith("submit")


def test_a_trace_without_the_window_span_is_refused():
    planes = _planes()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        reduce_planes(planes)


def test_names():
    assert program_name("jit__prepare_cell(123)") == "_prepare_cell"
    assert program_name("jit_scatter_propagate_body(5)") == \
        "scatter_propagate_body"
    assert op_name("%while.52 = (s32[]) while(...)") == "while"
