"""The exported-program cache (``common/program_cache.py``) on the CPU,
with the CPU named as the platform whose programs are kept: a second
process's program loads from disk without tracing, keeps the jitted
name, gives the jitted function's results, and keys static arguments
and shapes apart; under an outer trace, off the platform, or with a torn
file it falls back to the plain path."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lighthouse_tpu.common import compile_cache
from lighthouse_tpu.common import program_cache as PC


@pytest.fixture
def kept(tmp_path, monkeypatch):
    monkeypatch.setitem(compile_cache._state, "dir", str(tmp_path))
    monkeypatch.setattr(PC, "PLATFORM", jax.default_backend())
    return tmp_path / "exported"


def _fresh(traces: list):
    """A new jitted function object of the same name and module, as a
    new process would build: it records each trace in ``traces``."""
    from functools import partial

    @partial(jax.jit, static_argnames=("k",))
    def _kept_prog(x, y, *, k=1):
        traces.append(x.shape)
        return (x * y + k).sum(axis=0), x - y

    return _kept_prog


def _args(n=8):
    x = jnp.arange(n * 4, dtype=jnp.uint32).reshape(n, 4)
    return x, x + 3


def test_second_process_loads_without_tracing(kept):
    first, again = [], []
    want = _fresh([])(*_args(), k=2)
    got = PC.program(_fresh(first))(*_args(), k=2)
    assert first == [(8, 4)]
    assert len(os.listdir(kept)) == 1
    got2 = PC.program(_fresh(again))(*_args(), k=2)
    assert again == []                      # deserialized, not traced
    for a, b, c in zip(want, got, got2):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_caller_keeps_the_jitted_name(kept):
    prog = PC.program(_fresh([]))
    text = prog.lower(*_args(), k=1).as_text()
    assert "jit__kept_prog" in text


def test_static_arguments_and_shapes_key_apart(kept):
    traces = []
    prog = PC.program(_fresh(traces))
    for k in (1, 2):
        for n in (8, 16):
            want = _fresh([])(*_args(n), k=k)
            got = prog(*_args(n), k=k)
            for a, b in zip(want, got):
                np.testing.assert_array_equal(a, b)
    assert len(os.listdir(kept)) == 4
    prog(*_args(8), k=1)
    assert len(traces) == 4


def test_abstract_lowering_then_call(kept):
    """A boot-time warm-up lowers abstract shapes; the call reuses it."""
    traces = []
    prog = PC.program(_fresh(traces))
    avals = [jax.ShapeDtypeStruct((8, 4), jnp.uint32)] * 2
    prog.lower(*avals, k=3).compile()
    got = prog(*_args(), k=3)
    np.testing.assert_array_equal(got[0], _fresh([])(*_args(), k=3)[0])
    assert traces == [(8, 4)]


def test_outer_trace_uses_the_plain_program(kept):
    prog = PC.program(_fresh([]))
    out = jax.jit(lambda x, y: prog(x, y, k=1)[0])(*_args())
    np.testing.assert_array_equal(out, _fresh([])(*_args(), k=1)[0])
    assert not kept.exists()


def test_off_the_platform_nothing_is_kept(kept, monkeypatch):
    monkeypatch.setattr(PC, "PLATFORM", "no-such-platform")
    out = PC.program(_fresh([]))(*_args(), k=1)
    np.testing.assert_array_equal(out[1], _fresh([])(*_args(), k=1)[1])
    assert not kept.exists()


def test_torn_file_is_exported_again(kept):
    PC.program(_fresh([]))(*_args(), k=1)
    (path,) = kept.iterdir()
    path.write_bytes(path.read_bytes()[:100])
    traces = []
    out = PC.program(_fresh(traces))(*_args(), k=1)
    assert traces == [(8, 4)]
    np.testing.assert_array_equal(out[0], _fresh([])(*_args(), k=1)[0])
    assert PC._read(str(path)) is not None


def test_donating_program(kept):
    prog = PC.program(jax.jit(lambda a: a * 2, donate_argnums=(0,)),
                      donate_argnums=(0,))
    x = jnp.arange(16, dtype=jnp.uint32)
    np.testing.assert_array_equal(prog(x + 0), np.arange(16) * 2)
