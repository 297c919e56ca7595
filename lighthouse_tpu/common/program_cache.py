"""Exported-program cache: the traced and lowered BLS device programs,
kept on disk beside the persistent compilation cache.

The persistent compilation cache (:mod:`.compile_cache`) skips the
backend compile only; every process still traces and lowers each
program it runs, and the Pallas BLS programs (hash-to-curve, the Miller
cell, the final exponentiation, the K-bucket prepare) take minutes of
host time to trace and lower on every start.  :func:`program` wraps a
jitted function so that, on a TPU with the compilation cache enabled,
each (shapes, static arguments) signature is traced and lowered once
and kept as a serialized :mod:`jax.export` artifact under
``<compile cache dir>/exported``; a later process deserializes it and
jits a thin caller of the same name (the device trace names the program
as before), whose compile is a persistent-cache hit.

The key holds the package's source digest, the JAX version, the device
kind, every ``LIGHTHOUSE_TPU_*`` setting, the program's qualified name
and its argument shapes and static arguments, so an edit to any module,
another chip or another setting re-traces.  Off the TPU, with the cache
off, under an outer trace, for arguments that are not arrays, or where
export fails, the wrapped function is the plain jitted one.
"""

from __future__ import annotations

import hashlib
import os
import threading

import jax

_lock = threading.Lock()
_digest: dict = {}

# The platform whose programs are kept (the tests name the CPU's).
PLATFORM = "tpu"

PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _source_digest() -> str:
    """SHA-256 over every ``.py`` file of the package (path and bytes),
    once per process."""
    d = _digest.get("src")
    if d is None:
        h = hashlib.sha256()
        for root, dirs, files in os.walk(PACKAGE):
            dirs[:] = sorted(x for x in dirs if x != "__pycache__")
            for f in sorted(files):
                if f.endswith(".py"):
                    p = os.path.join(root, f)
                    h.update(os.path.relpath(p, PACKAGE).encode())
                    with open(p, "rb") as fh:
                        h.update(fh.read())
        d = _digest["src"] = h.hexdigest()
    return d


def _environment_digest() -> str:
    """The settings that can change what a program traces to."""
    dev = jax.devices()[0]
    env = sorted((k, v) for k, v in os.environ.items()
                 if k.startswith("LIGHTHOUSE_TPU_"))
    return repr((jax.__version__, dev.platform, dev.device_kind, env))


def _cache_dir() -> str | None:
    """``<compile cache dir>/exported`` on :data:`PLATFORM` with the
    compilation cache enabled, else None (the plain jitted path)."""
    from . import compile_cache
    base = compile_cache.cache_dir()
    if base is None or jax.default_backend() != PLATFORM:
        return None
    return os.path.join(base, "exported")


def _named(call, name: str):
    def caller(*args):
        return call(*args)
    caller.__name__ = caller.__qualname__ = name
    return caller


class Program:
    """A jitted function whose traced-and-lowered form is kept on disk
    per signature (see the module docstring).  Called like the jitted
    function; ``lower`` lowers what a call would run."""

    def __init__(self, jitted, *, donate_argnums=()):
        self.jitted = jitted
        self.name = jitted.__name__
        self.qualname = f"{jitted.__module__}.{self.name}"
        self.donate_argnums = tuple(donate_argnums)
        self._callers: dict = {}

    def __call__(self, *args, **static):
        return self._caller(args, static)(*args)

    def lower(self, *args, **static):
        return self._caller(args, static).lower(*args)

    def _caller(self, args, static):
        if any(isinstance(a, jax.core.Tracer) or not hasattr(a, "shape")
               for a in args):
            return _PlainCaller(self.jitted, static)
        key = (tuple((tuple(a.shape), str(a.dtype),
                      bool(getattr(a, "weak_type", False))) for a in args),
               tuple(sorted(static.items())))
        fn = self._callers.get(key)
        if fn is None:
            with _lock:
                fn = self._callers.get(key)
                if fn is None:
                    fn = self._callers[key] = self._load(key, static)
        return fn

    def _load(self, key, static):
        base = _cache_dir()
        if base is None:
            return _PlainCaller(self.jitted, static)
        ident = hashlib.sha256(repr((
            _source_digest(), _environment_digest(), self.qualname,
            key)).encode()).hexdigest()[:32]
        path = os.path.join(base, f"{self.name}-{ident}.jaxexport")
        exp = _read(path)
        if exp is None:
            avals = [jax.ShapeDtypeStruct(s, d, weak_type=w)
                     for s, d, w in key[0]]
            try:
                exp = jax.export.export(self._export_target(static),
                                        platforms=(PLATFORM,))(*avals)
            except Exception:  # noqa: BLE001 — not exportable: plain path
                return _PlainCaller(self.jitted, static)
            _write(path, exp.serialize())
        return jax.jit(_named(exp.call, self.name),
                       donate_argnums=self.donate_argnums)

    def _export_target(self, static):
        if not static:
            return self.jitted
        fn = self.jitted
        target = _named(lambda *a: fn(*a, **static), self.name)
        return jax.jit(target)


class _PlainCaller:
    """The jitted function with its static arguments bound."""

    def __init__(self, jitted, static):
        self.jitted, self.static = jitted, static

    def __call__(self, *args):
        return self.jitted(*args, **self.static)

    def lower(self, *args):
        return self.jitted.lower(*args, **self.static)


def _read(path: str):
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None
    try:
        return jax.export.deserialize(bytearray(blob))
    except Exception:  # noqa: BLE001 — a torn or foreign file: re-export
        return None


def _write(path: str, blob) -> None:
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(bytes(blob))
        os.replace(tmp, path)
    except OSError:
        pass  # a read-only cache directory: this process keeps its own


def program(jitted, *, donate_argnums=()):
    """``jitted`` (a ``jax.jit``-ed function, static arguments passed by
    keyword) as a :class:`Program`; ``donate_argnums`` as the jitted
    function donates."""
    return Program(jitted, donate_argnums=donate_argnums)
