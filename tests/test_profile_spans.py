"""The node's spans on the device trace's clock: ``Tracer`` spans land
in a JAX profiler trace as ``lh.<name>`` host events (ring on or off),
the verify and state-root paths open them at their layer boundaries,
host-computed verdicts are counted and kept out of the ledger's device
dispatches, and ``common/profile_spans`` charges device-idle time to
them.  CPU only: the profiler's host events need no chip."""

from contextlib import contextmanager
from types import SimpleNamespace

import numpy as np
import pytest

from lighthouse_tpu.common import profile_spans as PS
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.device_ledger import LEDGER
from lighthouse_tpu.common.tracing import TRACER, Tracer


@contextmanager
def _profiled(log_dir):
    import jax
    jax.profiler.start_trace(str(log_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _lh_events(log_dir) -> list:
    """``(name, start_ns, end_ns, stats)`` of every ``lh.`` host event."""
    from jax.profiler import ProfileData
    planes = ProfileData.from_file(PS.find_xplane(str(log_dir))).planes
    out = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.PROFILE_PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _named(events, name) -> list:
    return [e for e in events if e[0] == name]


# ---------------------------------------------------------------------------
# The tracer under a profiler session
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ring", [False, True], ids=["ring_off", "ring_on"])
def test_spans_reach_the_profiler_trace_nested(tmp_path, ring):
    t = Tracer(max_slots=4)
    if ring:
        t.enable()
    with _profiled(tmp_path):
        with t.span("outer", cat="x", route="fast_path"):
            with t.span("outer.inner"):
                with t.span("outer.inner.leaf"):
                    pass
        with t.span("outer"):
            pass
    ev = _lh_events(tmp_path)
    outer = _named(ev, "lh.outer")
    inner = _named(ev, "lh.outer.inner")
    leaf = _named(ev, "lh.outer.inner.leaf")
    assert len(outer) == 2 and len(inner) == 1 and len(leaf) == 1
    assert _inside(inner[0], outer[0]) and _inside(leaf[0], inner[0])
    assert outer[0][2] <= outer[1][1]  # the second call opens after
    assert outer[0][3].get("route") == "fast_path"
    # the ring records exactly when it is on
    assert bool(t.slots()) is ring
    if ring:
        names = {s["name"] for s in t.slot_trace(0)["spans"]}
        assert names == {"outer", "outer.inner", "outer.inner.leaf"}


def test_no_session_disabled_tracer_is_noop_again(tmp_path):
    assert not TRACER.enabled
    with _profiled(tmp_path):
        assert TRACER.span("a") is not tracing._NOOP
    assert TRACER.span("a") is TRACER.span("b") is tracing._NOOP
    with TRACER.span("a") as sp:
        sp.set(x=1)
        assert sp.ctx() is None


# ---------------------------------------------------------------------------
# The verify path: split, host routes, counter, ledger
# ---------------------------------------------------------------------------

def _signed_sets(n: int, bad: int):
    """``n`` single-key sets; set ``bad`` carries another message's
    signature."""
    from lighthouse_tpu.crypto import bls
    sks = [bls.SecretKey(0x51000 + 17 * i) for i in range(n)]
    msgs = [b"profile-spans %d" % i for i in range(n)]
    sets = [bls.SignatureSet(sk.sign(m), [sk.public_key()], m)
            for sk, m in zip(sks, msgs)]
    sets[bad] = bls.SignatureSet(sks[bad].sign(b"other"),
                                 [sks[bad].public_key()], msgs[bad])
    return sets


def _native_fast_path() -> bool:
    from lighthouse_tpu.crypto import native
    from lighthouse_tpu.crypto import tpu_backend as TB
    return native.available(block=True) and TB._host_fast(1)


@pytest.mark.parametrize("route", ["fast_path", "fallback"])
def test_split_reverifies_on_a_host_route_counted_and_traced(tmp_path,
                                                            route):
    """A rejected 6-message batch splits; its single-set re-verifies
    take the backend's host fast path (the native library built; else
    the fallback case's route) or, after an injected device fault, the
    envelope's host fallback.  ``host_verified_sets`` counts those sets
    and the trace holds ``lh.verify_split`` around the host verifies."""
    from lighthouse_tpu.beacon_chain.verification_service import (
        VerificationService)
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto import tpu_backend as TB

    host = bls._BACKENDS["python"].verify_signature_sets
    if route == "fallback" or not _native_fast_path():
        def small(sets):
            raise RuntimeError("injected device fault")
        want_route = None   # the breaker's route, whatever it is
    else:
        small = TB.TpuBackend().verify_signature_sets
        want_route = "fast_path"

    def device(sets):
        # The batch stands in for a device dispatch; single sets go
        # through the route under test.
        return host(sets) if len(sets) > 4 else small(sets)

    svc = VerificationService(slo_ms=60000, max_batch=8, deadline_ms=0,
                              retries=0, seed=0, device_verify=device,
                              host_verify=host, auto_pump=False)
    sets = _signed_sets(6, bad=3)
    got = {}
    for i, s in enumerate(sets):
        svc.submit("attestation", [s],
                   on_result=lambda ok, path, i=i: got.__setitem__(i, ok))
    with _profiled(tmp_path):
        svc.flush()
    assert got == {i: i != 3 for i in range(6)}
    assert svc.counters["splits"] == 1
    assert svc.counters["host_verified_sets"] == 6
    ev = _lh_events(tmp_path)
    split = _named(ev, "lh.verify_split")
    hv = _named(ev, "lh.bls.host_verify")
    assert len(split) == 1 and len(hv) == 6
    assert all(_inside(e, split[0]) for e in hv)
    assert _inside(split[0], _named(ev, "lh.verify_dispatch")[0])
    if want_route is not None:
        assert {e[3].get("route") for e in hv} == {want_route}
    assert _named(ev, "lh.stream_verify.prep")
    assert _named(ev, "lh.stream_verify.dispatch")


def test_host_fast_path_is_not_a_ledger_dispatch(monkeypatch):
    from lighthouse_tpu.beacon_chain.verification_service import (
        ResilienceEnvelope)
    from lighthouse_tpu.crypto import tpu_backend as TB

    monkeypatch.setattr(TB, "_host_fast", lambda n: True)
    sets = _signed_sets(2, bad=1)[:1]
    for deadline in (None, 30.0):          # inline AND watchdog thread
        env = ResilienceEnvelope("fast_path_bls", retries=0,
                                 deadline_s=deadline)
        base = LEDGER.snapshot()["subsystems"]["bls"]["dispatches"]
        ok, path, on_host = env.call_routed(
            TB.TpuBackend().verify_signature_sets, None, (sets,))
        assert ok is True and path == "device" and on_host
        assert LEDGER.snapshot()["subsystems"]["bls"][
            "dispatches"] == base
        # a device-run call through the same envelope still counts
        ok, path, on_host = env.call_routed(lambda s: True, None, (sets,))
        assert ok and not on_host
        assert LEDGER.snapshot()["subsystems"]["bls"][
            "dispatches"] == base + 1


def test_host_verified_sets_exported():
    from lighthouse_tpu.beacon_chain.verification_service import (
        VerificationService)
    from lighthouse_tpu.common.metrics import REGISTRY

    fam = REGISTRY.counter("stream_verify_host_verified_sets_total")
    before = fam.value

    def device(sets):
        raise RuntimeError("injected device fault")

    svc = VerificationService(slo_ms=60000, max_batch=4, deadline_ms=0,
                              retries=0, seed=0, device_verify=device,
                              host_verify=lambda sets: True,
                              auto_pump=False)
    for _ in range(3):
        svc.submit("attestation", [object()])
    svc.flush()
    assert svc.counters["host_verified_sets"] == 3
    assert fam.value - before == 3
    assert "stream_verify_host_verified_sets_total" in REGISTRY.encode()


# ---------------------------------------------------------------------------
# The state-root path
# ---------------------------------------------------------------------------

def _small_state(n: int):
    from lighthouse_tpu.types.chain_spec import ForkName
    from lighthouse_tpu.types.factory import spec_types
    from lighthouse_tpu.types.presets import MAINNET
    from lighthouse_tpu.types.validators import ValidatorRegistry

    rng = np.random.default_rng(11)
    state = spec_types(MAINNET).state_cls(ForkName.CAPELLA)()
    reg = ValidatorRegistry(n)
    reg._n = n
    reg.init_columns(
        pubkey=rng.integers(0, 256, (n, 48), dtype=np.uint8),
        withdrawal_credentials=rng.integers(0, 256, (n, 32),
                                            dtype=np.uint8),
        effective_balance=np.full(n, 32 * 10 ** 9, dtype=np.uint64))
    state.validators = reg
    state.balances = np.full(n, 32 * 10 ** 9, dtype=np.uint64)
    state.previous_epoch_participation = np.zeros(n, dtype=np.uint8)
    state.current_epoch_participation = np.zeros(n, dtype=np.uint8)
    state.inactivity_scores = np.zeros(n, dtype=np.uint64)
    return state


def test_device_state_root_emits_stage_spans(tmp_path):
    from lighthouse_tpu.types.device_state import materialize_state

    state = _small_state(64)
    assert materialize_state(state)
    state.tree_hash_root()
    state.balances[np.arange(3)] = np.uint64(7)
    state.slot = 5
    with _profiled(tmp_path):
        root = state.tree_hash_root()
    ev = _lh_events(tmp_path)
    top = _named(ev, "lh.state_root")
    assert len(top) == 1
    kinds = {e[0] for e in ev if e[0].startswith("lh.state_root.")}
    assert kinds == {f"lh.state_root.{k}" for k in
                     ("registry", "packed", "vectors", "small", "fold")}
    children = [e for e in ev if e[0].startswith("lh.state_root.")]
    assert all(_inside(e, top[0]) for e in children)
    fields = {e[3].get("field") for e in children
              if e[0] != "lh.state_root.fold"}
    assert {"validators", "balances", "block_roots", "slot"} <= fields
    packed = _named(ev, "lh.state_root.packed")
    prep = _named(ev, "lh.merkle.prep")
    scatter = _named(ev, "lh.merkle.scatter")
    assert prep and all(any(_inside(p, k) for k in packed) for p in prep)
    # the dirty balances scatter; prep ends before its scatter opens
    assert len(scatter) == 1 and any(p[2] <= scatter[0][1] for p in prep)
    # spans change no bits of the root: a state built the same way,
    # rooted once on the host path, agrees
    twin = _small_state(64)
    twin.balances[np.arange(3)] = np.uint64(7)
    twin.slot = 5
    assert root == twin.tree_hash_root()


# ---------------------------------------------------------------------------
# The reduction: idle time charged to spans
# ---------------------------------------------------------------------------

S = 1e9  # ns per second


def _ev(name, a, b):
    return SimpleNamespace(name=name, start_ns=a * S,
                           duration_ns=(b - a) * S)


def _planes():
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=[_ev("jit_f(1)", 0, 10)]),
        SimpleNamespace(name="XLA Ops", events=[
            _ev("%a = f32[] add()", 1, 3), _ev("%b = f32[] mul()", 5, 6),
            _ev("%c = f32[] add()", 11, 12)])])
    host = SimpleNamespace(name="/host:CPU", lines=[
        SimpleNamespace(name="python", events=[
            _ev("bench.window", 0, 10), _ev("bench.wait", 0, 1),
            _ev("lh.outer", 2, 8), _ev("lh.outer.child", 2.5, 4.5),
            _ev("lh.outer.child", 6.5, 7), _ev("py_function", 0, 9)]),
        SimpleNamespace(name="worker", events=[
            _ev("lh.other", 7.5, 9.5), _ev("lh.outer", 9, 9.5)])])
    return [host, dev]


def test_reduce_charges_idle_time_to_spans():
    r = PS.reduce_planes(_planes(), window="bench.window",
                         also=("bench.wait",))
    approx = pytest.approx
    assert r["window_s"] == approx(10) and r["busy_s"] == approx(3)
    assert r["idle_s"] == approx(7)
    sp = r["spans"]
    assert set(sp) == {"outer", "outer.child", "other"}
    assert sp["outer"]["span_s"] == approx(6.5)
    assert sp["outer"]["calls"] == 2
    assert sp["outer"]["idle_under_s"] == approx(4.5)
    assert sp["outer"]["idle_under_children_s"] == approx(2.0)
    assert sp["outer.child"] == approx(
        {"span_s": 2.5, "calls": 2, "idle_under_s": 2.0})
    assert sp["other"] == approx(
        {"span_s": 2.0, "calls": 1, "idle_under_s": 2.0})
    assert r["uncovered_idle_s"] == approx(0.5)
    gaps = r["longest_idle_gaps"]
    assert [g["s"] for g in gaps] == approx([4, 2, 1])
    assert gaps[0]["at_s"] == approx(6)
    assert gaps[0]["cover_s"] == approx(
        {"outer": 2.5, "other": 2.0, "outer.child": 0.5})
    assert gaps[2]["cover_s"] == {}


def test_reduce_window_defaults_to_the_trace_extent():
    r = PS.reduce_planes(_planes())
    assert r["window_s"] == pytest.approx(12)  # 0 .. the op at 11-12 s
    assert r["busy_s"] == pytest.approx(4)
    with pytest.raises(ValueError):
        PS.reduce_planes(_planes(), window="no.such.span")


def test_reduce_without_lh_events_reports_no_spans():
    planes = _planes()
    for ln in planes[0].lines:
        ln.events = [e for e in ln.events if not e.name.startswith("lh.")]
    r = PS.reduce_planes(planes, window="bench.window")
    assert r["spans"] == {}
    assert r["uncovered_idle_s"] == pytest.approx(r["idle_s"])


def test_reduce_reads_a_cpu_profiler_trace(tmp_path):
    """End to end on a real trace: a span around device work and one
    around a host sleep, reduced from the ``.xplane.pb``."""
    import time

    import jax
    import jax.numpy as jnp

    t = Tracer(max_slots=2)
    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    with _profiled(tmp_path):
        with t.span("host_wait"):
            time.sleep(0.05)
        with t.span("compute"):
            jax.block_until_ready(x @ x)
    r = PS.reduce_file(str(tmp_path))
    assert set(r["spans"]) == {"host_wait", "compute"}
    hw = r["spans"]["host_wait"]
    assert hw["calls"] == 1 and hw["span_s"] >= 0.05
    assert 0 <= hw["idle_under_s"] <= hw["span_s"]
