"""graftlint (ISSUE 12): the typed knob registry + the five repo
checkers + the waiver baseline, and the quick-tier gate asserting the
REAL tree is clean.

Fixture snippets pin the historical bug shapes by name: the PR-7
peek-then-observe dedup race (lock-discipline), the PR-10 raw
``kv.put`` into a CRC-framed column (store-write), and the
``LIGHTHOUSE_TPU_NO_NATIVE=0``-disables-native truthiness bug
(knob-registry + the knob_bool regression test).  Pure host logic —
no jax, no device.
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
import textwrap

import pytest

from lighthouse_tpu.analysis import core
from lighthouse_tpu.analysis import checkers as _checkers  # noqa: F401
from lighthouse_tpu.common import knobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Knob accessors
# ---------------------------------------------------------------------------


def test_knob_bool_one_truthiness_convention(monkeypatch):
    name = "LIGHTHOUSE_TPU_NO_NATIVE"
    for raw, want in [("1", True), ("true", True), ("yes", True),
                      ("on", True), ("0", False), ("false", False),
                      ("no", False), ("off", False),
                      ("TRUE", True), (" 1 ", True)]:
        monkeypatch.setenv(name, raw)
        assert knobs.knob_bool(name) is want, raw
    monkeypatch.delenv(name)
    assert knobs.knob_bool(name) is False  # registry default


def test_knob_bool_empty_means_unset(monkeypatch):
    """The `VAR= cmd` shell idiom: an empty value is UNSET, never
    false — RESILIENT='' must keep the envelope default-on."""
    monkeypatch.setenv("LIGHTHOUSE_TPU_RESILIENT", "")
    assert knobs.knob_bool("LIGHTHOUSE_TPU_RESILIENT") is True
    monkeypatch.setenv("LIGHTHOUSE_TPU_NO_NATIVE", "")
    assert knobs.knob_bool("LIGHTHOUSE_TPU_NO_NATIVE") is False


def test_knob_bool_malformed_is_actionable(monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_NO_NATIVE", "banana")
    with pytest.raises(knobs.KnobError) as exc:
        knobs.knob_bool("LIGHTHOUSE_TPU_NO_NATIVE")
    msg = str(exc.value)
    assert "LIGHTHOUSE_TPU_NO_NATIVE" in msg and "banana" in msg
    assert "boolean" in msg


def test_no_native_zero_keeps_native_enabled(monkeypatch):
    """THE bug: the old bare-truthy read made NO_NATIVE=0 disable the
    native backend.  =0 must mean 'native stays on'."""
    from lighthouse_tpu.crypto import native
    monkeypatch.setattr(native, "prebuild_async", lambda: None)
    monkeypatch.setattr(native, "available",
                        lambda block=True: True)
    monkeypatch.setenv("LIGHTHOUSE_TPU_NO_NATIVE", "1")
    assert native.ready() is False
    monkeypatch.setenv("LIGHTHOUSE_TPU_NO_NATIVE", "0")
    assert native.ready() is True  # the old read returned False here
    monkeypatch.delenv("LIGHTHOUSE_TPU_NO_NATIVE")
    assert native.ready() is True


def test_knob_int_parse_clamp_and_error(monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_TRACE_RING", "0")
    assert knobs.knob_int("LIGHTHOUSE_TPU_TRACE_RING") == 1  # min clamp
    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "-5")
    assert knobs.knob_int("LIGHTHOUSE_TPU_PIPELINE_SETS") == 0
    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "2")
    assert knobs.knob_int("LIGHTHOUSE_TPU_PIPELINE_SETS") == 2
    monkeypatch.setenv("LIGHTHOUSE_TPU_PIPELINE_SETS", "abc")
    with pytest.raises(knobs.KnobError) as exc:
        knobs.knob_int("LIGHTHOUSE_TPU_PIPELINE_SETS")
    assert "LIGHTHOUSE_TPU_PIPELINE_SETS" in str(exc.value)
    assert "integer" in str(exc.value)
    assert isinstance(exc.value, ValueError)  # legacy except-clauses


def test_knob_clamp_warns(monkeypatch):
    """Clamping is never silent: out-of-range values run at the
    boundary WITH a warning naming knob, value and range."""
    monkeypatch.setenv("LIGHTHOUSE_TPU_BREAKER_N", "0")
    with pytest.warns(UserWarning, match="LIGHTHOUSE_TPU_BREAKER_N"):
        assert knobs.knob_int("LIGHTHOUSE_TPU_BREAKER_N") == 1


def test_jax_cache_registry_default_is_usable(monkeypatch):
    """One cache directory, one name: JAX_COMPILATION_CACHE_DIR when set,
    else the REAL repo path (not a '<repo>' placeholder, which
    os.makedirs would create verbatim)."""
    from lighthouse_tpu.common import compile_cache as CC

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert CC.default_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/srv/jax-cache")
    assert CC.default_dir() == "/srv/jax-cache"
    assert "`JAX_COMPILATION_CACHE_DIR`" in knobs.render_knob_table()


def test_knob_tribool(monkeypatch):
    name = "LIGHTHOUSE_TPU_MXU"
    assert knobs.knob_tribool(name) is None  # unset → auto
    for raw, want in [("auto", None), ("", None), ("1", True),
                      ("on", True), ("0", False), ("off", False)]:
        monkeypatch.setenv(name, raw)
        assert knobs.knob_tribool(name) is want, raw
    monkeypatch.setenv(name, "banana")
    with pytest.raises(knobs.KnobError):
        knobs.knob_tribool(name)


def test_knob_choice_validates(monkeypatch):
    monkeypatch.setenv("LIGHTHOUSE_TPU_STORE_SYNC", "FULL")
    assert knobs.knob_choice("LIGHTHOUSE_TPU_STORE_SYNC") == "full"
    monkeypatch.setenv("LIGHTHOUSE_TPU_STORE_SYNC", "bogus")
    with pytest.raises(knobs.KnobError) as exc:
        knobs.knob_choice("LIGHTHOUSE_TPU_STORE_SYNC")
    assert "bogus" in str(exc.value) and "normal" in str(exc.value)


def test_undeclared_knob_read_raises():
    with pytest.raises(knobs.KnobError) as exc:
        knobs.knob_bool("LIGHTHOUSE_TPU_DOES_NOT_EXIST")
    assert "undeclared" in str(exc.value)


def test_push_chunk_rows_deduped_accessor(monkeypatch):
    """The parse+default logic the two builders used to duplicate now
    shares knob_int; each keeps only its site-specific rounding."""
    from lighthouse_tpu.ops import merkle_kernel as MK
    from lighthouse_tpu.types import validators as V
    monkeypatch.delenv("LIGHTHOUSE_TPU_PUSH_CHUNK_ROWS", raising=False)
    assert MK._push_chunk_rows() == MK.PUSH_CHUNK_ROWS
    assert V._reg_chunk_rows() == V.REG_PUSH_CHUNK_ROWS
    monkeypatch.setenv("LIGHTHOUSE_TPU_PUSH_CHUNK_ROWS", "300000")
    assert MK._push_chunk_rows() == 1 << 18          # pow2 round-down
    assert V._reg_chunk_rows() == (300000 // (1 << 15)) * (1 << 15)
    monkeypatch.setenv("LIGHTHOUSE_TPU_PUSH_CHUNK_ROWS", "0")
    assert MK._push_chunk_rows() == 0
    assert V._reg_chunk_rows() == 0
    monkeypatch.setenv("LIGHTHOUSE_TPU_PUSH_CHUNK_ROWS", "junk")
    with pytest.raises(knobs.KnobError):
        MK._push_chunk_rows()


def test_registry_covers_every_knob_in_tree():
    """Belt-and-braces for the checker: every LIGHTHOUSE_TPU_* literal
    under the lint set is declared (the checker enforces this too; a
    direct test keeps the invariant even if checkers are off)."""
    import re
    pat = re.compile(r"LIGHTHOUSE_TPU_[A-Z0-9][A-Z0-9_]*[A-Z0-9]")
    undeclared = set()
    for rel in core.lint_files(REPO):
        with open(os.path.join(REPO, rel), encoding="utf-8") as fh:
            undeclared |= set(pat.findall(fh.read())) - set(knobs.KNOBS)
    assert not undeclared, undeclared


def test_render_knob_table_lists_all():
    table = knobs.render_knob_table()
    for name in knobs.KNOBS:
        assert f"`{name}`" in table


# ---------------------------------------------------------------------------
# Checker fixtures — run a checker over in-memory snippets
# ---------------------------------------------------------------------------


def run_checker(checker: str, files) -> list:
    """files: {repo-relative path: snippet}.  Returns findings."""
    ctx = core.Context(root=os.path.join(REPO, "nonexistent"),
                       files=list(files))
    c = core.CHECKERS[checker]()
    parsed = {}
    for path, src in files.items():
        src = textwrap.dedent(src)
        parsed[path] = (ast.parse(src), src.splitlines())
    findings = []
    for path, (tree, lines) in parsed.items():
        c.collect(ctx, path, tree, lines)
    for path, (tree, lines) in parsed.items():
        findings.extend(c.check(ctx, path, tree, lines))
    findings.extend(c.finalize(ctx))
    return findings


def details(findings):
    return [f.detail for f in findings]


# -- knob-registry --


def test_knob_checker_flags_raw_reads_in_package():
    found = run_checker("knob-registry", {"lighthouse_tpu/x.py": """
        import os
        a = os.environ.get("LIGHTHOUSE_TPU_MXU")
        b = os.getenv("LIGHTHOUSE_TPU_TRACE", "0")
        c = os.environ["LIGHTHOUSE_TPU_TRACE"]
        d = "LIGHTHOUSE_TPU_TRACE" in os.environ
        e = os.environ.get(some_var)
    """})
    assert len(found) == 5
    assert all(f.checker == "knob-registry" for f in found)
    assert "env-read:dynamic" in details(found)


def test_knob_checker_scripts_flag_knobs_only():
    found = run_checker("knob-registry", {"scripts/x.py": """
        import os
        ok = os.environ.get("BENCH_BUDGET_S", "10")     # non-knob: fine
        bad = os.environ.get("LIGHTHOUSE_TPU_MXU")       # knob: finding
    """})
    assert details(found) == ["env-read:LIGHTHOUSE_TPU_MXU"]


def test_knob_checker_allows_writes_and_accessors():
    found = run_checker("knob-registry", {"lighthouse_tpu/x.py": """
        import os
        from lighthouse_tpu.common.knobs import knob_bool
        os.environ["LIGHTHOUSE_TPU_MXU"] = "1"           # write: fine
        os.environ.pop("LIGHTHOUSE_TPU_MXU", None)       # restore: fine
        del os.environ["LIGHTHOUSE_TPU_TRACE"]           # fine
        v = knob_bool("LIGHTHOUSE_TPU_MXU")              # the idiom
    """})
    assert found == []


def test_knob_checker_flags_typod_name():
    found = run_checker("knob-registry", {"scripts/x.py": """
        KNOB = "LIGHTHOUSE_TPU_NO_NATVE"  # typo'd literal anywhere
    """})
    assert details(found) == ["undeclared:LIGHTHOUSE_TPU_NO_NATVE"]


# -- lock-discipline --

PR7_PEEK_THEN_OBSERVE = """
    import threading

    class ObservedThings:
        def __init__(self):
            self._seen = {}  # guarded-by: _lock
            self._lock = threading.Lock()

        def observe(self, key):
            # the PR-7 race: check-then-add with no lock — two pump
            # threads finishing duplicate gossip copies both win
            if key in self._seen:
                return False
            self._seen[key] = True
            return True
"""


def test_lock_checker_flags_pr7_peek_then_observe():
    found = run_checker("lock-discipline",
                        {"lighthouse_tpu/x.py": PR7_PEEK_THEN_OBSERVE})
    assert found and all(f.detail == "ObservedThings.observe._seen"
                         for f in found)
    assert "with self._lock" in found[0].message


def test_lock_checker_passes_locked_and_marked():
    found = run_checker("lock-discipline", {"lighthouse_tpu/x.py": """
        import threading

        class ObservedThings:
            def __init__(self):
                self._seen = {}  # guarded-by: _lock
                self._lock = threading.Lock()
                self._seen[0] = True      # __init__ exempt

            def observe(self, key):
                with self._lock:
                    if key in self._seen:
                        return False
                    self._seen[key] = True
                    return True

            def _prune_locked(self):  # lock-held: _lock
                self._seen.clear()

            def unrelated(self):
                return self._lock is not None
    """})
    assert found == []


def test_lock_checker_ignores_unannotated_classes():
    found = run_checker("lock-discipline", {"lighthouse_tpu/x.py": """
        class Plain:
            def __init__(self):
                self._seen = {}
            def peek(self, k):
                return k in self._seen
    """})
    assert found == []


# -- jax-hygiene --


def test_jax_checker_flags_global_x64():
    found = run_checker("jax-hygiene", {"lighthouse_tpu/x.py": """
        import jax
        def f():
            jax.config.update("jax_enable_x64", True)
    """})
    assert details(found) == ["enable-x64-config:f"]
    assert "jax.enable_x64(True)" in found[0].hint


def test_jax_checker_flags_experimental_x64():
    found = run_checker("jax-hygiene", {"lighthouse_tpu/x.py": """
        import jax
        from jax.experimental import enable_x64
        def f():
            with jax.experimental.enable_x64():
                pass
    """})
    assert sorted(details(found)) == ["enable-x64-import",
                                      "enable-x64-spelling:f"]


def test_jax_checker_flags_shard_map_spellings():
    found = run_checker("jax-hygiene", {"lighthouse_tpu/x.py": """
        from jax.experimental.shard_map import shard_map

        def f(mesh):
            return shard_map(lambda x: x, mesh=mesh, check_rep=False)
    """})
    d = details(found)
    assert "shard-map-import" in d
    assert "shard-map-spelling:f" in d


def test_jax_checker_wrong_spelling_is_one_finding():
    """shard_map(...) through the old module is ONE defect (the
    spelling) — not a second stale-able check-vma waiver key."""
    found = run_checker("jax-hygiene", {"lighthouse_tpu/x.py": """
        import jax.experimental.shard_map as sm
        def f(mesh):
            return sm.shard_map(lambda x: x, mesh=mesh)
    """})
    assert details(found) == ["shard-map-spelling:f"]


def test_jax_checker_flags_missing_check_vma():
    found = run_checker("jax-hygiene", {"lighthouse_tpu/x.py": """
        import jax
        def f(mesh):
            return jax.shard_map(lambda x: x, mesh=mesh)
    """})
    assert details(found) == ["shard-map-check-vma:f"]


def test_jax_checker_passes_installed_spellings():
    found = run_checker("jax-hygiene", {"lighthouse_tpu/x.py": """
        import numpy as np
        import jax
        import jax.numpy as jnp
        from functools import partial

        TABLE = np.arange(16)              # numpy at import: fine

        @partial(jax.jit, static_argnums=(1,))
        def k(x, n):
            return jnp.arange(n) + x       # jnp inside function: fine

        def f(mesh, x):
            with jax.enable_x64(True):
                y = jnp.asarray(x)
            return jax.shard_map(lambda v: v, mesh=mesh,
                                 check_vma=False)(y)

        def cache(d):
            jax.config.update("jax_compilation_cache_dir", d)  # not x64
    """})
    assert found == []


def test_jax_checker_flags_import_time_jnp():
    found = run_checker("jax-hygiene", {"lighthouse_tpu/x.py": """
        import jax.numpy as jnp
        LANES = jnp.arange(128)
        def f(x=jnp.zeros(3)):             # defaults run at import too
            return x
    """})
    d = details(found)
    assert "module-jnp:jnp.arange" in d and "module-jnp:jnp.zeros" in d


# -- store-write --

PR10_RAW_PUT = """
    from lighthouse_tpu.store.kv import DBColumn

    def persist(kv, root, ssz):
        # the PR-10 shape: unframed write into a CRC-framed column —
        # reads back as StoreCorruption after the next restart
        kv.put(DBColumn.BeaconBlock, root, ssz)
"""


def test_store_checker_flags_pr10_raw_put():
    found = run_checker("store-write",
                        {"lighthouse_tpu/beacon_chain/x.py": PR10_RAW_PUT})
    assert details(found) == ["DBColumn.BeaconBlock.put"]
    assert "op" in found[0].hint


def test_store_checker_exemptions():
    files = {
        # inside the store package: the builders themselves
        "lighthouse_tpu/store/x.py": PR10_RAW_PUT,
        "lighthouse_tpu/slasher/x.py": """
            from lighthouse_tpu.store.kv import DBColumn
            def bump(kv, key, val):
                kv.put(DBColumn.BeaconMeta, key, val)  # unframed column
            def cache(pool, k, v):
                pool.put(k, v)                          # not a DBColumn
        """,
    }
    assert run_checker("store-write", files) == []


def test_store_checker_flags_delete_too():
    found = run_checker("store-write", {"lighthouse_tpu/x.py": """
        from lighthouse_tpu.store.kv import DBColumn
        def drop(kv, root):
            kv.delete(DBColumn.BeaconState, root)
    """})
    assert details(found) == ["DBColumn.BeaconState.delete"]


# -- stage-source --


def test_stage_checker_flags_direct_reads():
    found = run_checker("stage-source", {"bench.py": """
        from lighthouse_tpu.state_transition.per_block import \\
            LAST_BLOCK_TIMINGS
        from lighthouse_tpu.crypto import tpu_backend as TB

        def row():
            return dict(LAST_BLOCK_TIMINGS), dict(TB.LAST_PIPELINE_STATS)
    """})
    d = details(found)
    assert "import:LAST_BLOCK_TIMINGS" in d
    assert "attr:LAST_PIPELINE_STATS" in d


def test_stage_checker_owner_module_and_adapter_pass():
    files = {
        "lighthouse_tpu/common/tracing.py": """
            def _src_foo():
                from ..sub.mod import LAST_FOO_TIMINGS
                return LAST_FOO_TIMINGS
            _STAGE_SOURCES = {"foo": _src_foo}
        """,
        "lighthouse_tpu/sub/mod.py": """
            LAST_FOO_TIMINGS: dict = {}
            def record(ms):
                LAST_FOO_TIMINGS["x_ms"] = ms   # owner mutates freely
        """,
    }
    assert run_checker("stage-source", files) == []


def test_stage_checker_flags_unregistered_dict():
    found = run_checker("stage-source", {"lighthouse_tpu/sub/mod.py": """
        LAST_ORPHAN_TIMINGS: dict = {}
    """})
    assert details(found) == ["unregistered:LAST_ORPHAN_TIMINGS"]


def test_stage_checker_self_registration_passes():
    found = run_checker("stage-source", {"lighthouse_tpu/sub/mod.py": """
        from ..common import tracing
        LAST_SELFREG_TIMINGS: dict = {}
        tracing.register_stage_source("selfreg",
                                      lambda: LAST_SELFREG_TIMINGS)
    """})
    assert found == []


def test_stage_checker_exemption_is_per_dict_not_per_file():
    """A second unregistered dict in a self-registering module is
    still a finding — the exemption follows the registered NAME."""
    found = run_checker("stage-source", {"lighthouse_tpu/sub/mod.py": """
        from ..common import tracing
        LAST_SELFREG_TIMINGS: dict = {}
        LAST_FORGOTTEN_TIMINGS: dict = {}
        tracing.register_stage_source("selfreg",
                                      lambda: LAST_SELFREG_TIMINGS)
    """})
    assert details(found) == ["unregistered:LAST_FORGOTTEN_TIMINGS"]


# -- device-accounting --


def test_device_checker_flags_unannotated_primitives():
    found = run_checker("device-accounting", {"lighthouse_tpu/x.py": """
        import jax
        import numpy as np

        def push(arr):
            return jax.device_put(arr)

        def pull(self):
            return np.asarray(self._dev)

        def pull_copy(self):
            return [np.array(lv_dev) for lv_dev in self.levels]
    """})
    assert details(found) == ["unannotated:device_put",
                              "unannotated:np.asarray(device_array)",
                              "unannotated:np.asarray(device_array)"]


def test_device_checker_annotated_seams_pass():
    found = run_checker("device-accounting", {"lighthouse_tpu/x.py": """
        import jax
        import numpy as np

        def push(arr):  # device-io: staging
            return jax.device_put(arr)

        def pull(self):
            host = np.asarray(self._dev)  # device-io: packed_cache
            return host

        def host_only(arr):
            return np.asarray(arr)  # plain host conversion: not flagged
    """})
    assert found == []


def test_device_checker_rejects_unknown_subsystem():
    found = run_checker("device-accounting", {"lighthouse_tpu/x.py": """
        import jax

        def push(arr):  # device-io: warp_drive
            return jax.device_put(arr)
    """})
    assert details(found) == ["bad-subsystem:warp_drive"]


def test_device_checker_jnp_asarray_scoped_to_device_modules():
    src = """
        import jax.numpy as jnp

        def stage(x):
            return jnp.asarray(x)
    """
    # crypto/kernel modules: jnp.asarray is trace-time constant
    # material, not a runtime transfer — out of scope
    assert run_checker("device-accounting",
                       {"lighthouse_tpu/crypto/limb_field.py": src}) == []
    found = run_checker(
        "device-accounting",
        {"lighthouse_tpu/slasher/device_spans.py": src})
    assert details(found) == ["unannotated:jnp.asarray"]


def test_device_checker_skips_outside_package():
    found = run_checker("device-accounting", {"scripts/x.py": """
        import jax
        def push(arr):
            return jax.device_put(arr)
    """})
    assert found == []


# ---------------------------------------------------------------------------
# Baseline round-trip
# ---------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "lighthouse_tpu", "analysis"))
    f1 = core.Finding("jax-hygiene", "a.py", 3, "msg one", detail="k1")
    f2 = core.Finding("store-write", "b.py", 9, "msg two", detail="k2")

    core.write_baseline(root, [f1, f2])
    # fresh entries carry NO justification → load refuses
    with pytest.raises(core.BaselineError) as exc:
        core.load_baseline(root)
    assert "justification" in str(exc.value)

    path = os.path.join(root, core.BASELINE_PATH)
    data = json.load(open(path))
    for w in data["waivers"]:
        w["justification"] = f"argued: {w['key']}"
    json.dump(data, open(path, "w"))

    baseline = core.load_baseline(root)
    assert set(baseline) == {f1.key, f2.key}

    # regeneration preserves the written arguments
    core.write_baseline(root, [f1], keep=baseline)
    assert core.load_baseline(root) == {f1.key: f"argued: {f1.key}"}

    unwaived, waived, stale = core.apply_baseline(
        [f1, f2], core.load_baseline(root))
    assert unwaived == [f2] and waived == [f1] and stale == []
    _, _, stale = core.apply_baseline([], core.load_baseline(root))
    assert stale == [f1.key]


def test_baseline_keys_are_line_free():
    f = core.Finding("lock-discipline", "x.py", 123, "msg",
                     detail="Cls.fn.attr")
    assert "123" not in f.key
    assert f.key == "lock-discipline:x.py:Cls.fn.attr"


# ---------------------------------------------------------------------------
# The gate: the REAL tree is clean (quick tier)
# ---------------------------------------------------------------------------


def test_real_tree_zero_unwaived_findings():
    findings = core.run(REPO)
    baseline = core.load_baseline(REPO)  # raises if unjustified
    unwaived, _waived, stale = core.apply_baseline(findings, baseline)
    assert not unwaived, "\n" + "\n".join(f.render() for f in unwaived)
    assert not stale, f"stale waivers: {stale}"


def test_lint_cli_exits_zero_on_tree():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py")],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 unwaived" in proc.stdout
