"""Content-addressed compiled-program registry (ISSUE 18): key scheme,
atomic publish convergence under thread and process races, digest/ABI
verification at install, dynamic-kwarg executable round trips, the PR-9
cache-warm publish regression, and size-capped GC for both the registry and
the persistent compile cache.  The fleet acceptance bar (registry-warm fresh
process trains with ``new_compiles_during_train == 0``, 2-worker pool boots
with ≤1 compile) lives in scripts/ci_registry_smoke.py — in-process tests
can't prove it because the suite's own warm jit tables would mask it."""

import json
import os
import pickle
import subprocess
import sys
import threading
import time
from functools import partial

import numpy as np
import pytest

import jax

from transmogrifai_tpu import aot, aot_registry
from transmogrifai_tpu.resilience import FailureLog, use_failure_log
from transmogrifai_tpu.telemetry import REGISTRY


def _counter(name):
    return REGISTRY.snapshot()["counters"].get(f"aot_registry.{name}", 0)


@pytest.fixture()
def registry(tmp_path):
    """Configured registry rooted in a temp dir; restores env + module state
    so the rest of the suite keeps running registry-off."""
    saved_env = {k: os.environ.get(k) for k in
                 (aot_registry.REGISTRY_ENV, "TRANSMOGRIFAI_COMPILE_CACHE")}
    aot_registry.reset_for_tests()
    root = str(tmp_path / "registry")
    aot_registry.configure(root=root)
    yield root
    aot_registry.reset_for_tests()
    for k, v in saved_env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _publish(root, key, payload=b"x" * 1024, meta=None):
    assert aot_registry.publish(key, payload, meta or {"kind": "grid"},
                                root=root)


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

class TestKeys:
    def test_program_key_deterministic_and_sensitive(self, registry):
        avals = aot_registry.args_signature((np.zeros((4, 3)),))
        k = aot_registry.program_key("grid", "linear.grid_fit", 4,
                                     {"tol": 1e-6}, avals)
        assert k == aot_registry.program_key("grid", "linear.grid_fit", 4,
                                             {"tol": 1e-6}, avals)
        assert len(k) == 64
        # every field is load-bearing
        assert k != aot_registry.program_key("score", "linear.grid_fit", 4,
                                             {"tol": 1e-6}, avals)
        assert k != aot_registry.program_key("grid", "linear.grid_fit", 8,
                                             {"tol": 1e-6}, avals)
        assert k != aot_registry.program_key("grid", "linear.grid_fit", 4,
                                             {"tol": 1e-3}, avals)
        other = aot_registry.args_signature((np.zeros((4, 5)),))
        assert k != aot_registry.program_key("grid", "linear.grid_fit", 4,
                                             {"tol": 1e-6}, other)

    def test_args_signature_covers_shape_and_dtype(self, registry):
        sig32 = aot_registry.args_signature((np.zeros((2, 2), np.float32),))
        sig64 = aot_registry.args_signature((np.zeros((2, 2), np.float64),))
        assert sig32 != sig64
        # ShapeDtypeStructs (captured pretrace avals) hash like real arrays
        spec = jax.ShapeDtypeStruct((2, 2), np.float32)
        assert aot_registry.args_signature((spec,)) == sig32

    def test_model_family_digest_content_addressed(self, tmp_path):
        a, b = tmp_path / "a", tmp_path / "b"
        for d in (a, b):
            d.mkdir()
            (d / "model.json").write_bytes(b'{"stages": []}')
            (d / "params.npz").write_bytes(b"NPZPAYLOAD")
        assert aot_registry.model_family_digest(str(a)) == \
            aot_registry.model_family_digest(str(b))
        (b / "params.npz").write_bytes(b"NPZPAYLOAX")
        assert aot_registry.model_family_digest(str(a)) != \
            aot_registry.model_family_digest(str(b))
        empty = tmp_path / "empty"
        empty.mkdir()
        assert aot_registry.model_family_digest(str(empty)) is None


# ---------------------------------------------------------------------------
# publish / lookup
# ---------------------------------------------------------------------------

class TestPublishLookup:
    def test_round_trip(self, registry):
        key = "ab" + "0" * 62
        payload = os.urandom(2048)
        _publish(registry, key, payload)
        d = aot_registry.entry_dir(key)
        assert os.path.isdir(d)
        meta = json.load(open(os.path.join(d, "entry.json")))
        assert meta["key"] == key
        assert meta["payloadBytes"] == 2048
        assert aot.abi_mismatch(meta["abi"]) is None
        assert aot_registry.lookup(key) == payload
        assert aot_registry.registry_bytes() > 2048

    def test_publish_dedup(self, registry):
        key = "cd" + "1" * 62
        before = _counter("publish_dedup")
        _publish(registry, key)
        _publish(registry, key)
        assert _counter("publish_dedup") == before + 1

    def test_lookup_miss(self, registry):
        before = _counter("misses")
        assert aot_registry.lookup("ee" + "2" * 62) is None
        assert _counter("misses") == before + 1

    def test_disabled_registry_is_inert(self, registry):
        aot_registry.configure(enabled=False)
        assert not aot_registry.registry_enabled()
        assert os.environ[aot_registry.REGISTRY_ENV] == "0"
        # grid_call degrades to the plain jit path
        f = jax.jit(lambda x: x + 1)
        out = aot_registry.grid_call("t.inert", f, (np.zeros(3),))
        np.testing.assert_array_equal(np.asarray(out), np.ones(3))


# ---------------------------------------------------------------------------
# racing publishers
# ---------------------------------------------------------------------------

class TestRaces:
    def test_thread_race_converges(self, registry):
        key = "f0" + "3" * 62
        payload = os.urandom(4096)
        start = threading.Barrier(8)
        results = []

        def go():
            start.wait()
            results.append(aot_registry.publish(key, payload, root=registry))
        threads = [threading.Thread(target=go) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == [True] * 8
        parent = os.path.dirname(aot_registry.entry_dir(key))
        assert sorted(os.listdir(parent)) == [key]  # no torn/tmp leftovers
        assert aot_registry.lookup(key) == payload

    def test_process_race_converges(self, registry):
        key = "0a" + "4" * 62
        child = (
            "import sys\n"
            "from transmogrifai_tpu import aot_registry as R\n"
            "root, key = sys.argv[1], sys.argv[2]\n"
            "R.configure(root=root)\n"
            "payload = bytes(range(256)) * 256\n"
            "ok = R.publish(key, payload, {'kind': 'grid'})\n"
            "assert R.lookup(key) == payload\n"
            "print('OK' if ok else 'FAIL')\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
        procs = [subprocess.Popen([sys.executable, "-c", child,
                                   registry, key],
                                  stdout=subprocess.PIPE, env=env)
                 for _ in range(2)]
        outs = [p.communicate(timeout=180)[0].decode() for p in procs]
        assert all(p.returncode == 0 for p in procs)
        assert all("OK" in o for o in outs)
        parent = os.path.dirname(aot_registry.entry_dir(key))
        assert sorted(os.listdir(parent)) == [key]
        assert aot_registry.lookup(key) == bytes(range(256)) * 256


# ---------------------------------------------------------------------------
# verification: tampered payloads, foreign ABI stamps
# ---------------------------------------------------------------------------

class TestVerification:
    def test_tampered_payload_degrades_and_heals(self, registry):
        key = "1b" + "5" * 62
        _publish(registry, key, b"payload-bytes" * 100)
        ppath = os.path.join(aot_registry.entry_dir(key), "payload.bin")
        blob = bytearray(open(ppath, "rb").read())
        blob[10] ^= 0xFF
        open(ppath, "wb").write(bytes(blob))
        before = _counter("tampered")
        log = FailureLog()
        with use_failure_log(log):
            assert aot_registry.lookup(key) is None
        assert _counter("tampered") == before + 1
        notes = log.by_action("degraded")
        assert notes and notes[0].point == "aot_registry.lookup"
        # the poisoned slot is removed so the next publisher repairs it
        assert not os.path.isdir(aot_registry.entry_dir(key))
        _publish(registry, key, b"fresh" * 10)
        assert aot_registry.lookup(key) == b"fresh" * 10

    @pytest.mark.parametrize("field,value", [
        ("jaxVersion", "0.0.0"), ("platform", "tpu-v9"),
        ("machine", "riscv128"), ("deviceCount", 4096)])
    def test_foreign_abi_never_installs(self, registry, field, value):
        key = "2c" + "6" * 62
        _publish(registry, key)
        mpath = os.path.join(aot_registry.entry_dir(key), "entry.json")
        meta = json.load(open(mpath))
        meta["abi"][field] = value
        json.dump(meta, open(mpath, "w"))
        before = _counter("abi_skips")
        assert aot_registry.lookup(key) is None
        assert _counter("abi_skips") == before + 1
        # foreign entries are another fleet member's: skipped, NOT deleted
        assert os.path.isdir(aot_registry.entry_dir(key))

    def test_newer_format_version_skipped(self, registry):
        key = "3d" + "7" * 62
        _publish(registry, key)
        mpath = os.path.join(aot_registry.entry_dir(key), "entry.json")
        meta = json.load(open(mpath))
        meta["formatVersion"] = aot_registry.REGISTRY_FORMAT_VERSION + 1
        json.dump(meta, open(mpath, "w"))
        assert aot_registry.lookup(key) is None


# ---------------------------------------------------------------------------
# the train seam: grid_call / grid_compile round trips
# ---------------------------------------------------------------------------

def _fresh_process_sim():
    """Drop the in-process loaded/published tables (NOT the on-disk store):
    the closest an in-process test gets to a fresh process against a warm
    registry."""
    with aot_registry._LOCK:
        aot_registry._LOADED.clear()
        aot_registry._PUBLISHED.clear()
        aot_registry._DYN_KWARGS.clear()


class TestGridSeam:
    def test_miss_publish_then_install_bitwise(self, registry):
        @partial(jax.jit, static_argnames=("scale",))
        def f(x, *, tol, scale):
            return x * scale + tol

        x = np.arange(12, dtype=np.float32)
        statics = {"tol": np.float32(0.25), "scale": 3}
        out1 = np.asarray(aot_registry.grid_call(
            "test.dynkw", f, (x,), static_kwargs=statics))
        aot.pretrace_drain(30)  # background publish rides the pretrace pool
        key = aot_registry._grid_key("test.dynkw", (x,), statics, 12)
        assert os.path.isdir(aot_registry.entry_dir(key))
        rec = pickle.loads(aot_registry.lookup(key))
        assert rec["dynKwargs"] == ["tol"]  # traced kwarg rides the record

        _fresh_process_sim()
        before = _counter("call_fallbacks")
        out2 = np.asarray(aot_registry.grid_call(
            "test.dynkw", f, (x,), static_kwargs=statics))
        # installed executable replays the dynamic kwarg — no fallback
        assert _counter("call_fallbacks") == before
        assert _counter("installs") >= 1
        np.testing.assert_array_equal(out1, out2)  # bitwise parity

        hits = _counter("hits")
        out3 = np.asarray(aot_registry.grid_call(
            "test.dynkw", f, (x,), static_kwargs=statics))
        assert _counter("hits") > hits  # now served from the loaded table
        np.testing.assert_array_equal(out1, out3)

    def test_grid_compile_installs_for_foreground(self, registry):
        f = jax.jit(lambda x: (x * 2.0).sum())
        x = np.arange(6, dtype=np.float32)
        aot_registry.grid_compile("test.pretrace", f, (x,))
        key = aot_registry._grid_key("test.pretrace", (x,), {}, 6)
        assert os.path.isdir(aot_registry.entry_dir(key))
        with aot_registry._LOCK:
            assert key in aot_registry._LOADED  # foreground dispatches it
        out = np.asarray(aot_registry.grid_call("test.pretrace", f, (x,)))
        np.testing.assert_array_equal(out, np.asarray(f(x)))

    def test_broken_executable_falls_back_to_jit(self, registry):
        f = jax.jit(lambda x: x + 1.0)
        x = np.arange(4, dtype=np.float32)
        key = aot_registry._grid_key("test.broken", (x,), {}, 4)

        def boom(*a, **k):
            raise RuntimeError("executable rejected input")
        with aot_registry._LOCK:
            aot_registry._LOADED[key] = boom
        log = FailureLog()
        before = _counter("call_fallbacks")
        with use_failure_log(log):
            out = np.asarray(aot_registry.grid_call("test.broken", f, (x,)))
        np.testing.assert_array_equal(out, np.asarray(f(x)))
        assert _counter("call_fallbacks") == before + 1
        assert log.by_action("degraded")
        with aot_registry._LOCK:  # uninstalled: next call takes jit path
            assert key not in aot_registry._LOADED

    def test_shared_load_memoizes(self, registry):
        f = jax.jit(lambda x: x * 4.0)
        x = np.arange(3, dtype=np.float32)
        rec = pickle.loads(aot_registry.serialize_fresh(f, (x,)))
        n0 = aot_registry.loaded_count()
        a = aot_registry.shared_load("digest-tenant", rec)
        shared = _counter("shared_hits")
        b = aot_registry.shared_load("digest-tenant", rec)
        assert a is b  # two tenants share ONE executable + device memory
        assert _counter("shared_hits") == shared + 1
        assert aot_registry.loaded_count() == n0 + 1


# ---------------------------------------------------------------------------
# satellite: cache-warm processes still publish installable payloads (PR-9)
# ---------------------------------------------------------------------------

class TestScoreSeam:
    def test_published_executable_installs_over_the_shared_table(
            self, registry, tmp_path):
        """save() publishes the scoring executables and leaves the same
        programs in the process-wide table of compiled executables.  The
        same model loaded from a bundle without AOT artifacts installs the
        published one for its first score: the registry wins, the table is
        not asked."""
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from test_aux_subsystems import make_records, train_small_model
        from transmogrifai_tpu.compiled import trace_count
        from transmogrifai_tpu.serving.engine import records_to_batch
        from transmogrifai_tpu.workflow import WorkflowModel

        def shared():
            c = REGISTRY.snapshot()["counters"]
            return {k: c.get("compiled.shared." + k, 0)
                    for k in ("hit", "miss", "bypass")}

        def score(m):
            pred = next(f.name for f in m.result_features)
            batch = records_to_batch(
                m.raw_features, [{"x1": 0.4, "x2": 3.0, "cat": "a"}] * 4)
            return np.asarray(m.score(batch=batch)[pred].values["probability"])
        model = train_small_model(make_records(120))[0].train()
        want = score(model)
        bundle = str(tmp_path / "model")
        model.save(bundle)
        assert _counter("publishes") >= 1
        model.save(str(tmp_path / "jit-only"), aot=False)   # same content
        loaded = WorkflowModel.load(str(tmp_path / "jit-only"))
        assert loaded.aot_executables == 0
        assert loaded.score_program().registry_family
        before, traces = shared(), trace_count()
        np.testing.assert_array_equal(score(loaded), want)
        assert loaded.score_program().aot_installed_count() >= 1
        assert shared() == before and trace_count() == traces
        assert _counter("install_failures") == 0


class TestCacheWarmPublish:
    def test_cache_loaded_compile_republishes_fresh(self, registry,
                                                    tmp_path):
        """An executable jax re-loads from the persistent compile cache
        serializes with its fusion symbols missing — publish must detect
        the cache hit and re-compile once with the cache disabled rather
        than silently skipping (or worse, publishing garbage)."""
        cache_dir = tmp_path / "xla-cache"
        saved = (jax.config.jax_compilation_cache_dir,
                 jax.config.jax_enable_compilation_cache,
                 jax.config.jax_persistent_cache_min_compile_time_secs)
        try:
            jax.config.update("jax_compilation_cache_dir", str(cache_dir))
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
            # jax memoizes its cache object at first use — drop it so the
            # dir above is actually adopted, then drop the jit tables so
            # the next compile goes through the persistent cache
            aot_registry._reset_jax_compile_cache()
            jax.clear_caches()
            f = jax.jit(lambda x: (x * 3.0 + 1.0).sum())
            x = np.arange(16, dtype=np.float32)
            expect = np.asarray(f(x))
            f.lower(x).compile()  # populates the disk cache
            cached = sum(len(fs) for _, _, fs in os.walk(cache_dir))
            assert cached > 0, "precondition: persistent cache must engage"

            # fresh process simulation: the in-memory executable is gone,
            # the disk cache entry is not — the next compile is a cache
            # LOAD, whose serialization is garbage (the PR-9 hazard)
            jax.clear_caches()
            recomp0 = _counter("recompiles_for_publish")
            rec = aot_registry.serialize_fresh(f, (x,))
            assert _counter("recompiles_for_publish") == recomp0 + 1
            assert rec is not None  # NOT silently skipped
            assert aot_registry.payload_roundtrips(rec)
            # a cache-LOADED executable deserializes fine on jax 0.9 and
            # fails only here, at its first call
            fn = aot_registry.load_executable(pickle.loads(rec))
            np.testing.assert_array_equal(np.asarray(fn(x)), expect)
        finally:
            jax.config.update("jax_compilation_cache_dir", saved[0])
            jax.config.update("jax_enable_compilation_cache", saved[1])
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", saved[2])
            aot_registry._reset_jax_compile_cache()


    def test_overlapping_suspensions_restore_the_cache_dir(self):
        """save() suspends the persistent cache on the caller's thread while
        a publish job may suspend it on the pre-trace thread: however the
        blocks interleave, the directory is away while any is open and back,
        unchanged, when the last one exits."""
        saved = jax.config.jax_compilation_cache_dir
        assert saved, "conftest/package import place a cache directory"
        seen_set = []
        errors = []
        start = threading.Barrier(6)

        def worker(i):
            try:
                start.wait(timeout=30)
                for _ in range(40):
                    with aot_registry.persistent_cache_suspended():
                        if jax.config.jax_compilation_cache_dir is not None:
                            seen_set.append(i)
                        time.sleep(0.0005)   # hold it open: blocks overlap
                        if i % 2:   # nest, as export -> publish can
                            with aot_registry.persistent_cache_suspended():
                                pass
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(e)

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert errors == []
        assert seen_set == []   # never restored under an open block
        assert jax.config.jax_compilation_cache_dir == saved


# ---------------------------------------------------------------------------
# size-capped GC: registry entries + persistent compile cache
# ---------------------------------------------------------------------------

def _age(key, days):
    d = aot_registry.entry_dir(key)
    old = time.time() - days * 86400
    for f in os.listdir(d):
        os.utime(os.path.join(d, f), (old, old))


class TestGC:
    def test_lru_eviction_stale_abi_first(self, registry):
        keys = [f"{i:02d}" + "a" * 62 for i in range(6)]
        for i, k in enumerate(keys):
            _publish(registry, k, b"e" * 1000)
            _age(k, days=6 - i)  # keys[0] oldest ... keys[5] newest
        # keys[4] is RECENT but carries a foreign ABI stamp → goes first
        mpath = os.path.join(aot_registry.entry_dir(keys[4]), "entry.json")
        meta = json.load(open(mpath))
        meta["abi"]["jaxVersion"] = "0.0.0"
        json.dump(meta, open(mpath, "w"))

        log = FailureLog()
        before = _counter("evictions")
        with use_failure_log(log):
            n = aot_registry.enforce_budget(cap_bytes=3500, keep_min=1)
        assert n >= 3
        assert _counter("evictions") == before + n
        # stale-ABI victim went even though it was nearly the newest
        assert not os.path.isdir(aot_registry.entry_dir(keys[4]))
        # then LRU: the oldest fresh entries
        assert not os.path.isdir(aot_registry.entry_dir(keys[0]))
        assert not os.path.isdir(aot_registry.entry_dir(keys[1]))
        # the most recently used fresh entry survives (keep_min floor)
        assert os.path.isdir(aot_registry.entry_dir(keys[5]))
        notes = log.by_action("evicted")
        assert len(notes) == n
        assert all(e.point == "aot_registry.gc" for e in notes)
        reasons = {e.detail.get("reason") for e in notes}
        assert "stale ABI" in reasons

    def test_keep_min_floor_survives_zero_budget(self, registry):
        keys = [f"{i:02d}" + "b" * 62 for i in range(4)]
        for i, k in enumerate(keys):
            _publish(registry, k, b"e" * 500)
            _age(k, days=4 - i)
        aot_registry.enforce_budget(cap_bytes=0, keep_min=2)
        alive = [k for k in keys
                 if os.path.isdir(aot_registry.entry_dir(k))]
        assert alive == keys[-2:]  # the two most recently used

    def test_under_budget_is_noop(self, registry):
        _publish(registry, "aa" + "c" * 62, b"e" * 100)
        assert aot_registry.enforce_budget(cap_bytes=1 << 30) == 0

    def test_compile_cache_gc_lru(self, registry, tmp_path):
        cache = tmp_path / "xla-cache"
        cache.mkdir()
        now = time.time()
        for i in range(5):
            p = cache / f"entry-{i}"
            p.write_bytes(b"z" * 1000)
            os.utime(p, (now - (5 - i) * 3600,) * 2)
        log = FailureLog()
        with use_failure_log(log):
            n = aot_registry.gc_compile_cache(str(cache), cap_bytes=2500)
        assert n == 3
        assert sorted(os.listdir(cache)) == ["entry-3", "entry-4"]
        notes = log.by_action("evicted")
        assert notes and notes[0].point == "aot_registry.cache_gc"
        assert notes[0].detail["files"] == 3

    def test_compile_cache_gc_missing_dir_noop(self, registry, tmp_path):
        assert aot_registry.gc_compile_cache(
            str(tmp_path / "nope"), cap_bytes=1) == 0


# ---------------------------------------------------------------------------
# params / config plumbing
# ---------------------------------------------------------------------------

class TestPlumbing:
    def test_registry_params_round_trip(self):
        from transmogrifai_tpu.params import OpParams
        p = OpParams.from_json(
            {"registryParams": {"root": "/r", "capBytes": 123,
                                "enabled": True}})
        assert p.registry["capBytes"] == 123
        assert p.to_json()["registryParams"]["root"] == "/r"

    def test_root_defaults_from_env(self, registry, tmp_path):
        aot_registry.reset_for_tests()
        os.environ[aot_registry.REGISTRY_ENV] = str(tmp_path / "env-root")
        try:
            assert aot_registry.registry_root() == str(tmp_path / "env-root")
            assert aot_registry.registry_enabled()
        finally:
            os.environ.pop(aot_registry.REGISTRY_ENV, None)

    def test_stats_snapshot_shape(self, registry):
        s = aot_registry.registry_stats()
        for field in ("hits", "misses", "publishes", "evictions", "bytes",
                      "shared_hits", "installs", "root", "enabled"):
            assert field in s
        assert s["root"] == registry
        assert s["enabled"] is True
