"""AOT-serialized executables (ISSUE 9): bundle round-trip, digest coverage,
platform-mismatch fallback, tree pad-exactness, and the background pre-trace
pool.  The serve-side acceptance bar (zero compiles before the first score in
a FRESH process) lives in scripts/ci_aot_smoke.py — in-process tests can't
prove it because the suite's own warm jit tables would mask a regression."""

import json
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_aux_subsystems import make_records, train_small_model  # noqa: E402

from transmogrifai_tpu import aot  # noqa: E402
from transmogrifai_tpu.checkpoint import (CorruptModelError,  # noqa: E402
                                          read_manifest, write_manifest)
from transmogrifai_tpu.resilience import FailureLog, use_failure_log  # noqa: E402
from transmogrifai_tpu.serving.engine import records_to_batch  # noqa: E402
from transmogrifai_tpu.telemetry import REGISTRY  # noqa: E402
from transmogrifai_tpu.workflow import WorkflowModel  # noqa: E402


def _counter(name):
    return REGISTRY.snapshot()["counters"].get(name, 0)


def _score_rows(model, records):
    pred = next(f.name for f in model.result_features)
    batch = records_to_batch(model.raw_features, records)
    scored = model.score(batch=batch)
    return {k: np.asarray(v) for k, v in scored[pred].values.items()}


@pytest.fixture(scope="module")
def trained():
    wf, _ = train_small_model(make_records(120))
    return wf.train()


@pytest.fixture(scope="module")
def saved_bundle(trained, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("aot") / "model")
    os.environ.pop("TRANSMOGRIFAI_NO_AOT", None)
    trained.save(path)
    return path


# 4 records on purpose: rows=4 is a padding-ladder size, so the AOT-loaded
# model serves this batch from a shipped executable, not a fresh jit
SCORE_RECORDS = [{"x1": 0.4, "x2": 3.0, "cat": "a"},
                 {"x1": -1.2, "x2": None, "cat": "c"},
                 {"x1": 0.0, "x2": 7.5, "cat": "b"},
                 {}]


class TestBundleRoundTrip:
    def test_export_writes_digest_covered_artifacts(self, saved_bundle):
        import jax
        aot_dir = os.path.join(saved_bundle, "aot-" + jax.default_backend())
        assert os.path.isdir(aot_dir)
        with open(os.path.join(aot_dir, "aot.json")) as fh:
            meta = json.load(fh)
        assert meta["executables"], "no executables exported"
        assert aot.abi_mismatch(meta["abi"]) is None
        # every artifact (including the per-platform subdir) is covered by
        # the recursive v2 MANIFEST
        manifest = read_manifest(saved_bundle)
        assert manifest["formatVersion"] == 2
        covered = set(manifest["files"])
        for ent in meta["executables"]:
            assert f"aot-{jax.default_backend()}/{ent['file']}" in covered
        assert manifest["aot"]["executables"] == len(meta["executables"])

    def test_load_installs_and_scores_identically(self, saved_bundle,
                                                  monkeypatch):
        loaded = WorkflowModel.load(saved_bundle)
        assert loaded.aot_executables > 0
        assert loaded.score_program().aot_installed_count() > 0
        # the same bundle forced onto the JIT path is the parity oracle:
        # shipped executables must be bit-identical to a fresh compile
        monkeypatch.setenv("TRANSMOGRIFAI_NO_AOT", "1")
        jit = WorkflowModel.load(saved_bundle)
        assert jit.aot_executables == 0
        assert jit.score_program().aot_installed_count() == 0
        monkeypatch.delenv("TRANSMOGRIFAI_NO_AOT")
        got = _score_rows(loaded, SCORE_RECORDS)
        want = _score_rows(jit, SCORE_RECORDS)
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])

    def test_loaded_counter_incremented(self, saved_bundle):
        before = _counter("aot.executables_loaded")
        n = WorkflowModel.load(saved_bundle).aot_executables
        assert n > 0
        assert _counter("aot.executables_loaded") == before + n

    def test_export_traces_stay_off_the_books(self, tmp_path):
        """save()'s ladder warmup traces must not count toward the global
        trace_count(): a serving engine measuring its online-trace window
        while a concurrent save() runs (lifecycle retrain+promote, hot
        reload under traffic) would otherwise blame the export's traces on
        itself and demote to the local fallback."""
        from transmogrifai_tpu.compiled import trace_count
        wf, _ = train_small_model(make_records(120))
        model = wf.train()
        t0 = trace_count()
        model.save(str(tmp_path / "model"))
        # export really warmed + serialized (non-vacuous), yet traced zero
        assert read_manifest(str(tmp_path / "model"))["aot"]["executables"] > 0
        assert trace_count() == t0


    def test_export_and_install_after_a_table_hit(self, tmp_path):
        """A second train of the same content scores through the executable
        the first left in the process-wide table; its save() still lowers
        and builds what it ships, and the loaded bundle dispatches what it
        installed, not the table's."""
        from transmogrifai_tpu.compiled import trace_count
        from transmogrifai_tpu.profiling import program_stats

        def shared():
            return {k: _counter("compiled.shared." + k)
                    for k in ("hit", "miss", "bypass")}
        first = train_small_model(make_records(120))[0].train()
        _score_rows(first, SCORE_RECORDS)
        second = train_small_model(make_records(120))[0].train()
        before = shared()
        want = _score_rows(second, SCORE_RECORDS)
        after = shared()
        assert after["hit"] > before["hit"]
        assert (after["miss"], after["bypass"]) == (before["miss"],
                                                    before["bypass"])
        rows0 = dict(program_stats()["jit(traced)"])
        path = str(tmp_path / "model")
        second.save(path)
        written = read_manifest(path)["aot"]["executables"]
        rows1 = program_stats()["jit(traced)"]
        assert written > 0
        assert rows1["lowers"] - rows0["lowers"] >= written
        assert rows1["compiles"] - rows0["compiles"] >= written
        loaded = WorkflowModel.load(path)
        assert loaded.aot_executables == written
        before, traces = shared(), trace_count()
        fallbacks = _counter("aot.fallback")
        got = _score_rows(loaded, SCORE_RECORDS)
        assert shared() == before and trace_count() == traces
        assert _counter("aot.fallback") == fallbacks
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])


class TestFallbacks:
    def test_corrupt_artifact_is_caught_by_digest(self, trained, tmp_path):
        path = str(tmp_path / "model")
        trained.save(path)
        import glob
        seg = sorted(glob.glob(os.path.join(path, "aot-*", "seg-*.aotx")))[0]
        with open(seg, "r+b") as fh:
            fh.write(b"\xff\xff\xff\xff")
        with pytest.raises(CorruptModelError):
            WorkflowModel.load(path)

    def test_jit_only_bundle_loads_clean(self, trained, tmp_path):
        """A bundle saved without AOT (the pre-v2 layout) loads silently on
        the JIT path: no fallback counter, no degraded note."""
        path = str(tmp_path / "model")
        trained.save(path, aot=False)
        assert not any(d.startswith("aot-") for d in os.listdir(path))
        assert "aot" not in read_manifest(path)
        before = _counter("aot.fallback")
        log = FailureLog()
        with use_failure_log(log):
            model = WorkflowModel.load(path)
        assert model.aot_executables == 0
        assert _counter("aot.fallback") == before
        assert not [e for e in log.to_json()
                    if e.get("point") == "checkpoint.aot"]
        _score_rows(model, SCORE_RECORDS)   # JIT path still serves

    def test_abi_mismatch_degrades_to_jit(self, trained, tmp_path):
        path = str(tmp_path / "model")
        trained.save(path)
        import glob
        aot_dir = glob.glob(os.path.join(path, "aot-*"))[0]
        meta_path = os.path.join(aot_dir, "aot.json")
        with open(meta_path) as fh:
            meta = json.load(fh)
        meta["abi"]["jaxVersion"] = "0.0.0-other"
        with open(meta_path, "w") as fh:
            json.dump(meta, fh)
        write_manifest(path, extra={k: v for k, v in read_manifest(path).items()
                                    if k not in ("formatVersion", "createdAt",
                                                 "files")})
        before = _counter("aot.fallback")
        log = FailureLog()
        with use_failure_log(log):
            model = WorkflowModel.load(path)
        assert model.aot_executables == 0
        assert _counter("aot.fallback") == before + 1
        notes = [e for e in log.to_json()
                 if e.get("point") == "checkpoint.aot"
                 and e.get("action") == "degraded"]
        assert notes and "jaxVersion mismatch" in notes[0]["detail"]["detail"]
        # degraded, not broken: the bundle still scores via JIT
        _score_rows(model, SCORE_RECORDS)

    def test_other_platform_only_degrades_to_jit(self, trained, tmp_path):
        path = str(tmp_path / "model")
        trained.save(path)
        import glob
        import jax
        aot_dir = glob.glob(os.path.join(path, "aot-*"))[0]
        renamed = os.path.join(path, "aot-tpu6x")
        assert aot_dir != renamed
        os.rename(aot_dir, renamed)
        write_manifest(path, extra={k: v for k, v in read_manifest(path).items()
                                    if k not in ("formatVersion", "createdAt",
                                                 "files")})
        log = FailureLog()
        with use_failure_log(log):
            model = WorkflowModel.load(path)
        assert model.aot_executables == 0
        notes = [e for e in log.to_json()
                 if e.get("point") == "checkpoint.aot"]
        assert notes and "aot-tpu6x" in notes[0]["detail"]["detail"]
        assert f"aot-{jax.default_backend()}" in notes[0]["detail"]["detail"]

    def test_kill_switch(self, trained, tmp_path):
        path = str(tmp_path / "model")
        aot.set_aot_enabled(False)
        try:
            assert not aot.aot_enabled()
            trained.save(path)
            assert not any(d.startswith("aot-") for d in os.listdir(path))
        finally:
            aot.set_aot_enabled(True)


class TestTreePadExactness:
    """weighted_pad_exact for the tree family: zero-weight pad rows must not
    change a single split.  Leaf VALUES are compared to float tolerance only
    — the scan chunking inside the fitters depends on N, so reduction order
    (not membership) differs between the padded and exact runs."""

    N, D, PAD = 137, 6, 160

    def _data(self):
        rng = np.random.default_rng(3)
        X = rng.normal(size=(self.N, self.D)).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1]
             + rng.normal(size=self.N).astype(np.float32) * 0.3 > 0
             ).astype(np.float32)
        pad = self.PAD - self.N
        Xp = np.concatenate([X, np.zeros((pad, self.D), np.float32)])
        yp = np.concatenate([y, np.zeros(pad, np.float32)])
        W = np.ones((2, self.N), np.float32)
        W[1, ::3] = 0.0                     # a non-trivial fold mask
        Wp = np.concatenate([W, np.zeros((2, pad), np.float32)], axis=1)
        return X, y, W, Xp, yp, Wp

    def test_bin_splits_ignore_registered_pad_rows(self):
        from transmogrifai_tpu.models.trees import (build_bin_splits,
                                                    register_real_rows)
        X, _, _, Xp, _, _ = self._data()
        register_real_rows(Xp, self.N)
        np.testing.assert_array_equal(build_bin_splits(Xp, 16),
                                      build_bin_splits(X, 16))

    @pytest.mark.parametrize("family,grids", [
        ("OpGBTClassifier", [{"max_iter": 4, "max_depth": 3}]),
        # bootstrap=False: the resampling RNG stream depends on the padded
        # row count, so bootstrap draws are a VALID weight-masked sample but
        # not the SAME sample — only the deterministic fit is bit-comparable
        ("OpRandomForestClassifier",
         [{"num_trees": 5, "max_depth": 3, "seed": 9, "bootstrap": False}]),
        ("OpDecisionTreeRegressor", [{"max_depth": 4}]),
    ])
    def test_pad_vs_exact_same_trees(self, family, grids):
        from transmogrifai_tpu.models import trees
        from transmogrifai_tpu.models.trees import register_real_rows
        cls = getattr(trees, family)
        assert cls.weighted_pad_exact
        X, y, W, Xp, yp, Wp = self._data()
        if "Regressor" in family:
            y, yp = y * 2.5 - 1.0, yp * 2.5 - 1.0
        exact = cls().fit_arrays_grid(X, y, W, grids)
        register_real_rows(Xp, self.N)
        padded = cls().fit_arrays_grid(Xp, yp, Wp, grids)
        for k in range(W.shape[0]):
            e, p = exact[k][0], padded[k][0]
            feat_e, feat_p = np.asarray(e["feature"]), np.asarray(p["feature"])
            np.testing.assert_array_equal(feat_e, feat_p)
            np.testing.assert_array_equal(np.asarray(e["is_leaf"]),
                                          np.asarray(p["is_leaf"]))
            # thresholds only carry meaning at split nodes — pure-leaf nodes
            # hold argmax tie-break garbage that may differ legitimately
            split = ~np.asarray(e["is_leaf"]).astype(bool)
            np.testing.assert_array_equal(
                np.asarray(e["threshold"])[split],
                np.asarray(p["threshold"])[split])
            np.testing.assert_allclose(np.asarray(e["leaf"]),
                                       np.asarray(p["leaf"]), atol=1e-5)
            np.testing.assert_array_equal(np.asarray(e["bin_splits"]),
                                          np.asarray(p["bin_splits"]))


class TestPretrace:
    def test_scope_is_thread_local(self):
        assert not aot.pretrace_mode()
        with aot.pretrace_scope():
            assert aot.pretrace_mode()
            seen = []
            t = threading.Thread(
                target=lambda: seen.append(aot.pretrace_mode()))
            t.start()
            t.join()
            assert seen == [False]
        assert not aot.pretrace_mode()

    def test_enabled_requires_cache_env(self, monkeypatch):
        monkeypatch.delenv("TRANSMOGRIFAI_COMPILE_CACHE", raising=False)
        assert not aot.pretrace_enabled()
        monkeypatch.setenv("TRANSMOGRIFAI_COMPILE_CACHE", "/tmp/cc")
        assert aot.pretrace_enabled()
        aot.set_aot_enabled(False)
        try:
            assert not aot.pretrace_enabled()
        finally:
            aot.set_aot_enabled(True)

    def test_submit_runs_in_pretrace_scope_and_counts(self):
        before = _counter("aot.pretrace_compiled")
        modes = []
        aot.pretrace_submit("probe", lambda: modes.append(aot.pretrace_mode()))
        aot.pretrace_drain(timeout=30)
        assert modes == [True]
        assert _counter("aot.pretrace_compiled") == before + 1

    def test_submit_failure_lands_in_submitter_log(self):
        before = _counter("aot.pretrace_failed")
        log = FailureLog()

        def boom():
            raise RuntimeError("pretrace boom")
        with use_failure_log(log):
            aot.pretrace_submit("boom-task", boom)
        aot.pretrace_drain(timeout=30)
        assert _counter("aot.pretrace_failed") == before + 1
        notes = [e for e in log.to_json()
                 if e.get("point") == "tuning.pretrace"]
        assert notes and notes[0]["detail"]["detail"] == "boom-task"

    def test_pretrace_train_identical_winner(self, trained, tmp_path,
                                             monkeypatch):
        """The background pre-trace only compiles: a sweep run with it on
        picks the same model with bit-identical scores."""
        monkeypatch.setenv("TRANSMOGRIFAI_COMPILE_CACHE",
                           str(tmp_path / "compile-cache"))
        assert aot.pretrace_enabled()
        submitted = _counter("aot.pretrace_submitted")
        wf, _ = train_small_model(make_records(120))
        model = wf.train()
        aot.pretrace_drain(timeout=60)
        assert _counter("aot.pretrace_submitted") > submitted
        got = _score_rows(model, SCORE_RECORDS)
        want = _score_rows(trained, SCORE_RECORDS)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])


class TestCLI:
    def test_no_aot_flag_flows_into_params(self):
        from transmogrifai_tpu.runner import OpApp
        args = OpApp().parse_args(["--run-type", "train", "--no-aot"])
        assert args.no_aot
        args = OpApp().parse_args(["--run-type", "train"])
        assert not args.no_aot
