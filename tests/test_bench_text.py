"""``amazon_polarity_text`` / ``text_sweep``: free-text reviews (a title and
a text) through the default text vectorizer and both default linear families,
at a size a CPU can hold (the rows of ``fixtures/cpu_cells_text_sweep.json``).

(a) the program against the configuration's own plain reference, every number
under the fixture's CPU limits through ``run.verdict``, and the control over
one; (b) three broken paths make ``correct`` false: a column's tokens hashed
modulo 511, upper-case tokens not folded, the SVC's loss left as the
logistic; (c) the sampled SanityChecker and a squared-hinge winner's refit
against the reference; (d) ``svc_family`` against a two-iteration fit worked
by hand on six rows; (e) the rows with a character outside ASCII and the
missing titles land in the buckets the stated rule gives; (f) the new spans
and counters; (g) the three readers; (h) ``work/svc.py`` against its hand
count; (i) the configuration's file; (j) the cell's part of
``run.py --selftest``.
"""

import contextlib
import importlib
import json
import os

import numpy as np
import pytest

import jax

from benchmark import run
from benchmark.reference import common, plain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "text_sweep"
SEED = 2 ** 31 + 21
MANIFEST = run.load_json("BENCHMARK.json")
TINY = run.cpu_cells()[CELL]
STATED = plain.Precision.stated("cpu")
COUNTERS = ("text.tokens", "text.token_slots", "text.rows_python_tokenized",
            "selector.family_rounds")


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def over(compared):
    return sorted(k for k, c in compared.items() if c["value"] > c["limit"])


def fnv(token):
    h = 2166136261
    for byte in token.encode("utf-8"):
        h = ((h ^ byte) * 16777619) % 2 ** 32
    return h


@pytest.fixture(scope="module")
def cell():
    return run.Cell(MANIFEST, CELL, TINY["rows"], TINY["limits"])


@pytest.fixture(scope="module")
def data(cell):
    return cell.program.make_data(cell.rows, SEED, cell.config)


@pytest.fixture(scope="module")
def traced(cell, data):
    """One train under the program's tracer, with what it counted.  The
    ids unpack lane by lane, as they do at the cell's own size (the fixture's
    wire is short enough for the stacked form, which the selftest's trains
    below take)."""
    from transmogrifai_tpu.ops import text
    from transmogrifai_tpu.telemetry import REGISTRY, Tracer
    tracer = Tracer("text")
    before = {k: REGISTRY.counters().get(k, 0) for k in COUNTERS}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(text, "_STACKED_UNPACK_BYTES", 0)
        rec = run.one_train(cell, data, "cpu", tracer)
    jax.clear_caches()
    assert not rec["why_failed"], rec["why_failed"]
    rec["counted"] = {k: REGISTRY.counters().get(k, 0) - before[k]
                      for k in COUNTERS}
    rec["spans"] = list(tracer.spans)
    rec["profile"] = REGISTRY.gauge("train.span_profile").value
    return rec


@pytest.fixture(scope="module")
def references(cell, data):
    """The reference's answers, once a question."""
    asked = {}

    def answer(produced):
        ask = cell.reference.question(produced)
        key = json.dumps(ask, sort_keys=True)
        if key not in asked:
            asked[key] = cell.reference.reference(data, cell.config, STATED,
                                                  ask, seed=SEED)
        return asked[key]
    return answer


# (a) ----------------------------------------------------------------------

def test_program_agrees_with_its_reference(cell, traced, references):
    p = traced["produced"]
    ok, compared = run.verdict(cell, [p], references(p))
    assert ok, compared
    assert set(compared) == set(TINY["limits"])
    assert p["stats"].shape == (5, 1026) and len(p["kept"]) == 1025
    assert {r["family"] for r in p["cv"]} == {"OpLogisticRegression",
                                              "OpLinearSVC"}
    assert len(p["cv"]) == 12
    # a model, not the empty one (whose constant score reads AuPR 0.75)
    assert p["winner"]["metric"] > 0.85


def test_control_fails_the_cells_limits(cell, data, traced, references):
    p = traced["produced"]
    low = cell.reference.reference(
        data, cell.config, plain.Precision.control("cpu"),
        cell.reference.question(p), seed=SEED)
    ok, control = run.verdict(
        cell, [common.as_produced(low, p, cell.config)], references(p))
    assert not ok and over(control), control


# (b) ----------------------------------------------------------------------

def title_modulo_511(mp):
    """The title's tokens (the column that has missing values) land in
    their hash modulo 511."""
    from transmogrifai_tpu.ops.text_profile import TextProfile

    def bent(method):
        return lambda self, num_hashes: method(
            self, num_hashes - 1 if self.null.any() else num_hashes)
    # the packed wire the device reads, and the ids a host path reads
    for name in ("pack_jobs", "buckets"):
        mp.setattr(TextProfile, name, bent(getattr(TextProfile, name)))


def capitals_not_folded(mp):
    """The walk hashes tokens as they are written: 'Great' and 'great' are
    two tokens."""
    from transmogrifai_tpu.ops import text, text_profile
    tokenize = text.tokenize_text
    mp.setattr(text, "tokenize_text",
               lambda s, min_len=1, to_lowercase=True: tokenize(s, min_len,
                                                                False))

    def scan(strings, min_token_len=1, cap=None):
        strings = text_profile._object_column(strings)
        prof = text_profile._py_scan(strings, min_token_len)
        prof._strings = strings
        return prof
    mp.setattr(text_profile, "scan_strings", scan)


def svc_loss_left_logistic(mp):
    """The squared-hinge family descends the logistic loss."""
    from transmogrifai_tpu.models import solvers
    mp.setitem(solvers.LOSSES, "squared_hinge",
               lambda margin, ypm, w: solvers._logistic_loss_grad(
                   margin, 0.5 * (ypm + 1.0), w))


@pytest.mark.parametrize("fault", [title_modulo_511, capitals_not_folded,
                                   svc_loss_left_logistic],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(cell, data, references, fault,
                                    monkeypatch):
    jax.clear_caches()
    fault(monkeypatch)
    rec = run.one_train(cell, data, "cpu")
    monkeypatch.undo()
    jax.clear_caches()
    assert not rec["why_failed"], rec["why_failed"]
    p = rec["produced"]
    ok, compared = run.verdict(cell, [p], references(p))
    assert not ok and over(compared), compared


# (c) ----------------------------------------------------------------------

def small_cell(rows, **config):
    small = run.Cell(MANIFEST, CELL, rows, TINY["limits"])
    small.config = dict(small.config, **config)
    return small


def test_sampled_statistics_read_the_stated_rows():
    """Rows over ``sample_upper_limit``: the program's statistics are those
    of the rows ``plain.sanity_sample`` draws, index for index."""
    base = run.load_json("benchmark", "configs", "amazon_polarity_text.json")
    small = small_cell(
        4096, sanity_checker=dict(base["sanity_checker"],
                                  sample_upper_limit=3000),
        selector={"OpLogisticRegression": dict(
            base["selector"]["OpLogisticRegression"], reg_param=[0.1],
            elastic_net_param=[0.1], max_iter=2)})
    data = small.program.make_data(4096, SEED, small.config)
    rec = run.one_train(small, data, "cpu")
    assert not rec["why_failed"], rec["why_failed"]
    p = rec["produced"]
    ref = small.reference.reference(data, small.config, STATED,
                                    small.reference.question(p), seed=SEED)
    gaps = common.compare(p, ref, small.config)
    assert gaps["stats_gap"] <= TINY["limits"]["stats_gap"], gaps
    assert gaps["kept_mismatch"] == 0.0
    # the same statistics over ALL rows are another answer
    every = dict(small.config, sanity_checker=base["sanity_checker"])
    whole = small.reference.reference(data, every, STATED,
                                      small.reference.question(p), seed=SEED)
    assert common.compare(p, whole, every)["stats_gap"] > 1e-3


def test_a_squared_hinge_winner_is_refitted_as_the_reference_refits():
    base = run.load_json("benchmark", "configs", "amazon_polarity_text.json")
    small = small_cell(4096, selector={"OpLinearSVC":
                                       base["selector"]["OpLinearSVC"]})
    data = small.program.make_data(4096, SEED, small.config)
    rec = run.one_train(small, data, "cpu")
    assert not rec["why_failed"], rec["why_failed"]
    p = rec["produced"]
    assert p["winner"]["family"] == "OpLinearSVC" and p["coef"] is not None
    ref = small.reference.reference(data, small.config, STATED,
                                    small.reference.question(p), seed=SEED)
    ok, compared = run.verdict(small, [p], ref)
    assert ok, compared
    assert {"refit_coef_gap", "train_auroc_gap",
            "cv_gap.OpLinearSVC"} <= set(compared)


# (d) ----------------------------------------------------------------------

def test_svc_family_equals_a_fit_worked_by_hand():
    """Six rows, three columns, one fold that holds rows 4 and 5 out, two
    iterations from zero, in float64 with the standardised matrix written
    out."""
    import jax.numpy as jnp
    ref = importlib.import_module("benchmark.reference.amazon_polarity_text")
    X = np.array([[2.0, 0.0, 1.0], [0.0, 1.0, 0.0], [3.0, 1.0, 0.0],
                  [1.0, 3.0, 2.0], [0.0, 2.0, 5.0], [4.0, 1.0, 1.0]])
    y = np.array([1.0, 0.0, 1.0, 0.0, 0.0, 1.0], np.float32)
    l2, iters = 0.1, 2

    def by_hand(w):
        wn = w / w.sum()
        mean = wn @ X
        scale = np.sqrt(np.maximum(wn @ (X * X) - mean * mean, 1e-12))
        Xs = (X - mean) / scale
        gram = Xs.T @ (wn[:, None] * Xs)
        v = np.full(3, 1.0 / np.sqrt(3.0))
        for _ in range(16):                 # the stated 16 power iterations
            v = gram @ v / (np.linalg.norm(gram @ v) + 1e-12)
        sigma_sq = v @ gram @ v
        assert sigma_sq == pytest.approx(np.linalg.eigvalsh(gram)[-1],
                                         rel=1e-3)
        step = 1.0 / (2.0 * sigma_sq + l2)
        s = np.where(y > 0.5, 1.0, -1.0)
        c, b = np.zeros(3), 0.0
        # from zero t = 1, so the first momentum is 0 and the second point
        # is a plain step from the first: two gradient steps
        for _ in range(iters):
            viol = np.maximum(0.0, 1.0 - s * (Xs @ c + b))
            glin = w * (-2.0 * viol * s) / w.sum()
            c, b = c - step * (Xs.T @ glin + l2 * c), b - step * glin.sum()
        raw = c / scale
        return raw, b - mean @ raw

    M = plain.BlockedMatrix([jnp.asarray(X, jnp.float32)], [(0, 6)])
    folds = [np.array([4, 5])]
    p = {"reg_param": [l2], "max_iter": iters, "tol": 0.0}
    cv, fit = ref.svc_family(M, y, folds, p, STATED, {"reg_param": l2})
    fold_w = np.array([1.0, 1.0, 1.0, 1.0, 0.0, 0.0])
    coef, icpt = by_hand(fold_w)
    margins = X[folds[0]] @ coef + icpt
    assert cv == [{"params": {"reg_param": l2},
                   "per_fold": [plain.aupr(y[folds[0]], margins)]}]
    coef, icpt = by_hand(np.ones(6))
    assert fit["coef"] == pytest.approx(coef, rel=1e-4)
    assert fit["intercept"] == pytest.approx(icpt, rel=1e-4)
    assert abs(coef[0]) > 0.05      # a fit, not the state it started from
    assert fit["train_auroc"] == plain.auroc(y, X @ coef + icpt)
    # the family the selector maps is this one
    assert ref.FAMILIES == {"OpLogisticRegression": common.logistic_family,
                            "OpLinearSVC": ref.svc_family}
    assert ref.question is common.winner_question


# (e) ----------------------------------------------------------------------

ODD_ROWS = [
    (None, []),
    ("Cafés OPEN late", ["caf", "s", "open", "late"]),
    ("İstanbul IS big", ["i", "stanbul", "is", "big"]),   # U+0130 folds to i
    ("it’s Great, GREAT!", ["it", "s", "great", "great"]),
    ("plain ASCII Title", ["plain", "ascii", "title"]),
    ("don't_stop 42", ["don't_stop", "42"]),
    ("", []),
]


def test_odd_rows_land_in_the_buckets_the_rule_gives():
    from transmogrifai_tpu.ops.text_profile import scan_strings
    ref = importlib.import_module("benchmark.reference.amazon_polarity_text")
    strings = np.empty(len(ODD_ROWS), dtype=object)
    strings[:] = [s for s, _ in ODD_ROWS]
    want = [sorted(fnv(t) % 512 for t in toks) for _, toks in ODD_ROWS]
    rows, buckets, null = ref.column_tokens(strings, 512)
    assert [sorted(buckets[rows == i].tolist())
            for i in range(len(ODD_ROWS))] == want
    assert null.tolist() == [s is None for s, _ in ODD_ROWS]
    prof = scan_strings(strings)
    lens, flat = prof.buckets(512)
    at = np.r_[0, np.cumsum(lens)]
    assert [sorted(flat[at[i]:at[i + 1]].tolist())
            for i in range(len(ODD_ROWS))] == want
    assert prof.null.tolist() == null.tolist()


def test_generated_odd_rows_agree_row_by_row(data):
    """The seed's own rows with a character outside ASCII, and their
    neighbours: the program's walk and the reference give every row the
    same buckets; the missing titles are null in both."""
    from transmogrifai_tpu.ops.text_profile import scan_strings
    ref = importlib.import_module("benchmark.reference.amazon_polarity_text")
    for name in ("title", "text"):
        col = data[name]
        odd = [i for i, s in enumerate(col) if s and not s.isascii()]
        assert odd, name
        take = np.unique(np.asarray(
            odd + [max(i - 1, 0) for i in odd]
            + [i for i, s in enumerate(col) if s is None]))
        strings = col[take]
        rows, buckets, null = ref.column_tokens(strings, 512)
        prof = scan_strings(strings)
        lens, flat = prof.buckets(512)
        at = np.r_[0, np.cumsum(lens)]
        for i in range(len(take)):
            assert sorted(buckets[rows == i].tolist()) == sorted(
                flat[at[i]:at[i + 1]].tolist()), strings[i]
        assert prof.null.tolist() == null.tolist()
    assert any(s is None for s in data["title"])
    assert all(s is not None for s in data["text"])


# (f) ----------------------------------------------------------------------

def test_spans_and_counters_of_a_traced_train(cell, data, traced):
    packs = [s for s in traced["spans"] if s.name == "text.pack_ids"]
    assert len(packs) == 2                        # a span a hashed column
    tokens = sum(s.attrs["tokens"] for s in packs)
    assert tokens == traced["counted"]["text.tokens"] > 80 * cell.rows
    for s in packs:
        a = s.attrs
        assert a["num_hashes"] == 512
        assert a["words"] == -(-a["tokens"] // 3) <= a["capacity"]
    assert traced["counted"]["text.token_slots"] == 3 * sum(
        s.attrs["capacity"] for s in packs)
    odd = sum(bool(s) and not s.isascii()
              for name in ("title", "text") for s in data[name])
    assert traced["counted"]["text.rows_python_tokenized"] == odd > 0
    spliced = [s for s in traced["spans"] if s.name == "text.python_tokenize"]
    assert sum(s.attrs["rows"] for s in spliced) == odd
    # two families, each fitted in round A and again in round B
    assert traced["counted"]["selector.family_rounds"] == 4
    assert traced["profile"]["text.pack_ids"]["count"] == 2


def test_spans_cost_nothing_without_a_tracer():
    from transmogrifai_tpu import telemetry
    from transmogrifai_tpu.ops.text_profile import scan_strings
    assert telemetry.active_tracer() is None
    with telemetry.span("text.pack_ids", num_hashes=512) as sp:
        assert sp is None
    strings = np.empty(3, dtype=object)
    strings[:] = ["one two", "thrée", None]
    before = telemetry.REGISTRY.counters().get("text.tokens", 0)
    prof = scan_strings(strings)
    assert prof.device_ids(512).shape == (1024,)
    assert prof.device_ids(1024) is None          # the unpacked path
    assert telemetry.REGISTRY.counters()["text.tokens"] - before == 4


def test_hash_counts_scope_is_in_the_programs():
    from transmogrifai_tpu.ops import text
    words = np.full(1024, text._sentinel3(512), np.int32)
    lens = np.asarray([0, 0, 3 * 1024], np.int32)
    hlo = text._scatter_counts_packed.lower(words, lens, 2, 512, False
                                            ).compile().as_text()
    assert "text.hash_counts" in hlo


@pytest.mark.parametrize("long_wire", [False, True])
def test_unpacked_slots_are_those_of_the_token_order(long_wire, monkeypatch):
    """``_unpack_ids3`` against the plain reading of the wire: slot
    ``3 w + l`` holds lane ``l`` of word ``w`` and lies in the row
    ``jnp.repeat`` of the rows by their lengths gives it — in token order on
    a short wire, lane by lane on a long one; rows of no tokens, a word
    shared by three rows and a wire with no padding included."""
    from transmogrifai_tpu.ops import text
    if long_wire:
        monkeypatch.setattr(text, "_STACKED_UNPACK_BYTES", 0)
    for lens, cap in (([2, 0, 0, 5, 1, 1, 1, 0, 3], 6), ([3, 3], 2),
                      ([0, 0, 0], 1), ([7], 4)):
        total = sum(lens)
        flat = (np.arange(total) * 37 % 512).astype(np.int32)
        words = np.full(cap, text._sentinel3(512), np.int32)
        packed = text._pack_ids3(flat, 512)
        words[:packed.size] = packed
        lens_p = np.asarray(lens + [3 * cap - total], np.int32)
        rows, ids = (np.asarray(a) for a in text._unpack_ids3(words, lens_p))
        want_rows = np.repeat(np.arange(len(lens) + 1), lens_p)
        want_ids = np.r_[flat, np.full(3 * cap - total, 512)]
        slot = (np.r_[0:3 * cap:3, 1:3 * cap:3, 2:3 * cap:3] if long_wire
                else np.arange(3 * cap))
        assert rows.tolist() == want_rows[slot].tolist(), lens
        assert ids.tolist() == want_ids[slot].tolist(), lens


def test_both_unpacks_scatter_the_same_counts(monkeypatch):
    """Random wires through ``_scatter_counts_packed`` on either side of the
    length at which the unpack changes form, against the host's counts."""
    from transmogrifai_tpu.ops import text
    rng = np.random.default_rng(34)
    for trial in range(10):
        n = int(rng.integers(1, 40))
        lens = rng.integers(0, 6, size=n).astype(np.int32)
        if trial % 5 == 0:
            lens[:] = 0
        flat = rng.integers(0, 512, size=int(lens.sum())).astype(np.int32)
        want = text._counts_from_flat(lens, flat, 512, False)
        for limit in (1 << 28, 0):
            monkeypatch.setattr(text, "_STACKED_UNPACK_BYTES", limit)
            jax.clear_caches()
            got = text.device_counts_from_flat(lens, flat, 512)
            assert np.array_equal(want, np.asarray(got)), (trial, limit)
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip to compile for; made inside a
    fixture so that only the worker that runs this file loads the TPU's
    compiler."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache and
    cannot be read back without a chip: keep such compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def test_hash_scatter_compiles_for_the_chip_at_the_cells_size(one_chip):
    """The text column's scatter at the configuration's own rows and the
    size class of its token wire, compiled for a v5e chip: it has to fit the
    chip's 16 GB (the form that stacked the lanes as ``[words, 3]`` asked for
    25.8 GB in one copy and was refused).  Counts nothing but bytes."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import text
    cfg = run.load_json("benchmark", "configs", "amazon_polarity_text.json")
    rows = cfg["rows"]
    tokens = rows * cfg["generator"]["text"]["mean_tokens"]
    words = text._size_class(-(-tokens // 3))
    with no_compile_cache():
        compiled = text._scatter_counts_packed.lower(
            jax.ShapeDtypeStruct((words,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((rows + 1,), jnp.int32, sharding=one_chip),
            rows, 512, False).compile()
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert m.output_size_in_bytes == rows * 512 * 4
    assert held < 12 * 2 ** 30, m


def test_hash_kernel_compiles_for_the_chip_at_the_cells_size(one_chip):
    """The same counts through the Pallas kernel (``ops/hash_kernel.py``),
    compiled for a v5e chip at the cell's rows and wire: Mosaic takes the
    kernel, its block bounds fit the scalar memory, and the program holds
    the counts twice at most (the kernel's ``[512, rows]`` and the
    ``[rows, 512]`` it is read as), with no float32 ``[rows + 1, 513]``
    scatter target and no sort.  Here with this file's other compiles: one
    worker loads the TPU's compiler."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import text
    cfg = run.load_json("benchmark", "configs", "amazon_polarity_text.json")
    rows = cfg["rows"]
    tokens = rows * cfg["generator"]["text"]["mean_tokens"]
    words = text._size_class(-(-tokens // 3))
    with no_compile_cache():
        compiled = text._scatter_counts_packed.lower(
            jax.ShapeDtypeStruct((words,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((rows + 1,), jnp.int32, sharding=one_chip),
            rows, 512, False, True).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "sort(" not in hlo
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes == rows * 512 * 4
    assert m.temp_size_in_bytes < 1.1 * rows * 512 * 4, m


def test_round_b_panel_compiles_for_the_chip_at_the_typed_cells_size(
        one_chip):
    """The metric panel of two folds by three survivors (every cell's
    logistic family after racing) at ``nyc_taxi_typed``'s rows, compiled for
    a v5e chip.  Mapped over [N, 2, 3] where the axes lay, the compiler
    tiled (2, 3) to (8, 128), 768 bytes a score: 8.5 GB at 1.8 M rows and
    no program at all at 8.4 M (``metrics_device._rows_last``).  Here with
    this file's other compile: one worker loads the TPU's compiler."""
    import jax.numpy as jnp
    from transmogrifai_tpu import metrics_device
    rows = run.load_json("benchmark", "configs", "nyc_taxi_typed.json")["rows"]
    with no_compile_cache():
        held = {}
        for fn in (metrics_device.masked_aupr_fold_grid,
                   metrics_device.masked_auroc_fold_grid):
            m = fn.lower(
                jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip),
                jax.ShapeDtypeStruct((rows, 2, 3), jnp.float32,
                                     sharding=one_chip),
                jax.ShapeDtypeStruct((2, rows), jnp.float32,
                                     sharding=one_chip)
            ).compile().memory_analysis()
            held[fn.__name__] = m.temp_size_in_bytes / (6 * rows)
    assert all(b < 48.0 for b in held.values()), held


def test_packed_null_bits_unpack_cheaply_on_the_chip(one_chip):
    """The coordinate vectorizer's device body at ``nyc_taxi_typed``'s rows,
    compiled for a v5e chip.  With eight consecutive rows a word the unpack
    was a ``[W, 8] -> [N]`` reshape across the lane tile: fused into the
    fill it made 30 MB of code and took the compiler 235 s; in bit planes
    (``columns.pack_bits``) the body is under 3 MB.  Counts bytes, not
    seconds.  Here with this file's other compiles: one worker loads the
    TPU's compiler."""
    import jax.numpy as jnp
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.features import features_from_schema
    from transmogrifai_tpu.ops.geo import GeolocationVectorizer
    rows = run.load_json("benchmark", "configs", "nyc_taxi_typed.json")["rows"]
    _, (f,) = features_from_schema({"y": T.RealNN, "g": T.Geolocation},
                                   response="y")
    few = ColumnBatch({"g": Column(
        T.Geolocation, np.ones((16, 3), np.float32), np.arange(16) % 5 > 0)},
        16)
    wire, body = GeolocationVectorizer().set_input(f).fit(
        few).transform_staged(few)

    def at_size(a):
        per_row = {16: rows, 2: -(-rows // 8)}.get(a.shape[0])
        if a.ndim != 1 or per_row is None:
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
        return jax.ShapeDtypeStruct(
            (per_row,), jnp.bfloat16 if a.dtype == np.float32 else a.dtype,
            sharding=one_chip)
    with no_compile_cache():
        m = jax.jit(lambda w: body(w).values).lower(
            {k: at_size(v) for k, v in wire.items()}
        ).compile().memory_analysis()
    assert m.generated_code_size_in_bytes < 8e6, m
    assert m.temp_size_in_bytes < 48 * rows, m


# the kept matrices of the one-chip cells: rows after the sweep's padding,
# columns SanityChecker keeps, and the first stage's folds x grid points
ONE_PASS_SHAPES = {"mixed_sweep": (196608, 8815, 2, 4, "logistic"),
                   "text_sweep": (1835008, 1025, 2, 2, "squared_hinge"),
                   "maps_sweep": (1572864, 1169, 2, 3, "logistic"),
                   "typed_sweep": (6291456, 81, 2, 3, "squared_hinge")}


@pytest.mark.parametrize("cell", sorted(ONE_PASS_SHAPES))
def test_one_pass_grid_fit_compiles_for_the_chip_without_a_copy(cell,
                                                                one_chip):
    """The grid program with its gradients through the one-pass kernel,
    compiled for a v5e chip at a cell's matrix.  The chip lays such a
    matrix out column by column (no width here is a multiple of 128) and
    the kernel reads it so: a row-major read would make XLA copy the whole
    matrix first (3.5 GB in ``mixed_sweep``).  Here with this file's other
    compiles: one worker loads the TPU's compiler."""
    import jax.numpy as jnp
    from transmogrifai_tpu.models import solvers
    rows, cols, folds, grid, loss = ONE_PASS_SHAPES[cell]

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    with no_compile_cache():
        compiled = solvers.linear_grid_fit.lower(
            shape(rows, cols, dtype=jnp.bfloat16), shape(rows),
            shape(folds, rows), shape(grid), shape(grid), loss=loss,
            max_iter=50, one_pass=True).compile()
    assert compiled.input_formats[0][0].layout.major_to_minor == (1, 0)
    text = compiled.as_text()
    assert "linear_onepass" in text
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < rows * cols // 8, m


# (g) ----------------------------------------------------------------------

NEW_READERS = ("text_pack_s", "text_profile_s", "token_pad_share")


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_report_nothing_where_there_is_nothing(name, monkeypatch):
    from transmogrifai_tpu import telemetry
    monkeypatch.setattr(telemetry, "REGISTRY", telemetry.MetricsRegistry())
    assert reader(name).read({"trains": [], "trace": None}) is None
    # a traced run of a program that has neither the span nor the counters
    assert reader(name).read({"trains": [{"link_bytes": 0}],
                              "trace": {"busy_s": 1.0, "window_s": 2.0}}
                             ) is None
    telemetry.REGISTRY.gauge("train.span_profile").set(
        {"workflow.train": {"count": 1, "total_s": 1.0, "self_s": 1.0,
                            "jit_s": 0.0}})
    assert reader(name).read({"trains": [{}],
                              "trace": {"busy_s": 1.0, "window_s": 2.0}}
                             ) is None


def test_readers_read_what_the_program_set(monkeypatch):
    from transmogrifai_tpu import telemetry
    reg = telemetry.MetricsRegistry()
    monkeypatch.setattr(telemetry, "REGISTRY", reg)
    row = {"count": 2, "self_s": 0.5, "jit_s": 0.0}
    reg.gauge("train.span_profile").set(
        {"text.pack_ids": dict(row, total_s=1.5),
         "prefetch.text_profiles": dict(row, total_s=2.25)})
    reg.counter("text.tokens").inc(600)
    reg.counter("text.token_slots").inc(800)
    ctx = {"trains": [{}, {}], "trace": {"busy_s": 1.0, "window_s": 2.0}}
    assert reader("text_pack_s").read(ctx) == 1.5
    assert reader("text_profile_s").read(ctx) == 2.25
    assert reader("token_pad_share").read(ctx) == pytest.approx(25.0)
    # the spans are read from a traced train alone, the counters always
    untraced = dict(ctx, trace=None)
    assert reader("text_pack_s").read(untraced) is None
    assert reader("token_pad_share").read(untraced) == pytest.approx(25.0)


def test_readers_read_a_traced_train(traced):
    ctx = {"trains": [traced], "trace": {"busy_s": 1.0, "window_s": 2.0}}
    profile = traced["profile"]
    assert reader("text_pack_s").read(ctx) == \
        profile["text.pack_ids"]["total_s"] > 0.0
    share = reader("token_pad_share").read(ctx)
    assert 0.0 < share < 34.0
    # no prefetch on a CPU backend (no slow link to hide): the span is not
    # opened and its reader has nothing to read
    assert "prefetch.text_profiles" not in profile
    assert reader("text_profile_s").read(ctx) is None


def test_readers_and_appended_cells_say_what_benchmark_json_says():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW_READERS:
        mod, m = reader(name), entries[name]
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
        # the cell's own readers; ISSUE 36 appended its cell to the one of
        # them that reads something there
        assert m["workloads"] == [CELL] + ["typed_sweep", "maps_sweep"] * (
            name == "text_profile_s") + ["maps_sweep"] * (
            name != "text_profile_s") + ["lists_sweep"] \
            and m["better"] == "lower"
        assert m["layer"] in {e["layer"] for e in MANIFEST["per_layer"][:18]}
    for name in ("prologue_s", "selector_s", "host_link_MB",
                 "window_compiles", "setup_compile_s", "device_idle_share",
                 "sweep_mfu", "peak_hbm_GiB", "prologue_idle_s",
                 "transform_s", "sanity_s", "refit_s", "train_jit_s"):
        # appended with this cell, then the next cells after it
        assert entries[name]["workloads"][-4:] == [CELL, "typed_sweep",
                                                   "maps_sweep",
                                                   "lists_sweep"], name
    for name in ("mesh_devices", "place_s", "relayout_MB", "sweep_mfu_x4",
                 "chip_rows_per_s"):
        assert CELL not in entries[name]["workloads"], name
    # entries 18 to 20: what ISSUE 34 appended, where later issues append
    assert [m["name"] for m in MANIFEST["per_layer"][18:21]] == list(
        NEW_READERS)


# (h) ----------------------------------------------------------------------

def test_svc_work_equals_its_hand_count():
    svc = importlib.import_module("benchmark.work.svc")
    cases = run.load_json("benchmark", "fixtures", "work_expected_svc.json")
    assert len(cases) == 3 and {c["family"] for c in cases} == {"svc"}
    for case in cases:
        assert svc.required(case["shape"], case["won"]) == (
            case["ops"], case["bytes"]), case["comment"]
    assert svc.FAMILY == "OpLinearSVC"


def test_required_work_counts_both_families(cell):
    """One train's work at the cell's own rows: the SVC's panel is there
    whoever wins, a refit only for the family that won."""
    rows = cell.config["rows"]
    ref = {"stats": np.zeros((5, 1026)), "kept": np.arange(1025)}
    lr = run.required_work(cell, rows, ref, "OpLogisticRegression")
    svc = run.required_work(cell, rows, ref, "OpLinearSVC")
    none = run.required_work(cell, rows, ref, None)
    refit = 4.0 * 1025 * 67 * rows
    assert lr["ops"] - none["ops"] == pytest.approx(refit)
    assert svc["ops"] - none["ops"] == pytest.approx(refit)
    only_lr = run.required_work(
        small_cell(rows, work=["stats", "linear", "panel"]), rows, ref, None)
    assert none["bytes"] > only_lr["bytes"] > 0.5 * none["bytes"]


# (i) ----------------------------------------------------------------------

def test_configuration_keeps_every_default_and_states_its_cuts():
    from transmogrifai_tpu.selector import DefaultSelectorParams as D
    cfg = run.load_json("benchmark", "configs", "amazon_polarity_text.json")
    one = run.load_json("benchmark", "configs", "criteo_mixed.json")
    entry = run.by_name(MANIFEST["configs"], "amazon_polarity_text", "config")
    assert entry["file"] == "benchmark/configs/amazon_polarity_text.json"
    assert entry["reduced"] == cfg["reduced"] == ["rows", "model_types"]
    assert set(cfg["reduced_note"]) == {"rows", "model_types"}
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for said in ("1509.01626", "Amazon Review Polarity", "train.csv",
                 "3,600,000", "400,000", "balanced"):
        assert said in cfg["source"], said
    assert cfg["source_rows"] == 3600000
    assert 1048576 <= cfg["rows"] <= 2097152 and cfg["rows"] % 262144 == 0
    assert cfg["model_types"] == list(cfg["selector"]) == [
        "OpLogisticRegression", "OpLinearSVC"]
    assert cfg["source_model_types"] == one["source_model_types"]
    for key in ("transmogrify", "raw_feature_filter", "sanity_checker",
                "folds", "fold_seed", "validation_metric", "racing"):
        assert cfg[key] == one[key], key
    assert cfg["selector"]["OpLogisticRegression"] == \
        one["selector"]["OpLogisticRegression"]
    assert cfg["selector"]["OpLinearSVC"] == {
        "reg_param": D.REGULARIZATION, "max_iter": D.MAX_ITER_LIN[0],
        "tol": D.TOL[0]}
    assert cfg["transmogrify"]["num_hashes"] == 512
    assert cfg["precision"]["matrix_storage"] == \
        one["precision"]["matrix_storage"]
    assert cfg["precision"]["control"] == one["precision"]["control"]
    assert cfg["work"] == ["stats", "linear", "svc", "panel"]
    assert set(cfg["assumed"]) >= {"typing", "generator", "lengths",
                                   "label_weights", "splitter"}
    assert set(cfg["guarantees"]) >= {"answers", "rows", "precision",
                                      "failed"}
    g = cfg["generator"]
    assert g["text"]["mean_tokens"] + g["title"]["mean_tokens"] == 83
    assert g["non_ascii_row_share"] == 0.005
    assert g["missing_title_share"] == 0.001 and g["positive_share"] == 0.5
    wl = run.by_name(MANIFEST["workloads"], CELL, "workload")
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "amazon_polarity_text", "mixed_sweep", 1)
    assert len(wl["why"]) <= 200
    limits = run.load_json("benchmark", "limits", CELL + ".json")
    assert set(limits) - {"why"} == set(TINY["limits"])
    assert all(any(k in said for said in limits["why"])
               for k in TINY["limits"])
    assert all(limits[k] >= 0.0 for k in TINY["limits"])
    with open(os.path.join(ROOT, "benchmark", "fixtures",
                           "cpu_cells_text_sweep.json")) as fh:
        assert list(json.load(fh)["cells"]) == [CELL]


def test_generator_draws_what_the_configuration_states(cell, data):
    """Same seed, same rows, whatever the threads; the stated shares and
    lengths; halves of the arrays are halves of the rows."""
    again = cell.program.make_data(cell.rows, SEED, cell.config)
    assert all(np.array_equal(data[k], again[k]) for k in data)
    other = cell.program.make_data(cell.rows, SEED + 1, cell.config)
    assert not np.array_equal(data["text"], other["text"])
    assert set(data) == {"label", "title", "text"}
    assert all(len(v) == cell.rows for v in data.values())
    assert 0.47 < data["label"].mean() < 0.53
    tokens = np.asarray([len(plain.TOKEN_RE.findall(s))
                         for s in data["text"]])
    assert 74 < tokens.mean() < 82 and tokens.max() > 400
    titles = np.asarray([len(plain.TOKEN_RE.findall(s or ""))
                         for s in data["title"]])
    assert 4.5 < titles.mean() < 5.5
    text = " ".join(data["text"][:200])
    assert any(c.isupper() for c in text) and any(c in ",.!?;:" for c in text)
    words = plain.TOKEN_RE.findall(text)
    assert 2 <= min(map(len, words)) and max(map(len, words)) <= 12


# (j) ----------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_selftest_runs_the_cell(trace):
    """What ``run.py --selftest`` does with the cell: set-up, a window of
    one train, the reference, the verdict, the result's metrics (no device
    metric off the chip)."""
    res = run.run_cell(MANIFEST, CELL, 2 ** 31 + 7, 0, trace,
                       require_chip=False, rows=TINY["rows"],
                       limits=TINY["limits"])
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 1
    assert res["device"]["platform"] == "cpu"
    if not trace:
        assert sorted(res["metrics"]) == ["setup_s", "train_wall_s"]
        return
    got = set(res["metrics"])
    assert {"token_pad_share", "host_link_MB", "prologue_s", "selector_s",
            "window_compiles"} <= got
    # what is read from a device trace, or from spans beside one, is not
    assert not got & {"device_idle_share", "sweep_mfu", "peak_hbm_GiB",
                      "prologue_idle_s", "text_profile_s", "text_pack_s"}
