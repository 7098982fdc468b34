"""Per-type map vectorizers (≙ SmartTextMapVectorizerTest,
TextMapPivotVectorizerTest, MultiPickListMapVectorizerTest,
DateMapToUnitCircleVectorizerTest, GeolocationMapVectorizerTest in the
reference core test suite)."""

import numpy as np
import pytest

from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Column, ColumnBatch, numeric_column, object_column
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.ops.map_vectorizers import (
    DateMapToUnitCircleVectorizer, GeolocationMapVectorizer,
    MultiPickListMapVectorizer, SmartTextMapVectorizer, TextMapLenEstimator,
    TextMapNullEstimator, TextMapPivotVectorizer)


def make_batch(name, kind, maps):
    return ColumnBatch({name: object_column(kind, maps)}, len(maps))


def fit_transform(stage, feat, batch):
    stage.set_input(feat)
    stage.get_output()
    model = stage.fit(batch)
    return model, np.asarray(model.transform(batch).values)


def test_smart_text_map_pivot_and_hash():
    # key "cat" is low-cardinality → pivot; key "desc" is high-cardinality → hash
    maps = [{"cat": ("a" if i % 2 else "b"), "desc": f"unique text {i} {i*7}"}
            for i in range(40)]
    maps[0] = {}  # one empty row → nulls for both keys
    f = FeatureBuilder.TextMap("m").as_predictor()
    st = SmartTextMapVectorizer(max_cardinality=5, top_k=10, min_support=1,
                                num_hashes=16)
    model, arr = fit_transform(st, f, make_batch("m", T.TextMap, maps))
    assert model.metadata["strategies"]["m"] == {"cat": "pivot", "desc": "hash"}
    # widths: pivot = 2 values + OTHER + null = 4; hash = 16 + null
    assert arr.shape == (40, 4 + 17)
    meta = model.fitted["meta"]
    assert len(meta.columns) == arr.shape[1]
    # row 0 (empty map): null indicators set
    assert arr[0, 3] == 1.0  # pivot null
    assert arr[0, -1] == 1.0  # hash null
    # pivot one-hots: 'a' and 'b' sorted → col0='a', col1='b'
    assert arr[1, 0] == 1.0  # i=1 → 'a'
    assert arr[2, 1] == 1.0  # i=2 → 'b'


def test_text_map_pivot_vectorizer_values():
    maps = [{"k1": "x"}, {"k1": "y"}, {"k1": "x"}, {}]
    f = FeatureBuilder.PickListMap("m").as_predictor()
    st = TextMapPivotVectorizer(top_k=5, min_support=1)
    model, arr = fit_transform(st, f, make_batch("m", T.PickListMap, maps))
    # one key, 2 values + OTHER + null
    assert arr.shape == (4, 4)
    np.testing.assert_allclose(arr[0], [1, 0, 0, 0])
    np.testing.assert_allclose(arr[1], [0, 1, 0, 0])
    np.testing.assert_allclose(arr[3], [0, 0, 0, 1])
    # unseen value at transform → OTHER
    b2 = make_batch("m", T.PickListMap, [{"k1": "zzz"}])
    arr2 = np.asarray(model.transform(b2).values)
    np.testing.assert_allclose(arr2[0], [0, 0, 1, 0])


def test_multi_picklist_map_vectorizer():
    maps = [{"k": {"a", "b"}}, {"k": {"b"}}, {}, {"k": set()}]
    f = FeatureBuilder.MultiPickListMap("m").as_predictor()
    st = MultiPickListMapVectorizer(top_k=5, min_support=1)
    model, arr = fit_transform(st, f, make_batch("m", T.MultiPickListMap, maps))
    # pivot layout = (count desc, value asc) like the reference:
    # b appears twice, a once -> [b, a, OTHER, null]
    assert arr.shape == (4, 4)
    np.testing.assert_allclose(arr[0], [1, 1, 0, 0])
    np.testing.assert_allclose(arr[1], [1, 0, 0, 0])
    np.testing.assert_allclose(arr[2], [0, 0, 0, 1])
    np.testing.assert_allclose(arr[3], [0, 0, 0, 1])  # empty set = null


def test_date_map_unit_circle():
    ms_noon = 12 * 3600 * 1000  # noon epoch-day-0 → HourOfDay angle pi
    maps = [{"d": ms_noon}, {"d": 0}, {}]
    f = FeatureBuilder.DateMap("m").as_predictor()
    st = DateMapToUnitCircleVectorizer(time_period="HourOfDay")
    model, arr = fit_transform(st, f, make_batch("m", T.DateMap, maps))
    assert arr.shape == (3, 2)
    np.testing.assert_allclose(arr[0], [np.sin(np.pi), np.cos(np.pi)], atol=1e-5)
    np.testing.assert_allclose(arr[1], [0.0, 1.0], atol=1e-5)
    np.testing.assert_allclose(arr[2], [0.0, 0.0], atol=1e-5)  # missing → 0


def test_geolocation_map_vectorizer_mean_fill():
    maps = [{"home": [37.0, -122.0, 5.0]}, {"home": [39.0, -120.0, 5.0]}, {}]
    f = FeatureBuilder.GeolocationMap("m").as_predictor()
    st = GeolocationMapVectorizer()
    model, arr = fit_transform(st, f, make_batch("m", T.GeolocationMap, maps))
    assert arr.shape == (3, 4)  # lat, lon, acc, null
    np.testing.assert_allclose(arr[2, :3], [38.0, -121.0, 5.0], atol=1e-5)
    assert arr[2, 3] == 1.0 and arr[0, 3] == 0.0


def test_text_map_null_and_len():
    maps = [{"a": "hello", "b": "x"}, {"a": None}, {}]
    f = FeatureBuilder.TextMap("m").as_predictor()
    st = TextMapNullEstimator()
    model, arr = fit_transform(st, f, make_batch("m", T.TextMap, maps))
    assert arr.shape == (3, 2)  # keys a, b
    np.testing.assert_allclose(arr, [[0, 0], [1, 1], [1, 1]])

    st2 = TextMapLenEstimator()
    st2.set_input(f)
    st2.get_output()
    m2 = st2.fit(make_batch("m", T.TextMap, maps))
    arr2 = np.asarray(m2.transform(make_batch("m", T.TextMap, maps)).values)
    np.testing.assert_allclose(arr2, [[5, 1], [0, 0], [0, 0]])


def test_map_vectorizers_empty_batch_and_save_load(tmp_path):
    from transmogrifai_tpu.stages.serialization import (stage_from_json,
                                                        stage_to_json)
    maps = [{"k": "v%d" % (i % 3)} for i in range(10)]
    f = FeatureBuilder.TextMap("m").as_predictor()
    st = SmartTextMapVectorizer(max_cardinality=5, min_support=1)
    model, arr = fit_transform(st, f, make_batch("m", T.TextMap, maps))
    # transform on a fresh batch of empty maps still has fitted width
    b_empty = make_batch("m", T.TextMap, [{}, {}])
    arr_e = np.asarray(model.transform(b_empty).values)
    assert arr_e.shape == (2, arr.shape[1])


def test_e2e_workflow_with_map_features():
    """PassengerDataAll-style flow: numeric + text-map + picklist-map +
    geolocation-map predictors through transmogrify → selector → train."""
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(0)
    n = 200
    age = rng.uniform(18, 80, n).astype(np.float32)
    group = ["g%d" % (i % 3) for i in range(n)]
    y = ((age > 45) ^ (np.arange(n) % 3 == 0)).astype(np.float32)
    desc_maps = [{"group": group[i], "note": f"note {i}"} for i in range(n)]
    pick_maps = [{"tier": "gold" if y[i] else "silver"} for i in range(n)]
    geo_maps = [{"home": [37.0 + float(y[i]), -122.0, 1.0]} for i in range(n)]

    label = FeatureBuilder.RealNN("label").as_response()
    f_age = FeatureBuilder.Real("age").as_predictor()
    f_desc = FeatureBuilder.TextMap("desc").as_predictor()
    f_pick = FeatureBuilder.PickListMap("pick").as_predictor()
    f_geo = FeatureBuilder.GeolocationMap("geo").as_predictor()

    fv = transmogrify([f_age, f_desc, f_pick, f_geo], min_support=1)
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(), grid(reg_param=[0.01]),
                       "OpLogisticRegression")])
    sel.set_input(label, fv)
    pred = sel.get_output()

    batch = ColumnBatch({
        "label": numeric_column(T.RealNN, y),
        "age": numeric_column(T.Real, age),
        "desc": object_column(T.TextMap, desc_maps),
        "pick": object_column(T.PickListMap, pick_maps),
        "geo": object_column(T.GeolocationMap, geo_maps),
    }, n)
    model = Workflow().set_input_batch(batch).set_result_features(pred).train()
    from transmogrifai_tpu.evaluators import Evaluators
    m = model.evaluate(Evaluators.BinaryClassification.auROC(), batch=batch)
    assert m["AuROC"] > 0.95  # tier/geo encode the label


# ---------------------------------------------------------------------------
# String-valued maps and sets: one expansion a column, a staged form fused
# into the transform program, the eager transform the staged form run
# eagerly; all three against plain numpy
# ---------------------------------------------------------------------------

VALUES = ["a", "b", "c", "é", "ünï", "z z", "1", "2"]


def random_maps(seed, n=600, keys=7):
    """Maps with every edge case: None rows, empty maps, absent keys, None
    values, non-ASCII and numeric values, keys of equal counts."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        r = rng.random()
        if r < 0.05:
            out.append(None)
            continue
        if r < 0.1:
            out.append({})
            continue
        m = {}
        for k in range(keys):
            if rng.random() < 0.8 - 0.1 * k:
                v = VALUES[rng.integers(len(VALUES))] if rng.random() < 0.8 \
                    else f"v{rng.integers(40)}"
                m[f"k{k}"] = None if rng.random() < 0.05 else (
                    int(v) if v.isdigit() else v)
        out.append(m)
    # two keys held by exactly as many rows: the cut breaks the tie by key
    out[0] = dict(out[0] or {}, tie_b="a")
    out[1] = dict(out[1] or {}, tie_a="a")
    return out


def random_sets(seed, n=600):
    """Sets with every edge case: None, empty, duplicates in a list, values
    of a set of only unseen values, non-ASCII and numeric values."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        r = rng.random()
        if r < 0.05:
            out.append(None)
        elif r < 0.1:
            out.append([])
        elif r < 0.12:
            out.append([f"rare{i}", f"rare{i + 1}"])     # only unseen values
        else:
            s = [VALUES[j] for j in rng.integers(0, len(VALUES),
                                                 rng.integers(1, 5))]
            out.append(s + s[:1] if rng.random() < 0.2 else (
                tuple(s) if rng.random() < 0.1 else s))
    out[2] = [3, "3"]          # one value by its str
    return out


def plain_top(counts, top_k, min_support):
    kept = sorted((v for v, c in counts.items() if c >= min_support),
                  key=lambda v: (-counts[v], v))[:top_k]
    return {v: i for i, v in enumerate(kept)}


def plain_map_pivot(maps, top_k, min_support, max_keys):
    """(matrix, [(key, value or OTHER/null)]) by the stated rule, in
    plain Python and numpy."""
    n = len(maps)
    held = {}
    for m in maps:
        for k in (m or {}):
            held[k] = held.get(k, 0) + 1
    keys = sorted(sorted(held, key=lambda k: (-held[k], k))[:max_keys])
    blocks, names = [], []
    for k in keys:
        counts = {}
        for m in maps:
            v = (m or {}).get(k)
            if v is not None:
                counts[str(v)] = counts.get(str(v), 0) + 1
        vocab = plain_top(counts, top_k, min_support)
        block = np.zeros((n, len(vocab) + 2), np.float32)
        for i, m in enumerate(maps):
            v = (m or {}).get(k)
            block[i, len(vocab) + 1 if v is None
                  else vocab.get(str(v), len(vocab))] = 1.0
        blocks.append(block)
        names += [(k, v) for v in vocab] + [(k, "OTHER"), (k, "NullIndicatorValue")]
    return np.concatenate(blocks, axis=1), names


def plain_set_pivot(sets, top_k, min_support):
    n = len(sets)
    counts = {}
    for s in sets:
        for v in {str(v) for v in (s or ())}:
            counts[v] = counts.get(v, 0) + 1
    vocab = plain_top(counts, top_k, min_support)
    block = np.zeros((n, len(vocab) + 2), np.float32)
    for i, s in enumerate(sets):
        if not s:
            block[i, -1] = 1.0
        for v in (s or ()):
            block[i, vocab.get(str(v), len(vocab))] = 1.0
    return block, list(vocab) + ["OTHER", "NullIndicatorValue"]


def staged_and_eager(model, batch):
    import jax
    wire, body = model.transform_staged(batch)
    staged = np.asarray(jax.jit(lambda w: body(w).values)(wire))
    eager = np.asarray(model.transform(batch).values)
    return staged, eager


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_text_map_pivot_staged_equals_eager_equals_plain(seed):
    from transmogrifai_tpu.ops.map_vectorizers import \
        TextMapPivotVectorizerModel
    maps = random_maps(seed)
    f = FeatureBuilder.PickListMap("m").as_predictor()
    st = TextMapPivotVectorizer(top_k=4, min_support=3, max_keys=5)
    batch = make_batch("m", T.PickListMap, maps)
    st.set_input(f)
    st.get_output()
    model = st.fit(batch)
    assert isinstance(model, TextMapPivotVectorizerModel)
    assert model.supports_staging and not model.is_device_op
    want, names = plain_map_pivot(maps, 4, 3, 5)
    staged, eager = staged_and_eager(model, batch)
    np.testing.assert_array_equal(staged, want)
    np.testing.assert_array_equal(eager, want)
    meta = model.fitted["meta"].columns
    assert [(c.grouping, c.indicator_value) for c in meta] == names
    assert all(c.parent_feature_name == "m" for c in meta)
    # unseen values and keys of another batch: OTHER and null
    other = make_batch("m", T.PickListMap, [{"k0": "never"}, {"new": "a"}])
    staged, eager = staged_and_eager(model, other)
    np.testing.assert_array_equal(staged, eager)
    keys = model.fitted["per_feature"]["m"]["keys"]
    at = 0
    for k in keys:
        width = len(model.fitted["per_feature"]["m"]["vocabs"][k]) + 2
        assert eager[0, at + width - 2] == (1.0 if k == "k0" else 0.0)
        assert eager[1, at + width - 1] == 1.0
        at += width


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_multi_pick_list_staged_equals_eager_equals_plain(seed):
    from transmogrifai_tpu.ops.collections import (
        MultiPickListVectorizer, MultiPickListVectorizerModel)
    sets = random_sets(seed)
    f = FeatureBuilder.MultiPickList("s").as_predictor()
    st = MultiPickListVectorizer(top_k=5, min_support=4)
    batch = make_batch("s", T.MultiPickList, sets)
    st.set_input(f)
    st.get_output()
    model = st.fit(batch)
    assert isinstance(model, MultiPickListVectorizerModel)
    assert model.supports_staging and not model.is_device_op
    want, names = plain_set_pivot(sets, 5, 4)
    staged, eager = staged_and_eager(model, batch)
    np.testing.assert_array_equal(staged, want)
    np.testing.assert_array_equal(eager, want)
    assert [c.indicator_value for c in model.fitted["meta"].columns] == names
    # a set of only unseen values sets OTHER once; duplicates count once
    assert want[3 if sets[3] and sets[3][0].startswith("rare") else 0].max() \
        == 1.0
    assert eager.max() == 1.0


def test_a_set_vocabulary_counts_a_value_once_a_row_by_its_str():
    from transmogrifai_tpu.ops.collections import MultiPickListVectorizer
    sets = [["x", "x", "y"]] * 3 + [[3], ["3"], ("y",)] + [None, []]
    f = FeatureBuilder.MultiPickList("s").as_predictor()
    st = MultiPickListVectorizer(top_k=5, min_support=1)
    st.set_input(f)
    st.get_output()
    model = st.fit(make_batch("s", T.MultiPickList, sets))
    # y: 4 rows, x: 3 (not 6), "3": 2 (an int and its str)
    assert model.fitted["vocabs"]["s"] == {"y": 0, "x": 1, "3": 2}
    arr = np.asarray(model.transform(
        make_batch("s", T.MultiPickList, sets)).values)
    np.testing.assert_array_equal(arr[0], [1, 1, 0, 0, 0])
    np.testing.assert_array_equal(arr[3], [0, 0, 1, 0, 0])
    np.testing.assert_array_equal(arr[6:], [[0, 0, 0, 0, 1]] * 2)


def test_keys_are_cut_by_rows_then_by_key():
    """More keys than ``max_keys`` with ties at the cut: the rows that hold
    a key (descending), then the key, whatever order the rows show them
    in; the kept keys are laid out by key."""
    from transmogrifai_tpu.ops.map_vectorizers import _discover_keys
    maps = [{"d": "x", "c": "x", "b": "x"}, {"a": "x", "e": "x"},
            {"e": "x", "z": "x"}, {"z": "x", "y": "x"}]
    # e and z in two rows, then a, b, c, d, y in one each: "a" before the
    # first seen "d"
    assert _discover_keys(maps, 3) == ["a", "e", "z"]
    assert _discover_keys(maps, 4) == ["a", "b", "e", "z"]
    assert _discover_keys(list(reversed(maps)), 4) == ["a", "b", "e", "z"]
    f = FeatureBuilder.PickListMap("m").as_predictor()
    st = TextMapPivotVectorizer(top_k=2, min_support=1, max_keys=4)
    st.set_input(f)
    st.get_output()
    model = st.fit(make_batch("m", T.PickListMap, maps))
    assert model.fitted["per_feature"]["m"]["keys"] == ["a", "b", "e", "z"]


@pytest.mark.parametrize("seed", [0, 3])
def test_native_and_python_expansions_agree(seed):
    from transmogrifai_tpu.ops import map_profile as mp
    maps = np.asarray(random_maps(seed) + [None], dtype=object)[:-1]
    native = mp.expand_string_maps(maps)
    python = mp._py_expand_strings(maps)
    assert native.keys == python.keys
    assert native.keys == mp.key_order(dict(zip(native.keys,
                                                native.in_dict.tolist())))
    for j in range(len(native.keys)):
        assert native.value_counts(j) == python.value_counts(j)
        assert [native.uniq[j][i] if i >= 0 else None
                for i in native.ids[j]] == \
            [python.uniq[j][i] if i >= 0 else None for i in python.ids[j]]
    np.testing.assert_array_equal(native.nonempty, python.nonempty)
    assert native.inexact == python.inexact == 0
    sets = np.asarray(random_sets(seed) + [None], dtype=object)[:-1]
    native, python = mp.expand_sets(sets), mp._py_expand_sets(sets)
    assert native.value_counts() == python.value_counts()
    np.testing.assert_array_equal(native.offsets, python.offsets)
    assert [native.uniq[i] for i in native.ids] == \
        [python.uniq[i] for i in python.ids]
    assert dict(zip(native.uniq, native.items.tolist())) == \
        dict(zip(python.uniq, python.items.tolist()))
    np.testing.assert_array_equal(native.nonempty, python.nonempty)


def test_odd_rows_are_empty_maps_and_inexact():
    """A row that is no dict reads as an empty map (as the vectorizers
    always read it) and marks the expansion inexact, so that
    RawFeatureFilter walks the rows; a list value is its str."""
    from transmogrifai_tpu.ops import map_profile as mp
    maps = np.asarray([{"k": "a"}, "odd", {"k": ["x", "y"]}, None], object)
    for exp in (mp.expand_string_maps(maps), mp._py_expand_strings(maps)):
        assert exp.keys == ["k"] and exp.inexact == 2
        assert exp.value_counts(0) == {"a": 1, "['x', 'y']": 1}
        np.testing.assert_array_equal(exp.nonempty, [True, False, True,
                                                     False])
    # a frozenset and a string row go the Python walk, which iterates them
    # as the vectorizer's row loop always did
    sets = np.asarray([frozenset({"a"}), ["a"], "ab"], object)
    exp = mp.expand_sets(sets)
    assert exp.inexact == 2 and exp.value_counts() == {"a": 3, "b": 1}


def rff_distributions(kind, values, walk, monkeypatch):
    from transmogrifai_tpu import filters
    from transmogrifai_tpu.features import features_from_schema
    if walk:
        monkeypatch.setattr(filters, "_expansion", lambda col: None)
    col = object_column(kind, values)
    _, (f,) = features_from_schema({"y": T.RealNN, "m": kind}, response="y")
    dists = filters.compute_distribution(f, col, 100, 255)
    return [(d.key, d.count, d.nulls, d.distribution.tolist())
            for d in dists], filters._value_presence(col).tolist()


@pytest.mark.parametrize("kind", ["map", "set", "empty map column"])
def test_rff_distributions_from_the_expansion_equal_the_row_walk(
        kind, monkeypatch):
    from transmogrifai_tpu.telemetry import REGISTRY
    values, k = {"map": (random_maps(4), T.PickListMap),
                 "set": (random_sets(4), T.MultiPickList),
                 "empty map column": ([None, {}] * 5, T.PickListMap)}[kind]
    before = REGISTRY.counter("rff.python_rows").value
    fast = rff_distributions(k, values, False, monkeypatch)
    assert REGISTRY.counter("rff.python_rows").value == before
    assert fast == rff_distributions(k, values, True, monkeypatch)


def test_an_empty_set_or_tuple_is_null_to_the_filter():
    from transmogrifai_tpu import filters
    col = object_column(T.MultiPickList, [set(), (), ["a"], None, {"b"}])
    assert filters._value_presence(col).tolist() == [False, False, True,
                                                     False, True]
    frozen = object_column(T.MultiPickList, [frozenset(), frozenset("a")])
    assert filters._value_presence(frozen).tolist() == [False, True]


def test_a_small_train_over_maps_and_sets_runs_no_host_stage():
    """transmogrify → Workflow.train() over a PickListMap and a
    MultiPickList: no stage between compiled segments, no row walked in
    Python, and the map and set pivots in the fused program."""
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.selector import (BinaryClassificationModelSelector,
                                            ModelCandidate, grid)
    from transmogrifai_tpu.telemetry import REGISTRY
    from transmogrifai_tpu.workflow import Workflow

    n = 400
    maps, sets = random_maps(5, n), random_sets(5, n)
    y = np.asarray([float(bool(m) and m.get("k0") == "a") for m in maps],
                   np.float32)
    label = FeatureBuilder.RealNN("label").as_response()
    fm = FeatureBuilder.PickListMap("m").as_predictor()
    fs = FeatureBuilder.MultiPickList("s").as_predictor()
    fv = transmogrify([fm, fs], min_support=2)
    sel = BinaryClassificationModelSelector(models=[
        ModelCandidate(OpLogisticRegression(), grid(reg_param=[0.01]),
                       "OpLogisticRegression")])
    sel.set_input(label, fv)
    batch = ColumnBatch({"label": numeric_column(T.RealNN, y),
                         "m": object_column(T.PickListMap, maps),
                         "s": object_column(T.MultiPickList, sets)}, n)
    counters = ("transform.host_stages", "rff.python_rows")
    before = {c: REGISTRY.counter(c).value for c in counters}
    model = (Workflow().set_input_batch(batch)
             .set_result_features(sel.get_output())
             .with_raw_feature_filter(min_fill_rate=0.001).train())
    assert {c: REGISTRY.counter(c).value - before[c] for c in counters} == {
        "transform.host_stages": 0, "rff.python_rows": 0}
    assert not [e for e in model.failure_log.events
                if e.action in ("demoted", "degraded", "fallback")]
