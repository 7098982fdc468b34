"""A pivot's fit and id wire read the column's interning as arrays
(``ops/categorical.py``: ``top_value_indices`` over the interned counts, the
code → vocabulary-position table by native/textprof.cpp ``lookup``): the
vocabulary, its order, its OTHER and null slots and the wire are those of the
per-value Python forms they replace, value for value and byte for byte, and
the counter ``pivot.python_values`` says where Python still handled values
one by one."""

from collections import Counter

import numpy as np
import pytest

from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Column, ColumnBatch
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.native import load
from transmogrifai_tpu.ops.categorical import (OneHotEstimator,
                                               encode_column,
                                               encode_with_vocab,
                                               top_value_indices,
                                               top_values_by_count)
from transmogrifai_tpu.ops.text_profile import column_profile
from transmogrifai_tpu.telemetry import REGISTRY


def dict_top(counts, top_k, min_support):
    """The selection as it was: every eligible item sorted in Python."""
    eligible = [(v, c) for v, c in counts.items() if c >= min_support]
    eligible.sort(key=lambda vc: (-vc[1], vc[0]))
    return [v for v, _ in eligible[:top_k]]


def dict_get_ids(col, vocab, other_id):
    """The id wire as it was: a ``dict.get`` a distinct value."""
    iv = column_profile(col).values(-1)
    if not iv.uniq:
        return np.full(len(iv.codes), other_id + 1, np.int32)
    table = np.fromiter((vocab.get(v, other_id) for v in iv.uniq), np.int32,
                        count=len(iv.uniq))
    return np.where(iv.codes < 0, np.int32(other_id + 1),
                    table[np.maximum(iv.codes, 0)]).astype(np.int32)


def column_of(counts, nulls=0, seed=0):
    """An object column holding each value ``counts[value]`` times and
    ``nulls`` Nones, shuffled."""
    rows = [v for v, c in counts.items() for _ in range(c)] + [None] * nulls
    arr = np.empty(len(rows), dtype=object)
    arr[:] = rows
    return Column(T.PickList, arr[np.random.default_rng(seed).permutation(
        len(rows))])


def python_values():
    return REGISTRY.counters().get("pivot.python_values", 0)


def fitted(col, top_k=20, min_support=10):
    f = FeatureBuilder.PickList("c").as_predictor()
    est = OneHotEstimator(top_k=top_k, min_support=min_support).set_input(f)
    return est.fit(ColumnBatch({"c": col}, len(col)))


TOP_CASES = {
    # (counts, nulls, top_k, min_support)
    "ties_at_the_kth_count": (
        {"a": 12, "b": 11, **{f"t{i:02d}": 9 for i in range(30)}, "c": 3},
        5, 6, 2),
    "ties_broken_only_by_value": (
        {v: 7 for v in ("delta", "alpha", "echo", "charlie", "bravo")},
        0, 3, 1),
    "counts_exactly_at_min_support": (
        {"x": 10, "y": 10, "z": 9, "w": 11, "v": 30}, 4, 20, 10),
    "fewer_eligible_than_k": ({"p": 15, "q": 12, "r": 2}, 3, 20, 10),
    "no_eligible_value": ({"p": 9, "q": 1, "r": 5}, 2, 20, 10),
    "all_null": ({}, 25, 20, 10),
    "non_ascii_case_and_prefixes": (
        {"é": 4, "E": 4, "e": 4, "école": 6, "École": 6, "ÉCOLE": 6,
         "ab": 5, "abc": 5, "a": 5, "Ab": 5, "": 5, "日本": 4},
        3, 8, 4),
    "wider_than_a_byte": ({f"v{i:03d}": 10 + i % 7 for i in range(300)},
                          5, 300, 10),
}


@pytest.mark.parametrize("case", sorted(TOP_CASES))
def test_index_top_k_equals_the_dict_form(case):
    counts, nulls, top_k, min_support = TOP_CASES[case]
    col = column_of(counts, nulls)
    iv = column_profile(col).values(-1)
    got = [iv.uniq[i] for i in top_value_indices(iv.uniq, iv.counts, top_k,
                                                 min_support)]
    want = dict_top(Counter(counts), top_k, min_support)
    assert got == want
    assert top_values_by_count(Counter(counts), top_k, min_support) == want
    assert dict_top(iv.value_counts(), top_k, min_support) == want
    model = fitted(col, top_k, min_support)
    assert list(model.fitted["vocabs"]["c"]) == want
    assert list(model.fitted["vocabs"]["c"].values()) == list(range(len(want)))
    slots = [c.indicator_value for c in model.fitted["meta"].columns]
    assert slots == want + ["OTHER", "NullIndicatorValue"]


def test_top_k_of_zero_and_below_slices_as_the_dict_form_did():
    counts = Counter({"a": 5, "b": 4, "c": 3, "d": 3})
    for top_k in (0, -1, -3):
        assert top_values_by_count(counts, top_k, 1) == dict_top(counts,
                                                                 top_k, 1)


def no_native(monkeypatch):
    import transmogrifai_tpu.native as nat
    monkeypatch.setitem(nat._CACHE, "textprof", None)


def scoring_column(vocab):
    """Seen values, unseen ones and nulls, in a column of its own."""
    seen = list(vocab)[::2]
    return column_of({**{v: 3 for v in seen}, "never seen": 4, "Z": 2,
                      "": 1}, nulls=5, seed=7)


WIRE_CASES = ("fit_column", "scoring_column", "not_held_as_strings",
              "without_the_native_helper", "wider_than_a_byte")


@pytest.mark.parametrize("case", WIRE_CASES)
def test_id_wire_equals_the_dict_get_path(case, monkeypatch):
    counts, nulls, top_k, min_support = TOP_CASES[
        "wider_than_a_byte" if case == "wider_than_a_byte"
        else "non_ascii_case_and_prefixes"]
    if case == "without_the_native_helper":
        no_native(monkeypatch)
    col = column_of(counts, nulls)
    if case == "not_held_as_strings":
        # a fixed-width str array with a validity mask: the Counter path
        held = col.values != None          # noqa: E711 — elementwise
        col = Column(T.PickList, np.where(held, col.values, "").astype(str),
                     mask=held)
    model = fitted(col, top_k, min_support)
    vocab = model.fitted["vocabs"]["c"]
    if case == "scoring_column":
        col = scoring_column(vocab)
    other = len(vocab)
    strings = (col.values if col.is_host_object()
               else np.where(col.mask, col.values.astype(object), None))
    want = encode_with_vocab(strings, vocab, other)
    got = encode_column(col, vocab, other)
    assert got.dtype == want.dtype == np.int32
    assert got.tobytes() == want.tobytes()
    if not col.is_host_object():
        assert model.column_wire(0, col) is None       # the eager path
        return
    assert got.tobytes() == dict_get_ids(col, vocab, other).tobytes()
    wire = model.column_wire(0, col)["ids0"]
    narrow = want.astype(np.uint8) if other + 1 < 256 else want
    assert wire.dtype == narrow.dtype
    assert wire.dtype == (np.uint8 if case != "wider_than_a_byte"
                          else np.int32)
    assert wire.tobytes() == narrow.tobytes()


@pytest.mark.parametrize("path", ["native", "without_the_native_helper",
                                  "not_held_as_strings"])
def test_python_values_counts_what_python_handled(path, monkeypatch):
    """0 where the fit and the wire read the interning as arrays; the
    distinct values where the wire's table falls back to ``dict.get``; the
    distinct values of the fit's Counter and a row each of the wire where
    the column is not held as strings."""
    counts, nulls, top_k, min_support = TOP_CASES[
        "non_ascii_case_and_prefixes"]
    if path == "without_the_native_helper":
        no_native(monkeypatch)
    col = column_of(counts, nulls)
    if path == "not_held_as_strings":
        held = col.values != None          # noqa: E711 — elementwise
        col = Column(T.PickList, np.where(held, col.values, "").astype(str),
                     mask=held)
    before = python_values()
    model = fitted(col, top_k, min_support)
    fit_counted = python_values() - before
    vocab = model.fitted["vocabs"]["c"]
    encode_column(col, vocab, len(vocab))
    wire_counted = python_values() - before - fit_counted
    assert (fit_counted, wire_counted) == {
        "native": (0, 0),
        "without_the_native_helper": (0, len(counts)),
        "not_held_as_strings": (len(counts), len(col)),
    }[path]


@pytest.mark.parametrize("batch_kind", ["fit", "scoring"])
def test_smart_text_pivot_wire_is_unchanged(batch_kind):
    """SmartTextVectorizer's pivots encode through the same table: their
    staged ids are the per-row ``dict.get``'s, on the fit's column and on
    one with unseen values and nulls."""
    from transmogrifai_tpu.ops.text import SmartTextVectorizer
    counts = {"red": 40, "green": 25, "blue": 25, "Blue": 12, "teal": 3}
    col = column_of(counts, nulls=6, seed=3)
    f = FeatureBuilder.Text("t").as_predictor()
    st = SmartTextVectorizer(num_hashes=64, max_cardinality=10, top_k=3,
                             min_support=5).set_input(f)
    model = st.fit(ColumnBatch({"t": Column(T.Text, col.values)}, len(col)))
    assert model.fitted["strategies"]["t"] == "pivot"
    vocab = model.fitted["vocabs"]["t"]
    assert list(vocab) == dict_top(Counter(counts), 3, 5)
    if batch_kind == "scoring":
        col = scoring_column(vocab)
    before = python_values()
    wire, _ = model.transform_staged(
        ColumnBatch({"t": Column(T.Text, col.values)}, len(col)))
    assert python_values() == before
    want = encode_with_vocab(col.values, vocab, len(vocab)).astype(np.uint8)
    assert wire["ids0"].dtype == np.uint8
    assert wire["ids0"].tobytes() == want.tobytes()


def test_native_lookup_finds_each_value_in_the_table():
    native = load("textprof")
    if native is None:
        pytest.skip("no native toolchain")
    values = ["b", None, "a", "zz", "é", "", "A", "ab", "b"]
    table = ["a", "b", "", "é", "b"]          # a repeat keeps its first id
    got = native.lookup(values, table)
    assert got.dtype == np.int32
    assert got.tolist() == [1, -1, 0, -2, 3, 2, -2, -2, 1]
    assert native.lookup(tuple(values), []).tolist() == [
        -2, -1, -2, -2, -2, -2, -2, -2, -2]
    assert native.lookup([], table).tolist() == []
    with pytest.raises(TypeError):
        native.lookup(values, ["a", 3])
    for not_a_list in (None, np.asarray(values, dtype=object)):
        with pytest.raises(TypeError):
            native.lookup(not_a_list, table)
