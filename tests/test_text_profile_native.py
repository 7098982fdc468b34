"""The native text profile (native/textprof.cpp via ops/text_profile.py):
one walk of a column's object array, read in place, gives what ``_py_scan``
gives and — with a cap — what ``_py_intern`` gives, array for array; a
batch's columns walked side by side give the same profiles in feature order;
the counters say which of this happened."""

import os
import sys
import threading

import numpy as np
import pytest

from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Column, ColumnBatch
from transmogrifai_tpu.native import load
from transmogrifai_tpu.ops import text as text_ops
from transmogrifai_tpu.ops import text_profile as tp
from transmogrifai_tpu.telemetry import REGISTRY, Tracer, use_tracer

pytestmark = pytest.mark.skipif(load("textprof") is None,
                                reason="no native toolchain")

SCAN_FIELDS = ("null", "empty", "lengths", "crc", "tok_lens", "tok_hash")
COUNTERS = ("text_profile.scan", "text_profile.fused_intern",
            "text_profile.intern.hit", "text_profile.intern.miss")


def _column(values) -> np.ndarray:
    arr = np.empty(len(values), dtype=object)
    arr[:] = values
    return arr


def _ascii(n=400):
    # 1, 7, 8, 9, 16, 17 and 40 bytes: every tail of the 8-byte CRC step
    pool = ["x", "seven_b", "eight_by", "nine_byte", "sixteen bytes ok",
            "seventeen bytes s", "a much longer value, with 40 bytes in it",
            "it's", "a b c", "tab\tsep,comma;semi"]
    return _column([pool[(i * i + i // 3) % len(pool)] + str(i % 23)
                    for i in range(n)])


CASES = {
    "ascii": (_ascii(), 1),
    "non_ascii_row": (_column(["plain", "Ünïcode tøken K", "plain", "É",
                               "日本語 テキスト", "after"] * 20), 1),
    "nones": (_column([None, "a", None, None, "b", "a", None] * 30), 1),
    "empty_strings": (_column(["", "a", "", None, "b b", ""] * 30), 1),
    "upper_case": (_column(["Mixed CASE Words", "mixed case words", "ABC",
                            "abc", "AbC dEf"] * 30), 1),
    "min_token_len_2": (_column(["a bb ccc d", "x", "yy", "it's a b",
                                 "Ü bb c"] * 30), 2),
    "all_null": (_column([None] * 50), 1),
    "zero_rows": (np.empty(0, dtype=object), 1),
}


def _assert_scan_equal(prof, ref):
    for f in SCAN_FIELDS:
        a, b = getattr(prof, f), getattr(ref, f)
        assert a.dtype == b.dtype and np.array_equal(a, b), f


def _assert_interned_equal(iv, ref):
    assert iv.uniq == ref.uniq
    assert iv.counts.dtype == ref.counts.dtype == np.int64
    assert iv.codes.dtype == ref.codes.dtype == np.int32
    assert np.array_equal(iv.counts, ref.counts)
    assert np.array_equal(iv.codes, ref.codes)
    assert iv.frozen == ref.frozen and iv.cap == ref.cap


def _counters():
    c = REGISTRY.counters()
    return {k: c.get(k, 0) for k in COUNTERS}


def _moved(before):
    return {k.split("text_profile.")[1]: v - before[k]
            for k, v in _counters().items() if v != before[k]}


@pytest.mark.parametrize("cap", [None, -1, 0, 3, 30])
@pytest.mark.parametrize("case", list(CASES))
def test_fused_pass_equals_python_scan_and_intern(case, cap):
    arr, min_len = CASES[case]
    prof = tp.scan_strings(arr, min_len, cap)
    _assert_scan_equal(prof, tp._py_scan(arr, min_len))
    assert prof._strings is arr
    if cap is None:
        assert prof._interned == {}
        return
    assert list(prof._interned) == [cap]
    _assert_interned_equal(prof._interned[cap], tp._py_intern(arr, cap))
    # the in-place intern a cache miss falls to: the same answer
    _assert_interned_equal(tp._intern(arr, cap), tp._py_intern(arr, cap))


def test_non_ascii_rows_are_lowered_by_python_and_hashed_natively():
    """The splice of the rows the walk leaves to Python: every code point
    whose lowering is ASCII or longer than itself (the Kelvin sign is 'k',
    'İ' is 'i' and a combining dot), and every 211th of the others, between
    ASCII letters: tokens and hashes are the Python tokenizer's."""
    points = [cp for cp in range(0x80, 0x110000)
              if not 0xD800 <= cp <= 0xDFFF
              and (cp % 211 == 0 or len(chr(cp).lower()) > 1
                   or chr(cp).lower().isascii())]
    assert 0x212A in points and 0x130 in points
    arr = _column([f"Ab{chr(cp)}Cd {chr(cp)}e'F_{chr(cp)}" for cp in points])
    before = REGISTRY.counters().get("text.rows_python_tokenized", 0)
    for min_len in (1, 3):
        _assert_scan_equal(tp.scan_strings(arr, min_len),
                           tp._py_scan(arr, min_len))
    assert REGISTRY.counters()["text.rows_python_tokenized"] \
        == before + 2 * len(points)


def test_blocks_strides_and_what_the_walk_refuses():
    native = load("textprof")
    rng = np.random.default_rng(0)
    pool = _column(["", None, "Ünï", "it's"]
                   + [f"v{i:05d} w{i % 7}" for i in range(3000)])
    # more rows than one block holds (65,536), and every other one of them
    big = pool[rng.integers(0, len(pool), size=140_001)]
    for arr in (big, big[::2]):
        prof = tp.scan_strings(arr, 1, 30)
        _assert_scan_equal(prof, tp._py_scan(arr))
        _assert_interned_equal(prof._interned[30], tp._py_intern(arr, 30))
        _assert_interned_equal(tp._intern(arr, -1), tp._py_intern(arr, -1))
    assert not big[::2].flags.c_contiguous
    with pytest.raises(TypeError):
        native.profile(["a", "b"])                   # a list: not read
    with pytest.raises(TypeError):
        native.intern(np.zeros((2, 2), dtype=object))
    with pytest.raises(TypeError):
        native.profile(_column(["a", 3]))            # a value that is no str
    assert not hasattr(native, "scan")               # the list walk is gone


def _text_columns(n_cols=11, rows=5000):
    rng = np.random.default_rng(1)
    cols = []
    for j in range(n_cols):
        levels = _column([None, ""] + [f"c{j}_{i:x} t{i % 5}"
                                      for i in range(3 + 17 * j)])
        cols.append(Column(T.Text, levels[rng.integers(0, len(levels),
                                                       size=rows)]))
    return cols


@pytest.mark.parametrize("cores,most", [(1, 4), (2, 4), (8, 4), (64, 16)])
def test_pool_gives_the_same_profiles_in_feature_order(monkeypatch, cores,
                                                       most):
    """One worker is a plain loop; more walk side by side — in the last case
    a thread a column, more than this machine has cores — with threads
    switched every 10 us so that a lost update on a shared structure would
    show."""
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(cores)))
    monkeypatch.setattr(tp, "_MAX_WORKERS", most)
    cols = _text_columns()
    pairs = [(c, 30 if j % 3 else None) for j, c in enumerate(cols)]
    triples = [(c, cap, 64 if j % 2 else None)
               for j, (c, cap) in enumerate(pairs)]
    before = _counters()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        profs, walks = _walks(lambda: list(tp.profile_columns(triples)))
    finally:
        sys.setswitchinterval(interval)
    width = min(cores, most, len(cols))
    assert sorted(s.attrs["column"] for s in walks) == list(range(len(cols)))
    assert {s.attrs["kind"] for s in walks} == {"whole"}
    here = threading.get_ident()
    if width > 1:       # a pool of the call's own, as wide as its walks
        assert REGISTRY.gauge("prologue.workers").value == width
        assert here not in {s.thread for s in walks}
    else:
        assert {s.thread for s in walks} == {here}
        assert all(s.attrs["queued_s"] == 0.0 for s in walks)
    capped = sum(cap is not None for _, cap in pairs)
    assert _moved(before) == {"scan": len(cols), "fused_intern": capped}
    for (col, cap), prof in zip(pairs, profs):
        assert tp.column_profile(col) is prof          # cached on ITS column
        _assert_scan_equal(prof, tp._py_scan(col.values))
        if cap is None:
            assert prof._interned == {}
        else:
            _assert_interned_equal(prof._interned[cap],
                                   tp._py_intern(col.values, cap))


def test_a_failing_column_raises_from_the_pool(monkeypatch):
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(4)))
    cols = _text_columns(4, 100)
    cols[2] = Column(T.Text, _column(["a", 7, "b"]))
    with pytest.raises(TypeError):
        list(tp.profile_columns([(c, 30, 64) for c in cols]))


# -- a long column walked by row range -------------------------------------

BLOCK = tp.BLOCK_ROWS
RANGED_ROWS = 5 * BLOCK + 17            # a head of one block, four tail ranges


def _long_column(kind):
    """Short values, cheap to walk: what matters is where the capped table
    freezes and what lies at the blocks' edges, where the ranges are cut."""
    rows = np.arange(RANGED_ROWS)
    many = np.asarray([f"v{i} w{i % 7}" for i in range(257)], dtype=object)
    if kind == "freezes_in_block_0":
        arr = many[(rows * 31 + rows // 5) % len(many)]
    elif kind == "freezes_in_block_1":
        # two values for a block and a bit, then many
        arr = np.where(rows < BLOCK + 4321, many[rows % 2],
                       many[(rows * 31) % len(many)])
    else:                               # never (for a cap of 1 or more)
        arr = np.where(rows % 3 == 0, None, "only value")
    arr = arr.astype(object)
    edges = np.arange(BLOCK, RANGED_ROWS, BLOCK)
    if kind != "never_freezes":
        # non-ASCII rows on both sides of every edge, and a range of None
        arr[edges - 1] = "Ünï tøken " + many[edges % len(many)]
        arr[edges] = "日本語 テキスト"
        arr[3 * BLOCK:4 * BLOCK] = None
    return arr


@pytest.fixture(scope="module")
def long_columns():
    """{kind: (the column, its ``_py_scan``)}, scanned in Python once."""
    return {kind: (arr, tp._py_scan(arr)) for kind, arr in
            ((k, _long_column(k)) for k in ("freezes_in_block_0",
                                            "freezes_in_block_1",
                                            "never_freezes"))}


@pytest.fixture
def small_ranges(monkeypatch):
    """Ranges of one block on four workers, so that a column of five blocks
    is cut as ``text_sweep``'s 28 are."""
    monkeypatch.setattr(tp, "MIN_RANGE_BLOCKS", 1)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(4)))


def _walks(fn):
    """(what ``fn()`` returned, the ``prefetch.walk`` spans it opened)."""
    tracer = Tracer("walks")
    with use_tracer(tracer):
        out = fn()
    return out, [s for s in tracer.spans if s.name == "prefetch.walk"]


def _kinds(walks):
    kinds = {}
    for s in walks:
        kinds[s.attrs["kind"]] = kinds.get(s.attrs["kind"], 0) + 1
    return kinds


@pytest.mark.parametrize("cap", [None, -1, 0, 1, 30])
@pytest.mark.parametrize("kind", ["freezes_in_block_0", "freezes_in_block_1",
                                  "never_freezes"])
def test_head_and_ranges_equal_python_scan_and_intern(long_columns,
                                                      small_ranges, kind,
                                                      cap):
    arr, ref = long_columns[kind]
    col = Column(T.Text, arr)
    before = _counters()
    (prof,), walks = _walks(lambda: list(
        tp.profile_columns([(col, cap, 500)])))
    assert tp.column_profile(col) is prof and prof._strings is arr
    pieces = len(prof.hash_pieces)          # tok_hash joins them
    assert prof.tokens == ref.tok_hash.size
    _assert_scan_equal(prof, ref)
    # one COLUMN scanned and interned, however many ranges walked it
    assert _moved(before) == {"scan": 1, **(
        {} if cap is None else {"fused_intern": 1})}
    if cap is None:
        assert prof._interned == {}
    else:
        assert list(prof._interned) == [cap]
        _assert_interned_equal(prof._interned[cap], tp._py_intern(arr, cap))
    # where the table froze says how the column was cut
    frozen = cap is not None and prof._interned[cap].frozen
    head_blocks = {None: 0, -1: 5}.get(
        cap, (2 if kind == "freezes_in_block_1" and cap == 30 else 1)
        if frozen else 5)
    tails = 0 if head_blocks == 5 else 4
    first = "whole" if cap == -1 else "head"      # an exact count: one walk
    assert _kinds(walks) == {**({first: 1} if head_blocks else {}),
                             **({"range": tails} if tails else {})}
    assert sum(s.attrs["rows"] for s in walks) == RANGED_ROWS
    assert all(s.attrs["rows"] == (RANGED_ROWS if head_blocks == 5
                                   else head_blocks * BLOCK)
               for s in walks if s.attrs["kind"] == "head")
    assert pieces == (head_blocks > 0) + tails
    # the joined words of the ranged column are the unranged column's
    whole = tp.scan_strings(arr)
    words, jobs = whole.pack_jobs(500)
    assert len(jobs) == 1
    jobs[0]()
    assert np.array_equal(prof._host_words[500], words)
    flat = ref.buckets(500)[1]
    packed = text_ops._pack_ids3(flat, 500)
    assert np.array_equal(words[:packed.size], packed)
    assert np.all(words[packed.size:] == text_ops._sentinel3(500))


def test_a_strided_column_is_cut_into_ranges_of_views(small_ranges):
    arr = np.repeat(_long_column("freezes_in_block_0"), 2)[::2]
    assert not arr.flags.c_contiguous and len(arr) == RANGED_ROWS
    (prof,), walks = _walks(lambda: list(
        tp.profile_columns([(Column(T.Text, arr), 3, 64)])))
    assert _kinds(walks) == {"head": 1, "range": 4}
    whole = tp.scan_strings(arr.copy(), cap=3)
    _assert_scan_equal(prof, whole)
    _assert_interned_equal(prof._interned[3], whole._interned[3])
    assert np.array_equal(np.asarray(prof.device_ids(64)),
                          np.asarray(whole.device_ids(64)))
    assert prof._host_words == {}           # handed to the link, not kept


def test_a_short_column_and_an_exact_count_stay_one_walk(small_ranges,
                                                         monkeypatch):
    monkeypatch.setattr(tp, "MIN_RANGE_BLOCKS", 3)
    arr = _long_column("freezes_in_block_0")    # 5 blocks < 1 + 2 * 3
    profs, walks = _walks(lambda: list(tp.profile_columns(
        [(Column(T.Text, arr), 30, 64), (Column(T.Text, arr[:100]), None,
                                         64)], names=["long", "short"])))
    assert sorted((s.attrs["column"], s.attrs["kind"], s.attrs["rows"])
                  for s in walks) == [("long", "whole", RANGED_ROWS),
                                      ("short", "whole", 100)]
    assert [len(p.hash_pieces) for p in profs] == [1, 1]
    assert REGISTRY.gauge("prologue.workers").value == 2
    assert tp._ranges(BLOCK, 28 * BLOCK, 4) == [
        (BLOCK * a, BLOCK * b) for a, b in ((1, 7), (7, 14), (14, 21),
                                            (21, 28))]
    assert tp._ranges(0, 7 * BLOCK + 5, 4) == [(0, 4 * BLOCK),
                                               (4 * BLOCK, 7 * BLOCK + 5)]
    assert tp._ranges(BLOCK, BLOCK + 9, 4) == [(BLOCK, BLOCK + 9)]


@pytest.mark.parametrize("where", ["head", "tail"])
def test_a_failing_range_raises_from_the_pool(small_ranges, where):
    arr = _long_column("freezes_in_block_0")
    arr[{"head": 100, "tail": 2 * BLOCK + 100}[where]] = 7
    cols = _text_columns(2, 100) + [Column(T.Text, arr)]
    with pytest.raises(TypeError):
        list(tp.profile_columns([(c, 30, 64) for c in cols]))


def test_native_range_arguments():
    native = load("textprof")
    arr = _column(["a", "b", "a", None, "c", "b", "d"])
    # until_frozen on a column shorter than a block: every row walked
    d = native.profile(arr, 1, 1, None, True)
    assert d["rows"] == 7 and d["uniq"] == ["a", "b"]
    # a frozen table handed in: looked up only, nothing counted or returned
    d = native.profile(arr[2:], 1, 1, ["a", "b"])
    assert d["rows"] == 5 and "uniq" not in d and "counts" not in d
    assert d["codes"].tolist() == [0, -1, -2, 1, -2]
    with pytest.raises(ValueError):
        native.profile(arr, 1, None, ["a"])          # frozen without a cap
    with pytest.raises(TypeError):
        native.profile(arr, 1, 1, ["a", 3])


def _smart_text_stage(names, **params):
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.ops.text import SmartTextVectorizer

    st = SmartTextVectorizer(**params)
    st.set_input(*[FeatureBuilder.Text(n).as_predictor() for n in names])
    return st


def test_fit_after_a_profiled_batch_only_hits_and_a_score_batch_interns_nothing():
    cols = _text_columns(6, 3000)
    names = [f"t{j}" for j in range(len(cols))]
    batch = ColumnBatch(dict(zip(names, cols)), 3000)
    st = _smart_text_stage(names, num_hashes=64, max_cardinality=30)
    before = _counters()
    list(tp.profile_columns([(c, 30, None) for c in cols]))
    assert _moved(before) == {"scan": 6, "fused_intern": 6}
    before = _counters()
    model = st.fit(batch)
    assert _moved(before) == {"intern.hit": 6}
    strategies = model.fitted["strategies"]
    assert set(strategies.values()) == {"pivot", "hash"}
    out = np.asarray(model.transform(batch).values)

    # the same fit with nothing profiled ahead walks every column again
    fresh = ColumnBatch({n: Column(T.Text, c.values.copy())
                         for n, c in zip(names, cols)}, 3000)
    before = _counters()
    model2 = _smart_text_stage(names, num_hashes=64,
                               max_cardinality=30).fit(fresh)
    assert _moved(before) == {"scan": 6, "intern.miss": 6}
    assert model2.fitted["strategies"] == strategies
    assert np.array_equal(np.asarray(model2.transform(fresh).values), out)

    # a score batch: profiled without a cap; only a pivoted column's vocab
    # lookup interns (values(-1), as before), a hashed column never
    score = ColumnBatch({n: Column(T.Text, c.values.copy())
                         for n, c in zip(names, cols)}, 3000)
    before = _counters()
    assert np.array_equal(np.asarray(model.transform(score).values), out)
    pivots = sum(s == "pivot" for s in strategies.values())
    moved = _moved(before)
    assert moved.pop("scan") == 6
    assert moved.pop("intern.miss") == pivots
    assert "fused_intern" not in moved
    for n in names:
        interned = tp.column_profile(score[n])._interned
        assert list(interned) == ([-1] if strategies[n] == "pivot" else [])


def test_the_object_array_is_never_copied_into_a_list(monkeypatch):
    def no_list(*a, **k):
        raise AssertionError("a text column was copied into a list")

    monkeypatch.setattr(tp, "list", no_list, raising=False)
    col = _text_columns(1, 2000)[0]
    prof = tp.column_profile(col, 30)
    assert prof._strings is col.values
    prof.values(30), prof.values(-1), prof.values(0)
    plain = tp.column_profile(Column(T.Text, col.values.copy()))
    plain.values(3)
    with open(os.path.join(os.path.dirname(tp.__file__), "..", "..",
                           "native", "textprof.cpp")) as fh:
        src = fh.read()
    assert "PySequence_Fast(" not in src and "ALLOW_THREADS" in src


def test_prefetch_profiles_side_by_side_and_transfers_in_feature_order(
        monkeypatch):
    """``Workflow._prefetch_text_profiles`` returns early on the CPU
    backend; told it is on an accelerator, it profiles every column of the
    hashing stages with the stage's cap, has the workers pack their ids,
    puts the packed words on the device from the calling thread in feature
    order, and says so on its span."""
    import jax

    from transmogrifai_tpu import workflow as wf_mod
    from transmogrifai_tpu.profiling import host_link_bytes
    from transmogrifai_tpu.workflow import Workflow

    rows = 2000
    monkeypatch.setattr(wf_mod, "PREFETCH_MIN_ROWS", rows)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(3)))
    order = []
    real = tp.TextProfile.prefetch

    def spy(self, num_hashes):
        order.append((id(self), threading.get_ident()))
        return real(self, num_hashes)

    monkeypatch.setattr(tp.TextProfile, "prefetch", spy)
    cols = _text_columns(5, rows)
    names = [f"t{j}" for j in range(len(cols))]
    batch = ColumnBatch(dict(zip(names, cols)), rows)
    st = _smart_text_stage(names, num_hashes=64, max_cardinality=7)
    workflow = Workflow().set_input_batch(batch).set_result_features(
        st.get_output())
    before, link = _counters(), host_link_bytes()
    tracer = Tracer("prefetch")
    with use_tracer(tracer):
        workflow._prefetch_text_profiles(batch)
    assert _moved(before) == {"scan": 5, "fused_intern": 5}
    assert order == [(id(tp.column_profile(c)), threading.get_ident())
                     for c in cols]
    assert host_link_bytes() - link == sum(
        tp.column_profile(c)._device_packed[64].nbytes for c in cols)
    (sp,) = [s for s in tracer.spans if s.name == "prefetch.text_profiles"]
    assert sp.attrs == {"columns": 5, "rows": rows}
    assert REGISTRY.gauge("prologue.workers").value == 3
    packs = [s for s in tracer.spans if s.name == "text.pack_ids"]
    walks = [s for s in tracer.spans if s.name == "prefetch.walk"]
    assert len(packs) == 5                  # packed by the workers, inside
    assert sorted(s.attrs["column"] for s in walks) == names   # walked too
    assert {s.parent_id for s in packs + walks} == {sp.span_id}
    assert sp.thread == threading.get_ident() \
        and sp.thread not in {s.thread for s in packs + walks}
    assert {s.thread for s in tracer.spans
            if s not in packs + walks} == {sp.thread}
    before = _counters()
    st.fit(batch)
    assert _moved(before) == {"intern.hit": 5}
