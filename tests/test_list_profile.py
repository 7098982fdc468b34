"""Date lists held ragged, their one walk, and the URL / e-mail domain walk
(``ops.list_profile``, ``native/listprof.cpp``): the staged
``DateListVectorizerModel`` equals its eager form for every pivot, a ragged
and an object-held column give the same answers, the native walks equal the
Python rules they stand for, and RawFeatureFilter and the pivots read the
walks."""

import zlib

import numpy as np
import pytest

from transmogrifai_tpu import native
from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import (Column, ColumnBatch, RaggedList,
                                       column_from_values, ragged_of)
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.ops import list_profile as lp
from transmogrifai_tpu.ops.dates import DateListVectorizer
from transmogrifai_tpu.ops.text_specialized import email_parts, url_domain
from transmogrifai_tpu.telemetry import REGISTRY

PIVOTS = ("SinceFirst", "SinceLast", "ModeDay", "ModeMonth", "ModeHour")


def _lists(n=400, seed=0):
    """Lists of dates with None, empty lists, dates before 1970, ties of a
    period and one very long list."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        r = rng.random()
        if r < 0.1:
            out.append(None)
        elif r < 0.2:
            out.append([])
        else:
            out.append([int(x) for x in rng.integers(
                -10 ** 11, 2 * 10 ** 12, rng.integers(1, 30))])
    out[3] = [int(x) for x in rng.integers(1_200_000_000_000,
                                           1_700_000_000_000, 20000)]
    day = 86_400_000
    out[5] = [0, day, 2 * day, day, 0]            # a tie: first seen wins
    out[6] = [5 * day, 3 * day, 3 * day, 5 * day]
    return out


def _object_column(lists):
    return Column(T.DateList, np.array(lists + [None], object)[:-1])


@pytest.fixture(scope="module")
def lists():
    return _lists()


# ragged storage -------------------------------------------------------------

def test_ragged_list_reads_as_its_lists(lists):
    r = RaggedList.from_lists(lists)
    assert len(r) == len(lists)
    assert [list(v) for v in r] == [v or [] for v in lists]
    assert r[3] == lists[3] and r[-1] == (lists[-1] or [])
    idx = np.array([7, 0, 3, 3, 1])
    assert list(r.take(idx)) == [lists[i] or [] for i in idx]
    assert list(r.take(np.arange(len(lists)) % 2 == 0)) == [
        v or [] for v in lists[::2]]
    assert r[2:9] == r.take(np.arange(2, 9))
    held = np.asarray(r)
    assert held.dtype == object and held[3] == lists[3]
    with pytest.raises(ValueError):
        RaggedList(np.arange(3), np.array([0, 2, 1, 3]))


def test_a_date_list_column_is_built_ragged_and_read_row_by_row(lists):
    col = column_from_values(T.DateList, lists)
    assert isinstance(col.values, RaggedList)
    assert not col.is_device and not col.is_host_object()
    assert col.row_value(3) == T.DateList(lists[3])
    assert col.row_value(0).value == (lists[0] or [])
    batch = ColumnBatch({"dl": col}, len(lists)).take_rows(np.array([5, 6]))
    assert list(batch["dl"].values) == [lists[5], lists[6]]
    # an object-held column is converted once and kept
    obj = _object_column(lists)
    assert ragged_of(obj) is ragged_of(obj) and ragged_of(obj) == col.values


# the staged DateListVectorizer ---------------------------------------------

@pytest.mark.parametrize("track_nulls", [True, False])
@pytest.mark.parametrize("pivot", PIVOTS)
def test_staged_date_list_pivot_equals_the_eager_form(lists, pivot,
                                                      track_nulls):
    """Bit for bit on the CPU, a ragged and an object-held column alike."""
    f = FeatureBuilder.DateList("dl").as_predictor()
    st = DateListVectorizer(pivot=pivot, track_nulls=track_nulls)
    st.set_input(f)
    obj = ColumnBatch({"dl": _object_column(lists)}, len(lists))
    model = st.fit(obj)
    eager = np.asarray(model.transform(obj).values)
    assert eager.shape[1] == len(model.fitted["meta"].columns)
    for col in (_object_column(lists),
                Column(T.DateList, RaggedList.from_lists(lists))):
        batch = ColumnBatch({"dl": col}, len(lists))
        wire, body = model.transform_staged(batch)
        staged = np.asarray(body(wire).values)
        assert staged.dtype == eager.dtype == np.float32
        assert np.array_equal(staged.view(np.uint32), eager.view(np.uint32))
        assert np.array_equal(np.asarray(model.transform(batch).values),
                              eager)


def test_the_wire_is_narrow_and_the_ties_go_to_the_first_seen(lists):
    f = FeatureBuilder.DateList("dl").as_predictor()
    batch = ColumnBatch({"dl": _object_column(lists)}, len(lists))
    st = DateListVectorizer(pivot="SinceLast")
    st.set_input(f)
    wire, _ = st.fit(batch).transform_staged(batch)
    assert sorted(wire) == ["days0", "null0"]
    assert wire["days0"].dtype == np.float32
    assert wire["null0"].nbytes == -(-len(lists) // 8)
    st = DateListVectorizer(pivot="ModeHour")
    st.set_input(f)
    wire, _ = st.fit(batch).transform_staged(batch)
    assert list(wire) == ["mode0"] and wire["mode0"].dtype == np.uint8
    empty = np.array([not v for v in lists])
    assert (wire["mode0"][empty] == 255).all()
    assert (wire["mode0"][~empty] < 24).all()
    st = DateListVectorizer(pivot="ModeDay")
    st.set_input(f)
    wire, _ = st.fit(batch).transform_staged(batch)
    # row 5: Thursday x2, Friday x2, Saturday: Thursday (3) was seen first;
    # row 6: Tuesday x2, Sunday x2, Tuesday (1) first
    assert wire["mode0"][5] == 3 and wire["mode0"][6] == 1


# the walks ---------------------------------------------------------------

def test_date_walk_native_equals_numpy_and_the_row_rule(lists, monkeypatch):
    col = _object_column(lists)
    prof = lp.date_profile(col, 255)
    assert lp.date_profile(col, 255) is prof       # cached
    assert lp.date_profile(col) is prof            # a request without bins
    r = ragged_of(col)
    want = np.zeros(255)
    for v in r.values.tolist():
        want[zlib.crc32(str(v).encode("utf-8")) % 255] += 1
    assert np.array_equal(prof.hist, want)
    full = r.lengths() > 0
    assert np.array_equal(prof.present, full)
    assert np.array_equal(prof.lo[full], [min(v) for v in lists if v])
    assert np.array_equal(prof.hi[full], [max(v) for v in lists if v])
    monkeypatch.setitem(native._CACHE, "listprof", None)
    plain = lp.date_profile(_object_column(lists), 255)
    for k in ("lengths", "lo", "hi", "hist"):
        assert np.array_equal(getattr(plain, k), getattr(prof, k)), k


@pytest.mark.parametrize("bins", [1, 7, 255, 1_000_003])
def test_decimal_crc_of_every_length_and_sign(bins):
    """The walk's bins are zlib's crc32 of ``str(v)`` modulo ``bins`` for
    every length and sign of an int64."""
    mod = native.load("listprof")
    assert mod is not None, native.fallback_reasons()
    rng = np.random.default_rng(3)
    v = np.concatenate([
        rng.integers(-2 ** 63, 2 ** 63 - 1, 5000, dtype=np.int64),
        -rng.integers(0, 10 ** rng.integers(1, 18, 500)),
        rng.integers(0, 10 ** rng.integers(1, 18, 500)),
        np.array([0, 1, 9, 10, 99, 100, -1, -9, -10, -100, 2 ** 63 - 1,
                  -2 ** 63] + [10 ** k for k in range(19)]
                 + [-(10 ** k) for k in range(19)], np.int64)])
    crc = np.array([zlib.crc32(str(int(x)).encode()) for x in v], np.int64)
    _, _, hist = mod.date_lists(v, np.array([0, len(v)]), bins)
    assert np.array_equal(hist, np.bincount(crc % bins, minlength=bins))


URLS = ["http://Example.COM/x", "https://user:pw@Host.io:8080/p?q#f",
        "example.com", "www.ex.com:80/x", "ftp://f.org", "HTTP://A.B",
        "mailto:x@y", "http://", "http:///x", "http://[::1]/",
        "http://[::1", "http://héllo.com/x", "https://ÜBER.de",
        " http://x.com", "http://x.com%ZoNe/", "http://a@b@C.com",
        "https://x.com#frag", "http://x.com?q=1", "", None, 5,
        "https:x.com", "ht+tp://x.com", "HtTpS://UP.case/", "n/a",
        "http://x.com:notaport/", "https://www.github.com/u1", "\tx"]
EMAILS = ["a@b.com", "A@B.Com", "@x", "x@", "a@b@c", "noat", "ü@dömain.de",
          None, "", "a@", "first.last@sub.domain.org", 7, "a@\udcff"]


@pytest.mark.parametrize("native_walk", [True, False])
@pytest.mark.parametrize("kind,values,rule", [
    (T.URL, URLS, url_domain),
    (T.Email, EMAILS, lambda s: email_parts(s)[1])])
def test_domain_walk_equals_the_python_rule(kind, values, rule,
                                            native_walk, monkeypatch):
    """Valid, invalid, scheme-less, upper-case, port-bearing and unicode
    values; with the native walk and without it."""
    if not native_walk:
        monkeypatch.setitem(native._CACHE, "listprof", None)
    col = Column(kind, np.array(values + [None], object)[:-1])
    iv = lp.domain_values(col, kind, "c")
    got = [None if c < 0 else iv.uniq[c] for c in iv.codes.tolist()]
    assert got == [rule(s) for s in values]
    # first-occurrence order, counts a row each, as a walk of the strings
    from transmogrifai_tpu.ops.text_profile import _py_intern
    again = _py_intern(got, -1)
    assert iv.uniq == again.uniq and iv.counts.tolist() == again.counts.tolist()
    assert np.array_equal(iv.codes, again.codes)
    assert lp.domain_values(col, kind) is iv


def test_url_and_email_extractors_read_the_walk():
    from transmogrifai_tpu.ops.text_specialized import (
        EmailToPickListTransformer, UrlToPickListTransformer)
    for cls, kind, values, rule in (
            (UrlToPickListTransformer, T.URL, URLS, url_domain),
            (EmailToPickListTransformer, T.Email, EMAILS,
             lambda s: email_parts(s)[1])):
        f = FeatureBuilder("c", kind).as_predictor()
        st = cls()
        st.set_input(f)
        col = Column(kind, np.array(values + [None], object)[:-1])
        out = st.transform(ColumnBatch({"c": col}, len(values)))
        assert out.kind is T.PickList and out.is_host_object()
        assert list(out.values) == [rule(s) for s in values]


def test_the_domain_pivot_equals_the_extractor_then_the_pivot():
    """``OneHotEstimator`` on a URL column gives the columns
    ``UrlToPickListTransformer`` and a pivot gave, from arrays."""
    from transmogrifai_tpu.ops.categorical import OneHotEstimator
    from transmogrifai_tpu.ops.text_specialized import \
        UrlToPickListTransformer
    rng = np.random.default_rng(5)
    hosts = [f"h{k}.com" for k in range(30)]
    values = [None if rng.random() < 0.3 else
              f"https://{hosts[min(int(rng.exponential(6)), 29)]}/p"
              if rng.random() < 0.9 else "not a url" for _ in range(3000)]
    col = Column(T.URL, np.array(values, object))
    url = FeatureBuilder.URL("site").as_predictor()
    batch = ColumnBatch({"site": col}, len(values))
    before = REGISTRY.counters().get("pivot.python_values", 0)
    direct = OneHotEstimator()
    direct.set_input(url)
    model = direct.fit(batch)
    wire, body = model.transform_staged(batch)
    got = np.asarray(body(wire).values)
    assert REGISTRY.counters().get("pivot.python_values", 0) == before
    ext = UrlToPickListTransformer()
    ext.set_input(url)
    dom = ext.get_output()
    b2 = batch.with_column(dom.name, ext.transform(batch))
    old = OneHotEstimator()
    old.set_input(dom)
    want = np.asarray(old.fit(b2).transform(b2).values)
    assert np.array_equal(got, want)
    assert np.array_equal(np.asarray(model.transform(batch).values), want)
    assert [c.indicator_value for c in model.fitted["meta"].columns][:3] == [
        "h0.com", "h1.com", "h2.com"]


def test_transmogrify_pivots_a_url_with_no_stage_between(lists):
    from transmogrifai_tpu.dag import compute_dag, dag_stages
    from transmogrifai_tpu.ops.categorical import OneHotEstimator
    from transmogrifai_tpu.ops.transmogrify import transmogrify
    from transmogrifai_tpu.ops.text_specialized import (
        EmailToPickListTransformer, UrlToPickListTransformer)
    feats = [FeatureBuilder.URL("site").as_predictor(),
             FeatureBuilder.Email("mail").as_predictor(),
             FeatureBuilder.ID("who").as_predictor()]
    stages = dag_stages(compute_dag([transmogrify(feats)]))
    assert not any(isinstance(s, (UrlToPickListTransformer,
                                  EmailToPickListTransformer))
                   for s in stages)
    (pivot,) = [s for s in stages if isinstance(s, OneHotEstimator)]
    assert [f.name for f in pivot.input_features] == ["who", "mail", "site"]


def test_filter_reads_a_date_list_from_its_walk(lists):
    """RawFeatureFilter's distribution and presence of a date list are the
    row walk's, from one native walk, with no row in Python."""
    from transmogrifai_tpu.filters import (_histogram_of, _value_presence,
                                           compute_distribution,
                                           compute_sketches)
    f = FeatureBuilder.DateList("dl").as_predictor()
    before = REGISTRY.counters().get("rff.python_rows", 0)
    obj = _object_column(lists)
    rag = Column(T.DateList, RaggedList.from_lists(lists))
    dists = [compute_distribution(f, c, 100, 255)[0] for c in (obj, rag)]
    sketch = compute_sketches([f], ColumnBatch({"dl": rag}, len(lists)),
                              text_bins=255)[("dl", None)]
    assert REGISTRY.counters().get("rff.python_rows", 0) == before
    present = np.array([bool(v) for v in lists])
    want = _histogram_of(list(_object_column(lists).values), present,
                         T.DateList, 100, 255)
    for d in dists:
        assert np.array_equal(d.distribution, want)
        assert d.nulls == int((~present).sum()) == sketch.nulls
    assert np.array_equal(sketch.text_counts, want)
    assert np.array_equal(_value_presence(rag), present)
