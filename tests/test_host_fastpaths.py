"""Parity tests for the one-pass host fast paths (round-4 transmog work):
native text profile (native/textprof.cpp via ops/text_profile.py), packed
token-id wire (ops/text.py), map expansion (native/mapprof.cpp via
ops/map_profile.py) — each must reproduce the legacy per-consumer scans
bit-for-bit, because RFF/SmartTextVectorizer/OneHot goldens are pinned on
those behaviors."""

import numpy as np
import pytest

from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Column, ColumnBatch, column_from_values
from transmogrifai_tpu.native import load
from transmogrifai_tpu.ops.text import (TextStats, _counts_from_flat,
                                        _pack_ids3, _sentinel3, _size_class,
                                        device_counts_from_flat,
                                        fnv1a_32, hash_tokens_flat,
                                        tokenize_text)
from transmogrifai_tpu.ops.text_profile import (TextProfile, _py_intern,
                                                _py_scan, scan_strings)
from transmogrifai_tpu.telemetry import REGISTRY


def _mixed_strings(n=3000, seed=0):
    rng = np.random.default_rng(seed)
    pool = ["hello world", "foo_bar'2", "", None, "Ünïcode tøken K",
            "a b c", "xxxxx", None, "Mixed CASE Words", "tab\tsep"]
    vals = []
    for i in range(n):
        c = pool[rng.integers(0, len(pool))]
        vals.append(f"tok{i % 97} sal{i % 7}" if i % 3 == 0 else c)
    return np.asarray(vals, dtype=object)


def test_scan_matches_python_reference():
    arr = _mixed_strings()
    a, b = scan_strings(arr), _py_scan(arr)
    for f in ("null", "empty", "lengths", "crc", "tok_lens", "tok_hash"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f


def test_scan_matches_legacy_tokenize_hash():
    arr = _mixed_strings(seed=1)
    prof = scan_strings(arr)
    lens_old, flat_old = hash_tokens_flat(
        [tokenize_text(s) for s in arr], 512)
    lens_new, flat_new = prof.buckets(512)
    assert np.array_equal(lens_old, lens_new)
    assert np.array_equal(flat_old, flat_new)


def test_intern_matches_textstats_freeze_semantics():
    arr = _mixed_strings(seed=2)
    prof = scan_strings(arr)
    for cap in (0, 3, 30):
        iv = prof.values(cap)
        ref = _py_intern(arr, cap)
        assert iv.uniq == ref.uniq
        assert np.array_equal(iv.counts, ref.counts)
        assert np.array_equal(iv.codes, ref.codes)
        stats = TextStats.of_column(arr, cap)
        assert dict(stats.value_counts) == iv.value_counts()
        assert dict(stats.length_counts) == prof.length_counts()


def test_values_cap_aliasing_only_when_equivalent():
    arr = np.asarray(["a", "b", "a", "c", "d", None] * 10, dtype=object)
    prof = scan_strings(arr)
    exact = prof.values(-1)
    assert prof.values(10) is exact        # U=4 <= 10: freeze can't engage
    frozen = prof.values(1)                # must NOT alias to exact
    assert frozen is not exact and frozen.frozen
    ref = _py_intern(arr, 1)
    assert frozen.uniq == ref.uniq
    assert np.array_equal(frozen.counts, ref.counts)


def test_crc_hist_matches_legacy_filter_binning():
    import zlib
    arr = _mixed_strings(seed=3)
    prof = scan_strings(arr)
    bins = 97
    h = np.zeros(bins)
    for s in arr:
        if s is not None and s != "":
            h[zlib.crc32(s.encode("utf-8")) % bins] += 1.0
    assert np.array_equal(prof.crc_hist(bins), h)


def test_packed_wire_counts_match_host_counts():
    rng = np.random.default_rng(4)
    n = 257
    lens = rng.integers(0, 9, size=n).astype(np.int32)
    flat = rng.integers(0, 512, size=int(lens.sum())).astype(np.int32)
    host = _counts_from_flat(lens, flat, 512, binary=False)
    dev = np.asarray(device_counts_from_flat(lens, flat, 512))
    assert np.array_equal(host, dev)
    devb = np.asarray(device_counts_from_flat(lens, flat, 512, binary=True))
    assert np.array_equal((host > 0).astype(np.float32), devb)
    # >= 1024 bins takes the unpacked path
    flat2 = rng.integers(0, 2048, size=int(lens.sum())).astype(np.int32)
    host2 = _counts_from_flat(lens, flat2, 2048, binary=False)
    dev2 = np.asarray(device_counts_from_flat(lens, flat2, 2048))
    assert np.array_equal(host2, dev2)


def test_pack_ids3_roundtrip_and_size_class():
    rng = np.random.default_rng(5)
    flat = rng.integers(0, 512, size=1001).astype(np.int32)
    words = _pack_ids3(flat, 512)
    ids = np.stack([words & 0x3FF, (words >> 10) & 0x3FF,
                    (words >> 20) & 0x3FF], axis=1).reshape(-1)
    assert np.array_equal(ids[:1001], flat)
    assert np.all(ids[1001:] == 512)
    assert _size_class(1000) == 1024
    assert _size_class(1025) == 1536
    assert _size_class(1537) == 2048
    assert _size_class(5) == 1024


def _padded_words(hashes, num_hashes):
    """The wire as ops/text.py defines it: ``_pack_ids3`` of the bucket ids,
    padded with sentinel words to the size class."""
    words = _pack_ids3((hashes % np.uint32(num_hashes)).astype(np.int32),
                       num_hashes)
    out = np.full(_size_class(words.size), _sentinel3(num_hashes), np.int32)
    out[:words.size] = words
    return out


def _profile_in_pieces(hashes, cuts):
    edges = [0, *cuts, hashes.size]
    rows = np.zeros(1, np.int32)
    return TextProfile(rows.astype(bool), rows.astype(bool), rows,
                       rows.astype(np.uint32), rows,
                       [hashes[a:b] for a, b in zip(edges[:-1], edges[1:])])


@pytest.mark.parametrize("offset", [0, 1, 2])
@pytest.mark.parametrize("total", [0, 3000, 3001, 3002])
@pytest.mark.parametrize("num_hashes", [2, 500, 512, 1023])
def test_native_pack_equals_pack_ids3_word_for_word(num_hashes, total,
                                                    offset):
    """A column's tokens in three pieces, the second starting at a token
    offset ≡ ``offset`` (mod 3) and the third one token long, packed piece
    by piece in any order: the words, the sentinel lanes of the last one and
    the sentinel words up to the size class are ``_pack_ids3``'s."""
    if load("textprof") is None:
        pytest.skip("no native toolchain")
    rng = np.random.default_rng(num_hashes + total)
    hashes = rng.integers(0, 2 ** 32, size=total, dtype=np.uint64
                          ).astype(np.uint32)
    cuts = sorted({min(total, 999 + offset), max(total - 1, 0)})
    prof = _profile_in_pieces(hashes, cuts)
    assert prof.tokens == total
    before = REGISTRY.counters().get("text.pack_native", 0)
    words, jobs = prof.pack_jobs(num_hashes)
    assert len(jobs) == len(cuts) + 1
    words[:] = -1
    for job in reversed(jobs):
        job()
    assert words.dtype == np.int32
    assert np.array_equal(words, _padded_words(hashes, num_hashes))
    assert REGISTRY.counters()["text.pack_native"] == before + 1
    assert np.array_equal(prof.tok_hash, hashes)      # joined on demand


@pytest.mark.parametrize("num_hashes", [2, 500, 512, 1023])
def test_pack_falls_back_to_numpy_without_the_native_module(monkeypatch,
                                                            num_hashes):
    from transmogrifai_tpu import native
    monkeypatch.setattr(native, "load", lambda name: None)
    rng = np.random.default_rng(num_hashes)
    hashes = rng.integers(0, 2 ** 32, size=1001, dtype=np.uint64
                          ).astype(np.uint32)
    prof = _profile_in_pieces(hashes, [400])
    before = REGISTRY.counters().get("text.pack_numpy", 0)
    words, (job,) = prof.pack_jobs(num_hashes)
    job()
    assert np.array_equal(words, _padded_words(hashes, num_hashes))
    assert REGISTRY.counters()["text.pack_numpy"] == before + 1
    assert np.array_equal(np.asarray(prof.device_ids(num_hashes)), words)


def test_native_pack_refuses_what_it_cannot_pack():
    native = load("textprof")
    if native is None:
        pytest.skip("no native toolchain")
    hashes = np.arange(10, dtype=np.uint32)
    none = np.empty(0, np.uint32)
    out = np.empty(1024, np.int32)
    for bad in [(hashes, 1024, 0, out, none, True),        # an 11-bit id
                (hashes, 0, 0, out, none, True),
                (hashes, 512, -1, out, none, True),
                (hashes, 512, 0, out[:3], none, True),     # 4 words needed
                (hashes, 512, 0, out, hashes[:3], True)]:  # 3 carried
        with pytest.raises(ValueError):
            native.pack_ids3(*bad)
    for bad in [(hashes.astype(np.int64), 512, 0, out, none, True),
                (hashes[::2], 512, 0, out, none, True),
                (hashes, 512, 0, out.astype(np.int64), none, True),
                (list(hashes), 512, 0, out, none, True)]:
        with pytest.raises(TypeError):
            native.pack_ids3(*bad)


def test_map_expansion_parity_and_fallback():
    from transmogrifai_tpu.ops.map_profile import _py_expand, expand_maps

    rng = np.random.default_rng(6)
    n = 500
    maps = np.empty(n, dtype=object)
    for i in range(n):
        m = {}
        if i % 7 != 0:
            for j, k in enumerate(("a", "b", "c")):
                if rng.random() < 0.7:
                    m[k] = float(rng.normal()) if j else int(i)
            if i % 11 == 0:
                m["late_key"] = 1.5
            if i % 13 == 0:
                m["nullv"] = None
        maps[i] = m if i % 17 else None
    a, b = expand_maps(maps), _py_expand(maps)
    assert a.keys == b.keys
    assert np.array_equal(a.present, b.present)
    assert np.array_equal(a.in_dict, b.in_dict)
    assert np.array_equal(a.nonempty, b.nonempty)
    assert np.allclose(a.vals, b.vals, equal_nan=True)
    # key present only with None values still appears (in_dict counts it)
    assert "nullv" in a.keys

    # bool values → exact Python paths (pinned inconsistent bool handling)
    maps_b = np.asarray([{"a": True}, {"a": 1.0}], dtype=object)
    assert expand_maps(maps_b) is None


def test_map_vectorizer_fastpath_matches_legacy(monkeypatch):
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.ops import maps as maps_mod

    rng = np.random.default_rng(7)
    n = 400
    vals = np.empty(n, dtype=object)
    for i in range(n):
        m = {k: float(rng.normal()) for j, k in enumerate(("x", "y", "z"))
             if rng.random() < 0.8}
        vals[i] = m
    col = Column(T.RealMap, vals)
    batch = ColumnBatch({"m": col}, n)
    f = FeatureBuilder.RealMap("m").as_predictor()

    def run(disable_fast):
        c = Column(T.RealMap, vals)     # fresh column → fresh cache
        b = ColumnBatch({"m": c}, n)
        if disable_fast:
            monkeypatch.setattr(
                "transmogrifai_tpu.ops.map_profile.map_expansion",
                lambda col: None)
        st = maps_mod.MapVectorizer()
        st.set_input(f)
        model = st.fit(b)
        out = model.transform(b)
        monkeypatch.undo()
        return (np.asarray(out.values),
                model.fitted["keys"], model.fitted["fills"])

    fast_vals, fast_keys, fast_fills = run(False)
    slow_vals, slow_keys, slow_fills = run(True)
    assert fast_keys == slow_keys
    assert fast_fills == pytest.approx(slow_fills)
    assert np.allclose(fast_vals, slow_vals)


def test_encode_column_matches_encode_with_vocab():
    from transmogrifai_tpu.ops.categorical import (encode_column,
                                                   encode_with_vocab)

    arr = np.asarray(["a", "b", None, "zz", "a", "", "c"] * 30, dtype=object)
    col = Column(T.PickList, arr)
    vocab = {"a": 0, "b": 1, "": 2}
    got = encode_column(col, vocab, other_id=3)
    want = encode_with_vocab(arr, vocab, other_id=3)
    assert np.array_equal(got, want)

    all_null = Column(T.PickList, np.asarray([None] * 5, dtype=object))
    got = encode_column(all_null, {}, other_id=0)
    assert np.array_equal(got, np.full(5, 1, np.int32))


def test_smart_text_fit_transform_matches_across_native(monkeypatch):
    """End-to-end SmartTextVectorizer parity: profile path vs forced
    pure-Python profile path."""
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.ops.text import SmartTextVectorizer

    arr = _mixed_strings(1500, seed=8)
    f = FeatureBuilder.Text("t").as_predictor()

    def run(native_off):
        if native_off:
            import transmogrifai_tpu.native as nat
            monkeypatch.setitem(nat._CACHE, "textprof", None)
        c = Column(T.Text, arr)
        b = ColumnBatch({"t": c}, len(arr))
        st = SmartTextVectorizer(num_hashes=64, max_cardinality=10)
        st.set_input(f)
        model = st.fit(b)
        out = model.transform(b)
        monkeypatch.undo()
        return np.asarray(out.values), model.fitted["strategies"]

    v1, s1 = run(False)
    v2, s2 = run(True)
    assert s1 == s2
    assert np.array_equal(v1, v2)


def test_rff_histogram_mesh_invariant(monkeypatch):
    """RawFeatureFilter's sharded numeric histogram must be BIT-identical to
    the np.histogram single-device path — binning happens on host in
    float64, only the count reduction shards (round-4 review finding:
    float32 device binning moved edge-adjacent epoch timestamps across
    bins, making drop decisions mesh-dependent)."""
    import jax

    from transmogrifai_tpu.filters import _histogram_of
    from transmogrifai_tpu.types import Real

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device backend")
    rng = np.random.default_rng(21)
    # epoch-timestamp magnitudes with values planted exactly ON bin edges
    arr = (1.7e9 + rng.integers(0, 1_000_000, size=4096)).astype(np.float64)
    lo, hi = float(arr.min()), float(arr.max())
    edges = np.linspace(lo, hi, 51)
    arr[:50] = edges[:-1]          # exact left edges
    arr[50] = hi                   # inclusive last edge
    present = np.ones(arr.size, bool)

    off = _histogram_of(arr, present, Real, 50, 10, value_range=(lo, hi))
    monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "1")
    on = _histogram_of(arr, present, Real, 50, 10, value_range=(lo, hi))
    assert np.array_equal(off, on)
    assert float(on.sum()) == arr.size
