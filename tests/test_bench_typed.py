"""``nyc_taxi_typed`` / ``typed_sweep``: seventeen predictors typed as
upstream types them (ID, PickList, DateTime, Integral, Real, Currency,
Geolocation) through ``transmogrify`` and both default linear families, at a
size a CPU can hold (the rows of ``fixtures/cpu_cells_typed_sweep.json``).

(a) the program against the configuration's own plain reference, every number
under the fixture's CPU limits through ``run.verdict``, and the control over
one; (b) broken paths make ``correct`` false: a date period dropped, the
millisecond of the day dropped from the wire, (0, 0) coordinates read as
present, and the Cramér's V rule switched off in the REFERENCE; (c) the staged
date and coordinate transforms against the int64 / host forms they replace,
inside a fused program and eagerly; (d) RawFeatureFilter's array path against
its row-by-row branch; (e) the solver on a column far from 0 for its spread;
(f) the new spans, counters and scopes, for this table and for the Criteo and
text programs; (g) the three readers; (h) the configuration's file and the
generator; (i) the cell's part of ``run.py --selftest``.
"""

import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import run
from benchmark.reference import common, plain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "typed_sweep"
SEED = 2 ** 31 + 21
MANIFEST = run.load_json("BENCHMARK.json")
TINY = run.cpu_cells()[CELL]
STATED = plain.Precision.stated("cpu")
COUNTERS = ("transform.host_stages", "rff.python_rows",
            "sanity.groups_dropped", "selector.family_rounds",
            "pivot.python_values")
PERIODS = ("HourOfDay", "DayOfWeek", "DayOfMonth", "DayOfYear")
ULP = float(np.finfo(np.float32).eps)


def reader(name):
    return importlib.import_module("benchmark.layer_metrics." + name)


def reference_module():
    return importlib.import_module("benchmark.reference.nyc_taxi_typed")


def over(compared):
    return sorted(k for k, c in compared.items() if c["value"] > c["limit"])


def counted(train):
    from transmogrifai_tpu.telemetry import REGISTRY
    before = {k: REGISTRY.counters().get(k, 0) for k in COUNTERS}
    out = train()
    return out, {k: REGISTRY.counters().get(k) for k in COUNTERS}, before


@pytest.fixture(scope="module")
def cell():
    return run.Cell(MANIFEST, CELL, TINY["rows"], TINY["limits"])


@pytest.fixture(scope="module")
def data(cell):
    return cell.program.make_data(cell.rows, SEED, cell.config)


@pytest.fixture(scope="module")
def traced(cell, data):
    """One train under the program's tracer, with what it counted."""
    from transmogrifai_tpu.telemetry import REGISTRY, Tracer
    tracer = Tracer("typed")
    rec, after, before = counted(
        lambda: run.one_train(cell, data, "cpu", tracer))
    jax.clear_caches()
    assert not rec["why_failed"], rec["why_failed"]
    rec["counted"] = {k: after[k] - before[k] for k in COUNTERS}
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
    ref = references(p)
    ok, compared = run.verdict(cell, [p], ref)
    assert ok, compared
    assert set(compared) == set(TINY["limits"])
    assert p["stats"].shape[0] == 5 and 70 <= p["stats"].shape[1] <= 108
    assert {r["family"] for r in p["cv"]} == {"OpLogisticRegression",
                                              "OpLinearSVC"}
    assert len(p["cv"]) == 12 and p["rff_dropped"] == []
    # a model, not the empty one (whose constant score reads AuPR 0.76)
    assert p["winner"]["metric"] > 0.84


def test_the_payment_group_goes_by_cramers_v_in_both(cell, data, traced,
                                                     references):
    """SanityChecker drops every column of ``payment_type`` by the group
    rule, and the reference's plain table gives the same V."""
    from transmogrifai_tpu.dag import dag_stages
    R = reference_module()
    p = traced["produced"]
    pivots = R.pivots_of(data, cell.config)
    v = [R.cramers_v(ids, width, data["label"]) for ids, width in pivots]
    assert v[-1] > 0.95 and max(v[:-1]) < 0.7
    at = sum(w for _, w in pivots[:-1])
    group = set(range(at, at + pivots[-1][1]))
    assert not group & set(p["kept"].tolist())
    assert not group & set(references(p)["kept"].tolist())
    assert traced["counted"]["sanity.groups_dropped"] == 1
    # CSH and CRD go by their own correlation too; NOC, DIS, UNK, OTHER and
    # null only as the group's
    model = cell.program.build(data, cell.config).train()
    summary = [s.summary for s in dag_stages(model.fitted_dag)
               if hasattr(getattr(s, "summary", None), "cramers_v_by_group")]
    assert summary[0].cramers_v_by_group["payment_type"] == pytest.approx(
        v[-1], abs=1e-6)
    only_group = [n for n, why in summary[0].drop_reasons.items()
                  if "payment_type" in n and all("CramersV" in r
                                                 for r in why)]
    assert len(only_group) >= 3


def test_control_fails_the_cells_limits(cell, data, traced, references):
    p = traced["produced"]
    low = cell.reference.reference(
        data, cell.config, plain.Precision.control("cpu"),
        cell.reference.question(p), seed=SEED)
    ok, control = run.verdict(
        cell, [common.as_produced(low, p, cell.config)], references(p))
    assert not ok and "stats_gap" in over(control), control


# (b) ----------------------------------------------------------------------

def day_of_month_dropped(mp):
    """The 30.44-day period reads as the day's: its two columns repeat the
    hour's."""
    from transmogrifai_tpu.ops import dates
    whole = dates._period_fraction_device
    mp.setattr(dates, "_period_fraction_device",
               lambda day, ms, p: whole(
                   day, ms, "HourOfDay" if p == "DayOfMonth" else p))


def millisecond_of_day_dropped(mp):
    """The wire carries the day alone: every date reads as its midnight."""
    from transmogrifai_tpu.ops import dates
    split = dates._day_and_ms

    def midnight(ms):
        day, rest = split(ms)
        return day, np.zeros_like(rest)
    mp.setattr(dates, "_day_and_ms", midnight)


def zero_coordinates_read_as_present(mp):
    """The (0, 0) coordinates are not missing: the fill is never applied and
    the null column is empty."""
    from transmogrifai_tpu.ops import geo
    arrays = geo._geo_arrays

    def all_present(col):
        arr, mask = arrays(col)
        return arr, np.ones_like(mask)
    mp.setattr(geo, "_geo_arrays", all_present)


def cramers_v_off_in_the_reference(mp):
    """The REFERENCE has no group rule: the program that has one disagrees
    with it on the columns kept."""
    mp.setattr(reference_module(), "cramers_v",
               lambda ids, width, y: float("nan"))


@pytest.mark.parametrize("fault", [day_of_month_dropped,
                                   millisecond_of_day_dropped,
                                   zero_coordinates_read_as_present,
                                   cramers_v_off_in_the_reference],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(cell, data, fault, monkeypatch):
    jax.clear_caches()
    fault(monkeypatch)
    rec = run.one_train(cell, data, "cpu")
    assert not rec["why_failed"], rec["why_failed"]
    p = rec["produced"]
    ref = cell.reference.reference(data, cell.config, STATED,
                                   cell.reference.question(p), seed=SEED)
    monkeypatch.undo()
    jax.clear_caches()
    ok, compared = run.verdict(cell, [p], ref)
    assert not ok and over(compared), compared


# (c) ----------------------------------------------------------------------

MS_DAY = 86400000
DATES = np.asarray(
    [0, 1, -1, MS_DAY - 1, MS_DAY, -MS_DAY, -MS_DAY - 1,
     1356998400000,                       # 2013-01-01
     1388534399999,                       # the last millisecond of 2013
     -2208988800000,                      # 1900-01-01
     -62135596800000,                     # year 1
     253402300799999,                     # the end of year 9999
     np.iinfo(np.int64).max, np.iinfo(np.int64).min + 1,
     int(30.44 * MS_DAY) - 1, int(30.44 * MS_DAY),
     int(365.2425 * MS_DAY) - 1, int(365.2425 * MS_DAY),
     -int(365.2425 * MS_DAY) - 1, 7 * MS_DAY - 1], np.int64)


@pytest.mark.parametrize("period", PERIODS)
def test_the_wire_gives_the_int64_phase_to_three_ulps(period):
    """``_period_fraction_device`` from (day, millisecond of the day) int32
    against ``_period_fraction`` from the int64 milliseconds: dates before
    1970, the ends of every period, the ends of int64 and 200,000 drawn
    ones.  Three float32 ulps of the phase (two divisions and a sum of
    positive terms); the last millisecond of a day rounds to 1.0 in float32
    in both forms."""
    from transmogrifai_tpu.ops import dates
    rng = np.random.default_rng(36)
    ms = np.r_[DATES, rng.integers(-2 ** 45, 2 ** 45, size=200000),
               rng.integers(1356998400000, 1388534400000, size=100000)]
    day, rest = dates._day_and_ms(ms)
    assert day.dtype == rest.dtype == np.int32
    assert 0 <= day.min() and day.max() < dates._DAY_CYCLE
    assert 0 <= rest.min() and rest.max() < MS_DAY
    got = np.asarray(jax.jit(
        lambda d, r: dates._period_fraction_device(d, r, period))(day, rest),
        np.float64)
    shift, length = reference_module().PERIODS[period]
    want = np.asarray([((m + shift) % length) / length for m in ms.tolist()])
    assert np.all(np.abs(got - want) <= 3 * ULP * np.maximum(want, ULP))
    assert got.min() >= 0.0 and got.max() <= 1.0
    host = dates._period_fraction(np.clip(ms, -2 ** 62, 2 ** 62), period)
    inside = np.abs(ms) < 2 ** 62
    assert np.all(np.abs(got - host)[inside] <= 4 * ULP)


def typed_columns(n=4099, seed=3):
    """A batch of two dates (one with nulls and dates before 1970) and two
    coordinates (one held as arrays with (0, 0) rows masked, one held as
    objects with None and an empty list)."""
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.columns import Column, ColumnBatch
    rng = np.random.default_rng(seed)
    when = rng.integers(-2 ** 41, 2 ** 41, size=n)
    when[:len(DATES)] = np.clip(DATES, -2 ** 62, 2 ** 62)
    seen = rng.random(n) > 0.1
    later = when + rng.integers(0, 10 ** 7, size=n)
    xyz = np.c_[40.75 + 0.03 * rng.standard_normal(n),
                -73.98 + 0.04 * rng.standard_normal(n),
                np.ones(n)].astype(np.float32)
    here = rng.random(n) > 0.05
    xyz[~here] = 0.0
    held = np.empty(n, dtype=object)
    for i in range(n):
        held[i] = (xyz[i, ::-1].astype(np.float64).tolist()
                   if (i % 7) else ([] if i % 2 else None))
    cols = {"when": Column(T.DateTime, when, seen),
            "later": Column(T.DateTime, later, None),
            "at": Column(T.Geolocation, xyz, here),
            "from": Column(T.Geolocation, held)}
    kinds = {"when": T.DateTime, "later": T.DateTime,
             "at": T.Geolocation, "from": T.Geolocation}
    return ColumnBatch(cols, n), kinds


@pytest.fixture(scope="module")
def typed_models():
    """The two vectorizers fitted on ``typed_columns``, their unstaged
    outputs worked on the host as the transforms did before they were
    staged, and the fused program's outputs."""
    from transmogrifai_tpu.compiled import ScoreProgram
    from transmogrifai_tpu.features import features_from_schema
    from transmogrifai_tpu.ops import dates, geo
    from transmogrifai_tpu.telemetry import REGISTRY, Tracer, use_tracer
    batch, kinds = typed_columns()
    from transmogrifai_tpu import types as T
    _, feats = features_from_schema(dict(kinds, y=T.RealNN), response="y")
    by_name = {f.name: f for f in feats}
    dv = dates.DateToUnitCircleVectorizer()
    dv.set_input(by_name["when"], by_name["later"])
    gv = geo.GeolocationVectorizer()
    gv.set_input(by_name["at"], by_name["from"])
    names = [dv.get_output().name, gv.get_output().name]
    dm, gm = dv.fit(batch), gv.fit(batch)
    before = REGISTRY.counters().get("transform.host_stages", 0)
    tracer = Tracer("staged")
    with use_tracer(tracer):
        out = ScoreProgram([[dm, gm]], names)(batch)
    return {"batch": batch, "dates": dm, "geo": gm, "spans": tracer.spans,
            "fused": {"dates": np.asarray(out[names[0]].values),
                      "geo": np.asarray(out[names[1]].values)},
            "host_stages": REGISTRY.counters()["transform.host_stages"]
            - before}


def test_staged_dates_equal_the_host_form(typed_models):
    """Fused and eager alike: sin and cos of the int64 phase to 2e-6 (three
    ulps of a phase times 2 pi, and the sine's own rounding), zeros where
    null, the null column exact."""
    from transmogrifai_tpu.ops import dates
    batch, model = typed_models["batch"], typed_models["dates"]
    want = []
    for name in ("when", "later"):
        col = batch[name]
        m = np.ones(len(col), bool) if col.mask is None else col.mask
        for p in PERIODS:
            ang = 2 * np.pi * dates._period_fraction(col.values, p).astype(
                np.float64)
            want += [np.where(m, np.sin(ang), 0.0),
                     np.where(m, np.cos(ang), 0.0)]
        want.append((~m).astype(np.float64))
    want = np.stack(want, axis=1)
    eager = np.asarray(model.transform(batch).values)
    for got in (typed_models["fused"]["dates"], eager):
        assert got.shape == want.shape == (len(batch), 18)
        assert got.dtype == np.float32
        assert np.max(np.abs(got - want)) <= 2e-6
        assert np.array_equal(got[:, 8], want[:, 8])        # the null bits
        assert np.array_equal(got[:, 17], np.zeros(len(batch)))
        assert np.all(got[~batch["when"].mask, :8] == 0.0)
    assert model.fitted["meta"].size == 18


def test_staged_coordinates_equal_the_host_form_digit_for_digit(typed_models):
    """The same arithmetic (a select and a concatenation): exact.  A (0, 0)
    row, a None and an empty list all take the fitted mean and set the null
    column."""
    batch, model = typed_models["batch"], typed_models["geo"]
    xyz, here = np.asarray(batch["at"].values), batch["at"].mask
    held = batch["from"].values
    there = np.asarray([bool(v) for v in held])
    other = np.asarray([v[:3] if v else [0.0] * 3 for v in held], np.float32)
    fills = np.asarray(model.fitted["fills"])
    assert fills[0] == pytest.approx(
        xyz[here].astype(np.float64).mean(axis=0), rel=1e-7)
    want = np.c_[np.where(here[:, None], xyz, fills[0]), ~here,
                 np.where(there[:, None], other, fills[1]), ~there
                 ].astype(np.float32)
    eager = np.asarray(model.transform(batch).values)
    assert np.array_equal(typed_models["fused"]["geo"], want)
    assert np.array_equal(eager, want)
    assert (~here).sum() > 100 and (~there).sum() > 500


def test_staged_prologues_run_under_stage_wires(typed_models):
    spans = {s.name: s for s in typed_models["spans"]}
    parent = spans["transform.stage_wires"]
    n = len(typed_models["batch"])
    for cls, wire_bytes in (("DateToUnitCircleModel",
                             2 * 8 * n + -(-n // 8)),
                            ("GeolocationVectorizerModel",
                             2 * (12 * n + -(-n // 8) + 12))):
        child = spans["transform.stage_wires." + cls]
        assert child.parent_id == parent.span_id
        assert child.attrs["rows"] == n and child.attrs["columns"] == 2
        assert child.attrs["wire_bytes"] == wire_bytes
    assert "transform.host_stage" not in spans
    assert typed_models["host_stages"] == 0


@pytest.mark.parametrize("n", [1, 7, 8, 9, 1000, 65537])
def test_packed_bits_round_trip_in_planes(n):
    """``pack_bits`` lays row r at bit ``r // W`` of word ``r % W`` (W the
    words of the wire) and ``unpack_bits_device`` gives the rows back, for a
    count of rows that 8 does not divide too; the padding bits are 0."""
    from transmogrifai_tpu.columns import pack_bits, unpack_bits_device
    bits = np.random.default_rng(n).random(n) < 0.4
    wire = pack_bits(bits)
    words = -(-n // 8)
    assert wire.dtype == np.uint8 and wire.shape == (words,)
    rows = np.arange(n)
    assert np.array_equal((wire[rows % words] >> (rows // words)) & 1, bits)
    assert int(np.unpackbits(wire).sum()) == int(bits.sum())
    back = unpack_bits_device(jnp.asarray(wire), n)
    assert back.dtype == jnp.float32 and back.shape == (n,)
    assert np.array_equal(np.asarray(back), bits.astype(np.float32))
    if n % 4 == 0:
        assert np.array_equal(
            np.asarray(unpack_bits_device(jnp.asarray(wire), n, (n // 4, 4))),
            bits.astype(np.float32).reshape(n // 4, 4))


def test_geo_arrays_of_objects_equal_the_loop():
    from transmogrifai_tpu.ops.geo import _geo_arrays
    batch, _ = typed_columns(n=513, seed=9)
    arr, mask = _geo_arrays(batch["from"])
    for i, v in enumerate(batch["from"].values):
        assert mask[i] == bool(v)
        assert arr[i].tolist() == (np.asarray(v[:3], np.float32).tolist()
                                   if v else [0.0, 0.0, 0.0])
    again, every = _geo_arrays(batch["at"])
    assert again is batch["at"].values and every is batch["at"].mask


def test_a_transformed_row_is_the_batchs_row(typed_models):
    """Local scoring's one-row batch goes through the same staged form."""
    from transmogrifai_tpu import types as T
    batch = typed_models["batch"]
    i = int(np.flatnonzero(batch["when"].mask)[40])
    row = {"when": T.DateTime(int(batch["when"].values[i])),
           "later": T.DateTime(int(batch["later"].values[i]))}
    got = np.asarray(typed_models["dates"].transform_row(row).value)
    assert np.allclose(got, typed_models["fused"]["dates"][i], atol=1e-6)


# (d) ----------------------------------------------------------------------

def geolocation_feature():
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.features import features_from_schema
    _, (f,) = features_from_schema({"y": T.RealNN, "g": T.Geolocation},
                                   response="y")
    return f


@pytest.mark.parametrize("spread", ["a city", "a planet", "all missing",
                                    "signed zeros"])
def test_array_path_gives_the_python_branchs_bins(spread):
    """A coordinate column held as ``[N, 3]`` float32 with a mask against
    the same rows held as lists: presence, distribution and sketch bin for
    bin, and no row walked in Python on the array's side.  "signed zeros"
    has 0.0 and -0.0 in one column, which are equal and hash apart."""
    from transmogrifai_tpu import filters, types as T
    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.telemetry import REGISTRY
    rng = np.random.default_rng(5)
    n = 20000
    wide = 60.0 if spread == "a planet" else 0.03
    xyz = np.c_[40.75 + wide * rng.standard_normal(n),
                -73.98 + wide * rng.standard_normal(n),
                rng.integers(0, 3, size=n)].astype(np.float32)
    xyz[:5] = [[0.0, -0.0, 1.0], [np.nan, 1.0, 1.0], [1e-40, 2.0, 0.0],
               [np.inf, -np.inf, 2.0], [40.75, -73.98, 1.0]]
    if spread == "signed zeros":
        xyz[5:2000:2, 0], xyz[6:2000:2, 0] = 0.0, -0.0
    here = (np.zeros(n, bool) if spread == "all missing"
            else rng.random(n) > 0.03)
    held = np.empty(n, dtype=object)
    for i in range(n):
        held[i] = xyz[i].tolist() if here[i] else None
    f = geolocation_feature()
    arrays, lists = Column(T.Geolocation, xyz, here), Column(T.Geolocation,
                                                            held)
    rows = REGISTRY.counter("rff.python_rows")
    before = rows.value
    a = filters.compute_distribution(f, arrays, 100, 255)[0]
    sa = filters.compute_sketches([f], ColumnBatch({"g": arrays}, n),
                                  text_bins=255)
    assert rows.value == before
    b = filters.compute_distribution(f, lists, 100, 255)[0]
    sb = filters.compute_sketches([f], ColumnBatch({"g": lists}, n),
                                  text_bins=255)
    assert rows.value == before + 2 * n
    assert (a.count, a.nulls) == (b.count, b.nulls) == (n, int((~here).sum()))
    assert np.array_equal(a.distribution, b.distribution)
    assert a.distribution.sum() == 3 * here.sum()
    ka, kb = sa[("g", None)], sb[("g", None)]
    assert (ka.count, ka.nulls) == (kb.count, kb.nulls) == (a.count, a.nulls)
    assert np.array_equal(ka.text_counts, kb.text_counts)
    assert np.array_equal(ka.text_counts, a.distribution)
    assert np.array_equal(filters._value_presence(arrays), here)


def whole_column_range_and_histogram(values, present, bins, value_range):
    """What one pass over the whole column gives: numpy, no blocks."""
    arr = np.asarray(values, np.float64)
    keep = present & np.isfinite(arr)
    if not keep.any():
        return None, np.zeros(bins)
    found = float(arr[keep].min()), float(arr[keep].max())
    lo, hi = value_range or found
    if lo == hi:
        hi = lo + 1.0
    return found, np.histogram(arr[keep], bins=bins, range=(lo, hi))[0]


@pytest.mark.parametrize("column", ["epoch ms", "amounts", "all missing",
                                    "one value", "shared range"])
def test_a_numeric_column_by_blocks_is_the_whole_columns(column):
    """RawFeatureFilter walks a numeric column a block of rows at a time:
    range and histogram are the whole column's, count for count."""
    from transmogrifai_tpu import filters, types as T
    from transmogrifai_tpu.columns import Column
    from transmogrifai_tpu.features import features_from_schema
    rng = np.random.default_rng(21)
    n = 2 * filters._BLOCK_ROWS + 4099
    present, given = rng.random(n) > 0.02, None
    if column == "epoch ms":
        kind = T.DateTime
        values = 1356912000000 + rng.integers(0, 31536000000, size=n)
    else:
        kind = T.Currency
        values = rng.gamma(2.0, 6.0, size=n).astype(np.float32)
        values[::977], values[5::1201] = np.nan, np.inf
    if column == "all missing":
        present = np.zeros(n, bool)
    if column == "one value":
        values = np.full(n, 2.5, np.float32)
    if column == "shared range":
        given = (-3.0, 500.0)
    _, (f,) = features_from_schema({"y": T.RealNN, "x": kind}, response="y")
    col = Column(kind, values, present)
    found, hist = whole_column_range_and_histogram(values, present, 100,
                                                   given)
    ranges = filters.numeric_ranges(f, col)
    assert ranges == ({} if found is None else {None: found})
    dist = filters.compute_distribution(
        f, col, 100, 255, ranges={None: given} if given else ranges)[0]
    assert np.array_equal(dist.distribution, hist)
    assert (dist.count, dist.nulls) == (n, int((~present).sum()))
    assert np.array_equal(filters._histogram_of(values, present, kind, 100,
                                                255), hist if given is None
                          else whole_column_range_and_histogram(
                              values, present, 100, None)[1])


@pytest.mark.parametrize("presence", ["leaks", "independent", "all", "none"])
def test_presence_correlation_is_pearsons(presence):
    from transmogrifai_tpu import filters
    rng = np.random.default_rng(22)
    n = 2 * filters._BLOCK_ROWS + 17
    y = (rng.random(n) < 0.4).astype(np.float32)
    p = {"leaks": (y > 0) ^ (rng.random(n) < 0.01),
         "independent": rng.random(n) < 0.9,
         "all": np.ones(n, bool), "none": np.zeros(n, bool)}[presence]
    got = filters._CentredLabel(y).correlation_with(p)
    if presence in ("all", "none"):
        assert np.isnan(got)
    else:
        assert got == pytest.approx(
            np.corrcoef(p.astype(np.float64), y.astype(np.float64))[0, 1],
            rel=1e-12, abs=1e-15)
    assert np.isnan(filters._CentredLabel(np.ones(n)).correlation_with(
        rng.random(n) < 0.5))


def test_a_leaking_presence_is_dropped_and_a_plain_one_kept():
    """The drop rule end to end, over the block walk."""
    from transmogrifai_tpu import filters, types as T
    from transmogrifai_tpu.columns import Column, ColumnBatch
    from transmogrifai_tpu.features import features_from_schema
    rng = np.random.default_rng(23)
    n = filters._BLOCK_ROWS + 99
    y = (rng.random(n) < 0.5).astype(np.float32)
    schema = {"y": T.RealNN, "leak": T.Real, "plain": T.Real}
    label, predictors = features_from_schema(schema, response="y")
    batch = ColumnBatch({
        "y": Column(T.RealNN, y),
        "leak": Column(T.Real, rng.random(n).astype(np.float32), y > 0),
        "plain": Column(T.Real, rng.random(n).astype(np.float32),
                        rng.random(n) < 0.7)}, n)
    _, dropped, results = filters.RawFeatureFilter().filter_batch(
        batch, [label] + list(predictors))
    assert [f.name for f in dropped] == ["leak"]
    assert "null-label correlation 1.0000" in results.reasons["leak"][0]


def test_the_pivots_columns_are_walked_in_the_prefetch(cell, data,
                                                       monkeypatch):
    """On an accelerator the six string columns of the pivots are profiled
    up front, side by side on the pool and counted without a cap, so that
    RawFeatureFilter and the pivots' fit find them walked."""
    from transmogrifai_tpu import workflow
    from transmogrifai_tpu.telemetry import REGISTRY, Tracer, use_tracer
    monkeypatch.setattr(workflow, "PREFETCH_MIN_ROWS", 1000)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wf = cell.program.build(data, cell.config)
    batch = wf.generate_raw_data()
    strings = [n for n in batch.names() if batch[n].is_host_object()]
    assert len(strings) == 6
    tracer = Tracer("prefetch")
    scans = REGISTRY.counter("text_profile.scan")
    before = scans.value
    with use_tracer(tracer):
        wf._prefetch_text_profiles(batch)
    (walked,) = [s for s in tracer.spans
                 if s.name == "prefetch.text_profiles"]
    assert walked.attrs["columns"] == 6
    assert scans.value == before + 6
    monkeypatch.undo()
    misses = REGISTRY.counter("text_profile.intern.miss")
    before = (scans.value, misses.value)
    for name in strings:
        counts = batch[name]._text_profile.values(-1).value_counts()
        present = data[name][data[name] != None]         # noqa: E711
        assert sum(counts.values()) == len(present)
    wf._raw_feature_filter.filter_batch(batch, wf.raw_features)
    assert (scans.value, misses.value) == before


# (e) ----------------------------------------------------------------------

def test_the_solver_keeps_a_column_far_from_zero_for_its_spread():
    """A latitude (40.75 +- 0.03) beside an ordinary column: the grid fit's
    coefficients are those of the same fit on the columns centred by hand,
    to 1e-4 of their size, and the intercepts differ by the shift.  (About
    0, float32 leaves E[x^2] - mean^2 of such a column no digits: the scale
    read 0.044 for 0.034 and the fits moved by 0.17 of an AuPR.)"""
    from transmogrifai_tpu.models import solvers
    rng = np.random.default_rng(12)
    n = 20000
    z = rng.standard_normal((n, 2))
    X = np.c_[40.75 + 0.03 * z[:, 0], 2.0 * z[:, 1]].astype(np.float32)
    y = (rng.random(n) < 1 / (1 + np.exp(-(z[:, 0] - z[:, 1])))).astype(
        np.float32)
    shift = np.asarray([40.75, 0.0], np.float32)
    W = np.ones((2, n), np.float32)
    W[0, ::3] = 0.0
    l2, l1 = jnp.asarray([0.01, 0.1]), jnp.asarray([0.0, 0.01])
    kw = dict(loss="logistic", max_iter=50, tol=1e-6, n_classes=1)
    far = solvers.linear_grid_fit(jnp.asarray(X), jnp.asarray(y),
                                  jnp.asarray(W), l2, l1, **kw)
    near = solvers.linear_grid_fit(jnp.asarray(X - shift), jnp.asarray(y),
                                   jnp.asarray(W), l2, l1, **kw)
    a, b = np.asarray(far.coef), np.asarray(near.coef)
    assert a.shape == (2, 2, 2) and np.abs(b[..., 0]).min() > 5.0
    assert np.max(np.abs(a - b) / np.abs(b)) < 1e-4
    moved = np.asarray(near.intercept - far.intercept)[..., 0]
    assert moved == pytest.approx(40.75 * b[..., 0], rel=1e-4)
    mean, scale = solvers.standardize_moments(jnp.asarray(X),
                                              jnp.asarray(W[1]), True)
    assert np.asarray(scale) == pytest.approx(
        X.astype(np.float64).std(axis=0), rel=1e-4)
    assert np.asarray(mean) == pytest.approx(
        X.astype(np.float64).mean(axis=0), abs=1e-5)


@pytest.mark.parametrize("columns", ["counts and indicators", "term counts"])
def test_the_other_cells_columns_have_no_pivot(columns):
    """A column of the Criteo cells (floored Pareto counts, indicators of
    a null or a pivoted value) or of the text cell (hashed term counts) is
    not far from 0 for its spread: its pivot is 0 and its moments are, bit
    for bit, what E[x^2] - mean^2 about 0 gives.  What IS far: a constant,
    and an indicator that is 1 in over 99.6 % of the rows (mean over 16
    deviations), which no cell has; about 1 its moments keep their digits."""
    from transmogrifai_tpu.models import solvers
    rng = np.random.default_rng(12)
    n = 3 * solvers._PIVOT_ROWS
    if columns == "term counts":
        X = rng.poisson(0.16, size=(n, 64)).astype(np.float32)
        expect_far = []
    else:
        shares = np.asarray([0.003, 0.03, 0.2, 0.45, 0.77, 0.9, 0.97, 0.99])
        X = np.c_[np.floor(rng.pareto(1.2, size=(n, 8)) * 3.0),
                  rng.random((n, 8)) < shares,
                  np.full((n, 1), 7.0), rng.random((n, 1)) < 0.999
                  ].astype(np.float32)
        expect_far = [16, 17]
    Xd = jnp.asarray(X).astype(jnp.bfloat16)
    w = jnp.asarray((rng.random(n) < 0.67).astype(np.float32))
    far = np.flatnonzero(np.asarray(solvers.column_pivot(Xd))).tolist()
    assert far == expect_far
    plain = [j for j in range(X.shape[1]) if j not in far]
    mean, scale = solvers.standardize_moments(Xd, w, True)
    wn = w / jnp.sum(w)
    Xf = Xd.astype(jnp.float32)
    mean0 = wn @ Xf
    scale0 = jnp.sqrt(jnp.maximum(wn @ (Xf * Xf) - mean0 * mean0, 1e-12))
    assert np.array_equal(np.asarray(mean)[plain], np.asarray(mean0)[plain])
    assert np.array_equal(np.asarray(scale)[plain], np.asarray(scale0)[plain])
    if far:
        w64 = np.asarray(wn, np.float64)
        want = np.sqrt(w64 @ X[:, 17] ** 2 - (w64 @ X[:, 17]) ** 2)
        assert float(scale[17]) == pytest.approx(want, rel=1e-4)
        assert float(scale[16]) == pytest.approx(1e-6)


# (f) ----------------------------------------------------------------------

def test_spans_and_counters_of_a_traced_train(cell, traced):
    names = [s.name for s in traced["spans"]]
    for cls in ("OneHotModel", "DateToUnitCircleModel",
                "GeolocationVectorizerModel"):
        (child,) = [s for s in traced["spans"]
                    if s.name == "transform.stage_wires." + cls]
        assert child.attrs["rows"] == cell.rows
        assert child.attrs["columns"] == {"OneHotModel": 6}.get(cls, 2)
    (dates,) = [s for s in traced["spans"]
                if s.name == "transform.stage_wires.DateToUnitCircleModel"]
    assert dates.attrs["wire_bytes"] == 2 * (8 * cell.rows + cell.rows // 8)
    assert "transform.host_stage" not in names
    assert traced["counted"] == {"transform.host_stages": 0,
                                 "rff.python_rows": 0,
                                 "sanity.groups_dropped": 1,
                                 "selector.family_rounds": 4,
                                 "pivot.python_values": 0}
    profile = traced["profile"]
    # inline, as every train under 100,000 rows is: the flush's span holds
    # its stages' prologues (with a pool they are workers' jobs, started at
    # the fits: tests/test_stage_wire_jobs.py)
    assert profile["transform.stage_wires"]["total_s"] >= sum(
        profile["transform.stage_wires." + c]["total_s"]
        for c in ("OneHotModel", "DateToUnitCircleModel",
                  "GeolocationVectorizerModel"))


@pytest.mark.parametrize("other", ["mixed_sweep", "text_sweep"])
def test_no_host_stage_and_no_python_row_in_the_other_programs(other):
    """The Criteo and text programs, a small train each under one grid
    point: both counters are there and read 0."""
    tiny = run.cpu_cells()[other]
    small = run.Cell(MANIFEST, other, 2048, tiny["limits"])
    selector = {k: dict(v, **{a: v[a][:1] for a in common.grid_keys(v)},
                        max_iter=2)
                for k, v in small.config["selector"].items()}
    small.config = dict(small.config, selector=selector)
    data = small.program.make_data(2048, SEED, small.config)
    rec, after, before = counted(lambda: run.one_train(small, data, "cpu"))
    jax.clear_caches()
    assert not rec["why_failed"], rec["why_failed"]
    for name in ("transform.host_stages", "rff.python_rows",
                 "sanity.groups_dropped", "pivot.python_values"):
        assert after[name] is not None and after[name] == before[name], name


def test_typed_scopes_are_in_the_fused_program(typed_models):
    from transmogrifai_tpu.compiled import _stage_scope
    scopes = {_stage_scope(typed_models[k]) for k in ("dates", "geo")}
    assert scopes == {"transform.DateToUnitCircleModel.OPVector",
                      "transform.GeolocationVectorizerModel.OPVector"}
    batch = typed_models["batch"]
    for key, scope in (("dates", "transform.DateToUnitCircleModel.OPVector"),
                       ("geo", "transform.GeolocationVectorizerModel"
                               ".OPVector")):
        wire, body = typed_models[key].transform_staged(batch)

        def scoped(w, body=body, scope=scope):
            with jax.named_scope(scope):
                return body(w).values
        assert scope in jax.jit(scoped).lower(wire).compile().as_text()


# (g) ----------------------------------------------------------------------

NEW_READERS = ("stage_wires_s", "rff_s", "host_stages")


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_report_nothing_where_there_is_nothing(name, monkeypatch):
    from transmogrifai_tpu import telemetry
    monkeypatch.setattr(telemetry, "REGISTRY", telemetry.MetricsRegistry())
    assert reader(name).read({"trains": [], "trace": None}) is None
    # a traced run of a program that has neither the spans nor the counter
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
    row = {"count": 3, "self_s": 0.1, "jit_s": 0.0}
    reg.gauge("train.span_profile").set(
        {"transform.stage_wires": dict(row, total_s=1.5),
         "transform.stage_wires.OneHotModel": dict(row, total_s=1.0),
         "rff.distributions": dict(row, total_s=2.0),
         "rff.decide": dict(row, total_s=0.25)})
    reg.counter("transform.host_stages").inc(6)
    ctx = {"trains": [{}, {}], "trace": {"busy_s": 1.0, "window_s": 2.0}}
    assert reader("stage_wires_s").read(ctx) == 1.5
    assert reader("rff_s").read(ctx) == 2.25
    assert reader("host_stages").read(ctx) == 2.0     # set-up's train too
    untraced = dict(ctx, trace=None)
    assert reader("stage_wires_s").read(untraced) is None
    assert reader("rff_s").read(untraced) is None
    assert reader("host_stages").read(untraced) == 2.0


def test_readers_read_a_traced_train(traced):
    ctx = {"trains": [traced], "trace": {"busy_s": 1.0, "window_s": 2.0}}
    profile = traced["profile"]
    assert reader("stage_wires_s").read(ctx) == \
        profile["transform.stage_wires"]["total_s"] > 0.0
    assert reader("rff_s").read(ctx) == pytest.approx(
        profile["rff.distributions"]["total_s"]
        + profile["rff.decide"]["total_s"])
    assert reader("host_stages").read(ctx) == 0.0


def test_readers_and_appended_cells_say_what_benchmark_json_says():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW_READERS:
        mod, m = reader(name), entries[name]
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
        # the cell's own readers, and the next cell after it
        assert m["workloads"] == [CELL, "maps_sweep"] \
            and m["better"] == "lower"
        assert m["layer"] in {e["layer"] for e in MANIFEST["per_layer"][:21]}
    joined = ("prologue_s", "selector_s", "host_link_MB", "window_compiles",
              "setup_compile_s", "device_idle_share", "sweep_mfu",
              "peak_hbm_GiB", "prologue_idle_s", "transform_s", "sanity_s",
              "refit_s", "train_jit_s", "text_profile_s")
    for name in joined:
        assert entries[name]["workloads"][-2:] == [CELL, "maps_sweep"], name
    # what ISSUE 39 appended after them reads something in every cell
    every_cell = ("prologue_wait_s", "prologue_queue_s", "stage_fit_s")
    for name in set(entries) - set(joined) - set(NEW_READERS) - set(
            every_cell):
        assert CELL not in entries[name]["workloads"], name
    assert [m["name"] for m in MANIFEST["per_layer"][21:24]] == list(
        NEW_READERS)
    assert [w["name"] for w in MANIFEST["workloads"]][:4] == [
        "mixed_sweep", "mixed_sweep_x4", "text_sweep", CELL]
    assert [c["name"] for c in MANIFEST["configs"]][3] == "nyc_taxi_typed"


# (h) ----------------------------------------------------------------------

def test_configuration_keeps_every_default_and_states_its_cuts():
    from transmogrifai_tpu.ops.transmogrify import TransmogrifierDefaults as T
    from transmogrifai_tpu.preparators import sanity_checker as sc
    from transmogrifai_tpu.selector import DefaultSelectorParams as D
    cfg = run.load_json("benchmark", "configs", "nyc_taxi_typed.json")
    text = run.load_json("benchmark", "configs", "amazon_polarity_text.json")
    entry = run.by_name(MANIFEST["configs"], "nyc_taxi_typed", "config")
    assert entry["file"] == "benchmark/configs/nyc_taxi_typed.json"
    assert entry["reduced"] == cfg["reduced"] == ["rows", "model_types"]
    assert set(cfg["reduced_note"]) == {"rows", "model_types"}
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for said in ("trip_data", "trip_fare", "173,179,759", "tipped", "2013"):
        assert said in cfg["source"], said
    assert cfg["source_rows"] == 173179759
    assert 2097152 <= cfg["rows"] <= 8388608 and cfg["rows"] % 262144 == 0
    assert cfg["model_types"] == list(cfg["selector"]) == [
        "OpLogisticRegression", "OpLinearSVC"]
    for key in ("raw_feature_filter", "folds", "fold_seed", "selector",
                "validation_metric", "racing", "guarantees", "work",
                "source_model_types"):
        assert cfg[key] == text[key], key
    t = cfg["transmogrify"]
    assert (t["top_k"], t["min_support"], t["track_nulls"]) == (
        T.TOP_K, T.MIN_SUPPORT, T.TRACK_NULLS)
    assert tuple(t["circular_date_periods"]) == \
        T.CIRCULAR_DATE_REPRESENTATIONS == PERIODS
    assert (t["num_hashes"], t["max_categorical_cardinality"]) == (
        T.DEFAULT_NUM_OF_FEATURES, T.MAX_CATEGORICAL_CARDINALITY)
    s = cfg["sanity_checker"]
    assert (s["max_correlation"], s["min_correlation"], s["min_variance"],
            s["max_cramers_v"], s["sample_upper_limit"]) == (
        sc.DEFAULT_MAX_CORRELATION, sc.DEFAULT_MIN_CORRELATION,
        sc.DEFAULT_MIN_VARIANCE, sc.DEFAULT_MAX_CRAMERS_V,
        sc.DEFAULT_SAMPLE_UPPER_LIMIT)
    assert cfg["selector"]["OpLinearSVC"] == {
        "reg_param": D.REGULARIZATION, "max_iter": D.MAX_ITER_LIN[0],
        "tol": D.TOL[0]}
    assert cfg["precision"]["matrix_storage"] == \
        text["precision"]["matrix_storage"]
    assert cfg["precision"]["control"] == text["precision"]["control"]
    assert "40.75" in cfg["precision"]["coordinates"]
    assert set(cfg["assumed"]) >= {"typing", "generator", "profiles",
                                   "label", "splitter"}
    assert set(cfg["schema"]) == {"label", "left_out"} | set(
        sum((list(getattr(importlib.import_module(
            "benchmark.programs.nyc_taxi_typed"), group))
            for group in ("IDS", "PICKLISTS", "DATES", "INTEGRALS", "REALS",
                          "CURRENCIES", "GEOS")), []))
    assert len(cfg["schema"]) == 17 + 2
    g = cfg["generator"]
    assert g["medallion"]["levels"] == 13437
    assert g["tipped_share"]["CRD"] == 0.97 and g["card"]["share"] == 0.54
    assert abs(sum(p["pickup_weight"] for p in g["places"]) - 1.0) < 1e-9
    assert abs(sum(p["dropoff_weight"] for p in g["places"]) - 1.0) < 1e-9
    wl = run.by_name(MANIFEST["workloads"], CELL, "workload")
    assert (wl["config"], wl["traffic"], wl["chips"]) == (
        "nyc_taxi_typed", "mixed_sweep", 1)
    assert len(wl["why"]) <= 200 and len(entry["why"]) <= 200
    limits = run.load_json("benchmark", "limits", CELL + ".json")
    assert set(limits) - {"why"} == set(TINY["limits"])
    assert all(any(k in said for said in limits["why"])
               for k in TINY["limits"])
    assert all(limits[k] >= 0.0 for k in TINY["limits"])
    with open(os.path.join(ROOT, "benchmark", "fixtures",
                           "cpu_cells_typed_sweep.json")) as fh:
        assert list(json.load(fh)["cells"]) == [CELL]


def test_generator_draws_what_the_configuration_states(cell, data):
    """Same seed, same rows; the stated shares; one entry a row, so that
    halves of the arrays are halves of the rows."""
    again = cell.program.make_data(cell.rows, SEED, cell.config)
    assert all(np.array_equal(data[k], again[k]) for k in data)
    other = cell.program.make_data(cell.rows, SEED + 1, cell.config)
    assert not np.array_equal(data["label"], other["label"])
    assert len(data) == 1 + 17 + 2
    assert all(len(v) == cell.rows for v in data.values())
    g = cell.config["generator"]
    paid = data["payment_type"]
    assert abs((paid == "CRD").mean() - g["card"]["share"]) < 0.01
    tipped = data["label"] > 0.5
    assert abs(tipped[paid == "CRD"].mean() - 0.97) < 0.01
    assert tipped[paid == "CSH"].mean() < 0.002
    assert 0.50 < tipped.mean() < 0.55
    vts = data["vendor_id"] == "VTS"
    assert all(v is None for v in data["store_and_fwd_flag"][vts])
    assert all(v in ("Y", "N") for v in data["store_and_fwd_flag"][~vts])
    for name in ("pickup", "dropoff"):
        here = data[name + ".present"]
        assert 0.015 < (~here).mean() < 0.03
        assert np.all(data[name][~here] == 0.0)
        assert np.all(np.abs(data[name][here, 0] - 40.75) < 0.5)
        assert np.all(data[name][here, 2] == g["accuracy"])
    assert np.all(data["dropoff_datetime"] - data["pickup_datetime"]
                  == 1000 * data["trip_time_in_secs"])
    first = data["pickup_datetime"].min()
    assert g["first_monday_ms"] <= first
    assert data["pickup_datetime"].max() < g["first_monday_ms"] + \
        g["weeks"] * 7 * MS_DAY
    assert set(np.unique(data["surcharge"])) == {0.0, 0.5, 1.0}
    assert data["fare_amount"].min() >= g["flag_drop"]
    assert np.all(data["fare_amount"] * 2 == np.round(data["fare_amount"]
                                                      * 2))
    assert len(set(data["medallion"])) > 5000
    assert all(len(s) == 32 for s in data["hack_license"][:100])


def test_a_program_without_the_staged_forms_is_refused_at_once(
        cell, data, monkeypatch):
    """The parent commit laid under this cell's files has to fail soon and
    cleanly (it would walk 12 M coordinates in Python a train): ``build``
    leaves with an exit code before anything is trained."""
    from transmogrifai_tpu.ops.geo import GeolocationVectorizerModel
    monkeypatch.setattr(GeolocationVectorizerModel, "supports_staging",
                        False)
    with pytest.raises(SystemExit, match="staged GeolocationVectorizerModel"):
        cell.program.build(data, cell.config)


# (i) ----------------------------------------------------------------------

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
    assert {"host_stages", "host_link_MB", "prologue_s", "selector_s",
            "window_compiles"} <= got
    assert res["metrics"]["host_stages"]["value"] == 0.0
    # what is read from a device trace, or from spans beside one, is not
    assert not got & {"device_idle_share", "sweep_mfu", "peak_hbm_GiB",
                      "prologue_idle_s", "text_profile_s", "stage_wires_s",
                      "rff_s"}


def test_selftest_has_four_cells():
    """The four cells up to this one, and ``maps_sweep`` after it."""
    assert sorted(run.cpu_cells()) == ["maps_sweep", "mixed_sweep",
                                       "mixed_sweep_x4", "text_sweep", CELL]
    assert {w["name"] for w in MANIFEST["workloads"]} == set(run.cpu_cells())
