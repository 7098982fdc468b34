"""Temporal vectorizers (reference: core/.../stages/impl/feature/
DateToUnitCircleTransformer.scala, DateListVectorizer.scala,
TimePeriodTransformer.scala).

Dates are epoch-milliseconds (Integral storage).  Unit-circle embedding —
sin/cos of the requested periods — is a pure device op; the period extraction
(hour-of-day etc.) is modular arithmetic on ms, jit-friendly.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..columns import Column, ColumnBatch, pack_bits, unpack_bits_device
from ..stages.base import (ColumnWired, Estimator, Transformer,
                           TransformerModel)
from ..types import Date, DateList, Integral, OPVector, Real
from ..vector_meta import NULL_INDICATOR, VectorColumnMeta, VectorMeta

_MS_HOUR = 3600 * 1000
_MS_DAY = 24 * _MS_HOUR
_MS_WEEK = 7 * _MS_DAY
# epoch 1970-01-01 was a Thursday; shift so 0 = Monday like ISO
_EPOCH_DOW_SHIFT = 3 * _MS_DAY
_MS_YEAR = int(365.2425 * _MS_DAY)


def _period_fraction(ms: np.ndarray, period: str) -> np.ndarray:
    """Fraction in [0, 1) of the given circular period.

    The modulo runs on host in int64: epoch-milliseconds (~1.5e12) overflow
    int32 and lose ~131 s of resolution in float32, so only the small
    remainder is converted to float32 for the device sin/cos."""
    ms = np.asarray(ms, np.int64)
    if period == "HourOfDay":
        shift, per = 0, _MS_DAY
    elif period == "DayOfWeek":
        shift, per = _EPOCH_DOW_SHIFT, _MS_WEEK
    elif period == "DayOfMonth":
        # approximate month as 30.44 days (exact calendar month needs host calc)
        shift, per = 0, int(30.44 * _MS_DAY)
    elif period == "DayOfYear":
        shift, per = 0, _MS_YEAR
    else:
        raise ValueError(f"unknown time period {period}")
    return (((ms + shift) % per) / per).astype(np.float32)


# The wire of a date column: the chip has no int64, so a value crosses as two
# int32, its day and the millisecond of that day.  Every period is a whole
# number of some unit of a day (a week 7 days, the "month" 3,044 hundredths,
# the "year" 3,652,425 ten-thousandths), so whole periods are cast out in
# int32 arithmetic and only a remainder under 2**24 is ever made a float.
# The day is sent modulo _DAY_CYCLE, a whole number of every period (761
# days are 25 "months", 146,097 days 400 "years" and 20,871 weeks), so any
# int64 millisecond has a wire.
_DAY_CYCLE = 761 * 146097
# period -> (days added, units in a day, units in the period)
_PERIOD_UNITS = {"HourOfDay": (0, 1, 1), "DayOfWeek": (3, 1, 7),
                 "DayOfMonth": (0, 100, 3044),
                 "DayOfYear": (0, 10000, 3652425)}


def _day_and_ms(ms) -> tuple:
    """int64 epoch milliseconds -> (day modulo _DAY_CYCLE, millisecond of
    the day), both int32; floor division, so dates before 1970 too.  One
    pass of native/datewire.cpp over a 1-D array, the GIL released; numpy
    where there is no toolchain or another shape."""
    from ..native import load
    ms = np.asarray(ms, np.int64)
    native = load("datewire") if ms.ndim == 1 else None
    if native is not None and ms.dtype.isnative and ms.flags.aligned:
        return native.day_and_ms(ms)
    day, ms_of_day = np.divmod(ms, _MS_DAY)
    return (day % _DAY_CYCLE).astype(np.int32), ms_of_day.astype(np.int32)


def _period_fraction_device(day, ms_of_day, period: str):
    """``_period_fraction`` from the int32 wire, traceable.  The whole units
    elapsed in the period and the rest of the last unit are divided apart and
    added, two positive float32 terms: within three float32 ulps of the
    fraction the int64 form gives."""
    if period not in _PERIOD_UNITS:
        raise ValueError(f"unknown time period {period}")
    shift, per_day, per_period = _PERIOD_UNITS[period]
    # the fewest days that are a whole number of periods: day * per_day
    # stays inside int32 once the day is reduced by them
    cycle = per_period // math.gcd(per_period, per_day)
    ms_unit = _MS_DAY // per_day
    whole = ms_of_day // ms_unit
    rest = (ms_of_day - whole * ms_unit).astype(jnp.float32)
    units = (((day + shift) % cycle) * per_day + whole) % per_period
    return (units.astype(jnp.float32) / per_period
            + rest / (float(ms_unit) * per_period))


class DateToUnitCircleModel(ColumnWired, TransformerModel):
    out_kind = OPVector
    is_device_op = False  # int64 host split into the int32 wire, then device
    supports_staging = True

    def column_wire(self, i: int, col: Column):
        """``day{i}``, ``ms{i}``: input ``i`` as (day, millisecond of the
        day) int32; ``null{i}``: its null bits packed, where it has a mask.
        Nothing fitted is read."""
        wire = dict(zip((f"day{i}", f"ms{i}"), _day_and_ms(col.values)))
        if col.mask is not None:
            wire[f"null{i}"] = pack_bits(~np.asarray(col.mask))
        return wire

    def transform_staged(self, batch: ColumnBatch, parts=None):
        """Host prologue: every date's ``column_wire`` (or ``parts`` made
        by it already).  Device body: the periods' phases, sin and cos,
        zeros where null, the null column."""
        periods = list(self.get("periods"))
        track_nulls = self.get("track_nulls", True)
        parts = self.column_wires(batch) if parts is None else parts
        wire = {k: v for part in parts for k, v in part.items()}
        count = len(self.input_features)
        meta = self.fitted["meta"]

        def body(w):
            outs = []
            for i in range(count):
                day, ms = w[f"day{i}"], w[f"ms{i}"]
                null = (unpack_bits_device(w[f"null{i}"], day.shape[0])
                        if f"null{i}" in w
                        else jnp.zeros(day.shape[0], jnp.float32))
                here = 1.0 - null
                for p in periods:
                    ang = 2 * jnp.pi * _period_fraction_device(day, ms, p)
                    outs += [jnp.sin(ang) * here, jnp.cos(ang) * here]
                if track_nulls:
                    outs.append(null)
            return Column(OPVector, jnp.stack(outs, axis=1), meta=meta)

        return wire, body

    def transform(self, batch: ColumnBatch) -> Column:
        """The staged form run eagerly: one arithmetic for the fused program,
        local scoring and every eager path."""
        wire, body = self.transform_staged(batch)
        return body(wire)


class DateToUnitCircleVectorizer(Estimator):
    """sin/cos circular embedding of date periods
    (≙ DateToUnitCircleTransformer + transmogrify's circular-date default)."""

    out_kind = OPVector

    def __init__(self, periods: Sequence[str] = ("HourOfDay", "DayOfWeek",
                                                 "DayOfMonth", "DayOfYear"),
                 track_nulls: bool = True, **params):
        super().__init__(periods=list(periods), track_nulls=track_nulls, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        cols_meta: List[VectorColumnMeta] = []
        for f in self.input_features:
            for p in self.get("periods"):
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, descriptor_value=f"sin({p})"))
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, descriptor_value=f"cos({p})"))
            if self.get("track_nulls", True):
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, indicator_value=NULL_INDICATOR))
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(DateToUnitCircleModel(
            fitted={"meta": meta}, **self.params))


class TimePeriodTransformer(Transformer):
    """Date → integral period value (≙ TimePeriodTransformer.scala)."""

    out_kind = Integral

    def __init__(self, period: str = "DayOfWeek", **params):
        super().__init__(period=period, **params)

    def transform(self, batch: ColumnBatch) -> Column:
        (f,) = self.input_features
        col = batch[f.name]
        v = np.asarray(col.values, np.int64)
        p = self.get("period")
        if p == "HourOfDay":
            out = (v % _MS_DAY) // _MS_HOUR
        elif p == "DayOfWeek":
            out = ((v + _EPOCH_DOW_SHIFT) % _MS_WEEK) // _MS_DAY + 1
        elif p == "DayOfMonth":
            out = (v % int(30.44 * _MS_DAY)) // _MS_DAY + 1
        elif p == "DayOfYear":
            out = (v % _MS_YEAR) // _MS_DAY + 1
        elif p == "WeekOfYear":
            out = (v % _MS_YEAR) // _MS_WEEK + 1
        elif p == "MonthOfYear":
            out = (v % _MS_YEAR) // int(30.44 * _MS_DAY) + 1
        else:
            raise ValueError(f"unknown period {p}")
        return Column(Integral, out, mask=col.mask)


# DateListVectorizer's mode pivots: pivot -> (period, width)
_MODES = {"ModeDay": ("DayOfWeek", 7), "ModeMonth": ("MonthOfYear", 12),
          "ModeHour": ("HourOfDay", 24)}


def _period_index(ms: np.ndarray, period: str) -> np.ndarray:
    """int64 epoch milliseconds -> the period's index as the eager
    transform counts it (floor arithmetic, as Python's)."""
    if period == "DayOfWeek":
        return ((ms + _EPOCH_DOW_SHIFT) % _MS_WEEK) // _MS_DAY
    if period == "MonthOfYear":
        return ((ms % _MS_YEAR) // int(30.44 * _MS_DAY)) % 12
    return (ms % _MS_DAY) // _MS_HOUR


def _mode_index(ragged, pivot: str) -> np.ndarray:
    """uint8[N]: each row's most frequent period index, a tie going to the
    index first seen in the row (``Counter.most_common``'s order), 255 for
    an empty row."""
    lengths = ragged.lengths()
    out = np.full(len(lengths), 255, np.uint8)
    if not ragged.values.size:
        return out
    period, width = _MODES[pivot]
    rows = np.repeat(np.arange(len(lengths), dtype=np.int64), lengths)
    keys = rows * width + _period_index(ragged.values, period)
    uniq, first, counts = np.unique(keys, return_index=True,
                                    return_counts=True)
    # by row, then count descending, then first occurrence
    order = np.lexsort((first, -counts, uniq // width))
    uniq = uniq[order]
    lead = np.r_[True, uniq[1:] // width != uniq[:-1] // width]
    out[uniq[lead] // width] = (uniq[lead] % width).astype(np.uint8)
    return out


class DateListVectorizerModel(ColumnWired, TransformerModel):
    out_kind = OPVector
    is_device_op = False  # host walk of the lists, then device
    supports_staging = True

    def column_wire(self, i: int, col: Column):
        """Input ``i``'s wire from one walk of its lists
        (``ops.list_profile``): for ``SinceFirst`` / ``SinceLast``,
        ``days{i}`` the float32 days from ``reference_ms`` to the row's
        first or last date (0 for an empty row), exactly the eager
        transform's float64 quotient rounded once, and ``null{i}`` its
        packed null bits; for a mode pivot, ``mode{i}`` the uint8 index of
        the row's most frequent period (255 for none).  Nothing fitted is
        read."""
        from .list_profile import date_profile
        name = self.input_features[i].name
        pivot = self.get("pivot")
        if pivot in ("SinceFirst", "SinceLast"):
            prof = date_profile(col, name=name)
            pick = prof.lo if pivot == "SinceFirst" else prof.hi
            days = ((np.int64(self.get("reference_ms")) - pick)
                    / _MS_DAY).astype(np.float32)
            days[~prof.present] = 0.0
            wire = {f"days{i}": days}
            if self.get("track_nulls", True):
                wire[f"null{i}"] = pack_bits(~prof.present)
            return wire
        from ..columns import ragged_of
        return {f"mode{i}": _mode_index(ragged_of(col), pivot)}

    def transform_staged(self, batch: ColumnBatch, parts=None):
        """Host prologue: every input's ``column_wire`` (or ``parts`` made
        by it already).  Device body: the days and null column, or the
        mode's one-hot, input by input."""
        pivot = self.get("pivot")
        track_nulls = self.get("track_nulls", True)
        parts = self.column_wires(batch) if parts is None else parts
        wire = {k: v for part in parts for k, v in part.items()}
        count = len(self.input_features)
        meta = self.fitted["meta"]

        def body(w):
            outs = []
            for i in range(count):
                if pivot in ("SinceFirst", "SinceLast"):
                    days = w[f"days{i}"]
                    outs.append(days[:, None])
                    if track_nulls:
                        outs.append(unpack_bits_device(
                            w[f"null{i}"], days.shape[0])[:, None])
                else:
                    width = _MODES[pivot][1]
                    mode = w[f"mode{i}"].astype(jnp.int32)
                    outs.append((mode[:, None] == jnp.arange(width)[None, :]
                                 ).astype(jnp.float32))
            return Column(OPVector, jnp.concatenate(outs, axis=1), meta=meta)

        return wire, body

    def transform(self, batch: ColumnBatch) -> Column:
        """The unstaged form, row by row over each row's list."""
        pivot = self.get("pivot")
        ref = self.get("reference_ms")
        outs = []
        for f in self.input_features:
            col = batch[f.name]
            lists = (col.values if col.is_host_object()
                     else list(col.values))
            if pivot in ("SinceFirst", "SinceLast"):
                pick = min if pivot == "SinceFirst" else max
                vals, mask = [], []
                for lst in lists:
                    if lst:
                        vals.append((ref - pick(lst)) / _MS_DAY)
                        mask.append(True)
                    else:
                        vals.append(0.0)
                        mask.append(False)
                outs.append(np.asarray(vals, np.float32)[:, None])
                if self.get("track_nulls", True):
                    outs.append((~np.asarray(mask, bool)).astype(np.float32)[:, None])
            else:  # ModeDay / ModeMonth / ModeHour pivots one-hot the mode
                name, width = _MODES[pivot]
                block = np.zeros((len(lists), width), np.float32)
                for i, lst in enumerate(lists):
                    if not lst:
                        continue
                    from collections import Counter
                    cnt = Counter()
                    for ms in lst:
                        if name == "DayOfWeek":
                            cnt[int(((ms + _EPOCH_DOW_SHIFT) % _MS_WEEK) // _MS_DAY)] += 1
                        elif name == "MonthOfYear":
                            cnt[int((ms % _MS_YEAR) // int(30.44 * _MS_DAY)) % 12] += 1
                        else:
                            cnt[int((ms % _MS_DAY) // _MS_HOUR)] += 1
                    block[i, cnt.most_common(1)[0][0]] = 1.0
                outs.append(block)
        arr = np.concatenate(outs, axis=1)
        return Column(OPVector, jnp.asarray(arr), meta=self.fitted["meta"])


class DateListVectorizer(Estimator):
    """DateList pivots (≙ DateListVectorizer.scala): SinceFirst/SinceLast days
    or mode-of-period one-hot."""

    out_kind = OPVector

    def __init__(self, pivot: str = "SinceLast",
                 reference_ms: int = 1500000000000, track_nulls: bool = True,
                 **params):
        super().__init__(pivot=pivot, reference_ms=reference_ms,
                         track_nulls=track_nulls, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        cols_meta: List[VectorColumnMeta] = []
        pivot = self.get("pivot")
        for f in self.input_features:
            if pivot in ("SinceFirst", "SinceLast"):
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, descriptor_value=pivot))
                if self.get("track_nulls", True):
                    cols_meta.append(VectorColumnMeta(
                        f.name, f.kind.__name__, indicator_value=NULL_INDICATOR))
            else:
                width = _MODES[pivot][1]
                for j in range(width):
                    cols_meta.append(VectorColumnMeta(
                        f.name, f.kind.__name__,
                        descriptor_value=f"{pivot}_{j}"))
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(DateListVectorizerModel(
            fitted={"meta": meta}, **self.params))
