"""Geolocation vectorizer (reference: core/.../stages/impl/feature/
GeolocationVectorizer.scala): fill missing (lat, lon, accuracy) with the
train mean and track nulls.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..columns import Column, ColumnBatch, pack_bits, unpack_bits_device
from ..stages.base import ColumnWired, Estimator, TransformerModel
from ..types import OPVector
from ..vector_meta import NULL_INDICATOR, VectorColumnMeta, VectorMeta


def _geo_arrays(col) -> tuple:
    """Column of Geolocation → ([N,3] float32, [N] bool mask).  A column
    held as objects (lists, None or empty where missing) is gathered by
    numpy from its present values, not row by row."""
    if col.is_host_object():
        n = len(col.values)
        arr = np.zeros((n, 3), np.float32)
        mask = np.fromiter(map(bool, col.values), bool, count=n)
        if mask.any():
            arr[mask] = np.asarray(
                [v[:3] for v in col.values[mask].tolist()], np.float32)
        return arr, mask
    arr = np.asarray(col.values, np.float32)
    mask = (np.ones(len(arr), bool) if col.mask is None else np.asarray(col.mask))
    return arr, mask


_PARTS = ("lat", "lon", "accuracy")


class GeolocationVectorizerModel(ColumnWired, TransformerModel):
    out_kind = OPVector
    is_device_op = False  # host gather of the triples, then device fill
    supports_staging = True

    def column_wire(self, i: int, col: Column):
        """Input ``i``'s latitudes, longitudes and accuracies as three
        float32 vectors (``lat{i}``, ``lon{i}``, ``accuracy{i}``) and its
        null bits packed (``null{i}``).  Nothing fitted is read: the fills
        are an entry of their own."""
        arr, mask = _geo_arrays(col)
        wire = {f"{part}{i}": np.ascontiguousarray(arr[:, k])
                for k, part in enumerate(_PARTS)}
        wire[f"null{i}"] = pack_bits(~mask)
        return wire

    def transform_staged(self, batch: ColumnBatch, parts=None):
        """Host prologue: every column's ``column_wire`` (or ``parts`` made
        by it already) — three float32 vectors (bfloat16 on an
        accelerator's link, as every real value; a vector each, so that the
        device slices no ``[N, 3]`` operand along its lanes) and packed null
        bits — and the fitted fills as their bit patterns (an operand, not a
        constant: the program is the same for every fit; the bits, because a
        float32 wire is rounded on the link).  Device body: the fill, the
        null column."""
        track_nulls = self.get("track_nulls", True)
        fills = np.asarray(self.fitted["fills"], np.float32)
        parts = self.column_wires(batch) if parts is None else parts
        wire = {"fills": fills.view(np.int32)}
        wire.update((k, v) for part in parts for k, v in part.items())
        count = len(fills)
        meta = self.fitted["meta"]

        def body(w):
            fill = lax.bitcast_convert_type(jnp.asarray(w["fills"]),
                                            jnp.float32)
            outs = []
            for i in range(count):
                null = unpack_bits_device(w[f"null{i}"],
                                          w[f"{_PARTS[0]}{i}"].shape[0])
                for k, part in enumerate(_PARTS):
                    x = jnp.asarray(w[f"{part}{i}"]).astype(jnp.float32)
                    outs.append(jnp.where(null > 0, fill[i, k], x))
                if track_nulls:
                    outs.append(null)
            return Column(OPVector, jnp.stack(outs, axis=1), meta=meta)

        return wire, body

    def transform(self, batch: ColumnBatch) -> Column:
        """The staged form run eagerly: one arithmetic for the fused program,
        local scoring and every eager path."""
        wire, body = self.transform_staged(batch)
        return body(wire)


class GeolocationVectorizer(Estimator):
    out_kind = OPVector

    def __init__(self, track_nulls: bool = True, fill_mode: str = "mean", **params):
        super().__init__(track_nulls=track_nulls, fill_mode=fill_mode, **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        fills, cols_meta = [], []
        for f in self.input_features:
            arr, mask = _geo_arrays(batch[f.name])
            if self.get("fill_mode") == "mean" and mask.any():
                # float64: a float32 running sum of millions of 40.75s drifts
                fill = (np.add.reduce(arr, axis=0, dtype=np.float64,
                                      where=mask[:, None])
                        / mask.sum()).astype(np.float32)
            else:
                fill = np.zeros(3, np.float32)
            fills.append(fill)
            for d in _PARTS:
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, descriptor_value=d))
            if self.get("track_nulls", True):
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, indicator_value=NULL_INDICATOR))
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(GeolocationVectorizerModel(
            fitted={"fills": np.stack(fills), "meta": meta}, **self.params))
