"""Set/list vectorizers (reference: core/.../stages/impl/feature/
{MultiPickListMapVectorizer for maps, OpSetVectorizer}.scala — the top-K pivot
over MultiPickList sets).
"""

from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from .categorical import top_values_by_count
from .map_profile import set_expansion
from ..columns import Column, ColumnBatch
from ..stages.base import ColumnWired, Estimator, TransformerModel
from ..types import OPVector
from ..vector_meta import (NULL_INDICATOR, OTHER_INDICATOR, VectorColumnMeta,
                           VectorMeta)


class MultiPickListVectorizerModel(ColumnWired, TransformerModel):
    out_kind = OPVector
    is_device_op = False     # host pairs from the set expansion, then device
    supports_staging = True  # multi-hot scattered inside the fused program

    def _layout(self, name: str):
        """(vocabulary size, OTHER's column or None, null's column or None,
        block width) of input ``name``."""
        k = len(self.fitted["vocabs"][name])
        other = k if self.get("track_other", True) else None
        width = k + (other is not None)
        null = width if self.get("track_nulls", True) else None
        return k, other, null, width + (null is not None)

    def column_wire(self, i: int, col: Column):
        """Input ``i``'s set members as row-sorted (``rows{i}`` int32,
        ``slots{i}`` uint8 where the width allows) pairs, a slot a member's
        vocabulary position or OTHER, a value once a row, padded to
        ``_pair_capacity`` with rows past the end that the body drops;
        ``empty{i}``: uint8 1 where the row's set is empty or missing, a
        byte a row, so that the body reads the row count from the wire.
        Read from the column's cached expansion
        (``map_profile.set_expansion``); None for a column not held as
        objects."""
        if not col.is_host_object():
            return None
        name = self.input_features[i].name
        vocab: Dict[str, int] = self.fitted["vocabs"][name]
        k, other, _, width = self._layout(name)
        exp = set_expansion(col, name)
        dtype = np.uint8 if width <= 256 else np.int32
        # a distinct value's slot; an unseen one without OTHER goes nowhere
        slot = np.fromiter((vocab.get(u, k) for u in exp.uniq), np.int32,
                           count=len(exp.uniq))
        rows, slots = exp.rows(), slot[exp.ids]
        if other is None:
            rows, slots = rows[slots < k], slots[slots < k]
        # two unseen values of a row are two pairs at OTHER: the body sets
        # the slot, so it reads 1 once
        cap = _pair_capacity(len(rows))
        pad = cap - len(rows)
        return {f"rows{i}": np.r_[rows, np.full(pad, len(col), np.int32)
                                  ].astype(np.int32),
                f"slots{i}": np.r_[slots, np.zeros(pad, np.int32)
                                   ].astype(dtype),
                f"empty{i}": (~exp.nonempty).astype(np.uint8)}

    def transform_staged(self, batch: ColumnBatch, parts=None):
        """Host prologue: every input's ``column_wire`` (or ``parts`` made
        by it already).  Device body: a scatter of ones into each input's
        block, then its null column."""
        parts = self.column_wires(batch) if parts is None else parts
        if parts is None or None in parts:
            return None
        wire = {k: v for part in parts for k, v in part.items()}
        plan = [(i, self._layout(f.name))
                for i, f in enumerate(self.input_features)]
        n = len(batch)
        meta = self.fitted["meta"]

        def body(w):
            outs = []
            for i, (_, _, null, width) in plan:
                empty = jnp.asarray(w[f"empty{i}"])
                block = jnp.zeros((empty.shape[0], width), jnp.float32).at[
                    jnp.asarray(w[f"rows{i}"]),
                    jnp.asarray(w[f"slots{i}"]).astype(jnp.int32)
                ].set(1.0, mode="drop")
                if null is not None:
                    block = block.at[:, null].set(empty.astype(jnp.float32))
                outs.append(block)
            return Column(OPVector, jnp.concatenate(outs, axis=1) if outs
                          else jnp.zeros((n, 0), jnp.float32), meta=meta)

        return wire, body

    def transform(self, batch: ColumnBatch) -> Column:
        wire, body = self.transform_staged(batch)
        return body({k: jnp.asarray(v) for k, v in wire.items()})


def _pair_capacity(pairs: int) -> int:
    """Entries a set column's wire carries for ``pairs`` pairs: the next
    of 1,024 · {2^k, 1.5 · 2^k}, so that batches of about one size share
    one program."""
    cap = 1024
    while cap < pairs:
        cap = cap * 3 // 2 if cap & (cap - 1) == 0 else cap * 4 // 3
    return cap


class MultiPickListVectorizer(Estimator):
    """Top-K membership pivot of MultiPickList sets with OTHER + null slots.
    A value is its ``str``, counted once a row; the counts come from the
    column's one expansion (``map_profile.set_expansion``), which the
    model's wire reads too."""

    out_kind = OPVector

    def __init__(self, top_k: int = 20, min_support: int = 10,
                 track_nulls: bool = True, track_other: bool = True, **params):
        super().__init__(top_k=top_k, min_support=min_support,
                         track_nulls=track_nulls, track_other=track_other,
                         **params)

    def fit(self, batch: ColumnBatch) -> TransformerModel:
        vocabs: Dict[str, Dict[str, int]] = {}
        cols_meta: List[VectorColumnMeta] = []
        for f in self.input_features:
            # rows holding a value (its str), from the column's expansion
            counts = set_expansion(batch[f.name], f.name).value_counts()
            top = top_values_by_count(counts, self.get("top_k"),
                                      self.get("min_support"))
            vocab = {v: i for i, v in enumerate(top)}
            vocabs[f.name] = vocab
            for v in top:
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, indicator_value=v))
            if self.get("track_other", True):
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, indicator_value=OTHER_INDICATOR))
            if self.get("track_nulls", True):
                cols_meta.append(VectorColumnMeta(
                    f.name, f.kind.__name__, indicator_value=NULL_INDICATOR))
        meta = VectorMeta(self.output_name(), cols_meta)
        return self._finalize_model(MultiPickListVectorizerModel(
            fitted={"vocabs": vocabs, "meta": meta}, **self.params))
