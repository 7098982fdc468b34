"""The hashed-text count kernel (``ops/hash_kernel.py``) and the rule that
picks it (``ops/text.py``: ``_kernel_engages``).

(a) The kernel, run by the Pallas TPU interpreter, against XLA's scatter-add
    through both of its callers, ``_scatter_counts_packed`` and the fused
    transform's body of ``SmartTextVectorizerModel``, the whole matrix to
    the bit: empty rows, a row over several tiles, rows that start inside a
    word, a final word padded with the sentinel, a long padding row,
    indicators and counts, bfloat16 and float32 storage.
(b) The rule, without a chip: the CPU, a mesh, a short stacked wire and a
    hash width of 1,024 keep the scatter; the counters count one column for
    each hashed column of a flush, on the side it took; the scope
    ``text.hash_counts`` is in the program on both paths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from transmogrifai_tpu import columns
from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Column, ColumnBatch
from transmogrifai_tpu.compiled import ScoreProgram
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.ops import text
from transmogrifai_tpu.ops.text import SmartTextVectorizer
from transmogrifai_tpu.telemetry import REGISTRY

COUNTERS = ("text.hash_kernel_columns", "text.hash_scatter_columns")


def row_lengths(case, rng):
    """Tokens a row, and the sentinel words past the last token's word."""
    if case == "empty_rows":
        lens = rng.integers(0, 7, 700) * (rng.random(700) < 0.4)
        lens[:1] = 0
        lens[130:260] = 0                       # a whole block of 128 rows
        return lens, 0
    if case == "long_row":
        lens = rng.integers(0, 7, 300)
        lens[150] = 10_000                      # over four tiles of words
        return lens, 0
    if case == "mid_word":
        return rng.choice([1, 2, 4, 5], 2000), 0
    if case == "sentinel_tail":
        lens = rng.integers(0, 10, 1000)
        lens[-1] += (1 - lens.sum()) % 3        # one id in the last word
        return lens, 0
    return rng.integers(0, 5, 500), 2500        # padding_row


def packed(lens, pad_words):
    flat = np.random.default_rng(int(lens.sum())).integers(
        0, 512, int(lens.sum())).astype(np.int32)
    words = text._pack_ids3(flat, 512)
    wp = np.full(words.size + pad_words, text._sentinel3(512), np.int32)
    wp[:words.size] = words
    lens_p = np.append(lens, 3 * wp.size - flat.size).astype(np.int32)
    return wp, lens_p


def text_batch(lens, rng):
    """A Text column whose rows hold ``lens`` tokens each (an empty row is
    null or the empty string), of more than 30 distinct values: hashed."""
    values = np.empty(len(lens), dtype=object)
    for i, k in enumerate(lens):
        values[i] = (" ".join(f"w{j}" for j in rng.integers(0, 99_999, k))
                     if k else (None if i % 2 else ""))
    return ColumnBatch({"t": Column(T.Text, values),
                        "u": Column(T.Text, values[::-1].copy())}, len(lens))


def staged_matrix(batch, dtype, monkeypatch, kernel):
    """The fused transform's output for the batch, its counts built by the
    kernel (in the interpreter) or by XLA's scatter."""
    monkeypatch.setattr(columns, "feature_matrix_dtype", lambda n: dtype)
    if kernel:
        monkeypatch.setattr(text, "_platform", lambda: "tpu")
    st = SmartTextVectorizer(num_hashes=512, max_cardinality=30)
    st.set_input(FeatureBuilder.Text("t").as_predictor(),
                 FeatureBuilder.Text("u").as_predictor())
    model = st.fit(batch)
    name = model.output_features[0].name
    with pltpu.force_tpu_interpret_mode():
        out = ScoreProgram([[model]], [name])(batch)[name].values
    monkeypatch.setattr(text, "_platform", jax.default_backend)
    return np.asarray(out)


def moved(before):
    after = REGISTRY.counters()
    return tuple(after.get(k, 0) - before.get(k, 0) for k in COUNTERS)


# --------------------------------------------------------------------------
# (a) the kernel against XLA's scatter, through both callers
# --------------------------------------------------------------------------

@pytest.mark.parametrize("case,binary,dtype", [
    ("empty_rows", False, jnp.float32), ("empty_rows", True, jnp.bfloat16),
    ("long_row", False, jnp.bfloat16), ("long_row", True, jnp.float32),
    ("mid_word", False, jnp.float32), ("mid_word", True, jnp.bfloat16),
    ("sentinel_tail", False, jnp.bfloat16),
    ("sentinel_tail", True, jnp.float32),
    ("padding_row", False, jnp.float32), ("padding_row", True, jnp.bfloat16),
])
def test_kernel_counts_equal_the_scatters(case, binary, dtype, monkeypatch):
    rng = np.random.default_rng(45)
    lens, pad_words = row_lengths(case, rng)
    monkeypatch.setattr(text, "_STACKED_UNPACK_BYTES", 0)
    words, lens_p = packed(lens, pad_words)
    n = len(lens)
    want = text._scatter_counts_packed(words, lens_p, n, 512, binary, False)
    with pltpu.force_tpu_interpret_mode():
        got = text._scatter_counts_packed(words, lens_p, n, 512, binary,
                                          True)
    assert got.dtype == want.dtype and got.shape == (n, 512)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(want).sum() > 0

    batch = text_batch(lens, rng)
    scattered = staged_matrix(batch, dtype, monkeypatch, kernel=False)
    before = REGISTRY.counters()
    kernel = staged_matrix(batch, dtype, monkeypatch, kernel=True)
    assert moved(before) == (2, 0)
    assert kernel.dtype == scattered.dtype == np.dtype(dtype)
    assert kernel.shape == scattered.shape == (n, 2 * 513)
    assert np.array_equal(kernel, scattered)


# --------------------------------------------------------------------------
# (b) the rule and the counters
# --------------------------------------------------------------------------

@pytest.mark.parametrize("why", ["cpu", "mesh", "short_wire", "wide"])
def test_the_rule_keeps_the_scatter(why, monkeypatch):
    rng = np.random.default_rng(7)
    lens = rng.integers(0, 6, 256)
    monkeypatch.setattr(text, "_platform",
                        lambda: "cpu" if why == "cpu" else "tpu")
    if why != "short_wire":
        monkeypatch.setattr(text, "_STACKED_UNPACK_BYTES", 0)
    before = REGISTRY.counters()
    if why == "wide":
        assert not text._kernel_engages(1 << 20, 1024, True)
        flat = rng.integers(0, 1024, int(lens.sum())).astype(np.int32)
        got = text.device_counts_from_flat(lens.astype(np.int32), flat, 1024)
        assert np.array_equal(np.asarray(got),
                              text._counts_from_flat(lens, flat, 1024, False))
        assert moved(before) == (0, 1)
        return
    if why == "mesh":
        monkeypatch.setenv("TRANSMOGRIFAI_TPU_MESH", "1")
    assert not text._one_tpu(len(lens)) or why == "short_wire"
    batch = text_batch(lens, rng)
    st = SmartTextVectorizer(num_hashes=512, max_cardinality=30)
    st.set_input(FeatureBuilder.Text("t").as_predictor(),
                 FeatureBuilder.Text("u").as_predictor())
    model = st.fit(batch)
    name = model.output_features[0].name
    before = REGISTRY.counters()
    prog = ScoreProgram([[model]], [name])
    out = prog(batch)[name].values
    assert all(k in REGISTRY.counters() for k in COUNTERS)
    assert moved(before) == (0, 2)
    assert out.shape == (len(lens), 2 * 513) and not prog._demoted


def test_the_rule_takes_a_long_wire_on_one_tpu(monkeypatch):
    assert text._kernel_engages(1 << 20, 512, True)
    assert text._kernel_engages(1 << 20, 1023, True)
    assert not text._kernel_engages(1 << 20, 512, False)
    assert not text._kernel_engages(1 << 19, 512, True)   # 256 MiB stacked
    assert not text._one_tpu()                             # the CPU
    monkeypatch.setattr(text, "_platform", lambda: "tpu")
    assert text._one_tpu() and text._one_tpu(1024)


def test_an_eager_column_is_counted_on_its_side(monkeypatch):
    lens = np.asarray([3, 0, 5, 1], np.int32)
    flat = np.arange(9, dtype=np.int32) * 41 % 512
    want = text._counts_from_flat(lens, flat, 512, True)
    before = REGISTRY.counters()
    got = text.device_counts_from_flat(lens, flat, 512, binary=True)
    assert moved(before) == (0, 1)
    assert np.array_equal(np.asarray(got), want)
    monkeypatch.setattr(text, "_platform", lambda: "tpu")
    monkeypatch.setattr(text, "_STACKED_UNPACK_BYTES", 0)
    before = REGISTRY.counters()
    with pltpu.force_tpu_interpret_mode():
        got = text.device_counts_from_flat(lens, flat, 512, binary=True)
    assert moved(before) == (1, 0)
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("kernel", [False, True])
def test_the_scope_is_in_the_program_on_both_paths(kernel):
    words = np.full(2048, text._sentinel3(512), np.int32)
    lens = np.asarray([2, 0, 3 * 2048 - 2], np.int32)
    with pltpu.force_tpu_interpret_mode():
        lowered = text._scatter_counts_packed.lower(
            words, lens, 2, 512, False, kernel)
    assert "text.hash_counts" in lowered.as_text(debug_info=True)
    assert ("stablehlo.scatter" in lowered.as_text()) != kernel
