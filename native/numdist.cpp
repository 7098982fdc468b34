// numdist — the range and the equal-width histogram of a numeric column as
// it is stored, each in one pass that holds no GIL.
//
// RawFeatureFilter's distribution of a numeric raw feature (filters.py,
// ≙ FeatureDistribution.scala:58 over the Summary's range) used to be a
// Python loop of numpy calls over blocks of rows: a float64 copy, isfinite,
// a boolean index, np.histogram.  Some two thousand short calls a column,
// each taking the GIL again, do not run side by side on threads.  Here a
// column is read in place, one value at a time:
//
//   range(values, mask) -> (lo, hi) | None
//     min and max, as float64, of the values that are present (mask[i], or
//     every row where mask is None) and finite; None where there is none.
//
//   histogram(values, mask, edges) -> float64[len(edges) - 1]
//     np.histogram(x, bins=len(edges) - 1, range=(edges[0], edges[-1]))[0]
//     of those same values, for edges = np.linspace(lo, hi, bins + 1): the
//     bin is guessed as numpy guesses it, ((x - lo) / (hi - lo)) * bins
//     truncated, and the guess corrected against the edges as numpy
//     corrects it, so the counts are defined by the edges handed in: bin i
//     is [edges[i], edges[i + 1]), the last one closed, a value outside
//     [lo, hi] left out.
//
// values: a 1-D float64, float32, int64 or int32 ndarray of any stride, in
// native byte order and aligned; an integer becomes a float64 one value at
// a time, rounded as np.asarray(values, float64) rounds it.  mask: None or
// a 1-D bool ndarray of the same length.  Anything else raises TypeError or
// ValueError: the caller keeps its numpy path for it.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#define NPY_NO_DEPRECATED_API NPY_1_7_API_VERSION
#include <numpy/arrayobject.h>

#include <cmath>
#include <cstdint>
#include <limits>
#include <vector>

namespace {

// A 1-D array read in place: where it starts, bytes from row to row, rows.
struct Strided {
    const char* data = nullptr;
    npy_intp step = 0;
    npy_intp rows = 0;
};

struct Column {
    Strided values;
    Strided mask;           // data == nullptr: every row is present
    int type = 0;           // NPY_DOUBLE, NPY_FLOAT, NPY_INT64 or NPY_INT32
};

Strided strided(PyObject* obj) {
    PyArrayObject* a = reinterpret_cast<PyArrayObject*>(obj);
    return {static_cast<const char*>(PyArray_DATA(a)), PyArray_STRIDE(a, 0),
            PyArray_DIM(a, 0)};
}

bool column_of(PyObject* values, PyObject* mask, Column* col) {
    if (!PyArray_Check(values) ||
        PyArray_NDIM(reinterpret_cast<PyArrayObject*>(values)) != 1) {
        PyErr_SetString(PyExc_TypeError, "numdist: values must be a 1-D ndarray");
        return false;
    }
    PyArrayObject* v = reinterpret_cast<PyArrayObject*>(values);
    col->type = PyArray_TYPE(v);
    // NPY_LONG / NPY_LONGLONG are both 8 bytes here; NPY_INT is 4
    if (PyArray_ISINTEGER(v) && PyArray_ISSIGNED(v)) {
        if (PyArray_ITEMSIZE(v) == 8) col->type = NPY_INT64;
        else if (PyArray_ITEMSIZE(v) == 4) col->type = NPY_INT32;
    }
    if ((col->type != NPY_DOUBLE && col->type != NPY_FLOAT &&
         col->type != NPY_INT64 && col->type != NPY_INT32) ||
        !PyArray_ISALIGNED(v) || !PyArray_ISNOTSWAPPED(v)) {
        PyErr_SetString(PyExc_TypeError,
                        "numdist: values must be float64, float32, int64 or "
                        "int32, aligned and in native byte order");
        return false;
    }
    col->values = strided(values);
    if (mask == Py_None) return true;
    if (!PyArray_Check(mask) ||
        PyArray_NDIM(reinterpret_cast<PyArrayObject*>(mask)) != 1 ||
        PyArray_TYPE(reinterpret_cast<PyArrayObject*>(mask)) != NPY_BOOL) {
        PyErr_SetString(PyExc_TypeError,
                        "numdist: mask must be None or a 1-D bool ndarray");
        return false;
    }
    col->mask = strided(mask);
    if (col->mask.rows != col->values.rows) {
        PyErr_SetString(PyExc_ValueError,
                        "numdist: mask and values differ in length");
        return false;
    }
    return true;
}

// f(x) for every present, finite value of the column, as float64.
template <class T, class F>
void each_value(const Column& col, F&& f) {
    const char* v = col.values.data;
    const char* m = col.mask.data;
    for (npy_intp i = 0; i < col.values.rows;
         ++i, v += col.values.step, m += col.mask.step) {
        if (col.mask.data && !*m) continue;
        const double x = static_cast<double>(*reinterpret_cast<const T*>(v));
        if (std::isfinite(x)) f(x);
    }
}

template <class F>
void each(const Column& col, F&& f) {
    switch (col.type) {
        case NPY_DOUBLE: each_value<double>(col, f); break;
        case NPY_FLOAT: each_value<float>(col, f); break;
        case NPY_INT64: each_value<int64_t>(col, f); break;
        default: each_value<int32_t>(col, f); break;
    }
}

PyObject* range(PyObject*, PyObject* args) {
    PyObject *values, *mask;
    if (!PyArg_ParseTuple(args, "OO", &values, &mask)) return nullptr;
    Column col;
    if (!column_of(values, mask, &col)) return nullptr;
    double lo = std::numeric_limits<double>::infinity(), hi = -lo;
    Py_BEGIN_ALLOW_THREADS
    each(col, [&](double x) {
        if (x < lo) lo = x;
        if (x > hi) hi = x;
    });
    Py_END_ALLOW_THREADS
    if (!(lo <= hi)) Py_RETURN_NONE;
    return Py_BuildValue("(dd)", lo, hi);
}

PyObject* histogram(PyObject*, PyObject* args) {
    PyObject *values, *mask, *edges_obj;
    if (!PyArg_ParseTuple(args, "OOO", &values, &mask, &edges_obj))
        return nullptr;
    Column col;
    if (!column_of(values, mask, &col)) return nullptr;
    if (!PyArray_Check(edges_obj)) {
        PyErr_SetString(PyExc_TypeError, "numdist: edges must be an ndarray");
        return nullptr;
    }
    PyArrayObject* e = reinterpret_cast<PyArrayObject*>(edges_obj);
    if (PyArray_NDIM(e) != 1 || PyArray_TYPE(e) != NPY_DOUBLE ||
        !PyArray_IS_C_CONTIGUOUS(e) || !PyArray_ISALIGNED(e) ||
        !PyArray_ISNOTSWAPPED(e) || PyArray_DIM(e, 0) < 2) {
        PyErr_SetString(PyExc_TypeError,
                        "numdist: edges must be a contiguous 1-D float64 "
                        "ndarray of at least two");
        return nullptr;
    }
    const double* edges = static_cast<const double*>(PyArray_DATA(e));
    const npy_intp bins = PyArray_DIM(e, 0) - 1;
    const double lo = edges[0], hi = edges[bins];
    // numpy's own arithmetic (lib/_histograms_impl.py, the equal-bins path)
    const double denom = hi - lo, numerator = static_cast<double>(bins);
    if (!(lo < hi && std::isfinite(denom))) {
        PyErr_SetString(PyExc_ValueError,
                        "numdist: edges must run from lo up to a hi above "
                        "it, a finite way apart");
        return nullptr;
    }
    std::vector<int64_t> counts(bins, 0);
    Py_BEGIN_ALLOW_THREADS
    each(col, [&](double x) {
        if (!(x >= lo && x <= hi)) return;
        npy_intp i = static_cast<npy_intp>(((x - lo) / denom) * numerator);
        if (i == bins) --i;
        if (x < edges[i]) --i;
        if (x >= edges[i + 1] && i != bins - 1) ++i;
        ++counts[i];
    });
    Py_END_ALLOW_THREADS
    npy_intp dim = bins;
    PyArrayObject* out = reinterpret_cast<PyArrayObject*>(
        PyArray_SimpleNew(1, &dim, NPY_DOUBLE));
    if (!out) return nullptr;
    double* h = static_cast<double*>(PyArray_DATA(out));
    for (npy_intp i = 0; i < bins; ++i) h[i] = static_cast<double>(counts[i]);
    return reinterpret_cast<PyObject*>(out);
}

PyMethodDef methods[] = {
    {"range", range, METH_VARARGS,
     "range(values, mask) -> (lo, hi) | None: min and max, as float64, of "
     "the present finite values of a 1-D float64/float32/int64/int32 "
     "ndarray, read in place with the GIL released"},
    {"histogram", histogram, METH_VARARGS,
     "histogram(values, mask, edges) -> float64 counts: np.histogram's of "
     "the present finite values over edges = np.linspace(lo, hi, bins + 1), "
     "read in place with the GIL released"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_numdist",
    "Range and histogram of a numeric column, holding no GIL.", -1, methods,
};

}  // namespace

PyMODINIT_FUNC PyInit__numdist(void) {
    import_array();
    return PyModule_Create(&moduledef);
}
