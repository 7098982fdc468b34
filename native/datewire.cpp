// datewire — the wire of a date column, (day modulo the day cycle,
// millisecond of the day) as two int32, in one pass that holds no GIL.
//
// DateToUnitCircleModel's host prologue (ops/dates.py ``_day_and_ms``) was
// numpy: a floor divmod of the int64 milliseconds by a day, a second int64
// modulo of the day, two casts.  Four whole-column temporaries, each paying
// its page faults on a host without transparent huge pages: 0.25 s a column
// of 6,291,456 rows on one thread of a TPU v5e host, where this pass reads
// the column once and writes the two results:
//
//   day_and_ms(ms) -> (day int32[n], ms_of_day int32[n])
//     day, ms_of_day = divmod(ms, MS_DAY) with floor division, as numpy's
//     np.divmod gives them (dates before 1970 too), and day taken modulo
//     DAY_CYCLE into [0, DAY_CYCLE); both fit an int32.
//
// ms: a 1-D int64 ndarray of any stride, aligned and in native byte order.
// Anything else raises TypeError: the caller keeps its numpy path for it.
// MS_DAY and DAY_CYCLE are module constants, equal to ops/dates.py's (a test
// holds them so); constant divisors let the compiler divide by multiplying.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#define NPY_NO_DEPRECATED_API NPY_1_7_API_VERSION
#include <numpy/arrayobject.h>

#include <cstdint>

namespace {

constexpr int64_t MS_DAY = 24LL * 3600 * 1000;
constexpr int64_t DAY_CYCLE = 761LL * 146097;

PyObject* day_and_ms(PyObject*, PyObject* args) {
    PyObject* obj;
    if (!PyArg_ParseTuple(args, "O", &obj)) return nullptr;
    if (!PyArray_Check(obj)) {
        PyErr_SetString(PyExc_TypeError, "datewire: ms must be an ndarray");
        return nullptr;
    }
    PyArrayObject* a = reinterpret_cast<PyArrayObject*>(obj);
    if (PyArray_NDIM(a) != 1 || !PyArray_ISINTEGER(a) ||
        !PyArray_ISSIGNED(a) || PyArray_ITEMSIZE(a) != 8 ||
        !PyArray_ISALIGNED(a) || !PyArray_ISNOTSWAPPED(a)) {
        PyErr_SetString(PyExc_TypeError,
                        "datewire: ms must be a 1-D int64 ndarray, aligned "
                        "and in native byte order");
        return nullptr;
    }
    npy_intp n = PyArray_DIM(a, 0);
    const npy_intp step = PyArray_STRIDE(a, 0);
    const char* src = static_cast<const char*>(PyArray_DATA(a));
    PyArrayObject* day = reinterpret_cast<PyArrayObject*>(
        PyArray_SimpleNew(1, &n, NPY_INT32));
    if (!day) return nullptr;
    PyArrayObject* rest = reinterpret_cast<PyArrayObject*>(
        PyArray_SimpleNew(1, &n, NPY_INT32));
    if (!rest) {
        Py_DECREF(day);
        return nullptr;
    }
    int32_t* d = static_cast<int32_t*>(PyArray_DATA(day));
    int32_t* r = static_cast<int32_t*>(PyArray_DATA(rest));
    Py_BEGIN_ALLOW_THREADS
    for (npy_intp i = 0; i < n; ++i, src += step) {
        const int64_t ms = *reinterpret_cast<const int64_t*>(src);
        int64_t q = ms / MS_DAY, m = ms % MS_DAY;
        if (m < 0) {            // floor, as np.divmod
            m += MS_DAY;
            --q;
        }
        int64_t c = q % DAY_CYCLE;
        if (c < 0) c += DAY_CYCLE;
        d[i] = static_cast<int32_t>(c);
        r[i] = static_cast<int32_t>(m);
    }
    Py_END_ALLOW_THREADS
    return Py_BuildValue("(NN)", day, rest);
}

PyMethodDef methods[] = {
    {"day_and_ms", day_and_ms, METH_VARARGS,
     "day_and_ms(ms) -> (day, ms_of_day): int32 floor divmod of 1-D int64 "
     "epoch milliseconds by MS_DAY, the day modulo DAY_CYCLE, read in place "
     "with the GIL released"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_datewire",
    "The int32 wire of a date column, holding no GIL.", -1, methods,
};

}  // namespace

PyMODINIT_FUNC PyInit__datewire(void) {
    import_array();
    PyObject* m = PyModule_Create(&moduledef);
    if (!m) return nullptr;
    if (PyModule_AddIntConstant(m, "MS_DAY", MS_DAY) < 0 ||
        PyModule_AddIntConstant(m, "DAY_CYCLE", DAY_CYCLE) < 0) {
        Py_DECREF(m);
        return nullptr;
    }
    return m;
}
