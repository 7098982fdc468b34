// mapprof — one native pass over a numeric-valued map column.
//
// The reference expands map features per key on Spark executors
// (OPMapVectorizer.scala, RawFeatureFilter's PreparedFeatures); here the
// host-side analog used to walk a million Python dicts once per consumer
// (RawFeatureFilter ranges + histograms, MapVectorizer fit fills +
// transform).  This module expands the column ONCE into columnar arrays
// that every consumer reuses:
//
//   expand(maps) -> (keys list[str] first-occurrence order,
//                    vals float64[N, K]  (NaN where absent/None),
//                    present uint8[N, K] (value present and not None),
//                    in_dict int64[K]    (key in dict, even with None value),
//                    nonempty uint8[N]   (row is a non-empty dict))
//
// Only float/int values are supported (bool and everything else raises
// TypeError — callers fall back to the exact Python path, which treats
// bools inconsistently across consumers and must stay pinned).
//
// ``expand_strings`` does the same for a map whose values are strings (a
// PickListMap's), ``expand_sets`` for a column of sets (a MultiPickList's):
// every value interned once, with its counts, so that RawFeatureFilter's
// distributions and the pivots' fits and wires read arrays, not rows.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#define NPY_NO_DEPRECATED_API NPY_1_7_API_VERSION
#include <numpy/arrayobject.h>

#include <algorithm>
#include <cmath>
#include <cstring>
#include <string>
#include <string_view>
#include <unordered_map>
#include <vector>

namespace {

PyObject* expand(PyObject*, PyObject* args) {
    PyObject* maps;
    if (!PyArg_ParseTuple(args, "O", &maps)) return nullptr;
    PyObject* seq = PySequence_Fast(maps, "maps");
    if (!seq) return nullptr;
    const Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);

    std::unordered_map<std::string, int32_t> key_ids;
    std::vector<PyObject*> key_objs;             // borrowed
    std::vector<std::vector<double>> cols;       // NaN-initialized columns
    std::vector<std::vector<uint8_t>> pres;
    std::vector<int64_t> in_dict;

    npy_intp dim_n = n;
    PyArrayObject* nonempty = reinterpret_cast<PyArrayObject*>(
        PyArray_ZEROS(1, &dim_n, NPY_UINT8, 0));
    if (!nonempty) { Py_DECREF(seq); return nullptr; }
    npy_uint8* ne = static_cast<npy_uint8*>(PyArray_DATA(nonempty));

    const double nan = std::nan("");
    bool fail = false;
    for (Py_ssize_t i = 0; i < n && !fail; ++i) {
        PyObject* m = PySequence_Fast_GET_ITEM(seq, i);  // borrowed
        if (m == Py_None) continue;
        if (!PyDict_Check(m)) {
            PyErr_SetString(PyExc_TypeError, "non-dict map value");
            fail = true;
            break;
        }
        if (PyDict_Size(m) > 0) ne[i] = 1;
        PyObject *k, *v;
        Py_ssize_t pos = 0;
        while (PyDict_Next(m, &pos, &k, &v)) {
            Py_ssize_t blen;
            const char* kdata =
                PyUnicode_Check(k) ? PyUnicode_AsUTF8AndSize(k, &blen)
                                   : nullptr;
            if (!kdata) {
                if (!PyErr_Occurred())
                    PyErr_SetString(PyExc_TypeError, "non-str map key");
                fail = true;
                break;
            }
            std::string key(kdata, static_cast<size_t>(blen));
            auto it = key_ids.find(key);
            int32_t id;
            if (it == key_ids.end()) {
                id = static_cast<int32_t>(key_objs.size());
                key_ids.emplace(std::move(key), id);
                key_objs.push_back(k);
                cols.emplace_back(static_cast<size_t>(n), nan);
                pres.emplace_back(static_cast<size_t>(n), uint8_t{0});
                in_dict.push_back(0);
            } else {
                id = it->second;
            }
            in_dict[id] += 1;
            if (v == Py_None) continue;
            double val;
            if (PyFloat_Check(v)) {
                val = PyFloat_AS_DOUBLE(v);
            } else if (PyLong_Check(v) && !PyBool_Check(v)) {
                val = PyLong_AsDouble(v);
                if (val == -1.0 && PyErr_Occurred()) { fail = true; break; }
            } else {
                PyErr_SetString(PyExc_TypeError, "non-numeric map value");
                fail = true;
                break;
            }
            cols[id][static_cast<size_t>(i)] = val;
            pres[id][static_cast<size_t>(i)] = 1;
        }
    }
    Py_DECREF(seq);
    if (fail) {
        Py_DECREF(reinterpret_cast<PyObject*>(nonempty));
        return nullptr;
    }

    const npy_intp K = static_cast<npy_intp>(key_objs.size());
    npy_intp dims2[2] = {dim_n, K};
    PyArrayObject* vals = reinterpret_cast<PyArrayObject*>(
        PyArray_SimpleNew(2, dims2, NPY_FLOAT64));
    PyArrayObject* present = reinterpret_cast<PyArrayObject*>(
        PyArray_SimpleNew(2, dims2, NPY_UINT8));
    PyArrayObject* indict = reinterpret_cast<PyArrayObject*>(
        PyArray_SimpleNew(1, &K, NPY_INT64));
    PyObject* keys = PyList_New(K);
    if (!vals || !present || !indict || !keys) {
        Py_XDECREF(reinterpret_cast<PyObject*>(vals));
        Py_XDECREF(reinterpret_cast<PyObject*>(present));
        Py_XDECREF(reinterpret_cast<PyObject*>(indict));
        Py_XDECREF(keys);
        Py_DECREF(reinterpret_cast<PyObject*>(nonempty));
        return nullptr;
    }
    double* vd = static_cast<double*>(PyArray_DATA(vals));
    npy_uint8* pd = static_cast<npy_uint8*>(PyArray_DATA(present));
    for (npy_intp j = 0; j < K; ++j) {
        const auto& col = cols[static_cast<size_t>(j)];
        const auto& pr = pres[static_cast<size_t>(j)];
        for (npy_intp i = 0; i < dim_n; ++i) {
            vd[i * K + j] = col[static_cast<size_t>(i)];
            pd[i * K + j] = pr[static_cast<size_t>(i)];
        }
        Py_INCREF(key_objs[static_cast<size_t>(j)]);
        PyList_SET_ITEM(keys, j, key_objs[static_cast<size_t>(j)]);
    }
    if (K)
        memcpy(PyArray_DATA(indict), in_dict.data(),
               in_dict.size() * sizeof(int64_t));
    return Py_BuildValue("NNNNN", keys, vals, present, indict, nonempty);
}

// ---------------------------------------------------------------------------
// String-valued maps and sets of strings.
//
// Both walks intern every value by its string (``str(v)`` for a value that is
// no str), a table a map key or one for a whole set column, in the order the
// values are first seen.  Phase one, a block of rows at a time with the GIL
// held, reads the rows' dicts or lists and notes (row, key, utf-8 bytes) of
// every value; phase two, with the GIL released, interns them.  The strings
// stay alive while the column holds them; a value made by ``str(v)`` is kept
// in ``owned`` until the call returns.

constexpr Py_ssize_t BLOCK_ROWS = 65536;

struct Item {
    Py_ssize_t row;
    int32_t key;             // the map key's id, 0 for a set
    const char* data;
    Py_ssize_t size;
    PyObject* obj;           // borrowed: the value the bytes are of
};

// One interning table: the distinct values first seen first, and how many
// rows (or items) hold each.
struct Table {
    std::unordered_map<std::string_view, int32_t> ids;
    std::vector<PyObject*> uniq;             // borrowed
    std::vector<int64_t> counts;             // rows holding the value
    std::vector<int64_t> items;              // items, duplicates counted

    int32_t intern(const Item& it) {
        std::string_view s(it.data, static_cast<size_t>(it.size));
        auto found = ids.find(s);
        if (found != ids.end()) return found->second;
        const int32_t id = static_cast<int32_t>(uniq.size());
        ids.emplace(s, id);
        uniq.push_back(it.obj);
        counts.push_back(0);
        items.push_back(0);
        return id;
    }
};

// The utf-8 bytes of ``v`` (``str(v)`` where it is no str) into ``it``; a
// new string is kept in ``owned``.  False with an error set on failure.
bool value_bytes(PyObject* v, Item& it, std::vector<PyObject*>& owned) {
    PyObject* s = v;
    if (!PyUnicode_Check(v)) {
        s = PyObject_Str(v);
        if (!s) return false;
        owned.push_back(s);
    }
    if (PyUnicode_IS_COMPACT_ASCII(s)) {
        it.data = static_cast<const char*>(PyUnicode_DATA(s));
        it.size = PyUnicode_GET_LENGTH(s);
    } else {
        it.data = PyUnicode_AsUTF8AndSize(s, &it.size);
        if (!it.data) return false;
    }
    it.obj = s;
    return true;
}

// The rows of ``obj``: a 1-D object ndarray read in place, or any sequence
// through PySequence_Fast (``*keep`` then owns the list).
PyObject* const* rows_of(PyObject* obj, Py_ssize_t* n, npy_intp* stride,
                         PyObject** keep) {
    *keep = nullptr;
    if (PyArray_Check(obj)) {
        PyArrayObject* a = reinterpret_cast<PyArrayObject*>(obj);
        if (PyArray_NDIM(a) == 1 && PyArray_TYPE(a) == NPY_OBJECT) {
            *n = PyArray_DIM(a, 0);
            *stride = PyArray_STRIDE(a, 0) /
                      static_cast<npy_intp>(sizeof(PyObject*));
            if (PyArray_STRIDE(a, 0) % static_cast<npy_intp>(
                    sizeof(PyObject*)) == 0)
                return reinterpret_cast<PyObject* const*>(PyArray_DATA(a));
        }
    }
    *keep = PySequence_Fast(obj, "rows");
    if (!*keep) return nullptr;
    *n = PySequence_Fast_GET_SIZE(*keep);
    *stride = 1;
    return PySequence_Fast_ITEMS(*keep);
}

PyObject* list_of_uniq(const Table& t) {
    PyObject* out = PyList_New(static_cast<Py_ssize_t>(t.uniq.size()));
    if (!out) return nullptr;
    for (size_t j = 0; j < t.uniq.size(); ++j) {
        Py_INCREF(t.uniq[j]);
        PyList_SET_ITEM(out, static_cast<Py_ssize_t>(j), t.uniq[j]);
    }
    return out;
}

template <typename T>
PyObject* array_of(const std::vector<T>& v, int type) {
    npy_intp n = static_cast<npy_intp>(v.size());
    PyObject* a = PyArray_EMPTY(1, &n, type, 0);
    if (a && n)
        memcpy(PyArray_DATA(reinterpret_cast<PyArrayObject*>(a)), v.data(),
               v.size() * sizeof(T));
    return a;
}

void release(std::vector<PyObject*>& objs) {
    for (PyObject* o : objs) Py_XDECREF(o);
    objs.clear();
}

// A value RawFeatureFilter's row walk would bin item by item.
bool is_container(PyObject* v) {
    return PyList_Check(v) || PyTuple_Check(v) || PySet_Check(v);
}

// expand_strings(maps) -> (keys list[str] first-seen order,
//                          in_dict int64[K] rows whose dict holds the key,
//                          ids list of K int32[N]: the value's id, -1 where
//                              the key is absent or its value None,
//                          uniq list of K lists of str,
//                          counts list of K int64 arrays (rows a value),
//                          nonempty uint8[N] (the row is a non-empty dict),
//                          inexact int64: rows that are neither None nor a
//                              dict (read as empty maps) and values that are
//                              lists, tuples or sets (interned as their str))
// A key that is no str raises TypeError.
PyObject* expand_strings(PyObject*, PyObject* args) {
    PyObject* maps;
    if (!PyArg_ParseTuple(args, "O", &maps)) return nullptr;
    Py_ssize_t n;
    npy_intp stride;
    PyObject* keep;
    PyObject* const* rows = rows_of(maps, &n, &stride, &keep);
    if (!rows) return nullptr;

    // a key by its bytes, and by the str object itself: the rows' dicts
    // mostly share their key objects, and a pointer is cheaper to hash
    std::unordered_map<std::string_view, int32_t> key_ids;
    std::unordered_map<PyObject*, int32_t> key_by_obj;
    std::vector<PyObject*> key_objs;            // borrowed
    std::vector<int64_t> in_dict;
    std::vector<PyObject*> id_arrays;           // owned int32[N] a key
    std::vector<int32_t*> id_data;
    std::vector<Table> tables;
    std::vector<PyObject*> owned;
    std::vector<Item> block;
    int64_t inexact = 0;
    npy_intp dim_n = n;
    PyObject* nonempty = PyArray_ZEROS(1, &dim_n, NPY_UINT8, 0);
    bool fail = nonempty == nullptr;
    npy_uint8* ne = fail ? nullptr : static_cast<npy_uint8*>(
        PyArray_DATA(reinterpret_cast<PyArrayObject*>(nonempty)));

    for (Py_ssize_t start = 0; start < n && !fail; start += BLOCK_ROWS) {
        const Py_ssize_t stop = std::min(n, start + BLOCK_ROWS);
        block.clear();
        for (Py_ssize_t i = start; i < stop && !fail; ++i) {
            PyObject* m = rows[i * stride];
            if (!m || m == Py_None) continue;
            if (!PyDict_Check(m)) {
                ++inexact;
                continue;
            }
            if (PyDict_Size(m) > 0) ne[i] = 1;
            PyObject *k, *v;
            Py_ssize_t pos = 0;
            while (PyDict_Next(m, &pos, &k, &v)) {
                int32_t id;
                auto known = key_by_obj.find(k);
                if (known != key_by_obj.end()) {
                    id = known->second;
                } else {
                    Py_ssize_t klen;
                    const char* kdata = PyUnicode_Check(k)
                        ? PyUnicode_AsUTF8AndSize(k, &klen) : nullptr;
                    if (!kdata) {
                        if (!PyErr_Occurred())
                            PyErr_SetString(PyExc_TypeError,
                                            "a key that is no str");
                        fail = true;
                        break;
                    }
                    std::string_view key(kdata, static_cast<size_t>(klen));
                    auto it = key_ids.find(key);
                    if (it == key_ids.end()) {
                        id = static_cast<int32_t>(key_objs.size());
                        PyObject* a = PyArray_EMPTY(1, &dim_n, NPY_INT32, 0);
                        if (!a) { fail = true; break; }
                        int32_t* d = static_cast<int32_t*>(
                            PyArray_DATA(reinterpret_cast<PyArrayObject*>(a)));
                        std::fill(d, d + n, int32_t{-1});
                        id_arrays.push_back(a);
                        id_data.push_back(d);
                        key_ids.emplace(key, id);
                        key_objs.push_back(k);
                        in_dict.push_back(0);
                        tables.emplace_back();
                    } else {
                        id = it->second;
                    }
                    key_by_obj.emplace(k, id);
                }
                in_dict[static_cast<size_t>(id)] += 1;
                if (v == Py_None) continue;
                if (is_container(v)) ++inexact;
                Item item{i, id, nullptr, 0, nullptr};
                if (!value_bytes(v, item, owned)) { fail = true; break; }
                block.push_back(item);
            }
        }
        if (fail) break;
        Py_BEGIN_ALLOW_THREADS
        for (const Item& item : block) {
            Table& t = tables[static_cast<size_t>(item.key)];
            const int32_t vid = t.intern(item);
            t.counts[static_cast<size_t>(vid)] += 1;
            id_data[static_cast<size_t>(item.key)][item.row] = vid;
        }
        Py_END_ALLOW_THREADS
    }
    Py_XDECREF(keep);
    PyObject* out = nullptr;
    if (!fail) {
        const Py_ssize_t K = static_cast<Py_ssize_t>(key_objs.size());
        PyObject* keys = PyList_New(K);
        PyObject* ids = PyList_New(K);
        PyObject* uniq = PyList_New(K);
        PyObject* counts = PyList_New(K);
        PyObject* indict = array_of(in_dict, NPY_INT64);
        bool ok = keys && ids && uniq && counts && indict;
        for (Py_ssize_t j = 0; ok && j < K; ++j) {
            const Table& t = tables[static_cast<size_t>(j)];
            PyObject* u = list_of_uniq(t);
            PyObject* c = array_of(t.counts, NPY_INT64);
            if (!u || !c) { Py_XDECREF(u); Py_XDECREF(c); ok = false; break; }
            Py_INCREF(key_objs[static_cast<size_t>(j)]);
            PyList_SET_ITEM(keys, j, key_objs[static_cast<size_t>(j)]);
            Py_INCREF(id_arrays[static_cast<size_t>(j)]);
            PyList_SET_ITEM(ids, j, id_arrays[static_cast<size_t>(j)]);
            PyList_SET_ITEM(uniq, j, u);
            PyList_SET_ITEM(counts, j, c);
        }
        if (ok) {
            Py_INCREF(nonempty);
            out = Py_BuildValue("NNNNNNL", keys, indict, ids, uniq, counts,
                                nonempty, static_cast<long long>(inexact));
        } else {
            Py_XDECREF(keys); Py_XDECREF(ids); Py_XDECREF(uniq);
            Py_XDECREF(counts); Py_XDECREF(indict);
        }
    }
    release(id_arrays);
    Py_XDECREF(nonempty);
    release(owned);   // the uniq lists hold their own references
    return out;
}

// expand_sets(rows) -> (offsets int64[N+1], ids int32[nnz] (a value once a
//                       row, in the order first seen there),
//                       uniq list[str], counts int64[U] (rows a value),
//                       items int64[U] (items a value, duplicates counted),
//                       nonempty uint8[N] (the row holds an item))
// A row is None, a list, a tuple or a set; anything else raises TypeError.
PyObject* expand_sets(PyObject*, PyObject* args) {
    PyObject* values;
    if (!PyArg_ParseTuple(args, "O", &values)) return nullptr;
    Py_ssize_t n;
    npy_intp stride;
    PyObject* keep;
    PyObject* const* rows = rows_of(values, &n, &stride, &keep);
    if (!rows) return nullptr;

    Table table;
    std::vector<PyObject*> owned;
    std::vector<PyObject*> iterated;      // set rows made into lists
    std::vector<Item> block;
    std::vector<int64_t> offsets(static_cast<size_t>(n) + 1, 0);
    std::vector<int32_t> ids;
    std::vector<int32_t> row_ids;
    npy_intp dim_n = n;
    PyObject* nonempty = PyArray_ZEROS(1, &dim_n, NPY_UINT8, 0);
    bool fail = nonempty == nullptr;
    npy_uint8* ne = fail ? nullptr : static_cast<npy_uint8*>(
        PyArray_DATA(reinterpret_cast<PyArrayObject*>(nonempty)));

    for (Py_ssize_t start = 0; start < n && !fail; start += BLOCK_ROWS) {
        const Py_ssize_t stop = std::min(n, start + BLOCK_ROWS);
        block.clear();
        for (Py_ssize_t i = start; i < stop && !fail; ++i) {
            PyObject* s = rows[i * stride];
            if (!s || s == Py_None) continue;
            PyObject* seq = s;
            if (PySet_Check(s)) {
                seq = PySequence_List(s);
                if (!seq) { fail = true; break; }
                iterated.push_back(seq);
            } else if (!PyList_Check(s) && !PyTuple_Check(s)) {
                PyErr_SetString(PyExc_TypeError,
                                "a row that is no list, tuple or set");
                fail = true;
                break;
            }
            const Py_ssize_t m = PySequence_Fast_GET_SIZE(seq);
            PyObject** items = PySequence_Fast_ITEMS(seq);
            if (m > 0) ne[i] = 1;
            for (Py_ssize_t j = 0; j < m; ++j) {
                Item item{i, 0, nullptr, 0, nullptr};
                if (!value_bytes(items[j], item, owned)) { fail = true; break; }
                block.push_back(item);
            }
        }
        if (fail) break;
        Py_BEGIN_ALLOW_THREADS
        size_t at = 0;
        while (at < block.size()) {
            const Py_ssize_t row = block[at].row;
            row_ids.clear();
            for (; at < block.size() && block[at].row == row; ++at) {
                const int32_t vid = table.intern(block[at]);
                table.items[static_cast<size_t>(vid)] += 1;
                if (std::find(row_ids.begin(), row_ids.end(), vid) ==
                    row_ids.end())
                    row_ids.push_back(vid);
            }
            for (int32_t vid : row_ids) {
                table.counts[static_cast<size_t>(vid)] += 1;
                ids.push_back(vid);
            }
            offsets[static_cast<size_t>(row) + 1] =
                static_cast<int64_t>(row_ids.size());
        }
        Py_END_ALLOW_THREADS
    }
    Py_XDECREF(keep);
    PyObject* out = nullptr;
    if (!fail) {
        for (Py_ssize_t i = 0; i < n; ++i)
            offsets[static_cast<size_t>(i) + 1] += offsets[static_cast<size_t>(i)];
        PyObject* off = array_of(offsets, NPY_INT64);
        PyObject* idarr = array_of(ids, NPY_INT32);
        PyObject* uniq = list_of_uniq(table);
        PyObject* counts = array_of(table.counts, NPY_INT64);
        PyObject* items = array_of(table.items, NPY_INT64);
        if (off && idarr && uniq && counts && items) {
            Py_INCREF(nonempty);
            out = Py_BuildValue("NNNNNN", off, idarr, uniq, counts, items,
                                nonempty);
        } else {
            Py_XDECREF(off); Py_XDECREF(idarr); Py_XDECREF(uniq);
            Py_XDECREF(counts); Py_XDECREF(items);
        }
    }
    Py_XDECREF(nonempty);
    release(owned);
    release(iterated);
    return out;
}

PyMethodDef methods[] = {
    {"expand", expand, METH_VARARGS,
     "expand(maps) -> (keys, vals f64[N,K], present u8[N,K], "
     "in_dict i64[K], nonempty u8[N])"},
    {"expand_strings", expand_strings, METH_VARARGS,
     "expand_strings(maps) -> (keys, in_dict i64[K], ids [i32[N]] a key, "
     "uniq [[str]] a key, counts [i64[U]] a key, nonempty u8[N], "
     "inexact)"},
    {"expand_sets", expand_sets, METH_VARARGS,
     "expand_sets(rows) -> (offsets i64[N+1], ids i32[nnz], uniq [str], "
     "counts i64[U], items i64[U], nonempty u8[N])"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_mapprof",
    "One-pass columnar expansion of numeric map columns.", -1, methods,
};

}  // namespace

PyMODINIT_FUNC PyInit__mapprof(void) {
    import_array();
    return PyModule_Create(&moduledef);
}
