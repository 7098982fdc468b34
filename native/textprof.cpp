// textprof — ONE native walk over a text column for every host consumer.
//
// The transmogrification path used to scan each text column many times:
// RawFeatureFilter's presence + crc32 value binning (filters.py), the
// SmartTextVectorizer TextStats fit pass (ops/text.py), and the
// tokenize+hash transform pass (fasttok.cpp).  Each scan walked a million
// PyUnicode objects.  This module reads the object ndarray IN PLACE (its
// PyObject* buffer: no list copy, no PySequence_Fast) and computes the
// *parameter-free* per-row products and, when asked, the value interning in
// the same walk, so callers rebin/reuse without rescanning:
//
//   profile(arr, min_token_len=1, cap=None, frozen=None, until_frozen=False)
//       -> dict
//     null:     bool[N]    True where value is None
//     empty:    bool[N]    True where value == "" (present-but-empty: RFF
//                          counts it as missing, TextStats counts it)
//     lengths:  int32[N]   code-point length (0 for null)
//     crc:      uint32[N]  zlib-compatible crc32 of the utf-8 bytes
//                          (0 for null; rebin with % text_bins)
//     tok_lens: int32[N]   tokens per row (-1 = non-ASCII row, caller
//                          splices the Python tokenizer's output)
//     tok_hash: uint32[T]  full FNV-1a 32-bit per token (rebin with
//                          % num_hashes for any hash width)
//     fallback: intp[F]    rows with tok_lens == -1, ascending
//     uniq, counts, codes  only with a cap: what intern(arr, cap) returns
//     rows:     int        rows walked: N, or fewer with until_frozen
//
//   A ROW RANGE of a column is a slice view of its array (any stride is read
//   in place), and two arguments let a long column be walked as ranges:
//   `until_frozen` stops the walk at the end of the block in which a capped
//   table froze (the arrays keep N elements, the first `rows` written), and
//   `frozen` — the values of such a frozen table, in order — makes the walk
//   of a later range rebuild that small table and only look up in it: from
//   the freeze on no count moves and a row's code is its value's id or -2,
//   whatever came before it, so the ranges after the freeze are independent
//   of each other (`codes` alone is returned of the interning then).
//
//   intern(arr, cap=-1) -> (uniq list[str], counts int64[U], codes int32[N])
//     Value interning in first-occurrence order.  codes: -1 null, -2 value
//     seen only after the table froze.  cap < 0: exact counting of every
//     value (OneHotEstimator's Counter).  cap >= 0: the TextStats monoid's
//     freeze semantics (SmartTextVectorizer.scala:182-230 analog pinned in
//     ops/text.py TextStats.of_column): once the table holds cap+1 distinct
//     values ALL counting stops; lengths elsewhere keep accumulating.
//
//   lookup(values, table) -> int32[N]
//     Each of `values` (a list or tuple of str or None, read in place: a
//     column's distinct values, as intern gives them) as its index in
//     `table` (a few distinct str, a pivot's vocabulary), -2 where it is not
//     there, -1 for None: the walk of a frozen table, with its phase two
//     out of the GIL.  The values must not be resized during the call.
//
//   pack_ids3(hashes, num_hashes, offset, out, carry, last) -> words written
//     The packed token wire of the hashing trick (ops/text.py _pack_ids3 is
//     the definition): token t of a column goes, modulo num_hashes (< 1024),
//     into lane t % 3 of word t / 3, ten bits a lane.  `hashes` are the
//     tokens [offset, offset + T) of the column and `out` its whole int32
//     buffer; the call writes the words whose FIRST lane is one of its
//     tokens, taking the lanes past its last token from `carry` (the next
//     tokens of the column, at most two) and, where the column ends, the
//     sentinel num_hashes; with `last` also the sentinel words from there to
//     the end of `out`.  So the pieces of a column pack side by side, each
//     word written by exactly one call, with the GIL released.
//
// The walk goes a block of rows at a time, in two phases.  Phase one, under
// the GIL: each row's utf-8 pointer, byte length and code-point length (the
// array keeps every string alive for the call).  Phase two, with the GIL
// released: flags, CRC-32, tokenise + FNV-1a and the intern table, which is
// keyed on the bytes (a view into the strings, hashed by the CRC the row
// needs anyway).  So several columns walked on several threads serialise
// only on phase one.  The token hashes phase two appends are given room, and
// the block's buffers allocated, while the GIL is still held.
//
// Tokenization matches ops/text.py exactly for ASCII content (maximal runs
// of [A-Za-z0-9_'], A-Z lowered before hashing); rows containing non-ASCII
// bytes defer to the Python tokenizer for unicode case-folding parity.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#define NPY_NO_DEPRECATED_API NPY_1_7_API_VERSION
#include <numpy/arrayobject.h>

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <exception>
#include <vector>

namespace {

// rows a block: phase one of a block is ~0.5 ms of GIL, long beside the
// hand-over to the next thread (8,192 rows a block made eight threads slower
// than four), and most of the block's strings, a cache line or two each,
// are still in cache when phase two reads them (a whole column a block was
// slower again)
constexpr Py_ssize_t BLOCK_ROWS = 65536;
constexpr Py_ssize_t PREFETCH_ROWS = 16;

// zlib-compatible CRC-32 (IEEE 802.3 reflected, init/final 0xFFFFFFFF) —
// must match Python's zlib.crc32 bit-for-bit (filters._stable_text_bin) —
// and the tokenizer's byte classes: 0 for a separator, else the byte with
// A-Z lowered.
struct Tables {
    uint32_t crc[8][256];       // slicing-by-8: crc[k] advances k more bytes
    unsigned char tok[256];
    Tables() {
        for (uint32_t i = 0; i < 256; ++i) {
            uint32_t c = i;
            for (int k = 0; k < 8; ++k)
                c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
            crc[0][i] = c;
            const bool lower = i >= 'a' && i <= 'z';
            const bool upper = i >= 'A' && i <= 'Z';
            const bool digit = i >= '0' && i <= '9';
            tok[i] = static_cast<unsigned char>(
                upper ? i + 32
                      : (lower || digit || i == '_' || i == '\'') ? i : 0);
        }
        for (int k = 1; k < 8; ++k)
            for (uint32_t i = 0; i < 256; ++i)
                crc[k][i] = (crc[k - 1][i] >> 8) ^
                            crc[0][crc[k - 1][i] & 0xFFu];
    }
};
const Tables TABLES;

// CRC-32 of `n` bytes, eight a step (the byte-wise chain of dependent table
// loads is what a short value costs most); `seen` ORs every byte in, so its
// high bits say whether any is non-ASCII.
inline uint32_t crc32_of(const char* data, Py_ssize_t n, uint64_t* seen) {
    uint32_t c = 0xFFFFFFFFu;
    Py_ssize_t k = 0;
#if defined(__BYTE_ORDER__) && __BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__
    for (; k + 8 <= n; k += 8) {
        uint64_t w;
        memcpy(&w, data + k, 8);
        *seen |= w;
        const uint32_t lo = static_cast<uint32_t>(w) ^ c;
        const uint32_t hi = static_cast<uint32_t>(w >> 32);
        c = TABLES.crc[7][lo & 0xFFu] ^
            TABLES.crc[6][(lo >> 8) & 0xFFu] ^
            TABLES.crc[5][(lo >> 16) & 0xFFu] ^
            TABLES.crc[4][lo >> 24] ^
            TABLES.crc[3][hi & 0xFFu] ^
            TABLES.crc[2][(hi >> 8) & 0xFFu] ^
            TABLES.crc[1][(hi >> 16) & 0xFFu] ^
            TABLES.crc[0][hi >> 24];
    }
#endif
    for (; k < n; ++k) {
        const unsigned char b = static_cast<unsigned char>(data[k]);
        *seen |= b;
        c = TABLES.crc[0][(c ^ b) & 0xFFu] ^ (c >> 8);
    }
    return c ^ 0xFFFFFFFFu;
}

// The intern table: open addressing over views of the first occurrences'
// utf-8 bytes, indexed by the CRC-32 the row needs anyway.  A capped table
// is a few dozen slots that stay in cache, and a frozen one answers the
// rows of a high-cardinality column with one probe.
class InternTable {
    struct Slot {
        const char* data;
        size_t size;
        uint32_t crc;
        int32_t id;             // -1: empty
    };
    std::vector<Slot> slots_;
    size_t used_ = 0;

    Slot* probe(const char* data, size_t size, uint32_t crc) {
        const size_t mask = slots_.size() - 1;
        for (size_t i = crc & mask;; i = (i + 1) & mask) {
            Slot& s = slots_[i];
            if (s.id < 0 || (s.crc == crc && s.size == size &&
                             memcmp(s.data, data, size) == 0))
                return &s;
        }
    }

public:
    InternTable() : slots_(256, Slot{nullptr, 0, 0, -1}) {}

    // id of the value, or -1
    int32_t find(const char* data, size_t size, uint32_t crc) {
        return probe(data, size, crc)->id;
    }

    // the value must be absent
    void insert(const char* data, size_t size, uint32_t crc, int32_t id) {
        if (2 * (used_ + 1) > slots_.size()) {
            std::vector<Slot> old(2 * slots_.size(),
                                  Slot{nullptr, 0, 0, -1});
            old.swap(slots_);
            for (const Slot& s : old)
                if (s.id >= 0) *probe(s.data, s.size, s.crc) = s;
        }
        *probe(data, size, crc) = Slot{data, size, crc, id};
        ++used_;
    }
};

struct Walk {
    bool scan = false;          // per-row products wanted
    bool intern = false;        // interning wanted
    Py_ssize_t min_len = 1;
    Py_ssize_t cap = -1;
    bool frozen = false;        // the table was handed in frozen: look up only
    bool until_frozen = false;  // stop after the block in which it freezes
    Py_ssize_t rows = 0;        // rows walked

    // per-row outputs (numpy buffers, every element written)
    npy_bool* null = nullptr;
    npy_bool* empty = nullptr;
    npy_int32* lengths = nullptr;
    npy_uint32* crc = nullptr;
    npy_int32* tok_lens = nullptr;
    npy_int32* codes = nullptr;

    std::vector<npy_uint32> tok_hash;
    std::vector<npy_intp> fallback;
    InternTable table;
    std::vector<npy_intp> uniq_row;      // first row of each distinct value
    std::vector<int64_t> counts;

    // phase two of rows [start, start + m): bytes only, no Python object
    void block(Py_ssize_t start, Py_ssize_t m, const char* const* ptr,
               const Py_ssize_t* blen, const npy_int32* cplen) {
        for (Py_ssize_t j = 0; j < m; ++j) {
            const Py_ssize_t i = start + j;
            const char* data = ptr[j];
            if (!data) {
                if (scan) {
                    null[i] = 1; empty[i] = 0; lengths[i] = 0;
                    crc[i] = 0; tok_lens[i] = 0;
                }
                if (intern) codes[i] = -1;
                continue;
            }
            const Py_ssize_t n = blen[j];
            // the CRC, and whether any byte is non-ASCII
            uint64_t seen = 0;
            const uint32_t c = crc32_of(data, n, &seen);
            if (scan) {
                null[i] = 0;
                empty[i] = n == 0;
                lengths[i] = cplen[j];
                crc[i] = c;
                if (seen & 0x8080808080808080ull) {
                    tok_lens[i] = -1;
                    fallback.push_back(i);
                } else {
                    tok_lens[i] = tokenize(data, n);
                }
            }
            if (intern) codes[i] = intern_row(i, data, n, c);
        }
    }

    npy_int32 tokenize(const char* data, Py_ssize_t n) {
        npy_int32 count = 0;
        Py_ssize_t k = 0;
        while (k < n) {
            while (k < n && !TABLES.tok[static_cast<unsigned char>(data[k])])
                ++k;
            const Py_ssize_t start = k;
            uint32_t h = 2166136261u;
            unsigned char t;
            while (k < n &&
                   (t = TABLES.tok[static_cast<unsigned char>(data[k])])) {
                h = (h ^ t) * 16777619u;
                ++k;
            }
            if (k - start >= min_len && k > start) {
                tok_hash.push_back(h);
                ++count;
            }
        }
        return count;
    }

    npy_int32 intern_row(Py_ssize_t i, const char* data, Py_ssize_t n,
                         uint32_t c) {
        // TextStats freeze (ops/text.py of_column pins it): counting —
        // inserts AND increments of existing keys — happens only while the
        // table holds <= cap distinct values; the (cap+1)-th value may
        // still insert, after which every increment stops
        const bool can_count = !frozen &&
            (cap < 0 || static_cast<Py_ssize_t>(uniq_row.size()) <= cap);
        const size_t size = static_cast<size_t>(n);
        const int32_t found = table.find(data, size, c);
        if (found >= 0) {
            if (can_count) counts[found] += 1;
            return found;
        }
        if (!can_count) return -2;
        const int32_t id = static_cast<int32_t>(uniq_row.size());
        table.insert(data, size, c, id);
        uniq_row.push_back(i);
        counts.push_back(1);
        return id;
    }
};

// Grow `v` so that `extra` more elements fit, geometrically: what phase two
// appends is sized from the bytes phase one saw, before the GIL is dropped.
template <typename T>
void ensure_room(std::vector<T>& v, size_t extra) {
    const size_t need = v.size() + extra;
    if (need > v.capacity()) v.reserve(std::max(need, 2 * v.capacity()));
}

// The column as a 1-D object ndarray, or nullptr with TypeError set.
PyArrayObject* object_column(PyObject* obj) {
    if (!PyArray_Check(obj) ||
        PyArray_NDIM(reinterpret_cast<PyArrayObject*>(obj)) != 1 ||
        PyArray_TYPE(reinterpret_cast<PyArrayObject*>(obj)) != NPY_OBJECT) {
        PyErr_SetString(PyExc_TypeError,
                        "textprof: expected a 1-D object ndarray");
        return nullptr;
    }
    return reinterpret_cast<PyArrayObject*>(obj);
}

PyObject* new_rows(npy_intp n, int type, void** data) {
    PyObject* a = PyArray_EMPTY(1, &n, type, 0);
    if (a) *data = PyArray_DATA(reinterpret_cast<PyArrayObject*>(a));
    return a;
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

// Walks the `n` rows whose PyObject* lie `stride` bytes apart from `base`
// into `w`'s outputs.  False with a Python error set on failure.
bool walk_rows(const char* base, npy_intp stride, Py_ssize_t n, Walk& w) {
    const Py_ssize_t rows = std::min(n, BLOCK_ROWS);
    std::vector<const char*> ptr;
    std::vector<Py_ssize_t> blen;
    std::vector<npy_int32> cplen;
    try {
        ptr.resize(rows);
        blen.resize(rows);
        cplen.resize(rows);
    } catch (const std::exception&) {
        PyErr_NoMemory();
        return false;
    }
    for (Py_ssize_t start = 0; start < n; start += BLOCK_ROWS) {
        const Py_ssize_t m = std::min(BLOCK_ROWS, n - start);
        size_t bytes = 0;
        for (Py_ssize_t j = 0; j < m; ++j) {
            // the strings lie anywhere on the heap: ask for a later row's
            // header (its bytes follow in the same line or the next) now
            if (j + PREFETCH_ROWS < m)
                __builtin_prefetch(*reinterpret_cast<PyObject* const*>(
                    base + (start + j + PREFETCH_ROWS) * stride));
            PyObject* s = *reinterpret_cast<PyObject* const*>(
                base + (start + j) * stride);        // borrowed
            if (!s || s == Py_None) {
                ptr[j] = nullptr;
                continue;
            }
            if (PyUnicode_Check(s) && PyUnicode_IS_COMPACT_ASCII(s)) {
                // ASCII: the characters are the utf-8 bytes
                ptr[j] = static_cast<const char*>(PyUnicode_DATA(s));
                blen[j] = PyUnicode_GET_LENGTH(s);
            } else {
                ptr[j] = PyUnicode_AsUTF8AndSize(s, &blen[j]);
                if (!ptr[j]) return false;
            }
            cplen[j] = static_cast<npy_int32>(PyUnicode_GET_LENGTH(s));
            bytes += static_cast<size_t>(blen[j]);
        }
        bool oom = false;
        try {
            // a token is at least one byte and needs a separator before
            // the next: at most (bytes + rows) / 2 tokens in the block
            if (w.scan) ensure_room(w.tok_hash, (bytes + m) / 2 + 1);
            Py_BEGIN_ALLOW_THREADS
            try {
                w.block(start, m, ptr.data(), blen.data(), cplen.data());
            } catch (const std::exception&) {
                oom = true;
            }
            Py_END_ALLOW_THREADS
        } catch (const std::exception&) {
            oom = true;
        }
        if (oom) {
            PyErr_NoMemory();
            return false;
        }
        w.rows = start + m;
        if (w.until_frozen && w.cap >= 0 &&
            static_cast<Py_ssize_t>(w.uniq_row.size()) > w.cap)
            break;
    }
    return true;
}

bool walk_column(PyArrayObject* arr, Walk& w) {
    return walk_rows(PyArray_BYTES(arr), PyArray_STRIDE(arr, 0),
                     PyArray_DIM(arr, 0), w);
}

// Rebuilds a frozen table from its values, in order, so that a later range
// of the column finds the ids its first rows gave them.  `values` (a tuple
// this call owns) keeps the strings, and so their utf-8 bytes, alive for the
// walk.  False with a Python error set on failure.
bool seed_frozen(PyObject* values, Walk& w) {
    const Py_ssize_t count = PyTuple_GET_SIZE(values);
    for (Py_ssize_t id = 0; id < count; ++id) {
        PyObject* s = PyTuple_GET_ITEM(values, id);
        if (!PyUnicode_Check(s)) {
            PyErr_SetString(PyExc_TypeError,
                            "textprof: a frozen value that is no str");
            return false;
        }
        Py_ssize_t n;
        const char* data = PyUnicode_AsUTF8AndSize(s, &n);
        if (!data) return false;
        uint64_t seen = 0;
        const uint32_t c = crc32_of(data, n, &seen);
        const size_t size = static_cast<size_t>(n);
        try {
            if (w.table.find(data, size, c) < 0)
                w.table.insert(data, size, c, static_cast<int32_t>(id));
        } catch (const std::exception&) {
            PyErr_NoMemory();
            return false;
        }
    }
    w.frozen = true;
    return true;
}

// uniq (the first occurrences themselves, not copies) and counts of a
// finished walk.  False with an error set on failure.
bool interned_parts(PyArrayObject* arr, const Walk& w, PyObject** uniq,
                    PyObject** counts) {
    const char* base = PyArray_BYTES(arr);
    const npy_intp stride = PyArray_STRIDE(arr, 0);
    *uniq = PyList_New(static_cast<Py_ssize_t>(w.uniq_row.size()));
    *counts = array_of(w.counts, NPY_INT64);
    if (!*uniq || !*counts) {
        Py_CLEAR(*uniq);
        Py_CLEAR(*counts);
        return false;
    }
    for (size_t u = 0; u < w.uniq_row.size(); ++u) {
        PyObject* s = *reinterpret_cast<PyObject* const*>(
            base + w.uniq_row[u] * stride);
        Py_INCREF(s);
        PyList_SET_ITEM(*uniq, static_cast<Py_ssize_t>(u), s);
    }
    return true;
}

PyObject* profile(PyObject*, PyObject* args) {
    PyObject* obj;
    Py_ssize_t min_len = 1;
    PyObject* cap_obj = Py_None;
    PyObject* frozen_obj = Py_None;
    int until_frozen = 0;
    if (!PyArg_ParseTuple(args, "O|nOOp", &obj, &min_len, &cap_obj,
                          &frozen_obj, &until_frozen))
        return nullptr;
    PyArrayObject* arr = object_column(obj);
    if (!arr) return nullptr;
    Walk w;
    w.scan = true;
    w.min_len = min_len;
    w.until_frozen = until_frozen != 0;
    if (cap_obj != Py_None) {
        w.intern = true;
        w.cap = PyLong_AsSsize_t(cap_obj);
        if (w.cap == -1 && PyErr_Occurred()) return nullptr;
    }
    PyObject* frozen = nullptr;
    if (frozen_obj != Py_None) {
        if (!w.intern) {
            PyErr_SetString(PyExc_ValueError,
                            "textprof: frozen values without a cap");
            return nullptr;
        }
        frozen = PySequence_Tuple(frozen_obj);
        if (!frozen) return nullptr;
        if (!seed_frozen(frozen, w)) {
            Py_DECREF(frozen);
            return nullptr;
        }
    }

    const npy_intp n = PyArray_DIM(arr, 0);
    PyObject* nulls = new_rows(n, NPY_BOOL, reinterpret_cast<void**>(&w.null));
    PyObject* empty = new_rows(n, NPY_BOOL, reinterpret_cast<void**>(&w.empty));
    PyObject* lengths =
        new_rows(n, NPY_INT32, reinterpret_cast<void**>(&w.lengths));
    PyObject* crc = new_rows(n, NPY_UINT32, reinterpret_cast<void**>(&w.crc));
    PyObject* tok_lens =
        new_rows(n, NPY_INT32, reinterpret_cast<void**>(&w.tok_lens));
    PyObject* codes = w.intern
        ? new_rows(n, NPY_INT32, reinterpret_cast<void**>(&w.codes))
        : nullptr;
    PyObject *tok_hash = nullptr, *fallback = nullptr;
    PyObject *uniq = nullptr, *counts = nullptr, *out = nullptr;
    bool ok = nulls && empty && lengths && crc && tok_lens &&
              (codes || !w.intern) && walk_column(arr, w);
    if (ok) {
        tok_hash = array_of(w.tok_hash, NPY_UINT32);
        fallback = array_of(w.fallback, NPY_INTP);
        ok = tok_hash && fallback &&
             (!w.intern || w.frozen ||
              interned_parts(arr, w, &uniq, &counts));
    }
    if (ok)
        out = Py_BuildValue("{s:O,s:O,s:O,s:O,s:O,s:O,s:O,s:n}",
                            "null", nulls, "empty", empty, "lengths", lengths,
                            "crc", crc, "tok_lens", tok_lens,
                            "tok_hash", tok_hash, "fallback", fallback,
                            "rows", w.rows);
    if (out && w.intern &&
        (PyDict_SetItemString(out, "codes", codes) < 0 ||
         (!w.frozen && (PyDict_SetItemString(out, "uniq", uniq) < 0 ||
                        PyDict_SetItemString(out, "counts", counts) < 0))))
        Py_CLEAR(out);
    Py_XDECREF(frozen);
    Py_XDECREF(nulls);
    Py_XDECREF(empty);
    Py_XDECREF(lengths);
    Py_XDECREF(crc);
    Py_XDECREF(tok_lens);
    Py_XDECREF(codes);
    Py_XDECREF(tok_hash);
    Py_XDECREF(fallback);
    Py_XDECREF(uniq);
    Py_XDECREF(counts);
    return out;
}

PyObject* intern_values(PyObject*, PyObject* args) {
    PyObject* obj;
    Py_ssize_t cap = -1;
    if (!PyArg_ParseTuple(args, "O|n", &obj, &cap)) return nullptr;
    PyArrayObject* arr = object_column(obj);
    if (!arr) return nullptr;
    Walk w;
    w.intern = true;
    w.cap = cap;
    PyObject* codes = new_rows(PyArray_DIM(arr, 0), NPY_INT32,
                               reinterpret_cast<void**>(&w.codes));
    PyObject *uniq = nullptr, *counts = nullptr, *out = nullptr;
    if (codes && walk_column(arr, w) &&
        interned_parts(arr, w, &uniq, &counts))
        out = PyTuple_Pack(3, uniq, counts, codes);
    Py_XDECREF(codes);
    Py_XDECREF(uniq);
    Py_XDECREF(counts);
    return out;
}

PyObject* lookup(PyObject*, PyObject* args) {
    PyObject *values_obj, *table_obj;
    if (!PyArg_ParseTuple(args, "OO", &values_obj, &table_obj))
        return nullptr;
    // a list or tuple is read in place: its items are contiguous
    if (!PyList_Check(values_obj) && !PyTuple_Check(values_obj)) {
        PyErr_SetString(PyExc_TypeError,
                        "textprof: lookup wants a list or tuple of values");
        return nullptr;
    }
    PyObject* table = PySequence_Tuple(table_obj);
    PyObject* codes = nullptr;
    Walk w;
    w.intern = true;
    Py_INCREF(values_obj);
    if (table && seed_frozen(table, w)) {
        const Py_ssize_t n = PySequence_Fast_GET_SIZE(values_obj);
        codes = new_rows(n, NPY_INT32, reinterpret_cast<void**>(&w.codes));
        if (codes &&
            !walk_rows(reinterpret_cast<const char*>(
                           PySequence_Fast_ITEMS(values_obj)),
                       sizeof(PyObject*), n, w))
            Py_CLEAR(codes);
    }
    Py_XDECREF(table);
    Py_DECREF(values_obj);
    return codes;
}

// `obj` as a C-contiguous 1-D array of `type`, or nullptr with TypeError set.
PyArrayObject* flat_array(PyObject* obj, int type, const char* what) {
    if (!PyArray_Check(obj) ||
        PyArray_NDIM(reinterpret_cast<PyArrayObject*>(obj)) != 1 ||
        PyArray_TYPE(reinterpret_cast<PyArrayObject*>(obj)) != type ||
        !PyArray_IS_C_CONTIGUOUS(reinterpret_cast<PyArrayObject*>(obj))) {
        PyErr_Format(PyExc_TypeError,
                     "textprof: %s is not a contiguous 1-D array of its type",
                     what);
        return nullptr;
    }
    return reinterpret_cast<PyArrayObject*>(obj);
}

PyObject* pack_ids3(PyObject*, PyObject* args) {
    PyObject *hashes_obj, *out_obj, *carry_obj;
    Py_ssize_t num_hashes, offset;
    int last;
    if (!PyArg_ParseTuple(args, "OnnOOp", &hashes_obj, &num_hashes, &offset,
                          &out_obj, &carry_obj, &last))
        return nullptr;
    PyArrayObject* hashes = flat_array(hashes_obj, NPY_UINT32, "hashes");
    PyArrayObject* out = flat_array(out_obj, NPY_INT32, "out");
    PyArrayObject* carry = flat_array(carry_obj, NPY_UINT32, "carry");
    if (!hashes || !out || !carry) return nullptr;
    const Py_ssize_t tokens = PyArray_DIM(hashes, 0);
    const Py_ssize_t carried = PyArray_DIM(carry, 0);
    const Py_ssize_t cap = PyArray_DIM(out, 0);
    // the words whose first lane is a token of this piece
    const Py_ssize_t first = (offset + 2) / 3;
    const Py_ssize_t end = (offset + tokens + 2) / 3;
    if (num_hashes < 1 || num_hashes >= 1024 || offset < 0 || carried > 2 ||
        end > cap || !PyArray_ISWRITEABLE(out)) {
        PyErr_SetString(PyExc_ValueError,
                        "textprof: pack_ids3 wants 1 <= num_hashes < 1024, "
                        "offset >= 0, at most two carried tokens and a "
                        "writable out that holds the piece's words");
        return nullptr;
    }
    const uint32_t* h = static_cast<const uint32_t*>(PyArray_DATA(hashes));
    const uint32_t* more = static_cast<const uint32_t*>(PyArray_DATA(carry));
    int32_t* words = static_cast<int32_t*>(PyArray_DATA(out));
    const uint32_t width = static_cast<uint32_t>(num_hashes);
    Py_BEGIN_ALLOW_THREADS
    // the words that lie whole inside the piece, then the one that does not
    const uint32_t* t = h + (3 * first - offset);
    Py_ssize_t w = first;
    for (; 3 * w + 2 < offset + tokens; ++w, t += 3)
        words[w] = static_cast<int32_t>(
            t[0] % width | (t[1] % width) << 10 | (t[2] % width) << 20);
    for (; w < end; ++w) {
        uint32_t word = 0;
        for (int lane = 0; lane < 3; ++lane) {
            const Py_ssize_t i = 3 * w + lane - offset;
            const uint32_t id = i < tokens ? h[i] % width
                : i - tokens < carried ? more[i - tokens] % width : width;
            word |= id << (10 * lane);
        }
        words[w] = static_cast<int32_t>(word);
    }
    if (last) {
        const int32_t sentinel =
            static_cast<int32_t>(width | width << 10 | width << 20);
        std::fill(words + end, words + cap, sentinel);
    }
    Py_END_ALLOW_THREADS
    return PyLong_FromSsize_t(end - first);
}

PyMethodDef methods[] = {
    {"profile", profile, METH_VARARGS,
     "profile(arr, min_token_len=1, cap=None, frozen=None, "
     "until_frozen=False) -> dict of parameter-free per-row products "
     "(null/empty/lengths/crc/tok_lens/tok_hash/fallback, rows) of a 1-D "
     "object ndarray, read in place; with a cap also uniq/counts/codes as "
     "intern(arr, cap) gives them, from the same walk; until_frozen stops "
     "after the block in which the table froze, frozen (that table's "
     "values) makes the walk of a later range look up only"},
    {"pack_ids3", pack_ids3, METH_VARARGS,
     "pack_ids3(hashes, num_hashes, offset, out, carry, last) -> words "
     "written: the words of the packed token wire whose first lane is one "
     "of the tokens [offset, offset + len(hashes)) of a column, lanes past "
     "them from carry, then the sentinel; with last the sentinel words to "
     "the end of out"},
    {"intern", intern_values, METH_VARARGS,
     "intern(arr, cap=-1) -> (uniq, counts int64[U], codes int32[N]) of a "
     "1-D object ndarray, read in place; cap>=0 applies the TextStats "
     "freeze semantics"},
    {"lookup", lookup, METH_VARARGS,
     "lookup(values, table) -> int32[N]: each of a list or tuple of str or "
     "None as its index in table (distinct str), -2 where it is not there, "
     "-1 for None"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_textprof",
    "One-walk native text column profile.", -1, methods,
};

}  // namespace

PyMODINIT_FUNC PyInit__textprof(void) {
    import_array();
    return PyModule_Create(&moduledef);
}
