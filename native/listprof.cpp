// listprof — one native pass over a date-list column, or over the strings of
// a URL or e-mail column.
//
// A DateList column is held ragged (columns.RaggedList): one int64 array of
// every row's dates in row order and the offsets of each row's first.  Its
// consumers — DateListVectorizer's SinceFirst / SinceLast pivots and
// RawFeatureFilter's distribution — read ONE walk of it:
//
//   date_lists(values, offsets, text_bins)
//       -> (lo int64[N], hi int64[N], hist float64[text_bins])
//
// lo / hi: a row's least and greatest date (0 for an empty row); hist: the
// hashed-item histogram of RawFeatureFilter's text branch, every date binned
// by zlib's crc32 of its decimal string (str(int) as Python writes it)
// modulo text_bins; empty where text_bins is 0.  Held with the GIL released.
//
//   domains(strings, kind) -> (uniq list[str], counts int64[U], codes int32[N])
//
// kind 0: a URL's host as text_specialized.url_domain gives it (urllib's
// hostname of a valid http / https / ftp url, lower-cased); kind 1: an
// e-mail's domain as email_parts gives it.  codes: -1 where a row has none
// (None, empty, invalid), -3 where the walk leaves the row to Python (a URL
// with a byte outside printable ASCII or a bracket, whose rules are
// urllib's own; a value that is not a str), else an index into uniq, which
// is in the order of first occurrence; counts: the rows of each.  The
// strings' utf-8 is fetched with the GIL held, parsed and interned with it
// released.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#define NPY_NO_DEPRECATED_API NPY_1_7_API_VERSION
#include <numpy/arrayobject.h>

#include <cstdint>
#include <cstring>
#include <string>
#include <unordered_map>
#include <vector>

namespace {

// ---------------------------------------------------------------------------
// crc32 of a decimal string, by groups of digits
//
// zlib's crc32 of a message of L bytes is affine in its bytes: it is the crc
// of L zero bytes XOR, for each position, what the byte there adds alone.
// So for a number of L digits, a table a length and a group of four digits
// counted from the right (10,000 entries: what those four add together)
// gives its crc in ceil(L / 4) look-ups that do not wait on one another:
// four for a date in epoch milliseconds.  A negative number (a date before
// 1970) adds its '-' byte by byte.
constexpr int MAX_DIGITS = 19;      // 9223372036854775807
constexpr int MAX_LEN = 20;         // "-9223372036854775808"
constexpr int GROUP = 10000;

uint32_t CRC_TABLE[256];
uint32_t ZERO_CRC[MAX_LEN + 1];
// GROUPS[len] -> ceil(len / 4) tables of GROUP entries, the digits' places
// counted from the right end of a string of ``len`` characters
std::vector<uint32_t> GROUPS[MAX_LEN + 1];
uint64_t POW10[MAX_DIGITS + 1];

uint32_t crc32_of(const unsigned char* p, int n) {
    uint32_t c = 0xFFFFFFFFu;
    for (int i = 0; i < n; ++i) c = CRC_TABLE[(c ^ p[i]) & 0xFFu] ^ (c >> 8);
    return c ^ 0xFFFFFFFFu;
}

// what byte ``ch`` at position ``i`` adds to the crc of ``len`` bytes
uint32_t alone(int len, int i, unsigned char ch) {
    unsigned char msg[MAX_LEN] = {0};
    msg[i] = ch;
    return crc32_of(msg, len) ^ ZERO_CRC[len];
}

void build_tables() {
    for (uint32_t b = 0; b < 256; ++b) {
        uint32_t c = b;
        for (int k = 0; k < 8; ++k) c = (c & 1u) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
        CRC_TABLE[b] = c;
    }
    unsigned char zeros[MAX_LEN] = {0};
    for (int len = 1; len <= MAX_LEN; ++len) ZERO_CRC[len] = crc32_of(zeros, len);
    POW10[0] = 1;
    for (int k = 1; k <= MAX_DIGITS; ++k) POW10[k] = POW10[k - 1] * 10;
    for (int len = 1; len <= MAX_LEN; ++len) {
        const int groups = (len + 3) / 4;
        std::vector<uint32_t>& t = GROUPS[len];
        t.assign(static_cast<size_t>(groups) * GROUP, 0u);
        // a digit's place r from the right: position len - 1 - r
        uint32_t by_place[4][10];
        for (int g = 0; g < groups; ++g) {
            for (int j = 0; j < 4; ++j)
                for (int d = 0; d < 10; ++d)
                    by_place[j][d] = 4 * g + j < len
                        ? alone(len, len - 1 - (4 * g + j),
                                static_cast<unsigned char>('0' + d))
                        : 0u;
            uint32_t* out = t.data() + static_cast<size_t>(g) * GROUP;
            for (int w = 0; w < GROUP; ++w)
                out[w] = by_place[0][w % 10] ^ by_place[1][w / 10 % 10] ^
                         by_place[2][w / 100 % 10] ^ by_place[3][w / 1000];
        }
    }
}

inline int digits_of(uint64_t u) {
    // floor(log10(2) * bit length), then one comparison
    if (!u) return 1;
    const int k = ((64 - __builtin_clzll(u)) * 1233) >> 12;
    return k + (k < MAX_DIGITS && u >= POW10[k]);
}

inline uint32_t decimal_crc(int64_t v) {
    const bool neg = v < 0;
    uint64_t u = neg ? 0u - static_cast<uint64_t>(v) : static_cast<uint64_t>(v);
    const int digits = digits_of(u);
    const int len = digits + neg;
    const uint32_t* t = GROUPS[len].data();
    uint32_t crc = ZERO_CRC[len];
    for (int g = 0; 4 * g < digits; ++g, u /= GROUP)
        crc ^= t[static_cast<size_t>(g) * GROUP + u % GROUP];
    if (neg) {
        // the '-' at position 0, which the top group read as a '0' where it
        // holds other places too
        crc ^= alone(len, 0, '-');
        if (digits % 4) crc ^= alone(len, 0, '0');
    }
    return crc;
}

PyArrayObject* int64_vector(PyObject* obj, const char* what) {
    PyArrayObject* a = reinterpret_cast<PyArrayObject*>(PyArray_FROM_OTF(
        obj, NPY_INT64, NPY_ARRAY_IN_ARRAY));
    if (!a) return nullptr;
    if (PyArray_NDIM(a) != 1) {
        Py_DECREF(a);
        PyErr_Format(PyExc_ValueError, "%s must be 1-D", what);
        return nullptr;
    }
    return a;
}

PyObject* date_lists(PyObject*, PyObject* args) {
    PyObject *vobj, *oobj;
    Py_ssize_t bins;
    if (!PyArg_ParseTuple(args, "OOn", &vobj, &oobj, &bins)) return nullptr;
    if (bins < 0) {
        PyErr_SetString(PyExc_ValueError, "text_bins must be >= 0");
        return nullptr;
    }
    PyArrayObject* va = int64_vector(vobj, "values");
    if (!va) return nullptr;
    PyArrayObject* oa = int64_vector(oobj, "offsets");
    if (!oa) {
        Py_DECREF(va);
        return nullptr;
    }
    const npy_intp m = PyArray_DIM(va, 0);
    npy_intp n = PyArray_DIM(oa, 0) - 1;
    const int64_t* v = static_cast<const int64_t*>(PyArray_DATA(va));
    const int64_t* o = static_cast<const int64_t*>(PyArray_DATA(oa));
    bool ok = n >= 0 && o[0] == 0 && o[n] == m;
    for (npy_intp i = 0; ok && i < n; ++i) ok = o[i] <= o[i + 1];
    if (!ok) {
        Py_DECREF(va);
        Py_DECREF(oa);
        PyErr_SetString(PyExc_ValueError,
                        "offsets must rise from 0 to len(values)");
        return nullptr;
    }
    npy_intp nb = bins;
    PyArrayObject* lo = reinterpret_cast<PyArrayObject*>(
        PyArray_SimpleNew(1, &n, NPY_INT64));
    PyArrayObject* hi = reinterpret_cast<PyArrayObject*>(
        PyArray_SimpleNew(1, &n, NPY_INT64));
    PyArrayObject* hist = reinterpret_cast<PyArrayObject*>(
        PyArray_ZEROS(1, &nb, NPY_FLOAT64, 0));
    if (!lo || !hi || !hist) {
        Py_XDECREF(lo);
        Py_XDECREF(hi);
        Py_XDECREF(hist);
        Py_DECREF(va);
        Py_DECREF(oa);
        return nullptr;
    }
    int64_t* plo = static_cast<int64_t*>(PyArray_DATA(lo));
    int64_t* phi = static_cast<int64_t*>(PyArray_DATA(hi));
    double* h = static_cast<double*>(PyArray_DATA(hist));
    Py_BEGIN_ALLOW_THREADS
    std::vector<int64_t> counts(static_cast<size_t>(bins), 0);
    for (npy_intp i = 0; i < n; ++i) {
        const int64_t a = o[i], b = o[i + 1];
        if (a == b) {
            plo[i] = phi[i] = 0;
            continue;
        }
        int64_t least = v[a], most = v[a];
        for (int64_t j = a + 1; j < b; ++j) {
            least = v[j] < least ? v[j] : least;
            most = v[j] > most ? v[j] : most;
        }
        plo[i] = least;
        phi[i] = most;
    }
    if (bins > 0) {
        // crc % bins without a division (Lemire's fastmod: exact for any
        // 32-bit numerator and divisor)
        const uint64_t d = static_cast<uint64_t>(bins);
        const uint64_t inv = UINT64_C(0xFFFFFFFFFFFFFFFF) / d + 1;
        for (npy_intp j = 0; j < m; ++j) {
            const uint64_t low = inv * decimal_crc(v[j]);
            ++counts[static_cast<size_t>(
                (static_cast<__uint128_t>(low) * d) >> 64)];
        }
        for (Py_ssize_t k = 0; k < bins; ++k)
            h[k] = static_cast<double>(counts[static_cast<size_t>(k)]);
    }
    Py_END_ALLOW_THREADS
    Py_DECREF(va);
    Py_DECREF(oa);
    return Py_BuildValue("(NNN)", lo, hi, hist);
}

// ---------------------------------------------------------------------------
// domains

constexpr int8_t NONE = 0, TEXT = 1, PYTHON = 3;
constexpr int KIND_URL = 0, KIND_EMAIL = 1;

struct Row {
    const char* p;
    Py_ssize_t n;
    int8_t state;
};

inline bool scheme_char(unsigned char c) {
    return (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') ||
           (c >= '0' && c <= '9') || c == '+' || c == '-' || c == '.';
}

inline char lower(char c) {
    return (c >= 'A' && c <= 'Z') ? static_cast<char>(c - 'A' + 'a') : c;
}

// urllib.parse.urlsplit's scheme and netloc and the result's hostname, for a
// string of printable ASCII without brackets (the caller leaves every other
// to Python): the host of a valid http / https / ftp url into ``out``.
// Returns 1 with a host, 0 without one, PYTHON where urllib must decide.
int url_host(const char* s, Py_ssize_t n, std::string& out) {
    for (Py_ssize_t i = 0; i < n; ++i) {
        const unsigned char c = static_cast<unsigned char>(s[i]);
        // C0 controls and space are stripped or removed by urlsplit, a
        // non-ASCII netloc is checked against NFKC, a bracket is IPv6
        if (c <= 0x20 || c >= 0x80 || c == '[' || c == ']') return PYTHON;
    }
    Py_ssize_t rest = 0;
    const void* found = std::memchr(s, ':', static_cast<size_t>(n));
    if (!found) return 0;               // no scheme: not a valid url
    const Py_ssize_t colon = static_cast<const char*>(found) - s;
    const unsigned char first = static_cast<unsigned char>(s[0]);
    if (colon == 0 || !((first >= 'a' && first <= 'z') ||
                        (first >= 'A' && first <= 'Z')))
        return 0;
    for (Py_ssize_t i = 0; i < colon; ++i)
        if (!scheme_char(static_cast<unsigned char>(s[i]))) return 0;
    char scheme[6];
    if (colon > 5) return 0;
    for (Py_ssize_t i = 0; i < colon; ++i) scheme[i] = lower(s[i]);
    const std::string sch(scheme, static_cast<size_t>(colon));
    if (sch != "http" && sch != "https" && sch != "ftp") return 0;
    rest = colon + 1;
    if (n - rest < 2 || s[rest] != '/' || s[rest + 1] != '/') return 0;
    const Py_ssize_t start = rest + 2;
    Py_ssize_t end = start;
    while (end < n && s[end] != '/' && s[end] != '?' && s[end] != '#') ++end;
    // hostinfo: after the last '@' of the netloc; hostname: before its ':'
    Py_ssize_t host = start;
    for (Py_ssize_t i = start; i < end; ++i)
        if (s[i] == '@') host = i + 1;
    Py_ssize_t stop = host;
    while (stop < end && s[stop] != ':') ++stop;
    if (stop == host) return 0;
    out.assign(s + host, static_cast<size_t>(stop - host));
    // lower-cased up to a zone's '%', as urllib leaves the zone
    for (char& c : out) {
        if (c == '%') break;
        c = lower(c);
    }
    return 1;
}

// email_parts' domain: the part after the one '@', both parts non-empty.
int email_domain(const char* s, Py_ssize_t n, std::string& out) {
    Py_ssize_t at = -1;
    for (Py_ssize_t i = 0; i < n; ++i) {
        if (s[i] == '@') {
            if (at >= 0) return 0;
            at = i;
        }
    }
    if (at <= 0 || at == n - 1) return 0;
    out.assign(s + at + 1, static_cast<size_t>(n - at - 1));
    return 1;
}

PyObject* domains(PyObject*, PyObject* args) {
    PyObject* strings;
    int kind;
    if (!PyArg_ParseTuple(args, "Oi", &strings, &kind)) return nullptr;
    if (kind != KIND_URL && kind != KIND_EMAIL) {
        PyErr_SetString(PyExc_ValueError, "kind must be 0 (url) or 1 (email)");
        return nullptr;
    }
    PyObject* seq = PySequence_Fast(strings, "strings");
    if (!seq) return nullptr;
    const Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    std::vector<Row> rows(static_cast<size_t>(n));
    for (Py_ssize_t i = 0; i < n; ++i) {
        PyObject* s = PySequence_Fast_GET_ITEM(seq, i);
        Row& r = rows[static_cast<size_t>(i)];
        r.p = nullptr;
        r.n = 0;
        r.state = NONE;
        if (s == Py_None) continue;
        if (!PyUnicode_Check(s)) {
            r.state = PYTHON;
            continue;
        }
        if (kind == KIND_URL && !PyUnicode_IS_ASCII(s)) {
            r.state = PYTHON;
            continue;
        }
        r.p = PyUnicode_AsUTF8AndSize(s, &r.n);
        if (!r.p) {                     // a lone surrogate: Python's rules
            PyErr_Clear();
            r.state = PYTHON;
            continue;
        }
        r.state = r.n ? TEXT : NONE;
    }
    npy_intp dim = n;
    PyArrayObject* codes = reinterpret_cast<PyArrayObject*>(
        PyArray_SimpleNew(1, &dim, NPY_INT32));
    if (!codes) {
        Py_DECREF(seq);
        return nullptr;
    }
    int32_t* c = static_cast<int32_t*>(PyArray_DATA(codes));
    std::unordered_map<std::string, int32_t> table;
    std::vector<const std::string*> order;
    std::vector<int64_t> counts;
    Py_BEGIN_ALLOW_THREADS
    std::string host;
    for (Py_ssize_t i = 0; i < n; ++i) {
        const Row& r = rows[static_cast<size_t>(i)];
        if (r.state != TEXT) {
            c[i] = r.state == PYTHON ? -3 : -1;
            continue;
        }
        const int got = kind == KIND_URL ? url_host(r.p, r.n, host)
                                         : email_domain(r.p, r.n, host);
        if (got != 1) {
            c[i] = got == PYTHON ? -3 : -1;
            continue;
        }
        auto it = table.find(host);
        if (it == table.end()) {
            it = table.emplace(host, static_cast<int32_t>(order.size())).first;
            order.push_back(&it->first);
            counts.push_back(0);
        }
        c[i] = it->second;
        ++counts[static_cast<size_t>(it->second)];
    }
    Py_END_ALLOW_THREADS
    Py_DECREF(seq);
    const npy_intp u = static_cast<npy_intp>(order.size());
    PyObject* uniq = PyList_New(u);
    PyArrayObject* cnt = reinterpret_cast<PyArrayObject*>(
        PyArray_SimpleNew(1, &u, NPY_INT64));
    if (!uniq || !cnt) {
        Py_XDECREF(uniq);
        Py_XDECREF(cnt);
        Py_DECREF(codes);
        return nullptr;
    }
    int64_t* pc = static_cast<int64_t*>(PyArray_DATA(cnt));
    for (npy_intp j = 0; j < u; ++j) {
        const std::string& k = *order[static_cast<size_t>(j)];
        PyObject* s = PyUnicode_DecodeUTF8(k.data(),
                                           static_cast<Py_ssize_t>(k.size()),
                                           "strict");
        if (!s) {
            Py_DECREF(uniq);
            Py_DECREF(cnt);
            Py_DECREF(codes);
            return nullptr;
        }
        PyList_SET_ITEM(uniq, j, s);
        pc[j] = counts[static_cast<size_t>(j)];
    }
    return Py_BuildValue("(NNN)", uniq, cnt, codes);
}

PyMethodDef methods[] = {
    {"date_lists", date_lists, METH_VARARGS,
     "date_lists(values, offsets, text_bins) -> (lo i64[N], hi i64[N], "
     "hist f64[text_bins]): each row's least and greatest date and the crc32 "
     "bins of every date's decimal string, the GIL released"},
    {"domains", domains, METH_VARARGS,
     "domains(strings, kind) -> (uniq, counts i64[U], codes i32[N]): kind 0 "
     "a URL's host, 1 an e-mail's domain; codes -1 none, -3 Python's"},
    {nullptr, nullptr, 0, nullptr},
};

PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "_listprof",
    "One pass over a ragged date-list column or a URL / e-mail column.", -1,
    methods,
};

}  // namespace

PyMODINIT_FUNC PyInit__listprof(void) {
    import_array();
    build_tables();
    return PyModule_Create(&moduledef);
}
