// Native stdin-grammar parser for dmlp_tpu (the TPU-native analog of the
// reference harness's rank-0 ingest, common.cpp:93-117 + parsers :12-55).
//
// The grammar (one header line, num_data data lines, num_queries 'Q' lines,
// whitespace-tokenized decimals) is parsed straight into caller-allocated
// flat arrays — the SoA layout the device pipeline feeds — with strtod,
// which rounds identically to Python's float(), so results are
// bit-identical to the pure-Python parser (dmlp_tpu.io.grammar).
//
// Error contract mirrors common.cpp:101 ("Line is empty") and :114
// ("Line is wrongly formatted").
//
// The serving daemon's request lines use the same converter: the
// "queries" matrix of a query line (a JSON array of equal-length arrays
// of JSON numbers) is scanned straight into a caller-owned double buffer
// (dmlp_parse_json_matrix), bit-identical to json.loads + np.asarray.
//
// Build: g++ -O3 -shared -fPIC -o _fastparse.so fastparse.cpp
// (loaded via ctypes by dmlp_tpu.io.native; no pybind11 in this image).

#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <locale.h>
#if defined(__has_include)
#if __has_include(<charconv>)
#include <charconv>
#endif
#endif

// std::from_chars for double is correctly rounded, like strtod, and three
// to four times as fast on 16-18 digit tokens; libstdc++ has it where it
// defines __cpp_lib_to_chars (GCC 11+, -std=c++17 or later, g++'s default).
// DMLP_FASTPARSE_NO_FROM_CHARS forces the strtod build (the tests compare
// the two).
#if defined(__cpp_lib_to_chars) && !defined(DMLP_FASTPARSE_NO_FROM_CHARS)
#define DMLP_HAVE_FROM_CHARS 1
#endif

namespace {

// strtod is LC_NUMERIC-sensitive; a host app that set a comma-decimal
// locale would break the fallback path. Parse under a pinned "C" locale.
locale_t c_locale() {
    static locale_t loc = newlocale(LC_ALL_MASK, "C", (locale_t)0);
    return loc;
}

struct Cursor {
    const char* p;
    const char* end;
};

inline void skip_spaces(Cursor& c) {
    while (c.p < c.end && (*c.p == ' ' || *c.p == '\t' || *c.p == '\r'))
        ++c.p;
}

// Advance past the current line's newline; returns false at EOF.
inline bool next_line(Cursor& c) {
    while (c.p < c.end && *c.p != '\n') ++c.p;
    if (c.p < c.end) ++c.p;
    return c.p < c.end;
}

inline bool at_eol(const Cursor& c) {
    return c.p >= c.end || *c.p == '\n';
}

// A parsed token must end at whitespace/EOL/EOF — trailing garbage
// ("1.5abc", "1_0", "0x10") is a format error, exactly like the
// reference's stringstream extraction followed by a failed next read
// (common.cpp parsers) and the Python parser's per-token conversion.
inline bool token_ends(const char* q, const char* end) {
    return q >= end || *q == ' ' || *q == '\t' || *q == '\r' || *q == '\n';
}

// Parse an integer token. Strict: the token must end at whitespace/EOL
// ("3.5" as a label/k/header value is an error, matching the pure-Python
// parser's accept/reject behavior).
inline bool parse_long(Cursor& c, long* out) {
    skip_spaces(c);
    if (at_eol(c)) return false;
    char* q;
    long v = strtol(c.p, &q, 10);
    if (q == c.p) return false;
    if (!token_ends(q, c.end)) return false;
    c.p = q;
    *out = v;
    return true;
}

// Clinger fast path: a decimal with <= 15 significant digits and a small
// power-of-ten scale converts exactly with one rounding (mantissa and the
// power of ten are both exactly representable), i.e. bit-identical to
// correctly-rounded strtod / Python float(). Covers the generator's %.6f
// values; anything longer, or with an exponent, falls back to strtod.
static const double kPow10[23] = {
    1e0, 1e1, 1e2, 1e3, 1e4, 1e5, 1e6, 1e7, 1e8, 1e9, 1e10, 1e11, 1e12,
    1e13, 1e14, 1e15, 1e16, 1e17, 1e18, 1e19, 1e20, 1e21, 1e22};

inline double small_decimal(uint64_t mant, int frac, bool neg) {
    double v = static_cast<double>(mant);
    if (frac) v /= kPow10[frac];
    return neg ? -v : v;
}

inline bool parse_double(Cursor& c, double* out) {
    skip_spaces(c);
    if (at_eol(c)) return false;
    const char* s = c.p;
    bool neg = false;
    if (s < c.end && (*s == '-' || *s == '+')) {
        neg = (*s == '-');
        ++s;
    }
    uint64_t mant = 0;
    int digits = 0, frac = 0;
    const char* d = s;
    while (d < c.end && *d >= '0' && *d <= '9') {
        if (digits < 19) mant = mant * 10 + static_cast<uint64_t>(*d - '0');
        ++digits;
        ++d;
    }
    if (d < c.end && *d == '.') {
        ++d;
        while (d < c.end && *d >= '0' && *d <= '9') {
            if (digits < 19) {
                mant = mant * 10 + static_cast<uint64_t>(*d - '0');
                ++frac;
            }
            ++digits;
            ++d;
        }
    }
    bool has_exp = d < c.end && (*d == 'e' || *d == 'E');
    if (digits > 0 && digits <= 15 && frac <= 22 && !has_exp) {
        if (!token_ends(d, c.end)) return false;  // "1.5abc", "1_0", "0x10"
        *out = small_decimal(mant, frac, neg);
        c.p = d;
        return true;
    }
    char* q;
    double v = strtod_l(c.p, &q, c_locale());
    if (q == c.p) return false;
    if (!token_ends(q, c.end)) return false;
    c.p = q;
    *out = v;
    return true;
}

// Convert the decimal token [s, e) — its extent already established by
// the caller's grammar scan — with correct rounding: from_chars where the
// library has it, strtod_l where not, or where from_chars reports a range
// error (an underflow to zero or a subnormal, an overflow the caller then
// rejects). *e is readable and is not part of a number.
inline bool convert_token(const char* s, const char* e, double* out) {
#ifdef DMLP_HAVE_FROM_CHARS
    std::from_chars_result r = std::from_chars(s, e, *out);
    if (r.ec == std::errc() && r.ptr == e) return true;
#endif
    char* q;
    double v = strtod_l(s, &q, c_locale());
    if (q != e) return false;
    *out = v;
    return true;
}

inline bool is_digit(char ch) { return ch >= '0' && ch <= '9'; }

inline void skip_json_ws(Cursor& c) {
    while (c.p < c.end &&
           (*c.p == ' ' || *c.p == '\t' || *c.p == '\n' || *c.p == '\r'))
        ++c.p;
}

inline bool eat(Cursor& c, char ch) {
    if (c.p < c.end && *c.p == ch) {
        ++c.p;
        return true;
    }
    return false;
}

// One number of the JSON grammar (RFC 8259: -? int frac? exp?; no '+',
// no ".5", no "5.", no "01", no NaN / Infinity / hex) that is followed by
// at least one more byte, to the double json.loads + np.asarray(float64)
// give it: float(token), except that the INTEGER token "-0" is the int 0
// there and so +0.0. A value that does not fit a double is refused (the
// Python path raises on a huge int and serves inf for 1e999: the caller
// falls back and lets it).
inline bool parse_json_number(Cursor& c, double* out) {
    const char* s = c.p;
    const char* p = s;
    bool neg = p < c.end && *p == '-';
    if (neg) ++p;
    uint64_t mant = 0;
    int digits = 0, frac = 0;
    const char* int0 = p;
    while (p < c.end && is_digit(*p)) {
        if (digits < 19) mant = mant * 10 + static_cast<uint64_t>(*p - '0');
        ++digits;
        ++p;
    }
    if (p == int0 || (p - int0 > 1 && *int0 == '0')) return false;
    bool integral = true;
    if (p < c.end && *p == '.') {
        integral = false;
        const char* f0 = ++p;
        while (p < c.end && is_digit(*p)) {
            if (digits < 19) {
                mant = mant * 10 + static_cast<uint64_t>(*p - '0');
                ++frac;
            }
            ++digits;
            ++p;
        }
        if (p == f0) return false;
    }
    bool has_exp = p < c.end && (*p == 'e' || *p == 'E');
    if (has_exp) {
        integral = false;
        ++p;
        if (p < c.end && (*p == '+' || *p == '-')) ++p;
        const char* e0 = p;
        while (p < c.end && is_digit(*p)) ++p;
        if (p == e0) return false;
    }
    if (p >= c.end) return false;
    double v;
    if (digits <= 15 && !has_exp) {
        v = small_decimal(mant, frac, neg);
    } else if (!convert_token(s, p, &v)) {
        return false;
    }
    if (!std::isfinite(v)) return false;
    if (integral && v == 0.0) v = 0.0;
    *out = v;
    c.p = p;
    return true;
}

// Error messages carry the cursor's byte offset so the Python side
// (io.native) can surface a located ParseError — a truncated pipe or a
// corrupted payload should name WHERE the grammar broke, not just that
// it did.
void set_err(char* errbuf, size_t errlen, const char* msg, long off) {
    if (errbuf && errlen) {
        snprintf(errbuf, errlen, "%s (byte offset %ld)", msg, off);
    }
}

}  // namespace

extern "C" {

// Parse the header line "num_data num_queries num_attrs" (common.cpp:12-15).
// Returns 0 on success.
int dmlp_parse_header(const char* text, size_t len, long* out3) {
    Cursor c{text, text + len};
    for (int i = 0; i < 3; ++i) {
        if (!parse_long(c, &out3[i])) return 1;
    }
    return 0;
}

// Parse the full body into caller-allocated arrays:
//   labels      int32[num_data]
//   data_attrs  float64[num_data * num_attrs]
//   ks          int32[num_queries]
//   query_attrs float64[num_queries * num_attrs]
// Returns 0 on success; nonzero with errbuf set on malformed input.
int dmlp_parse_body(const char* text, size_t len, long num_data,
                    long num_queries, long num_attrs, int32_t* labels,
                    double* data_attrs, int32_t* ks, double* query_attrs,
                    char* errbuf, size_t errlen) {
    Cursor c{text, text + len};
    if (!next_line(c) && num_data + num_queries > 0) {  // skip header
        set_err(errbuf, errlen, "truncated input", (long)(c.p - text));
        return 1;
    }
    for (long i = 0; i < num_data; ++i) {
        skip_spaces(c);
        if (at_eol(c)) {
            set_err(errbuf, errlen, "Line is empty",
                    (long)(c.p - text));  // common.cpp:101
            return 2;
        }
        long label;
        if (!parse_long(c, &label)) {
            set_err(errbuf, errlen, "Line is wrongly formatted",
                        (long)(c.p - text));
            return 3;
        }
        labels[i] = static_cast<int32_t>(label);
        double* row = data_attrs + i * num_attrs;
        for (long a = 0; a < num_attrs; ++a) {
            if (!parse_double(c, &row[a])) {
                set_err(errbuf, errlen, "Line is wrongly formatted",
                        (long)(c.p - text));
                return 3;
            }
        }
        if (!next_line(c) && i + 1 < num_data + num_queries) {
            set_err(errbuf, errlen, "truncated input", (long)(c.p - text));
            return 1;
        }
    }
    for (long i = 0; i < num_queries; ++i) {
        // Query lines must start with 'Q' in column 0 — no leading
        // whitespace, exactly like the Python parser's line[0] != 'Q'
        // check (mirroring common.cpp:108-114).
        if (at_eol(c) || *c.p != 'Q') {
            set_err(errbuf, errlen, "Line is wrongly formatted",
                        (long)(c.p - text));
            return 4;
        }
        ++c.p;
        long k;
        if (!parse_long(c, &k)) {
            set_err(errbuf, errlen, "Line is wrongly formatted",
                        (long)(c.p - text));
            return 4;
        }
        ks[i] = static_cast<int32_t>(k);
        double* row = query_attrs + i * num_attrs;
        for (long a = 0; a < num_attrs; ++a) {
            if (!parse_double(c, &row[a])) {
                set_err(errbuf, errlen, "Line is wrongly formatted",
                        (long)(c.p - text));
                return 4;
            }
        }
        next_line(c);
    }
    return 0;
}

// Which converter this build gives tokens past the 15-digit fast path.
const char* dmlp_float_converter() {
#ifdef DMLP_HAVE_FROM_CHARS
    return "from_chars";
#else
    return "strtod";
#endif
}

// Parse a JSON array of one or more equal-length, non-empty arrays of
// JSON numbers from text[start, end) into out[0, cap), row-major. JSON
// whitespace (space, tab, LF, CR) may stand around every token and
// nothing else may. Returns 0 with out3 = {rows, cols, offset just past
// the closing bracket}; nonzero if the range does not start with exactly
// that (ragged or empty rows, anything that is not a number, a value no
// double holds) or needs more than cap doubles. Thread-safe; the caller
// (ctypes) has released the interpreter lock.
int dmlp_parse_json_matrix(const char* text, size_t start, size_t end,
                           double* out, size_t cap, long* out3) {
    Cursor c{text + start, text + end};
    skip_json_ws(c);
    if (!eat(c, '[')) return 1;
    long rows = 0, cols = -1;
    size_t n = 0;
    for (;;) {
        skip_json_ws(c);
        if (!eat(c, '[')) return 1;
        long width = 0;
        for (;;) {
            skip_json_ws(c);
            if (n >= cap) return 2;
            if (!parse_json_number(c, &out[n])) return 1;
            ++n;
            ++width;
            skip_json_ws(c);
            if (eat(c, ',')) continue;
            if (eat(c, ']')) break;
            return 1;
        }
        if (cols < 0) cols = width;
        else if (width != cols) return 1;
        ++rows;
        skip_json_ws(c);
        if (eat(c, ',')) continue;
        if (eat(c, ']')) break;
        return 1;
    }
    out3[0] = rows;
    out3[1] = cols;
    out3[2] = static_cast<long>(c.p - text);
    return 0;
}

}  // extern "C"
