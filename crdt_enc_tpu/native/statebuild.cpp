// Native sparse-state assembly for the streaming ORSet fold.
//
// The round-3 streaming pipeline (BASELINE config 5) ended in Python:
// numpy lexsort over segment keys (~48ms/200k rows on this host) plus
// per-member dict construction (~105ms) — the last non-columnar link in
// an otherwise native decrypt→decode→fold chain, and the measured wall
// at the 100k-replica scale.  This file moves that tail into C++:
//
//  * a packed-u64 LSD radix sort ((segment_key)·(maxc+1) + counter), so
//    "last of run holds the segment max" falls out of the sort order;
//  * the fresh-state writeback (the streaming shape: one combined fold
//    into an empty state) building the member→{actor: counter} dicts
//    directly through the CPython C-API.
//
// Semantics are exactly ops/columnar.py orset_fold_sparse_host +
// orset_apply_coo's fresh path (strict > horizon for adds, removes kept
// only above the merged clock); byte equality is pinned by the sparse
// fold tests plus bench.py's full-batch check.  Non-fresh states
// (pre-existing entries/deferred) stay on the Python path.
//
// This .so is loaded with ctypes.PyDLL (GIL held) because it creates
// Python objects; the compute sections are a few ms and this box is
// single-core, so holding the GIL costs nothing.
//
// Reference analogue: the consumer path crdt-enc/src/lib.rs:471-547 at
// 100k-replica streaming scale.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <memory>
#include <vector>

// Presized dict creation skips the grow/rehash cascade while filling
// (the member dicts average ~166 entries at the config-5 shape and the
// clock dict holds one entry per replica).  _PyDict_NewPresized is a
// private-but-exported CPython symbol (msgpack's C extension uses it
// the same way); weak-linked so a build against a Python that drops it
// falls back to PyDict_New.
extern "C" PyObject* _PyDict_NewPresized(Py_ssize_t minused)
    __attribute__((weak));

namespace {

PyObject* new_dict_presized(Py_ssize_t n) {
    if (_PyDict_NewPresized != nullptr && n > 5)
        return _PyDict_NewPresized(n);
    return PyDict_New();
}

// LSD radix sort of uint64 values, 8-bit digits, skipping passes whose
// digit is constant across the array (high zero bytes of small keys).
void radix_sort_u64(std::vector<uint64_t>& a, uint64_t maxval) {
    if (a.size() < 2) return;
    std::vector<uint64_t> tmp(a.size());
    uint64_t* src = a.data();
    uint64_t* dst = tmp.data();
    bool in_tmp = false;
    for (int pass = 0; pass < 8; ++pass) {
        const int shift = pass * 8;
        if ((maxval >> shift) == 0) break;  // no set bits at/after this byte
        size_t hist[256] = {0};
        const size_t n = a.size();
        for (size_t i = 0; i < n; ++i) hist[(src[i] >> shift) & 0xff]++;
        if (hist[(src[0] >> shift) & 0xff] == n) continue;  // constant digit
        size_t sum = 0;
        for (int b = 0; b < 256; ++b) {
            size_t c = hist[b];
            hist[b] = sum;
            sum += c;
        }
        for (size_t i = 0; i < n; ++i)
            dst[hist[(src[i] >> shift) & 0xff]++] = src[i];
        std::swap(src, dst);
        in_tmp = !in_tmp;
    }
    if (in_tmp) std::memcpy(a.data(), src, a.size() * sizeof(uint64_t));
}

// Dedup a sorted packed array (key = p / M, val = p % M) into (seg, val)
// arrays keeping the last (= max val) entry of every key run.
void dedup(const std::vector<uint64_t>& packed, uint64_t M,
           std::vector<int64_t>& seg, std::vector<int64_t>& val) {
    const size_t n = packed.size();
    seg.reserve(n);
    val.reserve(n);
    for (size_t i = 0; i < n; ++i) {
        if (i + 1 < n && packed[i] / M == packed[i + 1] / M) continue;
        seg.push_back((int64_t)(packed[i] / M));
        val.push_back((int64_t)(packed[i] % M));
    }
}

// Emit consecutive same-member groups of (seg, val) rows into
// target[member_obj] = {actor_obj: val}.  Rows are member-major because
// seg = member·R + actor and the arrays are sorted.
// Returns 0 ok, -1 on a Python error (exception set).
int emit_groups(PyObject* target, PyObject* member_objs, PyObject* actor_objs,
                int64_t R, const std::vector<int64_t>& seg,
                const std::vector<int64_t>& val) {
    const size_t n = seg.size();
    size_t s = 0;
    while (s < n) {
        const int64_t m = seg[s] / R;
        size_t e = s + 1;
        while (e < n && seg[e] / R == m) ++e;
        PyObject* d = new_dict_presized((Py_ssize_t)(e - s));
        if (!d) return -1;
        for (size_t i = s; i < e; ++i) {
            PyObject* a = PyList_GET_ITEM(actor_objs, (Py_ssize_t)(seg[i] % R));
            PyObject* c = PyLong_FromLongLong((long long)val[i]);
            if (!c || PyDict_SetItem(d, a, c) < 0) {
                Py_XDECREF(c);
                Py_DECREF(d);
                return -1;
            }
            Py_DECREF(c);
        }
        if (PyDict_SetItem(target, PyList_GET_ITEM(member_objs, (Py_ssize_t)m),
                           d) < 0) {
            Py_DECREF(d);
            return -1;
        }
        Py_DECREF(d);
        s = e;
    }
    return 0;
}

}  // namespace

extern "C" {

// Fold a raw (kind, member, actor, counter) op batch into an EMPTY
// ORSet's entries/deferred dicts + dense clock.
//
//  kind:    (n,) int8   0=add 1=remove (anything else ignored)
//  member:  (n,) int32  vocab index < E
//  actor:   (n,) int32  vocab index; >= R marks a padding row
//  counter: (n,) int32  dot counter / horizon
//  clock:   (R,) int32  in-out: the state's dense clock, merged in place
//  member_objs / actor_objs: vocab object lists (len E / R)
//  entries / deferred: empty dicts to fill (member -> {actor: counter})
//
// Returns 0 on success, -1 if the shape overflows the packed-key sort
// (caller must use the Python path), -2 on a Python error.
int orset_fresh_fold_impl(const int8_t* kind, const int32_t* member,
                          const int32_t* actor, const int32_t* counter,
                          int64_t n, int64_t E, int64_t R, int32_t* clock,
                          PyObject* member_objs, PyObject* actor_objs,
                          PyObject* entries, PyObject* deferred) {
    // pass 0: max counter over participating rows (packing modulus)
    int64_t maxc = 0;
    for (int64_t i = 0; i < n; ++i) {
        if (actor[i] >= R) continue;
        if (counter[i] > maxc) maxc = counter[i];
    }
    const uint64_t M = (uint64_t)maxc + 1;
    const uint64_t segspace = (uint64_t)E * (uint64_t)R;
    // overflow guard: packed = seg·M + c with seg < segspace must fit
    // u64 comfortably (two sides sorted separately, so no 2x factor)
    if (segspace != 0 && M > (((uint64_t)1 << 62) / (segspace + 1))) return -1;

    // pass 1: gate + pack into separate add/remove arrays.  Add rows
    // gate against the ORIGINAL clock (copy) while the merged clock
    // updates in place — same order of effects as the numpy path
    // (np.maximum.at over live adds, then the remove filter sees the
    // merged clock).
    std::vector<int32_t> clock0(clock, clock + (size_t)R);
    std::vector<uint64_t> adds, rms;
    adds.reserve((size_t)n);
    for (int64_t i = 0; i < n; ++i) {
        const int32_t a = actor[i];
        if (a < 0 || a >= R) continue;
        const int64_t c = counter[i];
        if (c < 0) continue;  // defensive: counters are non-negative
        const uint64_t seg = (uint64_t)member[i] * (uint64_t)R + (uint64_t)a;
        if (kind[i] == 0) {
            if (c > clock0[a]) {  // replay gate vs the incoming clock
                adds.push_back(seg * M + (uint64_t)c);
                if (c > clock[a]) clock[a] = (int32_t)c;  // merged clock
            }
        } else if (kind[i] == 1) {
            rms.push_back(seg * M + (uint64_t)c);
        }
    }
    const uint64_t maxpacked = segspace == 0 ? 0 : (segspace - 1) * M + maxc;
    radix_sort_u64(adds, maxpacked);
    radix_sort_u64(rms, maxpacked);

    std::vector<int64_t> aseg, aval, rseg, rval;
    dedup(adds, M, aseg, aval);
    dedup(rms, M, rseg, rval);

    // adds survive a STRICTLY greater horizon on their own segment
    // (equal horizon observed the dot — it dies); merge-join on the
    // sorted segs
    {
        size_t keep = 0, r = 0;
        for (size_t i = 0; i < aseg.size(); ++i) {
            while (r < rseg.size() && rseg[r] < aseg[i]) ++r;
            const int64_t horizon =
                (r < rseg.size() && rseg[r] == aseg[i]) ? rval[r] : 0;
            if (aval[i] > horizon) {
                aseg[keep] = aseg[i];
                aval[keep] = aval[i];
                ++keep;
            }
        }
        aseg.resize(keep);
        aval.resize(keep);
    }
    // removes survive only above the MERGED clock
    {
        size_t keep = 0;
        for (size_t i = 0; i < rseg.size(); ++i) {
            if (rval[i] > clock[rseg[i] % R]) {
                rseg[keep] = rseg[i];
                rval[keep] = rval[i];
                ++keep;
            }
        }
        rseg.resize(keep);
        rval.resize(keep);
    }

    if (emit_groups(entries, member_objs, actor_objs, R, aseg, aval) < 0)
        return -2;
    if (emit_groups(deferred, member_objs, actor_objs, R, rseg, rval) < 0)
        return -2;
    return 0;
}

// ---- split fold: rows out, dicts assembled separately ---------------------
//
// The monolithic orset_fresh_fold above fuses the FOLD (gate + radix
// sort + dedup + survivor filter — pure C, a few ms) with the STATE
// WRITEBACK (CPython dict assembly — the dominant cost at 200k rows).
// The split protocol below returns the surviving rows as plain int
// arrays FIRST — member-contiguous, actor-ascending: exactly the
// orset_pack_checkpoint row layout — so the caller can (a) time fold
// vs writeback honestly (the gap report's fold marginal), (b) hand the
// SAME rows to grouped_rows_dicts for the dict writeback, and (c) seal
// the warm-open checkpoint straight from the rows with no dict walk.

namespace {

struct FoldRows {
    std::vector<int64_t> aseg, aval, rseg, rval;
    int64_t R;
};

}  // namespace

// Fold a raw op batch against an empty state: merged clock in place,
// surviving add/remove rows retained on the returned handle.  Writes
// {n_adds, n_removes} into counts.  Returns NULL when the shape
// overflows the packed-key sort or allocation fails (caller falls back
// to the fused/Python paths; clock may be partially merged — callers
// pass a scratch copy).
void* orset_fold_rows(const int8_t* kind, const int32_t* member,
                      const int32_t* actor, const int32_t* counter,
                      int64_t n, int64_t E, int64_t R, int32_t* clock,
                      int64_t* counts) {
    try {
        int64_t maxc = 0;
        for (int64_t i = 0; i < n; ++i) {
            if (actor[i] >= R) continue;
            if (counter[i] > maxc) maxc = counter[i];
        }
        const uint64_t M = (uint64_t)maxc + 1;
        const uint64_t segspace = (uint64_t)E * (uint64_t)R;
        if (segspace != 0 && M > (((uint64_t)1 << 62) / (segspace + 1)))
            return nullptr;
        std::vector<int32_t> clock0(clock, clock + (size_t)R);
        std::vector<uint64_t> adds, rms;
        adds.reserve((size_t)n);
        for (int64_t i = 0; i < n; ++i) {
            const int32_t a = actor[i];
            if (a < 0 || a >= R) continue;
            const int64_t c = counter[i];
            if (c < 0) continue;
            const uint64_t seg =
                (uint64_t)member[i] * (uint64_t)R + (uint64_t)a;
            if (kind[i] == 0) {
                if (c > clock0[a]) {
                    adds.push_back(seg * M + (uint64_t)c);
                    if (c > clock[a]) clock[a] = (int32_t)c;
                }
            } else if (kind[i] == 1) {
                rms.push_back(seg * M + (uint64_t)c);
            }
        }
        const uint64_t maxpacked =
            segspace == 0 ? 0 : (segspace - 1) * M + maxc;
        radix_sort_u64(adds, maxpacked);
        radix_sort_u64(rms, maxpacked);

        FoldRows* out = new FoldRows;
        out->R = R;
        dedup(adds, M, out->aseg, out->aval);
        dedup(rms, M, out->rseg, out->rval);
        {
            size_t keep = 0, r = 0;
            for (size_t i = 0; i < out->aseg.size(); ++i) {
                while (r < out->rseg.size() && out->rseg[r] < out->aseg[i])
                    ++r;
                const int64_t horizon =
                    (r < out->rseg.size() && out->rseg[r] == out->aseg[i])
                        ? out->rval[r] : 0;
                if (out->aval[i] > horizon) {
                    out->aseg[keep] = out->aseg[i];
                    out->aval[keep] = out->aval[i];
                    ++keep;
                }
            }
            out->aseg.resize(keep);
            out->aval.resize(keep);
        }
        {
            size_t keep = 0;
            for (size_t i = 0; i < out->rseg.size(); ++i) {
                if (out->rval[i] > clock[out->rseg[i] % R]) {
                    out->rseg[keep] = out->rseg[i];
                    out->rval[keep] = out->rval[i];
                    ++keep;
                }
            }
            out->rseg.resize(keep);
            out->rval.resize(keep);
        }
        counts[0] = (int64_t)out->aseg.size();
        counts[1] = (int64_t)out->rseg.size();
        return out;
    } catch (const std::bad_alloc&) {
        return nullptr;
    }
}

// Copy the surviving rows out as (member, actor, counter) columns —
// member-contiguous (sort order), actor ascending within a member, the
// orset_pack_checkpoint group contract — and free the handle.  The
// caller sizes the six arrays from the counts orset_fold_rows wrote and
// passes them back as the write bounds; a mismatch (stale counts, a
// caller bug) writes NOTHING past either capacity and returns -1.
int orset_fold_rows_take(void* handle, int32_t* am, int32_t* aa,
                         int64_t* ac, int64_t a_capacity, int32_t* dm,
                         int32_t* da, int64_t* dc, int64_t d_capacity) {
    FoldRows* rows = (FoldRows*)handle;
    if ((int64_t)rows->aseg.size() != a_capacity ||
        (int64_t)rows->rseg.size() != d_capacity) {
        delete rows;
        return -1;
    }
    const int64_t R = rows->R;
    for (size_t i = 0; i < rows->aseg.size(); ++i) {
        am[i] = (int32_t)(rows->aseg[i] / R);
        aa[i] = (int32_t)(rows->aseg[i] % R);
        ac[i] = rows->aval[i];
    }
    for (size_t i = 0; i < rows->rseg.size(); ++i) {
        dm[i] = (int32_t)(rows->rseg[i] / R);
        da[i] = (int32_t)(rows->rseg[i] % R);
        dc[i] = rows->rval[i];
    }
    delete rows;
    return 0;
}

void orset_fold_rows_drop(void* handle) { delete (FoldRows*)handle; }

int orset_fresh_fold(const int8_t* kind, const int32_t* member,
                     const int32_t* actor, const int32_t* counter, int64_t n,
                     int64_t E, int64_t R, int32_t* clock,
                     PyObject* member_objs, PyObject* actor_objs,
                     PyObject* entries, PyObject* deferred) {
    // a bad_alloc must not unwind into ctypes; -1 = Python-path fallback.
    // Safe to retry in Python: vector allocation happens strictly before
    // any dict mutation (emit_groups allocates through the C-API, whose
    // failures surface as rc=-2 Python errors, not C++ exceptions), and
    // the caller's clock array is a scratch copy it discards on fallback.
    try {
        return orset_fresh_fold_impl(kind, member, actor, counter, n, E, R,
                                     clock, member_objs, actor_objs, entries,
                                     deferred);
    } catch (const std::bad_alloc&) {
        return -1;
    }
}

// ---------------------------------------------------------------------
// Canonical msgpack packer — the native twin of utils/codec.py pack():
// smallest-encoding msgpack with use_bin_type=True semantics and every
// map emitted with keys sorted by their packed bytes.  The one
// serialiser of states, ops, links, payloads, cursors and sort keys;
// a seal packs the whole state through it once a round.
//
// ONE growing buffer, handed down the whole recursion, and a map put in
// order by sorting an INDEX over that buffer:
//
//  * `Out` starts on the stack (a small pack allocates nothing but its
//    result) and moves into a Python ``bytes`` that doubles, or, once it
//    is large, steps to where the last large pack ended; the result is
//    that object cut to length, never a copy of it.
//  * A map writes its header, then each key and its value straight into
//    the buffer, one after the other, keeping one 24-byte record an
//    entry: where the key starts, its length, the entry's length and
//    the key's first 8 bytes as a big-endian word, so nearly every
//    comparison is one integer compare (ties: memcmp of the rest, then
//    the shorter key, then the earlier entry — `bytes <` and a stable
//    sort, which is the Python path's ``sort(key=packb)``).  Records of
//    a map of up to STACK_RECS entries live on the stack.
//  * The order is checked while emitting.  A map whose keys arrived in
//    packed-key order (every map of a state opened from a snapshot, a
//    one-entry map) is neither sorted nor copied.  A map out of order has its records sorted and its region
//    of the buffer permuted once through one scratch copy of that
//    region; an inner map is final before its parent's entry ends, so
//    the parent moves it as bytes.
//
// Unsupported types return 0 and the Python caller falls back; the four
// totals below say how it engaged (canon_counters).
// ---------------------------------------------------------------------

namespace {

// Process totals, bumped under the interpreter lock every entry point
// of this library holds: calls of canon_pack, maps emitted, maps whose
// keys arrived out of packed order, calls that declined (None).
uint64_t g_canon_packs = 0, g_canon_maps = 0, g_canon_maps_sorted = 0,
         g_canon_declined = 0;

// Where the last large pack ended (under the same lock).  A buffer that
// outgrows LARGE_PACK steps straight to that length and a sixteenth: a
// state sealed round after round is packed into one allocation of about
// its size, which the allocator serves from the block the last round
// freed.  Doubling passes a 23 MB state for a block of 32 MiB, which
// glibc maps fresh from the kernel every round (native.warm() pins
// M_MMAP_THRESHOLD there), and on the chip's host the spans that then
// read those pages cost 80 ms a round more (PERF.md, PR 52).  Only a
// capacity: too small and the buffer doubles on.
const size_t LARGE_PACK = 1 << 20;
size_t g_canon_last_large = 0;

// The big-endian stores and the key's first word are byte swaps.
static_assert(__BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__,
              "canon_pack swaps bytes: a little-endian host");

// A Python error is set (a failed allocation of the buffer's object).
struct PyErrSet {};

struct Out {
  static const size_t INLINE = 512;
  uint8_t* p;
  size_t n = 0, cap = INLINE;
  PyObject* obj = nullptr;  // the bytes object `p` points into, once grown
  uint64_t maps = 0, maps_sorted = 0;
  std::vector<uint8_t> scratch;  // one out-of-order map's entries, reused
  uint8_t inl[INLINE];

  Out() : p(inl) {}
  ~Out() { Py_XDECREF(obj); }
  Out(const Out&) = delete;
  Out& operator=(const Out&) = delete;

  void grow(size_t need) {
    size_t nc = cap * 2;
    if (nc < need) nc = need + (need >> 4);  // one large bin: about its size
    if (nc > LARGE_PACK && nc < g_canon_last_large)
      nc = g_canon_last_large + (g_canon_last_large >> 4);
    if (nc > (size_t)PY_SSIZE_T_MAX) throw std::bad_alloc();
    if (obj == nullptr) {
      obj = PyBytes_FromStringAndSize(nullptr, (Py_ssize_t)nc);
      if (obj == nullptr) throw PyErrSet();
      memcpy(PyBytes_AS_STRING(obj), inl, n);
    } else if (_PyBytes_Resize(&obj, (Py_ssize_t)nc) < 0) {
      throw PyErrSet();  // obj is NULL now, the old buffer freed
    }
    p = (uint8_t*)PyBytes_AS_STRING(obj);
    cap = nc;
  }
  // room for `k` more bytes; returns where they go
  inline uint8_t* room(size_t k) {
    if (__builtin_expect(n + k > cap, 0)) grow(n + k);
    uint8_t* at = p + n;
    n += k;
    return at;
  }
  inline void u8(uint8_t v) { *room(1) = v; }
  // a one-byte tag and a big-endian word, one reservation, one store each
  inline void tag8(uint8_t t, uint8_t v) {
    uint8_t* at = room(2);
    at[0] = t;
    at[1] = v;
  }
  inline void tag16(uint8_t t, uint16_t v) {
    uint8_t* at = room(3);
    at[0] = t;
    v = __builtin_bswap16(v);
    memcpy(at + 1, &v, 2);
  }
  inline void tag32(uint8_t t, uint32_t v) {
    uint8_t* at = room(5);
    at[0] = t;
    v = __builtin_bswap32(v);
    memcpy(at + 1, &v, 4);
  }
  inline void tag64(uint8_t t, uint64_t v) {
    uint8_t* at = room(9);
    at[0] = t;
    v = __builtin_bswap64(v);
    memcpy(at + 1, &v, 8);
  }
  inline void raw(const void* src, size_t k) { memcpy(room(k), src, k); }

  // the result: the buffer's own object cut to length (no copy), or a
  // new small bytes; NULL with the Python error set
  PyObject* finish() {
    if (obj == nullptr)
      return PyBytes_FromStringAndSize((const char*)inl, (Py_ssize_t)n);
    if (_PyBytes_Resize(&obj, (Py_ssize_t)n) < 0) return nullptr;
    if (n > LARGE_PACK) g_canon_last_large = n;
    PyObject* r = obj;
    obj = nullptr;
    return r;
  }
};

// One entry of a map being emitted: the entry (key, then value) starts
// at `off` in the buffer and is `elen` bytes, the first `klen` the key.
struct Rec {
  uint64_t pre;  // the key's first 8 bytes, big-endian, zero-padded
  uint64_t off;
  uint32_t klen, elen;
};

const Py_ssize_t STACK_RECS = 16;

inline uint64_t key_prefix(const uint8_t* k, size_t klen) {
  uint64_t w = 0;
  memcpy(&w, k, klen < 8 ? klen : 8);  // the low bytes, which the swap puts first
  return __builtin_bswap64(w);
}

// `a` before `b` in the canonical order?  `base` is the buffer the
// records index (either the live buffer or the scratch copy).  Zero
// padding keeps the word compare lexicographic: a key shorter than 8
// bytes that ties on the word is a prefix of the other.
inline bool rec_less(const Rec& a, const Rec& b, const uint8_t* base) {
  if (a.pre != b.pre) return a.pre < b.pre;
  const size_t m = a.klen < b.klen ? a.klen : b.klen;
  if (m > 8) {
    const int c = memcmp(base + a.off + 8, base + b.off + 8, m - 8);
    if (c != 0) return c < 0;
  }
  if (a.klen != b.klen) return a.klen < b.klen;
  return a.off < b.off;  // equal keys (two NaNs): as inserted
}

// Put the `n` entries that `recs` index, which fill the buffer from
// `start` to its end, into canonical order.
void order_entries(Out& out, Rec* recs, size_t n, size_t start) {
  const uint8_t* base = out.p;
  // a total order (the offset breaks every tie), so any sort is stable
  std::sort(recs, recs + n, [base](const Rec& a, const Rec& b) {
    return rec_less(a, b, base);
  });
  out.scratch.assign(out.p + start, out.p + out.n);
  const uint8_t* from = out.scratch.data() - start;
  uint8_t* w = out.p + start;
  const size_t AHEAD = 8;
  for (size_t i = 0; i < n; ++i) {
    if (i + AHEAD < n) __builtin_prefetch(from + recs[i + AHEAD].off);
    memcpy(w, from + recs[i].off, recs[i].elen);
    w += recs[i].elen;
  }
}

int canon_emit(PyObject* obj, Out& out, int depth);

// The entries of ``obj`` (the header is written), each key followed by
// its value, then in order.  Returns as canon_emit.
int canon_emit_entries(PyObject* obj, Out& out, int depth, Rec* recs,
                       size_t n) {
  const size_t start = out.n;
  bool in_order = true;
  size_t i = 0;
  Py_ssize_t pos = 0;
  PyObject *key, *val;
  while (PyDict_Next(obj, &pos, &key, &val)) {
    const size_t off = out.n;
    int rc = canon_emit(key, out, depth + 1);
    if (rc != 1) return rc;
    const size_t klen = out.n - off;
    rc = canon_emit(val, out, depth + 1);
    if (rc != 1) return rc;
    const size_t elen = out.n - off;
    // a record holds 32-bit lengths: an entry of 4 GB is the Python path's
    // (and a map that grew under the walk, which nothing here can cause)
    if (elen > 0xffffffffull || i >= n) return 0;
    Rec& r = recs[i];
    r.pre = key_prefix(out.p + off, klen);
    r.off = off;
    r.klen = (uint32_t)klen;
    r.elen = (uint32_t)elen;
    if (in_order && i > 0 && !rec_less(recs[i - 1], r, out.p))
      in_order = false;
    ++i;
  }
  out.maps += 1;
  if (!in_order) {
    out.maps_sorted += 1;
    order_entries(out, recs, i, start);
  }
  return 1;
}

// returns 1 ok, 0 unsupported (no exception), -1 python error (exc set)
int canon_emit(PyObject* obj, Out& out, int depth) {
  if (depth > 200) return 0;
  if (PyLong_CheckExact(obj)) {
    long long v;
    if (PyUnstable_Long_IsCompact((PyLongObject*)obj)) {
      // one machine word, the counters and keys of a state: no call
      v = (long long)PyUnstable_Long_CompactValue((PyLongObject*)obj);
    } else {
      int overflow = 0;
      v = PyLong_AsLongLongAndOverflow(obj, &overflow);
      if (overflow > 0) {
        unsigned long long u = PyLong_AsUnsignedLongLong(obj);
        if (u == (unsigned long long)-1 && PyErr_Occurred()) {
          PyErr_Clear();
          return 0;  // > 2^64-1: let the Python packer raise its error
        }
        out.tag64(0xcf, u);
        return 1;
      }
      if (overflow < 0) return 0;  // < -2^63
      if (v == -1 && PyErr_Occurred()) return -1;
    }
    if (v >= 0) {
      unsigned long long u = (unsigned long long)v;
      if (u < 0x80) out.u8((uint8_t)u);
      else if (u <= 0xff) out.tag8(0xcc, (uint8_t)u);
      else if (u <= 0xffff) out.tag16(0xcd, (uint16_t)u);
      else if (u <= 0xffffffffull) out.tag32(0xce, (uint32_t)u);
      else out.tag64(0xcf, u);
    } else {
      if (v >= -32) out.u8((uint8_t)(int8_t)v);
      else if (v >= -128) out.tag8(0xd0, (uint8_t)(int8_t)v);
      else if (v >= -32768) out.tag16(0xd1, (uint16_t)(int16_t)v);
      else if (v >= -2147483648ll) out.tag32(0xd2, (uint32_t)(int32_t)v);
      else out.tag64(0xd3, (uint64_t)v);
    }
    return 1;
  }
  if (PyBytes_CheckExact(obj)) {
    const size_t n = (size_t)PyBytes_GET_SIZE(obj);
    if (n <= 0xff) out.tag8(0xc4, (uint8_t)n);
    else if (n <= 0xffff) out.tag16(0xc5, (uint16_t)n);
    else if (n <= 0xffffffffull) out.tag32(0xc6, (uint32_t)n);
    else return 0;
    out.raw(PyBytes_AS_STRING(obj), n);
    return 1;
  }
  if (PyDict_CheckExact(obj)) {
    const Py_ssize_t n = PyDict_GET_SIZE(obj);
    if (n < 16) out.u8(0x80 | (uint8_t)n);
    else if (n <= 0xffff) out.tag16(0xde, (uint16_t)n);
    else if ((unsigned long long)n <= 0xffffffffull)
      out.tag32(0xdf, (uint32_t)n);
    else return 0;
    if (n <= STACK_RECS) {
      Rec recs[STACK_RECS];
      return canon_emit_entries(obj, out, depth, recs, (size_t)n);
    }
    const std::unique_ptr<Rec[]> recs(new Rec[(size_t)n]);
    return canon_emit_entries(obj, out, depth, recs.get(), (size_t)n);
  }
  if (PyList_CheckExact(obj) || PyTuple_CheckExact(obj)) {
    const int is_list = PyList_CheckExact(obj);
    const Py_ssize_t n =
        is_list ? PyList_GET_SIZE(obj) : PyTuple_GET_SIZE(obj);
    if (n < 16) out.u8(0x90 | (uint8_t)n);
    else if (n <= 0xffff) out.tag16(0xdc, (uint16_t)n);
    else if ((unsigned long long)n <= 0xffffffffull)
      out.tag32(0xdd, (uint32_t)n);
    else return 0;
    for (Py_ssize_t i = 0; i < n; ++i) {
      PyObject* it =
          is_list ? PyList_GET_ITEM(obj, i) : PyTuple_GET_ITEM(obj, i);
      int rc = canon_emit(it, out, depth + 1);
      if (rc != 1) return rc;
    }
    return 1;
  }
  if (PyUnicode_CheckExact(obj)) {
    Py_ssize_t n;
    const char* s = PyUnicode_AsUTF8AndSize(obj, &n);
    if (s == nullptr) return -1;
    if (n < 32) out.u8(0xa0 | (uint8_t)n);
    else if (n <= 0xff) out.tag8(0xd9, (uint8_t)n);
    else if (n <= 0xffff) out.tag16(0xda, (uint16_t)n);
    else if ((unsigned long long)n <= 0xffffffffull)
      out.tag32(0xdb, (uint32_t)n);
    else return 0;
    out.raw(s, (size_t)n);
    return 1;
  }
  if (obj == Py_None) { out.u8(0xc0); return 1; }
  if (obj == Py_True) { out.u8(0xc3); return 1; }
  if (obj == Py_False) { out.u8(0xc2); return 1; }
  if (PyFloat_CheckExact(obj)) {
    double d = PyFloat_AS_DOUBLE(obj);
    uint64_t bits;
    memcpy(&bits, &d, 8);
    out.tag64(0xcb, bits);
    return 1;
  }
  return 0;  // sets, numpy scalars, custom types → Python fallback
}

// --- canon_same: do two graphs pack to the same canonical bytes? -------
// Walks both under canon_emit's type table, without packing either.
// 1 = the same bytes, 0 = a difference found, 2 = cannot say (a type
// canon_emit declines, a map key whose Python equality is wider than
// its bytes, the depth limit, or a Python error, cleared).

const int SAME = 1, DIFFERS = 0, UNSURE = 2;

enum Kind { K_NONE, K_BOOL, K_INT, K_BYTES, K_STR, K_FLOAT, K_SEQ, K_MAP,
            K_OTHER };

Kind kind_of(PyObject* o) {
  // exact types by pointer, the common ones of a state first (bool has a
  // type of its own, so PyLong_Type is never a bool)
  PyTypeObject* t = Py_TYPE(o);
  if (t == &PyLong_Type) return K_INT;
  if (t == &PyBytes_Type) return K_BYTES;
  if (t == &PyDict_Type) return K_MAP;
  if (t == &PyUnicode_Type) return K_STR;
  if (t == &PyList_Type || t == &PyTuple_Type) return K_SEQ;
  if (t == &PyFloat_Type) return K_FLOAT;
  if (o == Py_None) return K_NONE;
  if (t == &PyBool_Type) return K_BOOL;
  return K_OTHER;
}

// A map is compared by looking each key of one side up in the other, so
// by Python's hash and ==, which make 1, True and 1.0 one key (and 0.0
// and -0.0) where their bytes differ.  Among None, exact int, bytes, str
// and tuples of these, equal keys are equal bytes; any other key is not
// looked up at all.
bool key_is_exact(PyObject* k, int depth) {
  const Kind kd = kind_of(k);
  if (kd == K_BYTES || kd == K_INT || kd == K_STR || kd == K_NONE)
    return true;
  if (!PyTuple_CheckExact(k) || depth > 200) return false;
  const Py_ssize_t n = PyTuple_GET_SIZE(k);
  for (Py_ssize_t i = 0; i < n; ++i)
    if (!key_is_exact(PyTuple_GET_ITEM(k, i), depth + 1)) return false;
  return true;
}

int canon_same_walk(PyObject* a, PyObject* b, int depth) {
  if (depth > 200) return UNSURE;
  const Kind ka = kind_of(a), kb = kind_of(b);
  if (ka == K_OTHER || kb == K_OTHER) return UNSURE;
  // every kind opens with header bytes no other kind uses
  if (ka != kb) return DIFFERS;
  switch (ka) {
    case K_NONE:
      return SAME;
    case K_BOOL:
      return a == b ? SAME : DIFFERS;
    case K_INT: {
      // one machine word, the counters of a state: no call at all
      if (PyUnstable_Long_IsCompact((PyLongObject*)a) &&
          PyUnstable_Long_IsCompact((PyLongObject*)b))
        return PyUnstable_Long_CompactValue((PyLongObject*)a) ==
                       PyUnstable_Long_CompactValue((PyLongObject*)b)
                   ? SAME : DIFFERS;
      // canon_emit's range: [-2^63, 2^64); outside it nothing packs
      int oa = 0, ob = 0;
      const long long va = PyLong_AsLongLongAndOverflow(a, &oa);
      const long long vb = PyLong_AsLongLongAndOverflow(b, &ob);
      if (oa < 0 || ob < 0) return UNSURE;
      if (!oa && !ob) return va == vb ? SAME : DIFFERS;
      if (oa != ob) return DIFFERS;  // one under 2^63, one at or over it
      const unsigned long long ua = PyLong_AsUnsignedLongLong(a);
      const unsigned long long ub = PyLong_AsUnsignedLongLong(b);
      if (PyErr_Occurred()) {
        PyErr_Clear();
        return UNSURE;
      }
      return ua == ub ? SAME : DIFFERS;
    }
    case K_BYTES: {
      const Py_ssize_t n = PyBytes_GET_SIZE(a);
      if (n != PyBytes_GET_SIZE(b)) return DIFFERS;
      return memcmp(PyBytes_AS_STRING(a), PyBytes_AS_STRING(b), (size_t)n)
                 ? DIFFERS : SAME;
    }
    case K_STR: {
      Py_ssize_t na, nb;
      const char* sa = PyUnicode_AsUTF8AndSize(a, &na);
      const char* sb = sa ? PyUnicode_AsUTF8AndSize(b, &nb) : nullptr;
      if (sb == nullptr) {
        PyErr_Clear();
        return UNSURE;
      }
      return (na == nb && !memcmp(sa, sb, (size_t)na)) ? SAME : DIFFERS;
    }
    case K_FLOAT: {
      const double da = PyFloat_AS_DOUBLE(a), db = PyFloat_AS_DOUBLE(b);
      return memcmp(&da, &db, 8) ? DIFFERS : SAME;
    }
    case K_SEQ: {
      const int la = PyList_CheckExact(a), lb = PyList_CheckExact(b);
      const Py_ssize_t n = la ? PyList_GET_SIZE(a) : PyTuple_GET_SIZE(a);
      if (n != (lb ? PyList_GET_SIZE(b) : PyTuple_GET_SIZE(b)))
        return DIFFERS;
      for (Py_ssize_t i = 0; i < n; ++i) {
        const int rc = canon_same_walk(
            la ? PyList_GET_ITEM(a, i) : PyTuple_GET_ITEM(a, i),
            lb ? PyList_GET_ITEM(b, i) : PyTuple_GET_ITEM(b, i), depth + 1);
        if (rc != SAME) return rc;
      }
      return SAME;
    }
    case K_MAP: {
      if (PyDict_GET_SIZE(a) != PyDict_GET_SIZE(b)) return DIFFERS;
      Py_ssize_t pos = 0;
      PyObject *key, *val;
      // the side looked up IN first: a lookup never shows which of its
      // keys it matched
      while (PyDict_Next(b, &pos, &key, &val))
        if (!key_is_exact(key, depth + 1)) return UNSURE;
      pos = 0;
      while (PyDict_Next(a, &pos, &key, &val)) {
        if (!key_is_exact(key, depth + 1)) return UNSURE;
        // exact keys alone: the lookup runs no code that could reach
        // either map, and the borrowed references stay good
        PyObject* other = PyDict_GetItemWithError(b, key);
        if (other == nullptr) {
          if (!PyErr_Occurred()) return DIFFERS;
          PyErr_Clear();
          return UNSURE;
        }
        const int rc = canon_same_walk(val, other, depth + 1);
        if (rc != SAME) return rc;
      }
      // equal sizes, every key of a in b under an equality that is
      // equality of bytes: the key sets are one
      return SAME;
    }
    case K_OTHER:
      break;
  }
  return UNSURE;
}

}  // namespace

extern "C" {

// ``True`` only if ``canon_pack(a) == canon_pack(b)``; ``False`` where a
// difference was found; ``None`` where it cannot say cheaply.  No sort,
// no buffer, no allocation: never an exception.
PyObject* canon_same(PyObject* a, PyObject* b) {
  const int rc = canon_same_walk(a, b, 0);
  if (rc == SAME) Py_RETURN_TRUE;
  if (rc == DIFFERS) Py_RETURN_FALSE;
  Py_RETURN_NONE;
}

// Canonical-pack ``obj``; returns a bytes object, Py_None when the
// object graph contains a type this packer does not handle, wherever in
// the graph it is met (the caller falls back to the Python path), or
// NULL on a Python error.
PyObject* canon_pack(PyObject* obj) {
  g_canon_packs += 1;
  // bad_alloc from the records or the scratch copy must not unwind into
  // ctypes — surface it as a Python MemoryError instead (same convention
  // as the fold and decode entry points); the buffer's own growth fails
  // with that error already set
  try {
    Out out;
    const int rc = canon_emit(obj, out, 0);
    g_canon_maps += out.maps;
    g_canon_maps_sorted += out.maps_sorted;
    if (rc < 0) return nullptr;
    if (rc == 0) {
      g_canon_declined += 1;
      Py_RETURN_NONE;
    }
    return out.finish();
  } catch (const PyErrSet&) {
    return nullptr;
  } catch (const std::bad_alloc&) {
    return PyErr_NoMemory();
  }
}

// The four process totals of canon_pack as a tuple, in the order
// ``canon_packs``, ``canon_maps``, ``canon_maps_sorted``,
// ``canon_declined``; NULL on a Python error.
PyObject* canon_counters() {
  return Py_BuildValue("(KKKK)", (unsigned long long)g_canon_packs,
                       (unsigned long long)g_canon_maps,
                       (unsigned long long)g_canon_maps_sorted,
                       (unsigned long long)g_canon_declined);
}

}  // extern "C" (canon_pack; the outer linkage block continues below)

// One pass over a list of bytes objects: write each length into
// ``lens`` and (when ``out`` is non-null) memcpy the payloads
// back-to-back into ``out``.  Returns the total byte count, or -1 when
// any element is not exactly ``bytes`` (caller falls back to Python).
// Replaces a np.fromiter(len, ...) + b"".join() pair that cost ~9ms at
// the 83k-tiny-blob config-5 shape (round-5 phase profile).
//
// ``out_capacity`` bounds the join pass and ``expected_n`` bounds BOTH
// buffers: callers size ``lens`` (and, for the join, ``out``) from an
// earlier ``len()`` / lengths-only call, and pure Python runs between
// those and this ctypes call — a list mutated in that window (grown,
// shrunk, or re-totalled) must return -1 BEFORE any write runs past a
// buffer, never overrun the heap (ADVICE r5, medium).  The caller must
// also verify the join's return equals its expected total (a short
// -1-free join is equally stale) and fall back to Python.
int64_t bytes_lens_join(PyObject* seq, uint64_t* lens, uint8_t* out,
                        int64_t out_capacity, int64_t expected_n) {
    if (!PyList_CheckExact(seq)) return -1;
    Py_ssize_t n = PyList_GET_SIZE(seq);
    if (expected_n >= 0 && n != (Py_ssize_t)expected_n) return -1;
    int64_t total = 0;
    for (Py_ssize_t i = 0; i < n; ++i) {
        PyObject* b = PyList_GET_ITEM(seq, i);
        if (!PyBytes_CheckExact(b)) return -1;
        Py_ssize_t ln = PyBytes_GET_SIZE(b);
        lens[i] = (uint64_t)ln;
        if (out) {
            if (total + (int64_t)ln > out_capacity) return -1;
            memcpy(out + total, PyBytes_AS_STRING(b), (size_t)ln);
        }
        total += (int64_t)ln;
    }
    return total;
}

// Build target[members[m]] = {actors[a]: counter} from checkpoint row
// arrays whose member runs are contiguous (ops/columnar.py
// orset_unpack_checkpoint) — the native twin of its per-member dict
// comprehensions, which cost ~0.5s of every 1M-dot warm open.  Returns
// 0, or -1 on any allocation failure / out-of-range index.  Every -1
// path clears the Python error indicator: the caller (a ctypes c_int
// restype, which never checks PyErr) treats -1 as "clear `target` and
// rebuild in Python", and a live indicator would surface later as an
// unrelated SystemError.
int grouped_rows_dicts(const int32_t* m_idx, const int32_t* a_idx,
                       const int64_t* ctr, int64_t n, PyObject* members,
                       PyObject* actors, PyObject* target) {
    if (!PyList_Check(members) || !PyList_Check(actors) ||
        !PyDict_Check(target))
        return -1;
    const Py_ssize_t n_m = PyList_GET_SIZE(members);
    const Py_ssize_t n_a = PyList_GET_SIZE(actors);
    int64_t i = 0;
    while (i < n) {
        const int32_t m = m_idx[i];
        if (m < 0 || (Py_ssize_t)m >= n_m) return -1;
        int64_t j = i;
        while (j < n && m_idx[j] == m) j++;
        PyObject* slot = new_dict_presized((Py_ssize_t)(j - i));
        if (!slot) { PyErr_Clear(); return -1; }
        for (int64_t t = i; t < j; ++t) {
            const int32_t a = a_idx[t];
            if (a < 0 || (Py_ssize_t)a >= n_a) { Py_DECREF(slot); return -1; }
            PyObject* c = PyLong_FromLongLong((long long)ctr[t]);
            if (!c || PyDict_SetItem(
                          slot, PyList_GET_ITEM(actors, (Py_ssize_t)a), c)
                          < 0) {
                Py_XDECREF(c);
                Py_DECREF(slot);
                PyErr_Clear();
                return -1;
            }
            Py_DECREF(c);
        }
        if (PyDict_SetItem(target, PyList_GET_ITEM(members, (Py_ssize_t)m),
                           slot) < 0) {
            Py_DECREF(slot);
            PyErr_Clear();
            return -1;
        }
        Py_DECREF(slot);
        i = j;
    }
    return 0;
}

// ``index[key]``, or ``key`` appended to ``items`` and entered at its
// position: -1 on any failure, the Python error left set.
static Py_ssize_t intern_at(PyObject* items, PyObject* index, PyObject* key) {
    PyObject* at = PyDict_GetItemWithError(index, key);  // borrowed
    if (at != nullptr) return PyLong_AsSsize_t(at);       // -1 + error: not an int
    if (PyErr_Occurred()) return -1;
    const Py_ssize_t idx = PyList_GET_SIZE(items);
    if (idx >= INT32_MAX) return -1;
    PyObject* pos = PyLong_FromSsize_t(idx);
    if (!pos) return -1;
    const bool ok = PyDict_SetItem(index, key, pos) == 0 &&
                    PyList_Append(items, key) == 0;
    Py_DECREF(pos);
    return ok ? idx : -1;
}

// The way back (ops/columnar.py orset_pack_checkpoint): one
// ``{member: {actor: counter}}`` table → its three checkpoint row
// buffers, ``(int32 member index, int32 actor index, int64 counter)``
// as three ``bytes``, in the order the Python loop there emits them:
// both levels walked by PyDict_Next, a member interned at its first
// sight (before its slots, so a member without slots is listed too), an
// actor looked up and appended on a miss.  ``members`` / ``actors`` are
// the interning tables' lists and ``member_index`` / ``actor_index``
// their ``{object: position}`` dicts; all four GROW here.  The buffers
// are sized from the slot dicts' lengths before they are filled, and
// the fill is bounded by that size (a key's ``__eq__`` is Python).
//
// Returns the tuple, or ``Py_None`` where it declines (a slot map that
// is not exactly a dict, a counter that is not an int or is outside
// int64, an index that does not fit, any Python error, cleared): the
// caller then runs its loop from scratch, on fresh tables.  Never an
// exception.
PyObject* dicts_grouped_rows(PyObject* table, PyObject* members,
                             PyObject* member_index, PyObject* actors,
                             PyObject* actor_index) {
    if (!PyDict_CheckExact(table) || !PyList_CheckExact(members) ||
        !PyDict_CheckExact(member_index) || !PyList_CheckExact(actors) ||
        !PyDict_CheckExact(actor_index))
        Py_RETURN_NONE;
    PyObject *m, *slots, *r, *c;
    Py_ssize_t pos = 0, n = 0;
    while (PyDict_Next(table, &pos, &m, &slots)) {
        if (!PyDict_CheckExact(slots)) Py_RETURN_NONE;
        n += PyDict_GET_SIZE(slots);
    }
    PyObject* mb = PyBytes_FromStringAndSize(nullptr, n * 4);
    PyObject* ab = PyBytes_FromStringAndSize(nullptr, n * 4);
    PyObject* cb = PyBytes_FromStringAndSize(nullptr, n * 8);
    PyObject* out = nullptr;
    if (mb && ab && cb) {
        // a bytes object's buffer is malloc-aligned past a 32-byte header
        int32_t* m_out = (int32_t*)PyBytes_AS_STRING(mb);
        int32_t* a_out = (int32_t*)PyBytes_AS_STRING(ab);
        int64_t* c_out = (int64_t*)PyBytes_AS_STRING(cb);
        Py_ssize_t k = 0;
        bool ok = true;
        pos = 0;
        while (ok && PyDict_Next(table, &pos, &m, &slots)) {
            const Py_ssize_t e = intern_at(members, member_index, m);
            if (e < 0 || !PyDict_CheckExact(slots)) { ok = false; break; }
            Py_ssize_t spos = 0;
            while (PyDict_Next(slots, &spos, &r, &c)) {
                if (k >= n || !PyLong_Check(c)) { ok = false; break; }
                const Py_ssize_t a = intern_at(actors, actor_index, r);
                if (a < 0 || a > INT32_MAX) { ok = false; break; }
                long long v;
                if (PyUnstable_Long_IsCompact((PyLongObject*)c)) {
                    v = (long long)PyUnstable_Long_CompactValue(
                        (PyLongObject*)c);
                } else {
                    int of = 0;
                    v = PyLong_AsLongLongAndOverflow(c, &of);
                    if (of != 0 || (v == -1 && PyErr_Occurred())) {
                        ok = false;
                        break;
                    }
                }
                m_out[k] = (int32_t)e;
                a_out[k] = (int32_t)a;
                c_out[k] = (int64_t)v;
                ++k;
            }
        }
        if (ok && k == n) out = PyTuple_Pack(3, mb, ab, cb);
    }
    Py_XDECREF(mb);
    Py_XDECREF(ab);
    Py_XDECREF(cb);
    if (out) return out;
    PyErr_Clear();
    Py_RETURN_NONE;
}

// Build {actor_obj: counter} for the nonzero entries of a dense clock —
// the native twin of ops/columnar.py dense_to_vclock's dict body.
// Returns a NEW dict, or NULL on error.
PyObject* dense_clock_dict(const int32_t* clock, int64_t R,
                           PyObject* actor_objs) {
    int64_t nz = 0;
    for (int64_t i = 0; i < R; ++i) nz += (clock[i] != 0);
    PyObject* d = new_dict_presized((Py_ssize_t)nz);
    if (!d) return nullptr;
    for (int64_t i = 0; i < R; ++i) {
        if (clock[i] == 0) continue;
        PyObject* c = PyLong_FromLong((long)clock[i]);
        if (!c ||
            PyDict_SetItem(d, PyList_GET_ITEM(actor_objs, (Py_ssize_t)i), c) <
                0) {
            Py_XDECREF(c);
            Py_DECREF(d);
            return nullptr;
        }
        Py_DECREF(c);
    }
    return d;
}

}  // extern "C"
